"""Collective microbenchmarks over the device mesh.

Equivalent of the reference's raw NCCL workload binaries
(``workloads/cuda/workload_*.cu``): time psum / all_gather /
reduce_scatter-style / ppermute / all_to_all over each mesh axis to
characterize ICI (or the CPU-simulation fabric).

Run: python workloads/collectives.py --axis dp --mb 64
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P


def bench(fn, x, iters=10):
    # host fetch, not block_until_ready: the latter is lazy through the
    # asynchronous dispatch (see utils.profiler.sync_result)
    from hetu_tpu.utils.profiler import sync_result
    sync_result(fn(x))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    sync_result(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=16.0,
                    help="payload megabytes")
    args = ap.parse_args()

    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",))
    el = int(args.mb * 1e6 / 4)
    rows = max(el // 1024, n)
    rows -= rows % n
    x = jnp.ones((rows, 1024), jnp.float32)
    nbytes = x.size * 4

    def run(name, body, in_spec, out_spec):
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=in_spec,
                              out_specs=out_spec, check_vma=False))
        dt = bench(f, x)
        print(f"{name:16s} {nbytes / 1e6:8.1f} MB  {dt * 1e3:8.3f} ms  "
              f"{nbytes / dt / 1e9:8.2f} GB/s (algo)")

    run("psum", lambda a: jax.lax.psum(a, "x"), P("x"), P("x"))
    run("all_gather",
        lambda a: jax.lax.all_gather(a, "x", axis=0, tiled=True),
        P("x"), P())
    run("ppermute",
        lambda a: jax.lax.ppermute(
            a, "x", [(i, (i + 1) % n) for i in range(n)]),
        P("x"), P("x"))
    run("all_to_all",
        lambda a: jax.lax.all_to_all(
            a.reshape(n, -1, a.shape[-1]), "x", 0, 0),
        P("x"), P("x"))


if __name__ == "__main__":
    main()
