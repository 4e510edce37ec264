"""The two power-retention kernels ALONE at the Brumby cell's shape (a
pack of 2,048 tokens, 40 query heads over 8 kv heads of 128, the stacked
state leaf of 10 layers x 14 slots): ms a layer call of
``ops.retention_pallas.hetu_retention_scan`` and
``hetu_retention_update``, beside what the equations' operations and
the state's bytes take at the chip's peaks (``benchmark/flops_brumby``).

    chiprun -- python workloads/retention_bench.py
    python workloads/retention_bench.py --aot    # compile for a v5e, no chip
"""

import argparse
import os
import sys
import time

if "--aot" in sys.argv:         # the real Mosaic lowering, from the CPU
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["HETU_PALLAS_INTERPRET"] = "0"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

LAYERS, SLOTS, C, H, HKV, D = 10, 14, 2048, 40, 8, 128
EPS = 1e-6


def operands(sds):
    from hetu_tpu.ops.retention import feature_rows, value_rows
    leaf = (LAYERS, SLOTS, HKV, feature_rows(D), value_rows(D), D)
    shapes = {
        "scan": [((C, H, D), jnp.bfloat16), ((C, HKV, D), jnp.bfloat16),
                 ((C, HKV, D), jnp.bfloat16), ((C, HKV), jnp.float32)],
        "update": [((SLOTS, H, D), jnp.bfloat16),
                   ((SLOTS, HKV, D), jnp.bfloat16),
                   ((SLOTS, HKV, D), jnp.bfloat16),
                   ((SLOTS, HKV), jnp.float32)]}
    if sds is not None:
        return ({k: [sds(*s) for s in v] for k, v in shapes.items()},
                sds(leaf, jnp.float32))
    rng = np.random.default_rng(0)
    ops = {k: [jnp.asarray(rng.standard_normal(s), dt) if len(s) == 3
               else jnp.asarray(-np.abs(rng.standard_normal(s)) * 1e-2, dt)
               for s, dt in v] for k, v in shapes.items()}
    return ops, jnp.zeros(leaf, jnp.float32)


def scan_call(ops, buf, slot, pos, valid, layer):
    from hetu_tpu.ops.retention_pallas import hetu_retention_scan
    return hetu_retention_scan(*ops, buf, slot, pos, valid, eps=EPS,
                               layer=layer)


def update_call(ops, buf, live, layer):
    from hetu_tpu.ops.retention_pallas import hetu_retention_update
    return hetu_retention_update(*ops, buf, live, eps=EPS, layer=layer)


def looped(fn, n):
    """``n`` layer calls in one dispatch, the leaf carried (donated),
    each call's ``q`` chained to the last one's result."""
    def run(ops, buf, *where):
        def body(i, c):
            buf, o = c
            o, buf = fn([ops[0] + (1e-30 * o).astype(ops[0].dtype)]
                        + ops[1:], buf, *where,
                        (i % LAYERS).astype(jnp.int32))
            return buf, o
        return jax.lax.fori_loop(
            0, n, body, (buf, jnp.zeros(ops[0].shape, jnp.float32)))
    return jax.jit(run, donate_argnums=(1,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args()
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
        sh = SingleDeviceSharding(dev)

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)
        ops, buf = operands(sds)
        i32 = sds((), jnp.int32)
        for name, fn, where in (
                ("scan", scan_call, (sds((C,), jnp.int32),
                                     sds((C,), jnp.int32),
                                     sds((C,), jnp.bool_))),
                ("update", update_call, (sds((SLOTS,), jnp.bool_),))):
            t0 = time.perf_counter()
            exe = jax.jit(fn).lower(ops[name], buf, *where, i32).compile()
            text = exe.as_text()
            mem = exe.memory_analysis()
            print(name, "compiled in", round(time.perf_counter() - t0, 1),
                  "s; temp", mem.temp_size_in_bytes >> 20, "MiB;",
                  "copies of the leaf:", sum(
                      1 for ln in text.splitlines()
                      if " copy(" in ln and "f32[10,14,8,65,136,128]" in
                      ln.split("=")[0]))
        return
    ops, buf = operands(None)
    slot = jnp.full((C,), 3, jnp.int32)
    pos = jnp.arange(C, dtype=jnp.int32) + C      # a run mid-prompt
    valid = jnp.ones((C,), bool)
    live = jnp.arange(SLOTS) < 12
    out = {"device": str(jax.devices()[0].device_kind)}
    for name, fn, where in (("scan", scan_call, (slot, pos, valid)),
                            ("update", update_call, (live,))):
        f = looped(fn, args.calls)
        buf, _ = f(ops[name], buf, *where)
        jax.block_until_ready(buf)
        t0 = time.perf_counter()
        buf, o = f(ops[name], buf, *where)
        jax.block_until_ready((buf, o))
        out[name + "_ms_a_call"] = round(
            (time.perf_counter() - t0) * 1e3 / args.calls, 3)
        out[name + "_finite"] = bool(jnp.isfinite(o).all())
    print(out)


if __name__ == "__main__":
    main()
