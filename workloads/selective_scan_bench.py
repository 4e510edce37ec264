"""The two selective-scan kernels ALONE at the Jamba cell's shape (a
pack of 2,048 tokens, 5120 channels of 16 states, the stacked state
leaf of 26 layers x 18 slots): ms a layer call of
``ops.selective_scan_pallas.hetu_selective_scan`` and
``hetu_selective_update`` — each with the relayout of its operands to
whole registers and of its result back, as the layer calls them —
beside what the equations' operations and bytes take at the chip's
peaks (``benchmark/flops_jamba``; the table has no vector peak).

    chiprun -- python workloads/selective_scan_bench.py [--chunk N ...]
    python workloads/selective_scan_bench.py --aot   # compile for a v5e
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

LAYERS, SLOTS, P, D, N = 26, 18, 2048, 5120, 16


def operands(sds):
    shapes = {"scan": [(P, D), (P, D), (N, D), (P, N), (P, N)],
              "update": [(SLOTS, D), (SLOTS, D), (N, D), (SLOTS, N),
                         (SLOTS, N)]}
    leaf = (LAYERS, SLOTS, N, D // 128, 128)
    if sds is not None:
        return ({k: [sds(s, jnp.float32) for s in v]
                 for k, v in shapes.items()}, sds(leaf, jnp.float32))
    rng = np.random.default_rng(0)

    def draw(i, s):
        a = rng.standard_normal(s)
        if i == 1:                  # dt > 0
            a = 0.01 + 0.1 * np.abs(a)
        if i == 2:                  # A < 0
            a = -np.broadcast_to(np.arange(1, N + 1)[:, None], s)
        return jnp.asarray(a, jnp.float32)
    return ({k: [draw(i, s) for i, s in enumerate(v)]
             for k, v in shapes.items()}, jnp.zeros(leaf, jnp.float32))


def scan_call(chunk):
    def call(ops, buf, slot, pos, valid, layer):
        from hetu_tpu.ops.selective_scan_pallas import hetu_selective_scan
        return hetu_selective_scan(*ops, buf, slot, pos, valid, layer=layer,
                                   chunk=chunk)
    return call


def update_call(ops, buf, live, layer):
    from hetu_tpu.ops.selective_scan_pallas import hetu_selective_update
    return hetu_selective_update(*ops, buf, live, layer=layer)


def looped(fn, n):
    """``n`` layer calls in one dispatch, the leaf carried (donated),
    each call's ``x`` chained to the last one's result."""
    def run(ops, buf, *where):
        def body(i, c):
            buf, y = c
            y, buf = fn([ops[0] + 1e-30 * y] + ops[1:], buf, *where,
                        (i % LAYERS).astype(jnp.int32))
            return buf, y
        return jax.lax.fori_loop(
            0, n, body, (buf, jnp.zeros(ops[0].shape, jnp.float32)))
    return jax.jit(run, donate_argnums=(1,))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--calls", type=int, default=26)
    ap.add_argument("--chunk", type=int, nargs="*", default=[None])
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
                ("scan", scan_call(args.chunk[0]),
                 (sds((P,), jnp.int32), sds((P,), jnp.int32),
                  sds((P,), jnp.bool_))),
                ("update", update_call, (sds((SLOTS,), jnp.bool_),))):
            t0 = time.perf_counter()
            exe = jax.jit(fn, donate_argnums=(1,)).lower(
                ops[name], buf, *where, i32).compile()
            mem = exe.memory_analysis()
            print(name, "compiled in", round(time.perf_counter() - t0, 1),
                  "s; temp", mem.temp_size_in_bytes >> 20, "MiB")
        return
    from benchmark import flops, flops_jamba
    from benchmark.peaks import peaks_for
    ops, buf = operands(None)
    slot = jnp.full((P,), 3, jnp.int32)
    pos = jnp.arange(P, dtype=jnp.int32) + P      # a run mid-prompt
    valid = jnp.ones((P,), bool)
    live = jnp.arange(SLOTS) < 16
    kind = jax.devices()[0].device_kind
    cfg = {"mamba_expand": 2, "hidden_size": D // 2, "mamba_d_state": N}
    out = {"device": str(kind)}
    calls = [("scan" if c is None else f"scan_chunk{c}", scan_call(c),
              (slot, pos, valid), "scan") for c in args.chunk] \
        + [("update", update_call, (live,), "update")]
    for name, fn, where, what in calls:
        f = looped(fn, args.calls)
        buf, _ = f(ops[what], buf, *where)
        jax.block_until_ready(buf)
        t0 = time.perf_counter()
        buf, y = f(ops[what], buf, *where)
        jax.block_until_ready((buf, y))
        out[name + "_ms_a_call"] = round(
            (time.perf_counter() - t0) * 1e3 / args.calls, 3)
        out[name + "_finite"] = bool(jnp.isfinite(y).all())
    need = {"scan": flops_jamba.selective_scan_call(cfg, P),
            "update": flops_jamba.selective_update_call(cfg, 16)}
    for what, call in need.items():
        out[what + "_roofline_ms"] = round(1e3 * flops.roofline_seconds(
            call["flops"], call["bytes"], peaks_for(kind)), 4)
    print(out)


if __name__ == "__main__":
    main()
