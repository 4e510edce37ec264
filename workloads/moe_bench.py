"""EP / gate-zoo bench: step time per gate variant + capacity-drop stats.

CPU-mesh ratios are meaningful (flat vs hierarchical a2a, gate overhead);
absolute times only matter on TPU.

``--grouped``: the sweep of the serving expert layer's grouped matmul
ALONE (``ops/grouped_matmul_pallas.py``) at the three expert cells' two
lanes — ms a call and the GB/s of weights it implies, for
``jax.lax.ragged_dot`` and for the kernel at each candidate row tile and
column block (``PERF.md`` section 6, PR 43, has the chip's table; the
tile rule's picks are marked). ``--aot`` compiles every variant for a
described v5e instead (no chip); names after the flags pick shapes.

Reference: HetuMoE gate zoo (``hetu/v1/python/hetu/layers/*Gate.py``) and
its MoE examples (``hetu/v1/examples/moe/``).
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


# -- the serving layer's grouped matmul alone --------------------------------
#: rows a call, held experts, live rows, hidden, expert width, the cell's
#: measured busiest-over-mean (``moe_local_imbalance.*``, ledger, PR 42)
GROUPED_SHAPES = {
    "kimi_decode": dict(rows=288, groups=64, live=288, hidden=2048,
                        width=1408, imbalance=1.39),
    "kimi_prefill": dict(rows=12288, groups=64, live=12288, hidden=2048,
                         width=1408, imbalance=1.39),
    "cmd_decode": dict(rows=128, groups=16, live=48, hidden=4096,
                       width=4096, imbalance=1.16),
    "cmd_prefill": dict(rows=1024, groups=16, live=512, hidden=4096,
                        width=4096, imbalance=1.16),
    "ling_decode": dict(rows=256, groups=64, live=72, hidden=2560,
                        width=768, imbalance=2.43),
    "ling_prefill": dict(rows=4096, groups=64, live=2048, hidden=2560,
                         width=768, imbalance=2.43),
}


def draw_group_sizes(rng, live: int, groups: int, imbalance: float):
    """``live`` rows over ``groups`` groups, multinomial on shares
    ``exp(s z)`` (``z`` normal) whose busiest-over-mean is
    ``imbalance`` (``s`` by bisection)."""
    import numpy as np
    z = rng.standard_normal(groups)
    lo, hi = 0.0, 8.0
    for _ in range(40):
        s = (lo + hi) / 2
        p = np.exp(s * z)
        lo, hi = (s, hi) if p.max() / p.mean() < imbalance else (lo, s)
    p = np.exp(lo * z)
    return rng.multinomial(live, p / p.sum()).astype(np.int32)


def grouped_main(args, only):
    import json

    import numpy as np
    from hetu_tpu.ops import grouped_matmul_pallas as gm
    aot = args.aot
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        shd = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    rng = np.random.default_rng(43)
    iters, layers = args.steps, 2
    for name, sh in GROUPED_SHAPES.items():
        if only and name not in only:
            continue
        rows, groups = sh["rows"], sh["groups"]
        sizes = draw_group_sizes(rng, sh["live"], groups, sh["imbalance"])
        touched = int((sizes > 0).sum())
        for side, (K, N) in (("up", (sh["hidden"], sh["width"])),
                             ("down", (sh["width"], sh["hidden"]))):
            rule = (gm.grouped_tile_rows(rows, groups),
                    gm.grouped_block_cols(K, N))
            print(json.dumps({
                "shape": name, "side": side, "rows": rows, "K": K, "N": N,
                "groups": groups, "touched": touched,
                "busiest_over_mean": round(
                    float(sizes.max() / sizes.mean()), 3),
                "rule": rule}), flush=True)
            if aot:
                def arr(s, t):
                    return jax.ShapeDtypeStruct(s, t, sharding=shd)
            else:
                def arr(s, t, _k=[0]):
                    _k[0] += 1
                    return (jax.random.normal(jax.random.key(_k[0]), s)
                            * 0.05).astype(t)
            x = arr((rows, K), jnp.bfloat16)
            w = arr((layers, groups, K, N), jnp.bfloat16)
            gs = jnp.asarray(sizes)

            # a variant: (tag, tile, cols, prepare, call, dense) — what
            # ``prepare`` makes of the rows is the route's (made once,
            # outside the timed loop: the matmul ALONE); ``dense``
            # brings a result back to the sorted rows, to compare
            def ragged():
                def call(ops, w, layer):
                    g = jax.lax.dynamic_update_slice(
                        jnp.zeros((layers * groups,), jnp.int32), ops[1],
                        (layer * groups,))
                    return jax.lax.ragged_dot(
                        ops[0], w.reshape((-1, K, N)), g,
                        preferred_element_type=jnp.float32)
                return (lambda x, gs: (x, gs)), call, (lambda ops, y: y)

            def kernel(tile, cols):
                def prepare(x, gs):
                    lay = gm.grouped_layout(gs, rows=rows, tile=tile)
                    return jnp.take(x, lay.src, axis=0), lay

                def call(ops, w, layer):
                    return gm.grouped_matmul(*ops[:1], w, ops[1],
                                             layer=layer, block_cols=cols,
                                             interpret=False)
                return prepare, call, (
                    lambda ops, y: jnp.take(y, ops[1].dst, axis=0))

            variants = [("ragged_dot", None, None) + ragged()]
            mean = rows // groups
            tiles = [t for t in (16, 32, 64, 128, 256, 512)
                     if mean // 4 <= t <= max(32, 2 * mean)
                     or t == rule[0]]
            cols = [rule[1]] if args.rule_cols else sorted(
                {c for c in (N, 1024, 512)
                 if c <= N and N % c == 0 and c % 128 == 0
                 and K * c * 2 <= 48 * 2 ** 20} | {rule[1]})
            variants += [("kernel", t, c) + kernel(t, c)
                         for t in tiles for c in cols]
            ref = None
            for tag, tile, c, prepare, call, dense in variants:
                rec = {"shape": name, "side": side, "variant": tag,
                       "tile": tile, "cols": c,
                       "pick": (tile, c) == rule}

                @jax.jit
                def one(x, w, gs, prepare=prepare, call=call, dense=dense):
                    ops = prepare(x, gs)
                    return dense(ops, call(ops, w, jnp.int32(1)))

                @jax.jit
                def loop(ops, w, call=call):
                    def body(i, c):
                        return c + call(ops, w, i % layers)[0, 0] \
                            .astype(jnp.float32)
                    return jax.lax.fori_loop(0, iters, body,
                                             jnp.float32(0))
                try:
                    t0 = time.perf_counter()
                    if aot:
                        one.lower(x, w, gs).compile()
                        rec["compile_s"] = round(
                            time.perf_counter() - t0, 2)
                        print(json.dumps(rec), flush=True)
                        continue
                    y = np.asarray(jax.block_until_ready(
                        one(x, w, gs)))[:sh["live"]]
                    rec["compile_s"] = round(time.perf_counter() - t0, 2)
                    ops = jax.jit(prepare)(x, gs)
                    jax.block_until_ready(loop(ops, w))
                    ts = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        jax.block_until_ready(loop(ops, w))
                        ts.append((time.perf_counter() - t0) / iters * 1e3)
                    rec["ms"] = [round(t, 4) for t in ts]
                    rec["weights_GBps"] = round(
                        touched * K * N * 2 / (min(ts) * 1e-3) / 1e9, 1)
                    if tag == "kernel":
                        rec["rows_computed"] = gm.grouped_rows_computed(
                            sizes, tile)
                    ref = y if ref is None else ref
                    rec["max_diff"] = float(np.abs(y - ref).max())
                    rec["finite"] = bool(np.isfinite(y).all())
                except Exception as e:   # a refusal by Mosaic is a result
                    rec["error"] = repr(e)[:300]
                print(json.dumps(rec), flush=True)


def main():
    if "--grouped" in sys.argv:
        ap = argparse.ArgumentParser()
        ap.add_argument("--grouped", action="store_true")
        ap.add_argument("--aot", action="store_true")
        ap.add_argument("--steps", type=int, default=40)
        ap.add_argument("--rule-cols", action="store_true")
        ap.add_argument("shapes", nargs="*")
        args = ap.parse_args()
        return grouped_main(args, args.shapes)
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    from hetu_tpu.nn.moe import MoEMLP, gate_drop_stats
    from hetu_tpu.parallel.sharding import (
        ActivationSharding, param_partition_specs, shard_params,
    )
    from hetu_tpu.parallel.strategy import Strategy
    from jax.sharding import NamedSharding

    n_dev = len(jax.devices())
    ep = min(args.experts, n_dev)
    dp = max(1, n_dev // ep)
    strat = Strategy(dp=dp, ep=ep)
    mesh = strat.build_mesh()
    act = ActivationSharding(mesh, batch=("dp", "ep"), seq="cp", tp="tp")
    T, d = args.tokens, args.dim
    x = jax.random.normal(jax.random.key(0), (dp * ep, T // (dp * ep), d))

    print(f"devices={n_dev} dp={dp} ep={ep} tokens={T} dim={d} "
          f"experts={args.experts}")
    for gate_type in ("topk", "ktop1", "sam", "balance"):
        kw = {"num_groups": max(1, args.experts // 2)} \
            if gate_type == "sam" else None
        moe = MoEMLP(d, args.hidden, args.experts, k=2,
                     capacity_factor=1.25, gate_type=gate_type,
                     gate_kwargs=kw)
        params = moe.init(jax.random.key(1), dtype=jnp.float32)
        sp = shard_params(params, mesh, param_partition_specs(
            moe, strat.axis_rules(), mesh))

        @jax.jit
        def f(p, x):
            with act:
                out, aux = moe(p, x)
            return out.sum(), aux

        xs = jax.device_put(x, NamedSharding(mesh, strat.data_spec(3)))
        f(sp, xs)[0].block_until_ready()          # compile
        t0 = time.perf_counter()
        for _ in range(args.steps):
            s, aux = f(sp, xs)
        s.block_until_ready()
        dt = (time.perf_counter() - t0) / args.steps * 1e3

        idx, wgt, _ = moe.gate(params["gate"], x.reshape(-1, d))
        stats = gate_drop_stats(idx, args.experts, moe.k, 1.25)
        print(f"{gate_type:8s} fwd {dt:8.2f} ms  "
              f"drop {float(stats['drop_frac']):.4f}  "
              f"imbalance {float(stats['load_imbalance']):.3f}  "
              f"aux {float(aux):.4f}")


if __name__ == "__main__":
    main()
