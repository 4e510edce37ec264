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
``--grouped --layer``: a layer call's WHOLE expert work at each shape
and row tile instead — the three calls one by one (gate; up with the
SwiGLU epilogue; down), the three chained (``trio``), those with the
gather back to token order, its weighting and sum over choices
(``trio+back``: the split form's scope) and the one fused call that does
it all (``fused``: ``grouped_swiglu``) (``PERF.md`` section 6, PR 62).

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
#: measured busiest-over-mean (``moe_local_imbalance.*``, ledger, PR 42;
#: Qwen3-Next's and SDAR's PR 61), the lane's (token, choice) pairs and
#: choices a token (what the gather back walks)
GROUPED_SHAPES = {
    "kimi_decode": dict(rows=288, groups=64, live=288, hidden=2048,
                        width=1408, imbalance=1.39, pairs=288, k=6),
    "kimi_prefill": dict(rows=12288, groups=64, live=12288, hidden=2048,
                         width=1408, imbalance=1.39, pairs=12288, k=6),
    "cmd_decode": dict(rows=128, groups=16, live=48, hidden=4096,
                       width=4096, imbalance=1.16, pairs=384, k=8),
    "cmd_prefill": dict(rows=1024, groups=16, live=512, hidden=4096,
                        width=4096, imbalance=1.16, pairs=4096, k=8),
    "ling_decode": dict(rows=256, groups=64, live=72, hidden=2560,
                        width=768, imbalance=2.43, pairs=576, k=8),
    "ling_prefill": dict(rows=4096, groups=64, live=2048, hidden=2560,
                         width=768, imbalance=2.43, pairs=16384, k=8),
    "qwen3next_decode": dict(rows=128, groups=64, live=20, hidden=2048,
                             width=512, imbalance=1.36, pairs=160, k=10),
    "qwen3next_prefill": dict(rows=5120, groups=64, live=2560,
                              hidden=2048, width=512, imbalance=1.36,
                              pairs=20480, k=10),
    "sdar_block": dict(rows=512, groups=16, live=256, hidden=2048,
                       width=768, imbalance=1.13, pairs=2048, k=8),
    "sdar_prefill": dict(rows=1024, groups=16, live=512, hidden=2048,
                         width=768, imbalance=1.13, pairs=4096, k=8),
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


def _described_chip():
    """One chip of a described v5e, to compile for without one."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def layer_main(args, only):
    """``--grouped --layer``: ms a layer call of the expert work at each
    row tile — ``wg`` / ``wi`` (gated, rounded) / ``wo`` one by one,
    ``trio`` (the three chained), ``trio+back`` (and a lane's pairs read
    from the float32 result, weighted, summed over choices: the split
    form's whole scope) and ``fused`` (one ``grouped_swiglu``: the
    tokens' sums from the laid-out rows in one call)."""
    import json

    import numpy as np
    from hetu_tpu.ops import grouped_matmul_pallas as gm
    aot = args.aot
    if aot:
        shd = _described_chip()
    rng = np.random.default_rng(62)
    iters, layers, dt = args.steps, 2, jnp.bfloat16
    for name, sh in GROUPED_SHAPES.items():
        if only and name not in only:
            continue
        rows, groups, live = sh["rows"], sh["groups"], sh["live"]
        K, N, pairs, k = sh["hidden"], sh["width"], sh["pairs"], sh["k"]
        sizes = draw_group_sizes(rng, live, groups, sh["imbalance"])
        fits = gm.grouped_swiglu_fits(K, N, pairs // k)
        share = live < rows
        rule = {"split": gm.grouped_tile_rows(rows, groups),
                "fused": gm.grouped_tile_rows(
                    3 * rows // 4 if share else rows, groups)}
        print(json.dumps({
            "shape": name, "rows": rows, "live": live, "K": K, "N": N,
            "groups": groups, "touched": int((sizes > 0).sum()),
            "busiest": int(sizes.max()), "fits": fits, "rule": rule,
            "weights_MB": round(int((sizes > 0).sum()) * 3 * K * N * 2
                                / 1e6, 1)}), flush=True)
        if aot:
            def arr(s, t):
                return jax.ShapeDtypeStruct(s, t, sharding=shd)
        else:
            def arr(s, t, _k=[0]):
                _k[0] += 1
                return (jax.random.normal(jax.random.key(_k[0]), s)
                        * 0.05).astype(t)
        x = arr((rows, K), dt)
        wg, wi = (arr((layers, groups, K, N), dt) for _ in range(2))
        wo = arr((layers, groups, N, K), dt)
        gs = jnp.asarray(sizes)
        # the lane's pairs: the live ones at random places of the sorted
        # rows, the rest (held elsewhere) clipped onto the last row
        back = np.full((pairs,), rows - 1, np.int32)
        at = np.zeros((rows,), np.int32)
        at[:live] = rng.permutation(pairs)[:live]
        back[at[:live]] = np.arange(live)
        mine = np.zeros((pairs,), bool)
        mine[at[:live]] = True
        w_pairs = arr((pairs,), jnp.float32)
        ws = (wg, wi, wo, jnp.asarray(back), jnp.asarray(mine), w_pairs,
              jnp.asarray(at // k), w_pairs if aot else w_pairs[at])
        mean = max(live // groups, 1)
        tiles = sorted({t for t in (16, 32, 64, 128, 256)
                        if mean // 2 <= t <= max(32, 4 * mean)}
                       | set(rule.values()))
        ref = {}
        for tile in tiles:
            def prepare(x, gs, tile=tile):
                lay = gm.grouped_layout(gs, rows=rows, tile=tile)
                return jnp.take(x, lay.src, axis=0), lay

            def call_wg(ops, ws, l):
                return gm.grouped_matmul(ops[0], ws[0], ops[1], layer=l,
                                         interpret=False)

            def call_wi(ops, ws, l, gate=None):
                gate = jnp.zeros((ops[0].shape[0], N), jnp.float32) \
                    if gate is None else gate
                return gm.grouped_matmul(ops[0], ws[1], ops[1], layer=l,
                                         gate=gate, out_dtype=dt,
                                         interpret=False)

            def call_wo(ops, ws, l, h=None):
                h = ops[0][:, :N] if h is None else h
                return gm.grouped_matmul(h, ws[2], ops[1], layer=l,
                                         interpret=False)

            def trio(ops, ws, l):
                return call_wo(ops, ws, l,
                               call_wi(ops, ws, l, call_wg(ops, ws, l)))

            def fused(ops, ws, l):
                return gm.grouped_swiglu(
                    ops[0], *ws[:3], ops[1], gm.grouped_combine(
                        ops[1], gs,
                        *(jnp.take(t, ops[1].src) for t in ws[6:])),
                    tokens=pairs // k, layer=l, interpret=False)

            def back_of(ops, ws, y):
                # the layer's gather back, weighting and sum over choices
                back, mine, w_pairs = ws[3:6]
                wanted = jnp.take(ops[1].dst, back)
                return jnp.where(
                    mine[:, None],
                    jnp.take(y, wanted, axis=0) * w_pairs[:, None],
                    0.0).reshape(-1, k, K).sum(1)

            def trio_back(ops, ws, l):
                return back_of(ops, ws, trio(ops, ws, l))

            variants = [("wg", call_wg), ("wi", call_wi), ("wo", call_wo),
                        ("trio", trio), ("trio+back", trio_back)]
            if fits:
                variants.append(("fused", fused))
            for tag, call in variants:
                rec = {"shape": name, "variant": tag, "tile": tile,
                       "pick": [f for f, t in rule.items() if t == tile]}

                @jax.jit
                def one(x, gs, ws, call=call, tag=tag):
                    ops = prepare(x, gs)
                    y = call(ops, ws, jnp.int32(1))
                    return y if y.shape[0] == pairs // k else \
                        jnp.take(y, ops[1].dst, axis=0)

                @jax.jit
                def loop(ops, ws, call=call):
                    def body(i, c):
                        return c + call(ops, ws, i % layers)[0, 0] \
                            .astype(jnp.float32)
                    return jax.lax.fori_loop(0, iters, body,
                                             jnp.float32(0))
                try:
                    t0 = time.perf_counter()
                    if aot:
                        one.lower(x, gs, ws).compile()
                        rec["compile_s"] = round(
                            time.perf_counter() - t0, 2)
                        print(json.dumps(rec), flush=True)
                        continue
                    y = np.asarray(jax.block_until_ready(one(x, gs, ws)),
                                   np.float32)
                    rec["compile_s"] = round(time.perf_counter() - t0, 2)
                    ops = jax.jit(prepare)(x, gs)
                    jax.block_until_ready(loop(ops, ws))
                    ts = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        jax.block_until_ready(loop(ops, ws))
                        ts.append((time.perf_counter() - t0) / iters * 1e3)
                    rec["ms"] = [round(t, 4) for t in ts]
                    if tag in ("trio+back", "fused"):
                        # the tokens' sums against the first of them
                        want = ref.setdefault("tokens", y)
                        rec["rows_computed"] = gm.grouped_rows_computed(
                            sizes, tile)
                        rec["max_diff"] = float(np.abs(y - want).max())
                        rec["ref_max"] = float(np.abs(want).max())
                        rec["finite"] = bool(np.isfinite(y).all())
                except Exception as e:   # a refusal by Mosaic is a result
                    rec["error"] = repr(e)[:300]
                print(json.dumps(rec), flush=True)


def grouped_main(args, only):
    import json

    import numpy as np
    from hetu_tpu.ops import grouped_matmul_pallas as gm
    aot = args.aot
    if aot:
        shd = _described_chip()
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
        ap.add_argument("--layer", action="store_true")
        ap.add_argument("shapes", nargs="*")
        args = ap.parse_args()
        return (layer_main if args.layer else grouped_main)(
            args, args.shapes)
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
