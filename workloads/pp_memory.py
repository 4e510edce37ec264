"""Per-device HBM analysis of the pipeline executor on the REAL TPU
target — no multi-chip hardware needed (AOT topology compilation).

The r3 verdict flagged the homogeneous pipeline's memory story as
unvalidated: CPU-sim RSS says nothing about HBM. But the TPU compiler
is LOCAL (libtpu) — only execution needs a chip — so
``jax.experimental.topologies.get_topology_desc("v5e:2x4")`` lets us
compile the full dp×pp train step exactly as it would run on a v5e-8
slice and read XLA's own memory analysis (argument/output/temp bytes
per device). That answers "does the single-jit scan-flush executor's
activation liveness fit HBM, and how much does remat buy" with the
compiler's ground truth instead of a simulation proxy.

Attention uses the XLA reference path here: Pallas kernels lower in
interpret mode when the process backend is not TPU, which would distort
the analysis (the flash kernel's VMEM working set is not modeled
anyway — this measures HBM residency, which the reference path bounds
from above).

Usage: python workloads/pp_memory.py [--layers 12] [--hidden 768]
         [--batch 16] [--seq 1024] [--topology v5e:2x4]
Writes workloads/out/pp_memory_L{layers}_h{hidden}.json; one row per config.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# XLA's own per-chip budget for v5e ("Used ... of 15.75G hbm" in its
# RESOURCE_EXHAUSTED messages) — NOT the 16G marketing figure
HBM_V5E = int(15.75 * 1024 ** 3)


def analyze(cfg, strategy, topo_devices, *, batch, seq, policy,
            attn_impl: str = "reference", model_cls=None):
    """AOT-compile the train step for the topology; return memory rows.

    ``attn_impl="pallas"`` compiles the real Mosaic kernels (pair with
    ``HETU_PALLAS_INTERPRET=0`` — see ``aot_check.py``); the default
    reference path measures HBM without kernel lowering in the loop."""
    from hetu_tpu import optim
    from hetu_tpu.core.dtypes import autocast
    from hetu_tpu.engine.state import new_train_state
    from hetu_tpu.engine.train_step import build_train_step, make_plan
    from hetu_tpu.models import GPTLMHeadModel

    model = (model_cls or GPTLMHeadModel)(cfg)
    opt = optim.adamw(1e-4)
    # the WHOLE lower+compile must stay inside the policy context: the
    # modules read the thread-local compute dtype at TRACE time, and
    # jax.jit traces lazily at .lower() — outside the block the step
    # would compile (and be measured) at fp32 compute
    with autocast(policy):
        plan = make_plan(model, opt, strategy, devices=topo_devices)
        step = build_train_step(model, opt, plan, attn_impl=attn_impl)

        shapes = jax.eval_shape(
            lambda k: new_train_state(model.init(k), opt),
            jax.random.key(0))
        state_abs = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            shapes, plan.state_shardings)
        bsh = plan.batch_sharding(2)
        batch_abs = {
            "input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                              sharding=bsh),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                           sharding=bsh),
        }
        t0 = time.perf_counter()
        compiled = step.lower(state_abs, batch_abs).compile()
        dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    if ma is None:
        return {"error": "no memory analysis from this backend",
                "compile_s": round(dt, 1)}
    row = {
        "compile_s": round(dt, 1),
        "arg_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "out_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }
    # XLA's own per-program cost estimate: absolute scale is off peak,
    # but it ranks programs by modeled flops+bytes
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        for src, dst in (("flops", "flops"),
                         ("bytes accessed", "bytes_accessed"),
                         ("optimal_seconds", "optimal_seconds")):
            if src in ca:
                row[dst] = float(ca[src])
    except Exception as e:                              # noqa: BLE001
        # keep the memory rows usable, but make the missing-cost cause
        # diagnosable downstream
        row["cost_analysis_error"] = repr(e)
    # peak HBM ≈ args + temps (+ outputs not aliased over args); the
    # donated state aliases, so args+temp is the honest per-device bound
    row["peak_bytes_est"] = row["arg_bytes"] + row["temp_bytes"] \
        + max(0, row["out_bytes"] - row["alias_bytes"])
    row["fits_hbm"] = row["peak_bytes_est"] < HBM_V5E
    return row


def _bytes_of(tree) -> int:
    """GLOBAL logical bytes of a ShapeDtypeStruct tree."""
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree)
               if hasattr(l, "shape") and hasattr(l, "dtype"))


def _bytes_dev(tree) -> int:
    """PER-DEVICE bytes: leaves with a sharding contribute their shard
    shape (what one device actually stores), unsharded leaves their full
    shape."""
    total = 0
    for l in jax.tree.leaves(tree):
        if not (hasattr(l, "shape") and hasattr(l, "dtype")):
            continue
        shape = l.shape
        sh = getattr(l, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            shape = sh.shard_shape(l.shape)
        total += int(np.prod(shape)) * l.dtype.itemsize
    return total


def analyze_1f1b(cfg, *, pp, dp, tp, nm, remat, topo_devices, batch, seq,
                 policy):
    """Compiler-derived per-device memory for the host-scheduled 1F1B
    executor (``parallel.hetero.homogeneous_1f1b``), assembled from its
    per-stage AOT programs + the schedule's liveness bound.

    Unlike ``analyze`` (one program = one compiler peak), 1F1B memory is
    a host-side composition: per-stage state + ≤pp in-flight
    microbatches' residuals (the 1F1B bound, reference
    ``executable_graph.cc:836``) + the largest stage program's temp
    peak. Residual bytes per microbatch come from ``jax.eval_shape`` of
    the residual-mode forward's vjp closure, minus the stage's param
    bytes (the closure passes the param buffers through — shared across
    microbatches, not per-mb cost)."""
    from hetu_tpu import optim
    from hetu_tpu.core.dtypes import autocast
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.parallel.hetero import (
        HeteroTrainStep, homogeneous_1f1b, make_hetero_plan,
    )
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-4)
    strategy = homogeneous_1f1b(cfg.num_layers, pp=pp, tp=tp, dp=dp,
                                num_microbatches=nm, remat=remat)
    mb = batch // nm
    with autocast(policy):
        plan = make_hetero_plan(model, strategy, devices=topo_devices)
        step = HeteroTrainStep(model, opt, plan, schedule="1f1b",
                               backward="residuals")

        pshapes = jax.eval_shape(
            lambda k: model.init(k, dtype=policy.param_dtype),
            jax.random.key(0))
        ranges = strategy.layer_ranges()

        def abs_tree(shapes, shardings):
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                shapes, shardings)

        outer_s = {k: v for k, v in pshapes.items() if k != "blocks"}
        outer_abs = abs_tree(outer_s, plan.outer_shardings)
        houter_abs = abs_tree(outer_s, plan.head_outer_shardings)
        # blocks params are layer-stacked; a stage chunk's aval is the
        # same leaf with the leading (layer) dim cut to the stage range
        # (hetero._slice_blocks does this on real arrays)
        chunk_abs = [
            jax.tree.map(
                lambda s, sh, n=hi - lo: jax.ShapeDtypeStruct(
                    (n,) + s.shape[1:], s.dtype, sharding=sh),
                pshapes["blocks"], plan.block_shardings[i])
            for i, (lo, hi) in enumerate(ranges)]

        def rep(mesh, shape, dtype):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, P()))

        ids_abs = jax.ShapeDtypeStruct(
            (mb, seq), jnp.int32, sharding=plan.batch_shardings[0])
        labels_abs = jax.ShapeDtypeStruct(
            (mb, seq), jnp.int32, sharding=plan.batch_shardings[-1])
        h_abs = [jax.ShapeDtypeStruct((mb, seq, cfg.hidden_size),
                                      policy.compute_dtype,
                                      sharding=plan.act_shardings[i])
                 for i in range(pp)]
        extras_of = [{"positions": rep(plan.meshes[i], (mb, seq),
                                       jnp.int32)} for i in range(pp)]
        gscale = rep(plan.meshes[-1], (), jnp.float32)

        def mem(compiled):
            ma = compiled.memory_analysis()
            return {"temp": int(ma.temp_size_in_bytes),
                    "arg": int(ma.argument_size_in_bytes),
                    "out": int(ma.output_size_in_bytes)}

        rows = {}
        # residuals and inter-stage activations are batch-sharded over
        # the stage's dp — eval_shape avals carry no shardings, so the
        # GLOBAL byte counts divide by dp for the per-device cost (state
        # trees DO carry shardings: _bytes_dev reads the shard shapes)
        # -- stage 0: embed + first chunk, residual-mode forward --------
        out0 = jax.eval_shape(step._fwd_res[0], outer_abs, chunk_abs[0],
                              ids_abs, extras_of[0]["positions"],
                              extras_of[0])
        c0 = step._fwd_res[0].lower(outer_abs, chunk_abs[0], ids_abs,
                                    extras_of[0]["positions"],
                                    extras_of[0]).compile()
        vjp0_abs = out0[1]
        r0 = max(0, _bytes_of(vjp0_abs)
                 - _bytes_of(chunk_abs[0]) - _bytes_of(outer_abs)) // dp
        b0 = step._bwd_apply[0].lower(vjp0_abs, out0[0]).compile()
        rows["first"] = {"fwd": mem(c0), "bwd": mem(b0),
                         "residual_mb": r0,
                         "state": _bytes_dev(chunk_abs[0]) * 4
                         + _bytes_dev(outer_abs) * 4}
        # -- mid stage (stage 1), the repeated shape --------------------
        if pp > 2:
            outm = jax.eval_shape(step._fwd_res[1], chunk_abs[1],
                                  h_abs[1], extras_of[1])
            cm = step._fwd_res[1].lower(chunk_abs[1], h_abs[1],
                                        extras_of[1]).compile()
            vjpm_abs = outm[1]
            rm = max(0, _bytes_of(vjpm_abs)
                     - _bytes_of(chunk_abs[1])) // dp
            bm = step._bwd_apply[1].lower(vjpm_abs, outm[0]).compile()
            rows["mid"] = {"fwd": mem(cm), "bwd": mem(bm),
                           "residual_mb": rm,
                           "state": _bytes_dev(chunk_abs[1]) * 4}
        # -- last stage: fused fwd+loss+bwd, h stored per in-flight mb --
        cl = step._bwd_last.lower(houter_abs, chunk_abs[-1], h_abs[-1],
                                  labels_abs, extras_of[-1],
                                  gscale).compile()
        rows["last"] = {"bwd_last": mem(cl),
                        "residual_mb": _bytes_of([h_abs[-1]]) // dp,
                        "state": _bytes_dev(chunk_abs[-1]) * 4
                        + _bytes_dev(houter_abs) * 4}

    # schedule bound: <= pp microbatches in flight per stage (1F1B)
    live = min(pp, nm)
    for r in rows.values():
        temps = max(p["temp"] for p in r.values()
                    if isinstance(p, dict) and "temp" in p)
        r["peak_bytes_est"] = r["state"] + live * r["residual_mb"] + temps
    peak = max(r["peak_bytes_est"] for r in rows.values())
    return {"stages": rows, "live_mb": live, "peak_bytes_est": peak,
            "fits_hbm": peak < HBM_V5E}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--nm", type=int, default=8)
    ap.add_argument("--topology", default="v5e:2x4")
    ap.add_argument("--compare-1f1b", action="store_true",
                    help="scan executor vs host-scheduled 1F1B peaks "
                         "(VERDICT r4 item 5: decide the pp default "
                         "with compiler evidence)")
    args = ap.parse_args()

    # script-entry only (a module-level set would flip the backend of any
    # importer, e.g. the test suite). Nothing executes on a device — the
    # AOT target is a TPU — so this process never takes the chip.
    jax.config.update("jax_platforms", "cpu")

    from jax.experimental import topologies

    from hetu_tpu.core.dtypes import Policy
    from hetu_tpu.models import GPTConfig
    from hetu_tpu.parallel.strategy import Strategy

    topo = topologies.get_topology_desc(args.topology, "tpu")
    devs = list(topo.devices)
    cfg = GPTConfig(vocab_size=50257, max_positions=args.seq,
                    hidden_size=args.hidden, num_layers=args.layers,
                    num_heads=max(4, args.hidden // 64))
    policy = Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)

    out = {"topology": args.topology, "n_devices": len(devs),
           "model": {"layers": args.layers, "hidden": args.hidden,
                     "batch": args.batch, "seq": args.seq,
                     "nm": args.nm},
           "rows": []}
    gib = 1024 ** 3

    if args.compare_1f1b:
        print(f"scan vs 1F1B, L={args.layers} h={args.hidden} "
              f"b={args.batch} s={args.seq} nm={args.nm} dp2 x pp4")
        cmp_out = {"model": out["model"], "rows": []}
        for remat in ("none", "selective"):
            try:
                scan = analyze(cfg, Strategy(dp=2, pp=4, remat=remat,
                                             num_microbatches=args.nm),
                               devs, batch=args.batch, seq=args.seq,
                               policy=policy)
            except Exception as e:   # noqa: BLE001 — keep other rows
                scan = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            try:
                f1b = analyze_1f1b(cfg, pp=4, dp=2, tp=1, nm=args.nm,
                                   remat=remat, topo_devices=devs,
                                   batch=args.batch, seq=args.seq,
                                   policy=policy)
            except Exception as e:   # noqa: BLE001
                f1b = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
            row = {"remat": remat, "scan": scan, "1f1b": f1b}
            cmp_out["rows"].append(row)
            sp = scan.get("peak_bytes_est")
            fp = f1b.get("peak_bytes_est")
            print(f"  remat={remat:<10} scan "
                  f"{scan.get('error') if sp is None else f'{sp/gib:.2f}G'}"
                  f" | 1f1b "
                  f"{f1b.get('error') if fp is None else f'{fp/gib:.2f}G'}",
                  flush=True)
            winner = None
            if sp is not None and fp is not None:
                winner = "scan" if sp <= fp else "1f1b"
            elif fp is not None:
                winner = "1f1b"
            elif sp is not None:
                winner = "scan"
            row["winner"] = winner
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "out", f"pp_1f1b_compare_L{args.layers}.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(cmp_out, f, indent=1)
        print(f"wrote {path}")
        return
    print(f"topology={args.topology} ({len(devs)} devices) "
          f"L={args.layers} h={args.hidden} b={args.batch} s={args.seq}")
    print(f"{'strategy':>22} {'remat':>10} {'temp GiB':>9} "
          f"{'peak GiB':>9} {"fitsHBM":>7} {'compile s':>9}")
    for name, strat in (
            ("dp2 x pp4 scan", Strategy(dp=2, pp=4, remat="none",
                                        num_microbatches=args.nm)),
            ("dp2 x pp4 scan", Strategy(dp=2, pp=4, remat="selective",
                                        num_microbatches=args.nm)),
            ("dp2 x pp4 scan", Strategy(dp=2, pp=4, remat="full",
                                        num_microbatches=args.nm)),
            ("dp8 (no pp)", Strategy(dp=8, remat="selective")),
    ):
        try:
            row = analyze(cfg, strat, devs, batch=args.batch,
                          seq=args.seq, policy=policy)
        except Exception as e:  # one config must not kill the table
            row = {"error": f"{type(e).__name__}: {str(e)[:150]}"}
        row = {"name": name, "remat": strat.remat, **row}
        out["rows"].append(row)
        if "error" in row:
            print(f"{name:>22} {strat.remat:>10}   ERROR {row['error']}",
                  flush=True)
        else:
            print(f"{name:>22} {strat.remat:>10} "
                  f"{row['temp_bytes'] / gib:>9.2f} "
                  f"{row['peak_bytes_est'] / gib:>9.2f} "
                  f"{str(row["fits_hbm"]):>7} {row['compile_s']:>9.1f}",
                  flush=True)

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "out",
        f"pp_memory_L{args.layers}_h{args.hidden}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
