"""Offline (AOT) time-calibration fallback for the auto-parallel search.

VERDICT r4 missing-item #1: every search entry point resolves
``TPUTopology.calibrated()`` to spec-sheet defaults because the
measured calibration (``workloads/calibrate_run.py``, needs a TPU
window) never ran. This workload needs NO window: libtpu is local, so
XLA's full TPU pipeline — including its per-program cost model — runs
against the offline v5e topology (``jax.experimental.topologies``).

Method (profile→fit→search, the reference Galvatron recipe
``tools/Galvatron/galvatron/profile_hardware`` re-based on compiler
evidence):

1. AOT-compile the SAME five strategies ``calibrate_run.py`` measures
   (GPT-2 small, B8 S1024) plus the headline-bench config (B32,
   selective, unroll) and read ``cost_analysis()``: flops and bytes
   accessed. (XLA's ``optimal_seconds`` is usable for single kernels
   but overflows to NEGATIVE totals on whole train-step programs —
   observed -98440 ms — so wall-time estimates come from a roofline
   over flops/bytes instead.)
2. Anchor the roofline: an on-chip headline measurement (a record
   passed by path, else ``ANCHOR_MS_FALLBACK`` — 367.86 ms at the bench
   config, older than PRs 1-20) fixes the achieved FLOP rate F_eff = flops_anchor /
   t_anchor (the anchor step is compute-bound at MFU 0.36). Each
   strategy's estimate is then max(flops/F_eff, bytes/BW_hbm) with the
   v5e spec HBM bandwidth — compute-bound programs scale by the
   MEASURED rate, memory-bound ones are floored by bandwidth.
3. Fit ``mxu_efficiency`` by inverting the cost model on the anchor
   (single chip, no comm terms: step ≈ flops_model/(eff·peak)).
4. Record a matmul micro table (per-shape flops/bytes/optimal_seconds
   — optimal_seconds IS sane for single-kernel programs) and probe the
   collective cost model on the 8-device topology.

Writes ``workloads/out/calibration.json`` with ``source:
"aot_anchored"`` — ``TPUTopology.calibrated()`` consumes it the same
way as a measured one, and ``calibrate_run.py`` OVERWRITES it with
``source: "measured"`` numbers when a window fires (this script refuses
to clobber a measured file).

Usage: python workloads/aot_calibrate.py [--skip-micro]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_V5E = 197e12
# the 2026-07-31 headline on a v5e — OLDER than PRs 1-20 and today's
# stack, never re-measured; the one place this figure survives
ANCHOR_MS_FALLBACK = 367.86


_ANCHOR_CFG_FALLBACK = {"batch": 32, "remat": "selective", "unroll": True,
                        "param_dtype": "fp32", "ce": "chunked"}


def _anchor_measured_ms(path=None):
    """(step_ms, device, config) of the last on-chip headline. The
    CONFIG matters as much as the time: bench.py may have recorded a
    sweep-winner or combo-adopted program (different batch/dtype/CE),
    and anchoring another program's flops to this time would skew
    f_eff — so the anchor compile below reproduces exactly the recorded
    config (older records without one get the builtin default)."""
    p = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "out", "last_tpu_bench.json")
    try:
        with open(p) as f:
            rec = json.load(f)
        cfg = {**_ANCHOR_CFG_FALLBACK, **rec.get("config", {})}
        return (float(rec["step_time_ms"]),
                rec.get("device", "TPU v5 lite"), cfg)
    except (OSError, ValueError, KeyError):
        return ANCHOR_MS_FALLBACK, "TPU v5 lite", dict(_ANCHOR_CFG_FALLBACK)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-micro", action="store_true",
                    help="skip the matmul/collective micro tables")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from workloads.aot_check import check_step
    from hetu_tpu.models import GPTConfig
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.tools.galvatron import ModelDims, TPUTopology
    from hetu_tpu.tools.galvatron.calibrate import (predicted_times,
                                                    validate_ranking)
    from hetu_tpu.tools.galvatron.cost_model import (CALIBRATION_PATH,
                                                     estimate)

    out_path = args.out or CALIBRATION_PATH
    try:
        with open(out_path) as f:
            if json.load(f).get("source") == "measured":
                print("measured calibration already present — not "
                      "overwriting; rerun with --out to write elsewhere")
                return
    except (OSError, ValueError):
        pass

    topo1 = topologies.get_topology_desc("v5e:2x2", "tpu")
    d1 = list(topo1.devices)[:1]
    anchor_ms, device_kind, acfg = _anchor_measured_ms()
    hbm = int(15.75 * 2 ** 30)

    BW_HBM_V5E = 819e9                   # bytes/s, v5e spec

    # --- 1. anchor: the exact program the recorded headline measured ---
    print(f"== compiling anchor {acfg} ==", flush=True)
    anchor = check_step(d1, Strategy(remat=acfg["remat"],
                                     unroll=bool(acfg["unroll"])),
                        batch=int(acfg["batch"]), seq=1024,
                        ce=acfg.get("ce", "chunked"),
                        param_dtype=acfg.get("param_dtype", "fp32"))
    if not anchor.get("flops"):
        raise SystemExit(f"anchor compile gave no cost analysis: {anchor}")
    f_eff = anchor["flops"] / (anchor_ms / 1e3)
    print(f"anchor: {anchor['flops']/1e12:.1f} TFLOP in {anchor_ms:.1f}ms"
          f" -> F_eff {f_eff/1e12:.1f} TF/s "
          f"({f_eff/PEAK_V5E:.3f} of peak)", flush=True)

    def roofline_ms(row):
        t = max(row["flops"] / f_eff,
                row.get("bytes_accessed", 0.0) / BW_HBM_V5E)
        return t * 1e3

    # --- 2. the calibrate_run strategy set, anchored ---------------------
    strategies = [
        Strategy(),
        Strategy(remat="selective"),
        Strategy(remat="full"),
        Strategy(num_microbatches=4),
        Strategy(remat="full", num_microbatches=4),
    ]
    B, S = 8, 1024
    rows, anchored_ms = [], []
    for st in strategies:
        tag = f"remat={st.remat},nm={st.num_microbatches}"
        r = check_step(d1, st, batch=B, seq=S)
        if not r.get("flops"):
            raise SystemExit(f"{tag}: no cost analysis: {r}")
        # XLA cost analysis counts a lax.scan BODY once, not trip-count
        # times (observed: nm=4 grad-accum steps report ~flops/4), so
        # microbatched steps get the trip multiplier back. Known residual:
        # remat recompute is also nearly invisible to the analysis (+2%
        # where the analytic model says +33%) — the anchored table
        # therefore ranks remat modes by their BYTES, not recompute.
        nm = max(st.num_microbatches, 1)
        r = dict(r, flops=r["flops"] * nm,
                 bytes_accessed=r.get("bytes_accessed", 0.0) * nm)
        ms = roofline_ms(r)
        anchored_ms.append(ms)
        rows.append({"strategy": tag, "anchored_ms": ms,
                     "flops": r.get("flops"),
                     "bytes_accessed": r.get("bytes_accessed"),
                     "scan_trip_correction": nm,
                     "compile_s": r["compile_s"]})
        print(f"  {tag:<28} {r['flops']/1e12:6.2f} TFLOP "
              f"anchored {ms:7.1f}ms", flush=True)

    # --- 3. mxu_efficiency from the anchor -------------------------------
    # single chip: estimate() has no comm terms, so step ∝ 1/eff exactly
    dims32 = ModelDims.from_config(GPTConfig.small(), seq_len=1024,
                                   global_batch=int(acfg["batch"]))
    eff0 = 0.5
    t0 = estimate(dims32, Strategy(remat=acfg["remat"],
                                   unroll=bool(acfg["unroll"])),
                  TPUTopology(1, peak_flops=PEAK_V5E, hbm_bytes=hbm,
                              mxu_efficiency=eff0)).step_time
    eff = float(np.clip(eff0 * t0 / (anchor_ms / 1e3), 0.05, 1.0))
    print(f"fitted mxu_efficiency = {eff:.3f}")

    micro = {}
    if not args.skip_micro:
        # --- 4a. matmul roofline table (XLA cost model per shape) --------
        mesh = Mesh(np.array(d1), ("x",))
        rep = NamedSharding(mesh, P())
        for m in (256, 1024, 4096, 8192):
            a = jax.ShapeDtypeStruct((m, 4096), jnp.bfloat16, sharding=rep)
            b = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16,
                                     sharding=rep)
            c = jax.jit(jnp.matmul, out_shardings=rep).lower(a, b).compile()
            ca = c.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
            fl, osec = ca.get("flops", 0.0), ca.get("optimal_seconds", 0.0)
            if osec > 0:
                micro[f"matmul_{m}x4096x4096"] = {
                    "flops": fl, "optimal_seconds": osec,
                    "xla_modeled_tflops": fl / osec / 1e12}
        # --- 4b. collective cost probe on the 8-device ring --------------
        topo8 = topologies.get_topology_desc("v5e:2x4", "tpu")
        mesh8 = Mesh(np.array(list(topo8.devices)), ("x",))
        spec = NamedSharding(mesh8, P("x"))
        nbytes = 32 * 2 ** 20
        x = jax.ShapeDtypeStruct((8, nbytes // 4), jnp.float32,
                                 sharding=spec)
        try:
            from jax.experimental.shard_map import shard_map
            f8 = jax.jit(shard_map(
                lambda v: jax.lax.psum(v, "x"), mesh=mesh8,
                in_specs=P("x"), out_specs=P(None)))
            c8 = f8.lower(x).compile()
            ca8 = c8.cost_analysis()
            ca8 = ca8[0] if isinstance(ca8, (list, tuple)) else (ca8 or {})
            osec = float(ca8.get("optimal_seconds", 0.0))
            if osec > 0:
                # ring allreduce moves 2(n-1)/n·bytes per link
                per_dev = nbytes
                bw = 2 * 7 / 8 * per_dev / osec
                micro["psum_32MiB_8dev"] = {
                    "optimal_seconds": osec,
                    "xla_modeled_ici_bw": bw}
                print(f"collective probe: XLA-modeled ici bw "
                      f"{bw/1e9:.1f} GB/s (spec 90)")
        except Exception as e:                      # noqa: BLE001
            print(f"collective probe skipped: {type(e).__name__}: "
                  f"{str(e)[:120]}")

    # --- predictions + ranking ------------------------------------------
    dims8 = ModelDims.from_config(GPTConfig.small(), seq_len=S,
                                  global_batch=B)
    cal_topo = TPUTopology(1, peak_flops=PEAK_V5E, hbm_bytes=hbm,
                           mxu_efficiency=eff)
    pred = predicted_times(dims8, strategies, cal_topo)
    ranking = validate_ranking(anchored_ms, [p * 1e3 for p in pred])
    print(json.dumps(ranking))

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({
            "source": "aot_anchored",
            "device_kind": device_kind,
            "anchor_step_ms": anchor_ms,
            "anchor_config": acfg,
            "anchor_f_eff": f_eff,
            "peak_flops": PEAK_V5E,
            "hbm_bytes": hbm,
            "mxu_efficiency": eff,
            "measured_ms": anchored_ms,
            "predicted_ms": [p * 1e3 for p in pred],
            "strategies": [s.to_json() for s in strategies],
            "ranking": ranking,
            "rows": rows,
            "micro": micro,
        }, f, indent=1)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
