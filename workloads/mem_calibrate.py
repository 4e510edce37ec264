"""Calibrate the auto-parallel search's MEMORY model against compiler
ground truth — no TPU window needed (AOT topology compilation).

The analytic activation model in ``tools/galvatron/cost_model.py`` was
off by 5-16× for scan-flush pipelines before r4 (it even approved the
pp4 no-remat config the compiler refuses). This workload AOT-compiles a
set of real train steps (Pallas attention — the path the bench runs)
for the v5e target, reads XLA's ``memory_analysis()``, solves the
per-row activation-scale the analytic model needs to match it, and
writes the CONSERVATIVE (max) scale to
``workloads/out/mem_calibration.json`` — which
``TPUTopology.calibrated()`` loads so ``CostBreakdown.fits()`` prunes
with measured, not hoped-for, memory.

Usage: python workloads/mem_calibrate.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()

    jax.config.update("jax_platforms", "cpu")   # AOT only: no chip

    from jax.experimental import topologies

    from workloads.aot_check import check_step
    from hetu_tpu.models import GPTConfig
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.tools.galvatron import ModelDims, TPUTopology
    from hetu_tpu.tools.galvatron.cost_model import estimate

    topo8 = topologies.get_topology_desc("v5e:2x4", "tpu")
    d8 = list(topo8.devices)
    cfg = GPTConfig(vocab_size=50257, max_positions=args.seq,
                    hidden_size=768, num_layers=12, num_heads=12)
    # spec topology with NO correction: we are measuring the raw model
    topo = TPUTopology(num_devices=8, peak_flops=197e12,
                       hbm_bytes=int(15.75 * 2 ** 30), mem_scale=1.0)

    # per-row batch: the no-remat row must use a batch that FITS so the
    # compiler yields a number to calibrate against (b16 is refused)
    grid = [
        ("dp2pp4_none_b8", Strategy(dp=2, pp=4, remat="none",
                                    num_microbatches=8), 8),
        ("dp2pp4_sel", Strategy(dp=2, pp=4, remat="selective",
                                num_microbatches=8), args.batch),
        ("dp2pp4_full", Strategy(dp=2, pp=4, remat="full",
                                 num_microbatches=8), args.batch),
        ("dp8_sel", Strategy(dp=8, remat="selective"), args.batch),
        ("dp2pp2tp2_sel", Strategy(dp=2, pp=2, tp=2, remat="selective",
                                   num_microbatches=2), args.batch),
    ]
    rows, scales, remat_scales = [], [], {}
    gib = 2 ** 30
    print(f"{'config':>16} {'model GiB':>10} {'aot GiB':>8} "
          f"{'act scale':>9}")
    for name, strat, batch in grid:
        bdims = ModelDims.from_config(cfg, seq_len=args.seq,
                                      global_batch=batch)
        cb = estimate(bdims, strat, topo)
        try:
            r = check_step(d8, strat, batch=batch, seq=args.seq)
        except Exception as e:
            rows.append({"name": name,
                         "error": f"{type(e).__name__}: {str(e)[:120]}"})
            print(f"{name:>16}   ERROR {str(e)[:80]}", flush=True)
            continue
        meas = r["peak_bytes_est"]
        act_model = max(cb.mem_per_device - cb.mem_params - cb.mem_opt,
                        1.0)
        act_meas = max(meas - cb.mem_params - cb.mem_opt, 0.0)
        scale = act_meas / act_model
        if scale <= 0.05:
            # degenerate (aliasing brought the peak under params+opt):
            # a ~0 scale would turn activation accounting OFF for this
            # remat mode and approve configs the compiler refuses
            rows.append({"name": name, "batch": batch,
                         "aot_peak_bytes": int(meas),
                         "degenerate_scale": round(scale, 4)})
            print(f"{name:>16}   degenerate scale {scale:.3f} — skipped",
                  flush=True)
            continue
        scales.append(scale)
        # conservative per remat mode: the largest underestimate decides
        remat_scales[strat.remat] = round(
            max(remat_scales.get(strat.remat, 0.0), scale), 3)
        rows.append({"name": name, "batch": batch,
                     "model_bytes": int(cb.mem_per_device),
                     "aot_peak_bytes": int(meas),
                     "act_scale": round(scale, 3),
                     "compile_s": r["compile_s"]})
        print(f"{name:>16} {cb.mem_per_device / gib:>10.2f} "
              f"{meas / gib:>8.2f} {scale:>9.2f}", flush=True)

    if not scales:
        print("no successful rows — nothing written")
        return 1
    # conservative: the LARGEST underestimate decides (fits() must not
    # approve a config the compiler refuses); per-remat refinements
    # because the analytic act_factor ratios between modes are off too
    mem_scale = round(max(scales), 3)
    out = {"mem_scale": mem_scale, "remat_scales": remat_scales,
           "backend": "tpu-aot",
           "model": {"batch": args.batch, "seq": args.seq,
                     "layers": 12, "hidden": 768}, "rows": rows}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "out", "mem_calibration.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"mem_scale={mem_scale} → {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
