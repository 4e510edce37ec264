"""Run the cost-model calibration on the real chip, print its table and
write ``workloads/out/calibration.json`` (VERDICT r2 item 7).

Usage: python workloads/calibrate_run.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from hetu_tpu import optim
from hetu_tpu.core.dtypes import Policy
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.tools.galvatron import ModelDims, TPUTopology
from hetu_tpu.tools.galvatron.calibrate import (
    calibrate_topology, measure_matmul_efficiency, measure_strategies,
    predicted_times, validate_ranking,
)


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": "needs the TPU chip"}))
        return
    cfg = GPTConfig.small()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-4)
    B, S = 8, 1024
    dims = ModelDims.from_config(cfg, seq_len=S, global_batch=B)
    # hardware-true constants: peak by the device's kind, HBM from the
    # allocator's own limit (an unknown kind raises — the calibration
    # file must not bake one chip's specs onto another)
    from hetu_tpu.tools.galvatron.cost_model import device_spec
    spec = device_spec(dev)
    peak, hbm = spec["peak_flops"], spec["hbm_bytes"]
    topo = TPUTopology(num_devices=1, peak_flops=peak, hbm_bytes=hbm)

    print(f"== device {getattr(dev, 'device_kind', '?')}: peak "
          f"{peak/1e12:.0f} TF/s, HBM {hbm/1e9:.0f} GB ==")
    print("== MXU efficiency curve ==")
    for shape, eff in measure_matmul_efficiency(peak).items():
        print(f"  {shape}: {eff:.3f}")

    params = model.init(jax.random.key(0), dtype=jnp.bfloat16)
    ids = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    batch = {"input_ids": ids, "labels": ids}
    cal = calibrate_topology(model, params, batch, topo, dims)
    print(f"== calibrated mxu_efficiency: {cal.mxu_efficiency:.3f} ==")
    del params

    strategies = [
        Strategy(),
        Strategy(remat="selective"),
        Strategy(remat="full"),
        Strategy(num_microbatches=4),
        Strategy(remat="full", num_microbatches=4),
    ]
    pol = Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)
    measured = measure_strategies(model, opt, strategies, (B, S),
                                  cfg.vocab_size, policy=pol)
    predicted = predicted_times(dims, strategies, cal)
    print("\nstrategy                          measured_ms predicted_ms")
    for st, m, p in zip(strategies, measured, predicted):
        tag = f"remat={st.remat},nm={st.num_microbatches}"
        print(f"{tag:<34}{m * 1e3:>10.1f}{p * 1e3:>12.1f}")
    ranking = validate_ranking(measured, predicted)
    print(json.dumps(ranking))

    # persist: TPUTopology.calibrated() loads this by default, making
    # every later search (galvatron/malleus/hydraulis) profile-first
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                       "calibration.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({
            # "measured" marks on-chip numbers (the file in the tree is
            # "aot_anchored": fitted offline to an older step time)
            "source": "measured",
            "device_kind": getattr(dev, "device_kind", "tpu"),
            "peak_flops": peak,
            "hbm_bytes": hbm,
            "mxu_efficiency": cal.mxu_efficiency,
            "measured_ms": [m * 1e3 for m in measured],
            "predicted_ms": [p * 1e3 for p in predicted],
            "strategies": [s.to_json() for s in strategies],
            "ranking": ranking,
        }, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
