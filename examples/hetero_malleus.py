"""Straggler-aware hetero-parallel training — the reference's
``examples/malleus`` flow on TPU.

Measure per-device speed (StragglerMonitor) → Malleus-style planner emits a
HeteroStrategy (stragglers co-located in a small stage) → hetero executor
trains with per-stage meshes.

Run (CPU simulation):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/hetero_malleus.py
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

from hetu_tpu import optim
from hetu_tpu.engine.malleus import plan_hetero
from hetu_tpu.engine.straggler import StragglerMonitor, StragglerReport
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.hetero import (
    build_hetero_train_step, init_hetero_state, make_hetero_plan,
)


def main():
    devices = jax.devices()
    print(f"devices: {devices}")

    # 1) measure — on shared virtual CPU devices timings are noise, so a
    # synthetic straggler stands in (the planner only sees ratios)
    report = StragglerMonitor(size=512, iters=2).measure(devices)
    if devices[0].platform == "cpu":
        report = StragglerReport(
            times_s={}, ratios={i: 1.0 for i in range(len(devices))})
        report.ratios[len(devices) - 1] = 2.5
    print("straggler ratios:", report.ratios)

    # 2) plan
    cfg = GPTConfig(vocab_size=512, max_positions=128, hidden_size=64,
                    num_layers=6, num_heads=4)
    strategy = plan_hetero(report, num_layers=cfg.num_layers,
                           num_stages=2, max_tp=4, num_microbatches=2)
    print("planned hetero strategy:", strategy.to_json())

    # 3) train
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(3e-3)
    plan = make_hetero_plan(model, strategy)
    state = init_hetero_state(model, opt, plan, jax.random.key(0))
    step = build_hetero_train_step(model, opt, plan)
    ids = jax.random.randint(jax.random.key(1), (8, 65), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    for i in range(10):
        state, m = step(state, batch)
        print(f"step {i}: loss {float(m['loss']):.4f} "
              f"gnorm {float(m['grad_norm']):.3f}")


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
