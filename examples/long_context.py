"""Long-context training — the reference's ``examples/lobra`` /
``examples/efficiency`` regime (BASELINE config 5): context parallelism
(ring or Ulysses) + per-layer recomputation at the longest sequence the
hardware allows.

Run (CPU simulation, scaled down):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/long_context.py --seq 512 --cp 4
On a TPU slice, raise --seq (32k+) and drop the platform overrides.
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import argparse
import time

import jax

from hetu_tpu import optim
from hetu_tpu.engine import build_train_step, init_state, make_plan
from hetu_tpu.models import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.utils.profiler import sync_result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--cp", type=int, default=4)
    ap.add_argument("--cp-impl", default="ring",
                    choices=["ring", "ulysses"])
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    import dataclasses
    cfg = dataclasses.replace(LlamaConfig.tiny(), max_positions=args.seq,
                              num_layers=2)
    model = LlamaLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    n = len(jax.devices())
    strategy = Strategy(dp=max(1, n // args.cp), cp=args.cp,
                        cp_impl=args.cp_impl, remat="full")
    print(f"strategy: {strategy.to_json()}")
    plan = make_plan(model, opt, strategy)
    state = init_state(model, opt, plan, jax.random.key(0))
    step = build_train_step(model, opt, plan)

    b = strategy.dp
    ids = jax.random.randint(jax.random.key(1), (b, args.seq + 1), 0,
                             cfg.vocab_size)
    batch = plan.shard_batch({"input_ids": ids[:, :-1],
                              "labels": ids[:, 1:]})
    for i in range(args.steps):
        t0 = time.perf_counter()
        state, m = step(state, batch)
        sync_result(m["loss"])
        dt = time.perf_counter() - t0
        print(f"step {i}: loss {float(jax.device_get(m['loss'])):.4f} "
              f"({b * args.seq / dt:.0f} tokens/s)")


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
