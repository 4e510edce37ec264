"""Bottleneck profile of the headline bench step on the real chip.

Produces, in priority order (a short window must get the cheap parts):
1. per-module fwd/bwd timing table (embed / block / head),
2. device memory stats + train-state memory breakdown,
3. an xplane trace of a few steps (TensorBoard/Perfetto viewable) under
   ``workloads/out/xplane/`` for op-level analysis.

Run: python workloads/profile_step.py  (TPU; CPU works for smoke)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax
import jax.numpy as jnp


def main():
    from hetu_tpu import optim
    from hetu_tpu.core.dtypes import Policy, autocast
    from hetu_tpu.engine import make_plan, init_state, build_train_step
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.utils.profiler import (
        device_memory_stats, format_module_table, memory_breakdown,
        profile_modules, xla_trace,
    )

    on_tpu = jax.default_backend() == "tpu"
    cfg = GPTConfig.small() if on_tpu else GPTConfig.tiny()
    # profile bench.py's headline config (batch 32 x 1024, fp32 params,
    # bf16 compute, selective remat, unrolled layers) so the bottleneck
    # table reflects what bench.py measures
    B, S = (32, 1024) if on_tpu else (4, 64)
    model = GPTLMHeadModel(cfg)
    pol = Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16) \
        if on_tpu else Policy()

    def run(B):
        with autocast(pol):
            params = model.init(jax.random.key(0))
            ids = jax.random.randint(jax.random.key(1), (B, S), 0,
                                     cfg.vocab_size)
            batch = {"input_ids": ids, "labels": ids}
            print(f"== per-module fwd/bwd (ms), batch {B} ==")
            print(format_module_table(profile_modules(model, params, batch)))
            del params

            opt = optim.adamw(1e-4)
            strategy = Strategy(remat="selective", unroll=True) \
                if on_tpu else Strategy()
            plan = make_plan(model, opt, strategy)
            state = init_state(model, opt, plan, jax.random.key(0))
            step = build_train_step(model, opt, plan)
            sbatch = plan.shard_batch(batch)
            state, m = step(state, sbatch)          # compile
            float(jax.device_get(m["loss"]))

            print("\n== device memory ==")
            for k, v in device_memory_stats().items():
                print(f"  {k}: {v}")
            print("\n== state/batch bytes ==")
            for k, v in memory_breakdown(state, batch=sbatch).items():
                print(f"  {k}: {v / 1e6:.1f} MB")

            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "out", "xplane")
            with xla_trace(out):
                for _ in range(5):
                    state, m = step(state, sbatch)
                float(jax.device_get(m["loss"]))
            print(f"\nxplane trace written under {out}")

    # OOM fallback chain like bench.py's: the sweep winner's batch is
    # known to fit a train step, but profiling holds extra buffers
    from bench import is_oom
    while True:
        try:
            run(B)
            break
        except Exception as e:
            if B <= 4 or not is_oom(e):
                raise
            print(f"batch {B} OOM during profiling — retrying at {B // 2}")
            B //= 2


if __name__ == "__main__":
    main()
