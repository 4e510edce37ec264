"""Pipeline executor at depth: compile time + memory vs layers and remat.

VERDICT r2 item 9: the single-jit scan pipeline saves activations for all
``nm + pp - 1`` ticks unless remat is on — measure where that bites.
Runs on the 8-device CPU mesh (compile + step walltime; allocator stats
where the backend reports them) and on real hardware unchanged.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python workloads/pipeline_depth.py [--layers 24] [--pp 4]
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

from hetu_tpu import optim
from hetu_tpu.engine import build_train_step, init_state, make_plan
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.utils.profiler import device_memory_stats, sync_result


def measure(cfg, strategy, batch_rows, seq):
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-4)
    plan = make_plan(model, opt, strategy)
    state = init_state(model, opt, plan, jax.random.key(0),
                       dtype=jnp.float32)
    step = build_train_step(model, opt, plan)
    ids = jax.random.randint(jax.random.key(1), (batch_rows, seq + 1), 0,
                             cfg.vocab_size)
    b = plan.shard_batch({"input_ids": ids[:, :-1], "labels": ids[:, 1:]})

    t0 = time.perf_counter()
    state, m = step(state, b)          # trace + compile + run
    sync_result(m["loss"])
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(3):
        state, m = step(state, b)
    sync_result(m["loss"])
    step_s = (time.perf_counter() - t0) / 3
    mem = device_memory_stats()
    return {"compile_s": round(compile_s, 1),
            "step_ms": round(step_s * 1e3, 1),
            "loss": round(float(jax.device_get(m["loss"])), 3),
            "peak_bytes": mem.get("peak_bytes_in_use")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    cfg = GPTConfig(vocab_size=2048, max_positions=args.seq,
                    hidden_size=args.hidden, num_layers=args.layers,
                    num_heads=args.hidden // 64)
    n = len(jax.devices())
    dp = max(1, n // args.pp)
    for remat in ("none", "full"):
        strategy = Strategy(dp=dp, pp=args.pp, num_microbatches=4,
                            remat=remat)
        rec = measure(cfg, strategy, batch_rows=4 * dp, seq=args.seq)
        print(json.dumps({"layers": args.layers, "pp": args.pp,
                          "remat": remat, **rec,
                          "device": jax.devices()[0].platform}))


if __name__ == "__main__":
    main()
