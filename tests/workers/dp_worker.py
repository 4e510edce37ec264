"""Worker entry for multi-process tests: DP training across process
boundaries with checkpoint-based resume.

Launched by ``ElasticWorkerPool`` (env: HETU_COORD_PORT/HETU_RANK/
HETU_NUM_PROCS/HETU_GENERATION). Trains a tiny GPT under Strategy(dp=n)
on one CPU device per process, saving a sharded checkpoint every step;
on restart (generation > 0) it resumes from the latest checkpoint.

Fault injection: HETU_DIE_AT_STEP + HETU_DIE_RANK kill that rank with
os._exit(1) in generation 0 right after the step's checkpoint lands.
"""

import json
import os
import sys

sys.path.insert(0, os.environ["HETU_REPO"])

os.environ["JAX_PLATFORMS"] = "cpu"   # CPU workers by design

import jax

import numpy as np

from hetu_tpu import optim
from hetu_tpu.engine import build_train_step, init_state, make_plan
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.rpc.launcher import bootstrap_distributed
from hetu_tpu.utils.dist_checkpoint import (
    load_checkpoint_distributed, save_checkpoint_distributed,
)


def main():
    out_dir = os.environ["HETU_OUT"]
    total_steps = int(os.environ.get("HETU_STEPS", "4"))
    die_at = int(os.environ.get("HETU_DIE_AT_STEP", "-1"))
    die_rank = int(os.environ.get("HETU_DIE_RANK", "-1"))

    ctx = bootstrap_distributed()
    assert jax.process_count() == ctx.num_processes

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-2)
    plan = make_plan(model, opt, Strategy(dp=ctx.num_processes))
    ckpt = os.path.join(out_dir, "ckpt")

    if ctx.generation > 0 and os.path.exists(
            os.path.join(ckpt, "meta.json")):
        state = load_checkpoint_distributed(ckpt, model, opt, plan=plan)
    else:
        state = init_state(model, opt, plan, jax.random.key(0))
    start_step = int(jax.device_get(state.step))

    step_fn = build_train_step(model, opt, plan)
    rng = np.random.RandomState(0)  # same data stream on every rank
    ids = rng.randint(0, cfg.vocab_size, (2 * ctx.num_processes, 65))
    batch = plan.shard_batch({"input_ids": ids[:, :-1],
                              "labels": ids[:, 1:]})

    losses = []
    for s in range(start_step, total_steps):
        state, metrics = step_fn(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
        save_checkpoint_distributed(ckpt, state)
        ctx.client.barrier(f"step{s}-g{ctx.generation}",
                           ctx.num_processes, f"w{ctx.rank}")
        if ctx.generation == 0 and s + 1 == die_at \
                and ctx.rank == die_rank:
            os._exit(1)

    with open(os.path.join(
            out_dir, f"result-g{ctx.generation}-r{ctx.rank}.json"),
            "w") as f:
        json.dump({"rank": ctx.rank, "generation": ctx.generation,
                   "start_step": start_step,
                   "final_step": int(jax.device_get(state.step)),
                   "losses": losses}, f)
    ctx.shutdown()


if __name__ == "__main__":
    main()
