"""Worker entry for kill-based chaos tests: DP training with a
per-step distributed checkpoint series and INCREMENTAL loss logging.

Unlike ``dp_worker.py`` (which writes its result file only at the end —
a SIGKILLed generation leaves nothing), every completed step appends one
JSON line to ``losses-r{rank}.jsonl`` immediately, so the chaos test can
reconstruct the loss curve of a generation that was killed mid-step.
Checkpoints are saved every step into ONE directory as a delta series
(``delta_base=path``), exactly the production cadence the chaos harness
is meant to interrupt; resume always starts from the newest COMPLETE
step the loader accepts.

Fault injection is EXTERNAL (the launcher's ``pool.kill_worker`` /
``engine.chaos`` env-armed points inherited through the pool env) — this
script has no cooperative exit.
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
    total_steps = int(os.environ.get("HETU_STEPS", "6"))
    resume_from = os.environ.get("HETU_RESUME_FROM")

    ctx = bootstrap_distributed()
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-2)
    plan = make_plan(model, opt, Strategy(dp=ctx.num_processes))
    ckpt = resume_from or os.path.join(out_dir, "ckpt")

    if os.path.exists(os.path.join(ckpt, "meta.json")):
        state = load_checkpoint_distributed(ckpt, model, opt, plan=plan)
    else:
        state = init_state(model, opt, plan, jax.random.key(0))
    start_step = int(jax.device_get(state.step))

    step_fn = build_train_step(model, opt, plan)
    rng = np.random.RandomState(0)  # same data stream on every rank
    ids = rng.randint(0, cfg.vocab_size, (2 * ctx.num_processes, 65))
    batch = plan.shard_batch({"input_ids": ids[:, :-1],
                              "labels": ids[:, 1:]})

    loss_log = os.path.join(out_dir, f"losses-r{ctx.rank}.jsonl")
    ckpt_dir = os.path.join(out_dir, "ckpt")
    for s in range(start_step, total_steps):
        state, metrics = step_fn(state, batch)
        loss = float(jax.device_get(metrics["loss"]))
        delta = os.path.exists(os.path.join(
            ckpt_dir, f"index-host{ctx.rank:05d}.json"))
        w = save_checkpoint_distributed(
            ckpt_dir, state, delta_base=ckpt_dir if delta else None)
        w.wait()
        # one line per COMPLETED step, flushed before the barrier: the
        # chaos test's forensic record survives a SIGKILL one step later
        with open(loss_log, "a") as f:
            f.write(json.dumps({"gen": ctx.generation, "step": s,
                                "loss": loss}) + "\n")
            f.flush()
            os.fsync(f.fileno())
        ctx.client.barrier(f"step{s}-g{ctx.generation}",
                           ctx.num_processes, f"w{ctx.rank}")

    with open(os.path.join(
            out_dir, f"done-g{ctx.generation}-r{ctx.rank}.json"),
            "w") as f:
        json.dump({"rank": ctx.rank, "generation": ctx.generation,
                   "start_step": start_step,
                   "final_step": int(jax.device_get(state.step))}, f)
    ctx.shutdown()


if __name__ == "__main__":
    main()
