"""Online rollout loop: the train↔serve cycle the fleet plane exists
for (RLHF / online-distillation shape, SURVEY §3.4's HotSPa scenario).

One process, the full cycle, every round:

1. **rollout** — the fleet Router fans ``generate_many`` prompts over N
   ServingEngine replicas (load-aware + prefix-sticky dispatch);
2. **train** — the (prompt, rollout) pairs feed ``engine/sft_trainer``
   (response-masked loss), a few optimizer steps;
3. **publish** — ``WeightPublisher`` pushes the trainer's new params
   into every replica, rolling drain → swap → resume, while a trickle
   of concurrent requests keeps hitting the fleet — the continuity
   ledger (submitted == completed, zero rejected) is the zero-downtime
   evidence, and every replica lands on the new weight generation.

Self-distillation on random tokens is not meant to LEARN anything
interesting — the workload exercises the plumbing end to end and
reports the signals that matter: per-round rollout throughput, train
loss, push duration, requeues, and the continuity ledger. CPU-runnable
(tiny model); on TPU pass ``--model small``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np


def run_rollout_loop(*, rounds: int = 2, n_replicas: int = 2,
                     prompts_per_round: int = 8, max_tokens: int = 8,
                     steps_per_round: int = 4, model_size: str = "tiny",
                     slots: int = 4, max_len: int = 64,
                     prefill_chunk: int = 16, seq_len: int = 32,
                     batch_size: int = 4, lr: float = 1e-3,
                     trickle: int = 4, seed: int = 0) -> dict:
    """Drive ``rounds`` full rollout→train→publish cycles; returns the
    summary dict (per-round stats + the continuity ledger)."""
    from hetu_tpu import optim, telemetry
    from hetu_tpu.engine.sft_trainer import SFTTrainer
    from hetu_tpu.engine.trainer import TrainerConfig
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.rpc.launcher import launch_serving_fleet
    from hetu_tpu.serving import (
        SamplingParams, ServingEngine, WeightPublisher,
    )

    telemetry.enable(True)
    cfg = GPTConfig.small() if model_size == "small" else GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    trainer = SFTTrainer(
        model, optim.adamw(lr), Strategy(),
        TrainerConfig(total_steps=steps_per_round, log_every=1,
                      precision="fp32"))
    trainer.initialize(jax.random.key(seed))

    def copy_params():
        # replicas must never alias the trainer's buffers: the train
        # step DONATES its state (serving.router.materialize_params
        # does the same on every later push)
        return jax.tree.map(
            lambda x: jnp.array(x, copy=True)
            if isinstance(x, jax.Array) else x, trainer.state.params)

    fleet = launch_serving_fleet(
        lambda i: ServingEngine(model, copy_params(), slots=slots,
                                max_len=max_len,
                                prefill_chunk=prefill_chunk),
        n_replicas)
    publisher = WeightPublisher(fleet.router)
    rng = np.random.default_rng(seed)
    sp = SamplingParams(max_tokens=max_tokens)
    plen_hi = max_len - max_tokens - 1
    ledger = {"submitted": 0, "completed": 0, "rejected": 0}
    per_round = []
    try:
        for rnd in range(rounds):
            prompts = [rng.integers(
                1, cfg.vocab_size,
                (int(rng.integers(4, min(16, plen_hi))),)).tolist()
                for _ in range(prompts_per_round)]
            t0 = time.perf_counter()
            outs = fleet.router.generate_many(prompts, sp)
            roll_s = time.perf_counter() - t0
            history = trainer.fit(
                [np.asarray(p, np.int32) for p in prompts],
                [np.asarray(o, np.int32) for o in outs],
                seq_len=seq_len, batch_size=batch_size,
                steps=steps_per_round, shuffle=False)
            loss = next((h["loss"] for h in reversed(history)
                         if "loss" in h), None)
            # publish under a concurrent trickle: the continuity ledger
            # is the zero-downtime proof the bench + tests assert on
            trickle_reqs = []

            def submit_trickle():
                for _ in range(trickle):
                    p = rng.integers(1, cfg.vocab_size, (6,)).tolist()
                    trickle_reqs.append(fleet.router.submit(p, sp))
                    time.sleep(0.002)

            t = threading.Thread(target=submit_trickle)
            t.start()
            push = publisher.publish(trainer.state)
            t.join()
            for r in trickle_reqs:
                r.done.wait(60.0)
                ledger["submitted"] += 1
                ledger["completed"] += int(r.status == "done")
                ledger["rejected"] += int(r.status == "rejected")
            fleet_doc = fleet.router.fleet_status()
            per_round.append({
                "round": rnd,
                "rollout_tokens": sum(len(o) for o in outs),
                "rollout_s": round(roll_s, 3),
                "loss": None if loss is None else round(float(loss), 4),
                "push_ms": push["duration_ms"],
                "weight_version": push["version"],
                "fleet_versions": fleet_doc["weight_versions"],
                "requeues_total": fleet_doc["requeues_total"],
            })
    finally:
        fleet.stop()
    return {
        "rounds": per_round,
        "continuity": ledger,
        "replicas": n_replicas,
        "zero_downtime": ledger["submitted"] == ledger["completed"]
        and ledger["rejected"] == 0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--prompts", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--model", default="tiny", choices=("tiny", "small"))
    ap.add_argument("--trickle", type=int, default=4)
    args = ap.parse_args()
    out = run_rollout_loop(
        rounds=args.rounds, n_replicas=args.replicas,
        prompts_per_round=args.prompts, max_tokens=args.max_tokens,
        steps_per_round=args.steps, model_size=args.model,
        trickle=args.trickle)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
