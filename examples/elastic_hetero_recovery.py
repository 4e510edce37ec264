"""Live elastic recovery onto a heterogeneous pipeline (Ampelos flow).

The reference's Ampelos planner re-plans around dead devices instead of
stranding survivors (``python/hetu/engine/strategy_ampelos.py:906``):
when the surviving device count is not a power of two, the recovery
strategy is a hetero pipeline whose pow2-wide stages sum to exactly the
survivor count. This example drives the whole loop on the 8-device CPU
simulation:

  1. train GPT-tiny on dp2 x tp4 (8 devices),
  2. "lose" devices 2 and 3 (6 survivors, non-contiguous ids),
  3. ``ElasticController.recovery_plan`` emits a hetero 4+2 pipeline
     that keeps all 6 survivors busy (vs 4 on the stranded-uniform plan),
  4. ``Trainer.shrink_to`` hot-switches the LIVE state onto it — no
     checkpoint is read — and training continues.

Run: python examples/elastic_hetero_recovery.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import re
_flags = os.environ.get("XLA_FLAGS", "")
# this example needs exactly 8 simulated devices — replace any existing
# count flag rather than silently keeping a different one
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", _flags)
os.environ["XLA_FLAGS"] = \
    _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu import optim
from hetu_tpu.engine.elastic import ElasticController
from hetu_tpu.engine.trainer import Trainer, TrainerConfig
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.hetero import HeteroStrategy
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.tools.galvatron import ModelDims, TPUTopology


def main():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    trainer = Trainer(model, optim.adamw(3e-3), Strategy(dp=2, tp=4),
                      TrainerConfig(total_steps=3, log_every=1))

    rng = np.random.RandomState(0)

    def batches(n):
        out = []
        for _ in range(n):
            ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 33)))
            out.append({"input_ids": ids[:, :-1], "labels": ids[:, 1:]})
        return out

    trainer.train(batches(3))
    step0 = int(jax.device_get(trainer.state.step))
    print(f"trained to step {step0} on dp2xtp4 (8 devices)")

    # devices 2 and 3 "die": 6 survivors with a hole in the id space
    alive_ids = [0, 1, 4, 5, 6, 7]
    survivors = [d for d in jax.devices() if d.id in alive_ids]
    dims = ModelDims.from_config(cfg, seq_len=32, global_batch=8)
    # recovery_plan is a staticmethod: usable without a live coordinator
    strat = ElasticController.recovery_plan(
        dims, TPUTopology(num_devices=8), n_alive_devices=len(survivors),
        num_layers=cfg.num_layers, alive_device_ids=alive_ids)
    assert isinstance(strat, HeteroStrategy), strat
    print("recovery strategy:", strat.to_json())

    trainer.shrink_to(survivors, strat)
    used = sorted({d.id for m in trainer.plan.meshes
                   for d in m.devices.flat})
    assert used == alive_ids, used
    print(f"hot-switched live state onto {used} (no checkpoint read)")

    trainer.train(batches(2), steps=2)
    print(f"continued to step {int(jax.device_get(trainer.state.step))} "
          f"on the hetero pipeline — recovery complete")


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
