"""LoRA SFT example — the reference's LobRA flow (``examples/lobra``):
freeze a pretrained base, train multi-task LoRA adapters on instruction
pairs.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/lora_sft.py
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import numpy as np

from hetu_tpu import optim
from hetu_tpu.engine.sft_trainer import SFTTrainer
from hetu_tpu.engine.trainer import TrainerConfig
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.peft import (
    LoraConfig, inject_lora, lora_trainable_mask, wrap_params_for_lora,
)


def main():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    base_params = model.init(jax.random.key(0))  # stands in for pretrained

    inject_lora(model, LoraConfig(r=8, num_tasks=1))
    params = wrap_params_for_lora(model, base_params, jax.random.key(1))
    mask = lora_trainable_mask(params)
    opt = optim.masked(optim.adamw(1e-3), mask)

    trainer = SFTTrainer(model, opt, Strategy(dp=len(jax.devices())),
                         config=TrainerConfig(total_steps=20, log_every=5,
                                              precision="fp32"))
    # adopt the migrated params instead of fresh init
    trainer.initialize()
    trainer.state = trainer.state._replace(
        params=jax.device_put(params,
                              trainer.plan.state_shardings.params))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=rng.integers(4, 12))
               for _ in range(256)]
    responses = [rng.integers(1, cfg.vocab_size, size=rng.integers(4, 16))
                 for _ in range(256)]
    trainer.fit(prompts, responses, seq_len=32, batch_size=8)


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
