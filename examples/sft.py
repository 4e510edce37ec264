"""Full-parameter SFT example — the reference's ``examples/sft`` flow:
instruction-tune a pretrained model end to end (no adapters), loss on
response tokens only, with dropout as the regularizer.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/sft.py
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import numpy as np

from hetu_tpu import optim
from hetu_tpu.engine.sft_trainer import SFTTrainer
from hetu_tpu.engine.trainer import TrainerConfig
from hetu_tpu.models import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel.strategy import Strategy


def main():
    n_dev = len(jax.devices())
    # resid dropout is the conventional SFT regularizer (rates are config
    # fields; the train step threads PRNG keys, eval never drops)
    cfg = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      max_positions=128, resid_pdrop=0.1)
    model = LlamaLMHeadModel(cfg)

    # stands in for loading a pretrained checkpoint
    # (utils.checkpoint.load_checkpoint reshapes any source strategy)
    opt = optim.chain(optim.clip_by_global_norm(1.0),
                      optim.adamw(5e-4, weight_decay=0.01))
    strategy = Strategy(dp=max(1, n_dev // 2), tp=min(2, n_dev))
    trainer = SFTTrainer(model, opt, strategy,
                         config=TrainerConfig(total_steps=30, log_every=10,
                                              precision="fp32"))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=rng.integers(4, 12))
               for _ in range(512)]
    responses = [rng.integers(1, cfg.vocab_size, size=rng.integers(4, 16))
                 for _ in range(512)]
    metrics = trainer.fit(prompts, responses, seq_len=64, batch_size=16)
    print("final:", metrics)


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
