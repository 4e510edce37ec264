"""Pretraining example — the reference's ``examples/pretrain/train_hetu.py``
flow on TPU: config → strategy (explicit or auto-searched) → packed data →
Trainer, with checkpointing.

Run (CPU simulation):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/pretrain.py --auto
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import argparse

import jax

from hetu_tpu import optim
from hetu_tpu.data import SyntheticLMDataset, build_data_loader
from hetu_tpu.engine.trainer import Trainer, TrainerConfig
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-rows", type=int, default=8)
    ap.add_argument("--strategy", type=str, default=None,
                    help='Strategy JSON, e.g. \'{"dp": 4, "tp": 2}\'')
    ap.add_argument("--auto", action="store_true",
                    help="pick the strategy with the Galvatron search")
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--config", type=str, default=None,
                    help="YAML experiment config (examples/configs/*.yaml)")
    args = ap.parse_args()

    n = len(jax.devices())
    if args.config:
        from hetu_tpu.utils.config import build_experiment
        exp = build_experiment(args.config)
        cfg, model = exp["model_config"], exp["model"]
        trainer = Trainer(model, optim.adamw(3e-3, weight_decay=0.01),
                          exp["strategy"], config=exp["trainer_config"])
        ds = SyntheticLMDataset(cfg.vocab_size, num_docs=4096, min_len=16,
                                max_len=args.seq_len, seed=0)
        loader = build_data_loader(ds, seq_len=args.seq_len,
                                   batch_rows=args.batch_rows, pack=True)
        trainer.train(loader)
        return

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)

    if args.auto:
        from hetu_tpu.tools.galvatron import (
            ModelDims, TPUTopology, search_uniform,
        )
        dims = ModelDims.from_config(
            cfg, seq_len=args.seq_len,
            global_batch=args.batch_rows)
        # profile-first: measured calibration (workloads/out/
        # calibration.json) seeds the topology when present
        cands = search_uniform(dims, TPUTopology.calibrated(n))
        strategy = cands[0].strategy
        print(f"auto-parallel picked: {strategy.to_json()}")
    elif args.strategy:
        strategy = Strategy.from_json(args.strategy)
    else:
        strategy = Strategy(dp=n)

    if getattr(strategy, "pp", 1) > 1:
        # pp executor decision (compiler-evidence rule — workloads/
        # pp_memory.py --compare-1f1b): scan pipeline when its flush
        # residency fits HBM, host-scheduled 1F1B otherwise
        from hetu_tpu.parallel.pipeline import resolve_pipeline_strategy
        resolved = resolve_pipeline_strategy(
            cfg, strategy, seq_len=args.seq_len,
            global_batch=args.batch_rows)
        if resolved is not strategy:
            print(f"pp executor: promoted to 1F1B "
                  f"({resolved.to_json()}) — scan flush residency "
                  f"exceeds HBM")
            strategy = resolved

    trainer = Trainer(
        model, optim.adamw(3e-3, weight_decay=0.01), strategy,
        config=TrainerConfig(total_steps=args.steps, log_every=5,
                             precision="fp32", ckpt_dir=args.ckpt))
    ds = SyntheticLMDataset(cfg.vocab_size, num_docs=4096, min_len=16,
                            max_len=args.seq_len, seed=0)
    loader = build_data_loader(ds, seq_len=args.seq_len,
                               batch_rows=args.batch_rows, pack=True)
    trainer.train(loader)


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
