"""Hot strategy switching example — HotSPa
(reference ``examples/hotspa/llama_hot_switch_trainer.py``): start under
one hybrid-parallel strategy, switch mid-training without losing state,
then switch BACK — the return leg is free (StepCache) and, with
``--precompile``, even the first switch compiles off the critical path.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/hot_switch.py [--trace-dir runs/hotswitch] \
    [--no-step-cache] [--precompile]

A/B the control-plane tax (docs/PERFORMANCE.md):

    python examples/hot_switch.py --trace-dir /tmp/warm
    python examples/hot_switch.py --trace-dir /tmp/cold --no-step-cache
    python -m hetu_tpu.tools.trace_summary /tmp/warm/telemetry.jsonl
    python -m hetu_tpu.tools.trace_summary /tmp/cold/telemetry.jsonl

— the warm run's compile share shrinks and its goodput rises.
"""

import argparse
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

from hetu_tpu import optim
from hetu_tpu.data import SyntheticLMDataset, build_data_loader
from hetu_tpu.engine.trainer import Trainer, TrainerConfig
from hetu_tpu.models import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel.strategy import Strategy


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-dir", default=None,
                    help="export telemetry artifacts here (enables "
                         "telemetry)")
    ap.add_argument("--no-step-cache", action="store_true",
                    help="disable the StepCache (the cache-disabled "
                         "baseline for goodput A/B runs)")
    ap.add_argument("--precompile", action="store_true",
                    help="AOT-compile the switch targets in the "
                         "background before the first switch")
    ap.add_argument("--steps", type=int, default=10,
                    help="steps per phase")
    args = ap.parse_args(argv)

    cfg = LlamaConfig.tiny()
    phase_a = Strategy(dp=2, tp=4)
    phase_b = Strategy(dp=2, cp=4, zero=True, remat="full")
    phase_c = Strategy(dp=2, pp=2, tp=2, num_microbatches=4)
    batch_rows, seq = 8, 64

    trainer = Trainer(
        LlamaLMHeadModel(cfg), optim.adamw(3e-3), phase_a,
        config=TrainerConfig(total_steps=args.steps, log_every=5,
                             precision="fp32",
                             step_cache=not args.no_step_cache,
                             telemetry=bool(args.trace_dir),
                             trace_dir=args.trace_dir))
    if args.precompile:
        # warm the cache for the phases we KNOW are coming while phase A
        # trains — the later set_strategy calls become cache hits. The
        # packed loader emits 4-key batches; the AOT executable is
        # selected by exact batch signature, so the keys must match.
        trainer.precompile([phase_b, phase_c],
                           batch_shape=(batch_rows, seq),
                           batch_keys=("input_ids", "labels",
                                       "positions", "segment_ids"))
    ds = SyntheticLMDataset(cfg.vocab_size, num_docs=1024, min_len=16,
                            max_len=64, seed=0)

    def loader():
        return build_data_loader(ds, seq_len=seq, batch_rows=batch_rows,
                                 pack=True)

    trainer.train(loader(), steps=args.steps)
    # e.g. a long-context phase: switch to context parallelism + ZeRO
    trainer.set_strategy(phase_b)
    trainer.train(loader(), steps=args.steps)
    # and to a pipeline layout
    trainer.set_strategy(phase_c)
    trainer.train(loader(), steps=args.steps)
    # ... and back: with the StepCache this leg never re-traces
    trainer.set_strategy(phase_a)
    trainer.train(loader(), steps=args.steps)

    print(f"step cache: {trainer.cache.stats()}")
    if args.trace_dir:
        from hetu_tpu.tools.trace_summary import summarize
        print(summarize(os.path.join(args.trace_dir, "telemetry.jsonl")))


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
