"""Dynamic sequence-length training — the reference's
``examples/hydraulis`` flow (``examples/hydraulis/strategy/
new_planning.py``): train a BPE tokenizer in-tree, bucket the corpus by
length, plan per-bucket batch composition AND a per-bucket parallel
strategy with the cost model (short buckets dp-heavy + no remat, the
long bucket remat'd; cp candidates compete too and win when sequences
outgrow what remat can fix), then train the mixed stream in ONE run —
the Trainer hot-switches the live state between plans at bucket
boundaries through its plan pool.

Run (CPU simulation):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/hydraulis_dynamic.py
"""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import dataclasses

import jax
import numpy as np

from hetu_tpu import optim
from hetu_tpu.data.bucket import SeqLenBuckets
from hetu_tpu.data.hydraulis import DynamicDispatcher, plan_buckets
from hetu_tpu.data.tokenizers import train_bpe
from hetu_tpu.engine.trainer import Trainer, TrainerConfig
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.tools.galvatron import ModelDims, TPUTopology
from hetu_tpu.tools.galvatron.cost_model import estimate


def main():
    # corpus with a bimodal length distribution
    rs = np.random.RandomState(0)
    words = ["alpha", "beta", "gamma", "delta", "tokens", "mesh", "ring"]
    texts = [" ".join(rs.choice(words, size=int(n)))
             for n in np.concatenate([rs.randint(5, 30, 80),
                                      rs.randint(80, 200, 20)])]
    tok = train_bpe(texts, vocab_size=400)
    seqs = [np.asarray(tok.encode(t), np.int32) for t in texts]
    print(f"tokenizer vocab={tok.vocab_size}, docs={len(seqs)}")

    cfg = GPTConfig(vocab_size=512, max_positions=512, hidden_size=64,
                    num_layers=2, num_heads=4)
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)

    # per-bucket strategies from the cost model (profile-first: a
    # measured/AOT calibration seeds the topology when present)
    n_dev = len(jax.devices())
    # global_batch is a placeholder: plan_buckets re-derives it per
    # bucket (rows at that length) before every estimate
    dims = ModelDims.from_config(cfg, seq_len=512, global_batch=n_dev)
    topo = TPUTopology.calibrated(n_dev)
    # the toy model fits everything on a real chip, so simulate a
    # memory-tight device: HBM set between "no remat at the longest
    # bucket" (too big) and "full remat" (fits), making the planner
    # assign DIFFERENT strategies per bucket — the regime where
    # Hydraulis' per-bucket planning earns its keep
    buckets = SeqLenBuckets(min_len=32, max_len=512)
    lmax = max(buckets.group([len(s) - 1 for s in seqs]))
    dmax = dataclasses.replace(dims, seq_len=lmax, global_batch=n_dev)
    hi = estimate(dmax, Strategy(dp=n_dev), topo).mem_per_device
    lo = estimate(dmax, Strategy(dp=n_dev // 2, cp=2, remat="full"),
                  topo).mem_per_device
    topo = dataclasses.replace(topo, hbm_bytes=(hi + lo) / 2)
    plans = plan_buckets([len(s) - 1 for s in seqs], buckets=buckets,
                         token_budget=512, dims_base=dims, topo=topo,
                         max_cp=2, row_multiple=n_dev)
    for L, p in sorted(plans.items()):
        st = p.strategy
        print(f"bucket {L:4d}: rows={p.batch_rows:3d} strategy="
              f"dp{st.dp}xcp{st.cp} remat={st.remat} "
              f"est={p.est_step_ms:.1f}ms")

    # ONE run over the mixed stream: the Trainer routes each bucket to
    # its own plan, hot-switching the live state at bucket boundaries
    trainer = Trainer(model, opt, plans[min(plans)].strategy,
                      TrainerConfig(log_every=1, precision="fp32"))
    disp = DynamicDispatcher(plans)
    hist = trainer.train_dynamic(disp, seqs, use_bucket_strategies=True)
    for h in hist:
        print(f"step {int(h['step']):3d} bucket {int(h['bucket']):4d} "
              f"loss {h['loss']:.4f} strategy {h['strategy']}")
    used = {h["strategy"] for h in hist}
    print(f"pad fraction: {disp.stats.pad_fraction:.2%}; "
          f"{len(used)} distinct plans in one run")


if __name__ == "__main__":
    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    enable_persistent_compilation_cache()
    main()
