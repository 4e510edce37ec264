"""Ring vs Ulysses context parallelism: measured step-time comparison.

Sweeps cp x seq on the available mesh and writes the winners to
``workloads/out/cp_compare.json`` — ``data.hydraulis.preferred_cp_impl``
loads that table to pick per-bucket defaults (measured-profile-first, the
same philosophy as the Galvatron calibration flow).

CPU-mesh RATIOS are meaningful (both impls pay their collectives through
the same fabric); absolute times need the TPU window. Defaults are sized
for the 8-virtual-CPU mesh; pass --seqs 4096,16384 on real hardware.

Reference: AttnCommRing (``hetu/graph/ops/ParallelAttention.h:391-470``)
vs the beyond-reference Ulysses head-scatter (``parallel/ulysses.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax
import jax.numpy as jnp


def measure(cp: int, seq: int, *, heads: int, steps: int, hidden: int,
            layers: int) -> dict:
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan, init_state, build_train_step
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy

    cfg = GPTConfig(vocab_size=512, max_positions=seq, hidden_size=hidden,
                    num_layers=layers, num_heads=heads)
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-4)
    n_dev = len(jax.devices())
    dp = max(1, n_dev // cp)
    out = {}
    for impl in ("ring", "ulysses"):
        strategy = Strategy(dp=dp, cp=cp, cp_impl=impl,
                            remat="full").validate(n_dev)
        plan = make_plan(model, opt, strategy)
        state = init_state(model, opt, plan, jax.random.key(0))
        step = build_train_step(model, opt, plan)
        ids = jax.random.randint(jax.random.key(1), (dp, seq + 1), 0,
                                 cfg.vocab_size)
        batch = plan.shard_batch({"input_ids": ids[:, :-1],
                                  "labels": ids[:, 1:]})
        state, m = step(state, batch)           # compile
        float(jax.device_get(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch)
        float(jax.device_get(m["loss"]))
        out[impl] = (time.perf_counter() - t0) / steps * 1e3
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cps", default="2,4")
    ap.add_argument("--seqs", default=None,
                    help="comma list; default 4096,16384 on TPU, "
                         "1024,4096 on CPU sim")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    # short seqs included on TPU: high-head/short-seq is ulysses's
    # theorized best regime (two dense all_to_alls vs cp-1 ring hops) —
    # the demote-or-promote call (VERDICT r4 item 7) needs those cells
    seqs = [int(s) for s in (args.seqs or
                             ("512,2048,4096,16384" if on_tpu
                              else "1024,4096")
                             ).split(",")]
    cps = [int(c) for c in args.cps.split(",")]

    results = []
    print(f"backend={jax.default_backend()} devices={len(jax.devices())}")
    print(f"{'cp':>3} {'seq':>6} {'ring ms':>9} {'ulysses ms':>11} "
          f"{'ring/ulysses':>13} winner")
    # base grid rows are written UNTAGGED (generic: they decide for any
    # model head count in preferred_cp_impl); only the dedicated
    # high-head block carries a "heads" tag so it decides solely for its
    # own head count
    grid = [(cp, seq, args.heads, args.hidden, False)
            for cp in cps for seq in seqs]
    if on_tpu:
        # high-head block (heads=16): per-head dim shrinks, ring's
        # per-hop KV chunks get skinnier while ulysses's all_to_all
        # volume is head-count-invariant. Skip cells the user's grid
        # already measures (same cp/seq/heads — a second hidden size
        # would write conflicting same-key rows).
        base_keys = {(cp, seq, args.heads) for cp in cps for seq in seqs}
        grid += [(cp, seq, 16, 512, True)
                 for cp in cps for seq in (512, 2048)
                 if (cp, seq, 16) not in base_keys]
    for cp, seq, heads, hidden, tag in grid:
        if heads % cp:
            continue                        # ulysses needs heads % cp == 0
        r = measure(cp, seq, heads=heads, steps=args.steps,
                    hidden=hidden, layers=args.layers)
        ratio = r["ring"] / r["ulysses"]
        winner = "ring" if ratio < 1 else "ulysses"
        row = {"cp": cp, "seq": seq, **r, "winner": winner}
        if tag:
            row["heads"] = heads
        results.append(row)
        print(f"{cp:>3} {seq:>6} h{heads:<3} {r['ring']:>9.1f} "
              f"{r['ulysses']:>11.1f} {ratio:>13.2f} {winner}",
              flush=True)

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                       "cp_compare.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"backend": jax.default_backend(),
                   "heads": args.heads, "results": results}, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
