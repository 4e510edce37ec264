"""Flash-kernel block-size autotune on the real chip.

Sweeps (block_q, block_k) for fwd and fwd+bwd at representative shapes —
including the bench shape (batch 32, heads 12, seq 1024) — and records the
winners to ``workloads/out/flash_blocks.json``, which
``ops.flash_pallas`` consults for its default tiling on TPU.

Timing runs the kernel inside ONE jit via ``lax.scan`` (iterations
chained through a negligible 1e-30 feedback term so XLA cannot hoist or
dead-code them): per-call dispatch costs host time, which would
otherwise swamp sub-ms kernels and make every block choice look
identical.

Usage: python workloads/flash_tune.py [--iters 32]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from hetu_tpu.ops.flash_pallas import flash_attention_pallas
from workloads._timing import scan_loop, scan_loop_grad, time_loop_ms

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "out", "flash_blocks.json")

# (batch, seq, heads, head_dim, iters): bench shape first, then
# long-context — iters shrink as the quadratic cost grows (32k causal is
# ~0.5 s/call; 4 chained iterations amortize dispatch well enough)
SHAPES = [(32, 1024, 12, 64, 32), (4, 2048, 16, 64, 32),
          (2, 4096, 16, 64, 16), (1, 8192, 16, 64, 8),
          (1, 32768, 16, 64, 4)]




def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=32)
    args = ap.parse_args()

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "autotune needs the TPU chip"}))
        return
    kind = jax.devices()[0].device_kind

    entries = []
    for b, s, h, d, iters in SHAPES:
        q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.bfloat16)
        v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.bfloat16)
        blocks = [x for x in (128, 256, 512, 1024) if s % x == 0]
        if s >= 16384:
            # long-context: each config costs seconds of device time plus
            # a long compile — only sweep the plausible tilings
            blocks = [x for x in blocks if x >= 512]
        rows = []
        for bq in blocks:
            for bk in blocks:
                def f(q, k, v, bq=bq, bk=bk):
                    return flash_attention_pallas(
                        q, k, v, causal=True, interpret=False,
                        block_q=bq, block_k=bk)
                try:
                    f_ms = time_loop_ms(scan_loop(f, iters),
                                        (q, k, v), iters)
                    b_ms = time_loop_ms(scan_loop_grad(f, iters),
                                        (q, k, v), iters)
                except Exception as e:
                    rows.append({"bq": bq, "bk": bk, "error": str(e)[:80]})
                    continue
                rec = {"bq": bq, "bk": bk, "fwd_ms": round(f_ms, 3),
                       "bwd_ms": round(b_ms, 3)}
                rows.append(rec)
                print(json.dumps({"shape": [b, s, h, d], **rec}), flush=True)
        ok = [r for r in rows if "fwd_ms" in r]
        if ok:
            best_f = min(ok, key=lambda r: r["fwd_ms"])
            best_b = min(ok, key=lambda r: r["bwd_ms"])
            entries.append({"seq": s, "batch": b, "heads": h, "head_dim": d,
                            "fwd": [best_f["bq"], best_f["bk"]],
                            "bwd": [best_b["bq"], best_b["bk"]],
                            "fwd_ms": best_f["fwd_ms"],
                            "bwd_ms": best_b["bwd_ms"]})
            print(json.dumps({"seq": s, "best_fwd": best_f,
                              "best_bwd": best_b}), flush=True)

    if entries:
        os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
        with open(OUT_PATH, "w") as f:
            json.dump({"device": kind, "entries": entries}, f, indent=1)
        print(f"wrote {OUT_PATH}")


if __name__ == "__main__":
    main()
