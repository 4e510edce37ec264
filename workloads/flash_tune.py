"""Flash-kernel compute-tile autotune on the real chip.

Sweeps the compute sub-tile (block_q, block_k) of the three kernels
APART — forward, dq, dk/dv — at representative shapes, the bench shape
(batch 32, heads 12, seq 1024) first, on PACKED rows: segment ids drawn
as ``pretrain-packed-1k`` draws them (documents of half a row to a whole
row, first-fit packed, a trailing pad id), so the rows have the dead,
interior and edge tiles the kernels walk by class
(``ops.flash_pallas.tile_classes``, printed beside each time). It
prints every time and, a shape, the fastest tile of each kernel ("bwd":
the tile at which dq + dk/dv is least) and chooses nothing: the tile
the kernels take is the static rule of
``ops.flash_pallas._default_blocks``, which these sweeps set (PERF.md,
PR 40). ``--out FILE`` keeps the winners as JSON.

Timing runs the kernel inside ONE jit via ``lax.scan`` (iterations
chained through a negligible 1e-30 feedback term so XLA cannot hoist or
dead-code them): per-call dispatch costs host time, which would
otherwise swamp sub-ms kernels and make every block choice look
identical. The backward's two calls are timed apart by taking only dq,
or only dk + dv, of ``_flash_bwd``: XLA drops the call nobody reads.

Usage: python workloads/flash_tune.py [--shapes N] [--seed S] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.data.packing import pack_sequences
from hetu_tpu.ops.flash_pallas import _flash_bwd, _flash_fwd, tile_classes
from workloads._timing import scan_loop, time_loop_ms

# (batch, seq, heads, head_dim, iters): bench shape first, then
# long-context — iters shrink as the quadratic cost grows (32k causal is
# ~0.5 s/call; 4 chained iterations amortize dispatch well enough)
SHAPES = [(32, 1024, 12, 64, 32), (4, 2048, 16, 64, 32),
          (2, 4096, 16, 64, 16), (1, 8192, 16, 64, 8),
          (1, 32768, 16, 64, 4)]
KERNELS = ("fwd", "dq", "dkv")


def packed_segment_ids(rows: int, seq: int, rng) -> np.ndarray:
    """(rows, seq) ids as the train cell's loader packs them: documents
    of seq/2..seq tokens (uniform), first fit, a trailing pad id."""
    docs = [np.zeros(n, np.int32)
            for n in rng.integers(seq // 2, seq + 1, 2 * rows)]
    return pack_sequences(docs, seq).segment_ids[:rows]


def kernel_fn(kind, seg, scale, bq, bk):
    """``fn(q, k, v, out, lse, do) -> (b, h, s, d)``: one kernel alone."""
    kw = dict(causal=True, scale=scale, block_q=bq, block_k=bk)
    if kind == "fwd":
        return lambda q, k, v, *_: _flash_fwd(q, k, v, seg, seg, **kw)[0]

    def bwd(q, k, v, out, lse, do):
        dq, dk, dv = _flash_bwd(q, k, v, seg, seg, out, lse, do, **kw)
        return dq if kind == "dq" else dk + dv
    return bwd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", type=int, default=len(SHAPES),
                    help="sweep the first N shapes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", help="write the winners here as JSON")
    args = ap.parse_args()

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "autotune needs the TPU chip"}))
        return
    kind_name = jax.devices()[0].device_kind
    rng = np.random.default_rng(args.seed)

    entries = []
    for b, s, h, d, iters in SHAPES[:args.shapes]:
        q, k, v, do = (jax.random.normal(jax.random.key(i), (b, h, s, d),
                                         jnp.bfloat16) for i in range(4))
        seg_np = packed_segment_ids(b, s, rng)
        seg = jnp.asarray(seg_np)
        scale = d ** -0.5
        out, lse = jax.jit(lambda q, k, v: _flash_fwd(
            q, k, v, seg, seg, causal=True, scale=scale))(q, k, v)
        blocks = [x for x in (128, 256, 512, 1024) if s % x == 0]
        if s >= 16384:
            # long-context: each config costs seconds of device time plus
            # a long compile — only sweep the plausible tilings
            blocks = [x for x in blocks if x >= 512]
        rows = {}
        for bq in blocks:
            for bk in blocks:
                dead, interior, edge = (int(n) for n in tile_classes(
                    seg_np, seg_np, sq=s, sk=s, block_q=bq, block_k=bk,
                    causal=True))
                rec = {"bq": bq, "bk": bk, "dead": dead,
                       "interior": interior, "edge": edge}
                for kind in KERNELS:
                    try:
                        rec[f"{kind}_ms"] = round(time_loop_ms(
                            scan_loop(kernel_fn(kind, seg, scale, bq, bk),
                                      iters),
                            (q, k, v, out, lse, do), iters), 3)
                    except Exception as e:
                        rec[f"{kind}_error"] = str(e)[:80]
                rows[bq, bk] = rec
                print(json.dumps({"shape": [b, s, h, d], **rec}),
                      flush=True)

        def best(*kinds):
            ok = [r for r in rows.values()
                  if all(f"{x}_ms" in r for x in kinds)]
            return min(ok, key=lambda r: sum(r[f"{x}_ms"] for x in kinds)) \
                if ok else None

        best_f, best_b = best("fwd"), best("dq", "dkv")
        if best_f and best_b:
            entries.append({
                "seq": s, "batch": b, "heads": h, "head_dim": d,
                "fwd": [best_f["bq"], best_f["bk"]],
                "bwd": [best_b["bq"], best_b["bk"]],
                "fwd_ms": best_f["fwd_ms"],
                "bwd_ms": round(best_b["dq_ms"] + best_b["dkv_ms"], 3)})
            print(json.dumps({"seq": s, "best_fwd": best_f,
                              "best_dq": best("dq"),
                              "best_dkv": best("dkv"),
                              "best_bwd": best_b}), flush=True)

    if entries and args.out:
        with open(args.out, "w") as f:
            json.dump({"device": kind_name, "entries": entries}, f,
                      indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
