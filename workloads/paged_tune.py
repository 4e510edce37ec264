"""Paged-attention kernel ``pages_per_step`` autotune on the real chip.

The paged decode kernel (``hetu_tpu/ops/paged_pallas.py``) streams KV
through block tables with a tunable number of page DMAs per grid step:
too few and the per-step overhead dominates small blocks, too many and
VMEM pressure/stragglers bite. This sweep measures the winner per
BLOCK SIZE at representative serving shapes and records it to
``workloads/out/paged_blocks.json``, which ``default_pages_per_step``
consults on TPU — the same measured-defaults persistence the flash
block sweep (``flash_tune.py`` → ``flash_blocks.json``) uses.

Timing chains iterations through a ``lax.scan`` feedback term so the
host's per-call dispatch cost cannot swamp sub-ms kernels (see
``flash_tune.py``'s rationale).

Usage: python workloads/paged_tune.py [--iters 32]
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

from hetu_tpu.ops.paged_pallas import paged_attention_pallas
from workloads._timing import scan_loop, time_loop_ms

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "out", "paged_blocks.json")

# (slots, rows, hq, hkv, d, block_size, table_len, context): the bench
# serving shapes first (16-token blocks), then the long-table lane the
# dead-lane skip exists for
SHAPES = [
    (16, 1, 16, 16, 64, 16, 2048, 1536),
    (64, 1, 16, 4, 128, 16, 4096, 3072),
    (16, 4, 16, 16, 64, 16, 2048, 1536),     # spec-decode verify rows
    (16, 1, 16, 16, 64, 32, 8192, 6144),
    (8, 1, 16, 16, 64, 64, 32768, 24576),    # CP-lane wide tables
]

PAGES = (1, 2, 4, 8, 16)

#: the arena is the engine's stacked leaf, read at a traced layer: a
#: page's index map is (layer, table[s, w], 0, 0), as in the fused step
LAYERS = 2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=32)
    args = ap.parse_args()

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "autotune needs the TPU chip"}))
        return
    kind = jax.devices()[0].device_kind

    rng = np.random.default_rng(0)
    best_by_bs: dict[int, dict] = {}
    for (S, R, hq, hkv, d, bs, table_len, ctx) in SHAPES:
        W = table_len // bs
        n_blocks = 1 + S * (-(-ctx // bs))
        q = jnp.asarray(rng.normal(size=(S, R, hq, d)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(LAYERS, n_blocks, bs, hkv * d)),
                        jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(LAYERS, n_blocks, bs, hkv * d)),
                        jnp.bfloat16)
        layer = jnp.asarray(LAYERS - 1, jnp.int32)
        tbl = np.zeros((S, W), np.int32)
        per = -(-ctx // bs)
        for s in range(S):
            tbl[s, :per] = 1 + s * per + np.arange(per)
        tbl = jnp.asarray(tbl)
        off = jnp.full((S,), ctx - R, jnp.int32)
        rows = []
        for L in PAGES:
            if L > W:
                continue

            def f(q, k, v, layer, L=L):
                return paged_attention_pallas(
                    q, k, v, tbl, off, layer=layer, pages_per_step=L,
                    interpret=False)

            try:
                ms = time_loop_ms(scan_loop(f, args.iters),
                                  (q, k, v, layer), args.iters)
            except Exception as e:                  # noqa: BLE001
                rows.append({"pages": L, "error": str(e)[:80]})
                continue
            rows.append({"pages": L, "ms": round(ms, 4)})
            print(json.dumps({"shape": [S, R, hq, hkv, d, bs,
                                        table_len, ctx],
                              "pages": L, "ms": round(ms, 4)}),
                  flush=True)
        ok = [r for r in rows if "ms" in r]
        if not ok:
            continue
        win = min(ok, key=lambda r: r["ms"])
        prev = best_by_bs.get(bs)
        # one winner per block size (the kernel's lookup key): keep the
        # choice from the shape where it mattered most (slowest sweep)
        if prev is None or win["ms"] > prev.get("_win_ms", 0.0):
            best_by_bs[bs] = {
                "block_size": bs, "pages_per_step": win["pages"],
                "shape": [S, R, hq, hkv, d, table_len, ctx],
                "ms": win["ms"], "_win_ms": win["ms"],
            }

    entries = []
    for e in best_by_bs.values():
        e.pop("_win_ms", None)
        entries.append(e)
    if entries:
        os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
        with open(OUT_PATH, "w") as f:
            json.dump({"device": kind, "entries": entries}, f, indent=1)
        print(f"wrote {OUT_PATH}")


if __name__ == "__main__":
    main()
