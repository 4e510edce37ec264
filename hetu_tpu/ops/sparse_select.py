"""Block selection for block-sparse attention over a compressed-key
cache (InfLLM-v2 / MiniCPM4 style: ``nn.parallel.BlockSparseAttention``).

What is cached beside K and V is, per ``stride`` tokens and kv head, the
MEAN of those tokens' keys (a *stride mean*; ``block_size // stride``
rows a page). A *compressed key* is the mean of ``kernel`` consecutive
keys starting every ``stride`` tokens, ``k̄_j = mean(k[j*stride :
j*stride + kernel])`` = the mean of ``kernel // stride`` consecutive
stride means; it counts for a query at position ``t`` once it is
complete, ``j*stride + kernel <= t + 1``.

For a query at ``t`` and kv group ``g`` (float32 throughout):

1. ``p_h = softmax_j(q_h . k̄_j * scale)`` per head over the visible
   windows, ``s_j = sum_{h in g} p_h[j]`` (:func:`window_scores`);
2. a block's score is the max of ``s_j`` over the windows that overlap
   it; the first ``init_blocks`` blocks, the query's own block and the
   ``window_blocks`` before it score ``+inf``; blocks above the query's
   own are out; the ``topk`` highest are chosen, lowest index first
   among equals — fewer than ``topk`` visible: all of them
   (:func:`choose_blocks`).

The chosen logical blocks come back ASCENDING, so the query's own
(partly filled) block is the last valid entry: through a slot's block
table they are a ``topk``-lane table of physical pages that the paged
attention call (``ops.paged_pallas``) reads like any other slot's — at
the virtual position ``(n_chosen - 1) * block_size + t % block_size``
every chosen key at or below ``t`` is seen and nothing else
(:func:`virtual_tables`). The selected-page list is DATA to that call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def compressed_keys(cmean, ratio: int):
    """Stride means ``(..., J, hkv, d)`` -> compressed keys of ``ratio``
    strides each, float32, same shape: entry ``j`` is the mean of stride
    means ``j .. j + ratio - 1`` (the last ``ratio - 1`` wrap around and
    are never complete, so never visible)."""
    c = cmean.astype(jnp.float32)
    out = c
    for i in range(1, ratio):
        out = out + jnp.roll(c, -i, axis=-3)
    return out / ratio


def window_scores(q, kbar, pos, *, stride: int, kernel: int, scale: float):
    """``s (N, hkv, J)``: per kv group, the sum over its heads of each
    head's softmax over the visible windows.

    ``q`` ``(N, hkv, G, d)``; ``kbar`` compressed keys ``(J, hkv, d)``
    (every row reads the same slot) or ``(N, J, hkv, d)`` (a slot a
    row); ``pos`` ``(N,)``. A row that sees no window gets zeros."""
    kb = kbar.astype(q.dtype)
    eq = "nkgd,jkd->nkgj" if kbar.ndim == 3 else "nkgd,njkd->nkgj"
    sc = jnp.einsum(eq, q, kb, preferred_element_type=jnp.float32) * scale
    J = kbar.shape[-3]
    vis = (jnp.arange(J)[None, :] * stride + kernel) <= (pos[:, None] + 1)
    vis = vis[:, None, None, :]
    sc = jnp.where(vis, sc, -jnp.inf)
    m = jnp.max(sc, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(vis, jnp.exp(sc - m), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(den == 0.0, 1.0, den)
    return jnp.sum(p, axis=2)


def block_scores(s, *, per_block: int, ratio: int):
    """Window scores ``(..., J)`` -> block scores ``(..., J //
    per_block)``: the max over the windows that overlap the block — the
    ``per_block`` that start in it and the ``ratio - 1`` before them
    that reach into it."""
    J = s.shape[-1]
    W = J // per_block
    out = jnp.max(s.reshape(s.shape[:-1] + (W, per_block)), axis=-1)
    for i in range(1, ratio):
        # window b*per_block - i, for every block b >= 1
        before = s[..., per_block - i::per_block][..., :W - 1]
        before = jnp.concatenate(
            [jnp.full(s.shape[:-1] + (1,), -jnp.inf, s.dtype), before], -1)
        out = jnp.maximum(out, before)
    return out


def choose_blocks(s, pos, *, block_size: int, stride: int, kernel: int,
                  topk: int, init_blocks: int, window_blocks: int):
    """The chosen logical blocks of every (row, kv group):
    ``(ids (N, hkv, topk) int32, n (N,) int32)`` — ``ids`` ascending,
    the first ``n = min(topk, own block + 1)`` valid, the rest ``W``
    (one past the table)."""
    blk = block_scores(s, per_block=block_size // stride,
                       ratio=kernel // stride)
    W = blk.shape[-1]
    b = jnp.arange(W, dtype=jnp.int32)[None, None, :]
    own = (pos // block_size).astype(jnp.int32)[:, None, None]
    forced = (b < init_blocks) | ((b >= own - window_blocks) & (b <= own))
    score = jnp.where(forced, jnp.inf, blk)
    score = jnp.where(b <= own, score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, min(topk, W))
    ids = jnp.where(vals > -jnp.inf, idx.astype(jnp.int32), W)
    if topk > W:
        ids = jnp.pad(ids, ((0, 0), (0, 0), (0, topk - W)),
                      constant_values=W)
    return jnp.sort(ids, axis=-1), \
        jnp.minimum(topk, own[:, 0, 0] + 1).astype(jnp.int32)


def virtual_tables(ids, n, tables, pos, *, block_size: int):
    """The chosen pages as the paged call's operands, one virtual slot
    a (row, kv head): ``(tables (N * hkv, topk), q_offset (N * hkv,))``.

    ``tables`` ``(N, W)`` is each row's slot's block table over pages
    that hold ONE kv head each, head-minor (``page = block * hkv +
    head``: ``BlockSparseAttention.kv_leaf_shapes``). Invalid lanes
    name the null block and lie above the virtual position."""
    N, hkv, topk = ids.shape
    W = tables.shape[1]
    blocks = jnp.take_along_axis(
        tables[:, None, :], jnp.minimum(ids, W - 1), axis=-1)
    pages = blocks * hkv + jnp.arange(hkv, dtype=blocks.dtype)[None, :, None]
    pages = jnp.where(ids < W, pages, 0)
    off = (n - 1) * block_size + pos % block_size
    return pages.reshape(N * hkv, topk).astype(jnp.int32), \
        jnp.repeat(off.astype(jnp.int32), hkv)
