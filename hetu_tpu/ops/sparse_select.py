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

A prefill pack reads the two parts of a token's choice apart. The
FORCED blocks — the first ``init_blocks``, the ``window_blocks`` before
the token's own and its own — are the same pages for the tokens of one
block, and all but a few for the tokens of a tile of the pack: a tile
reads them once, through a table of its own under the paged call's
banded causal mask (:func:`band_tables`). What is left of a token's
choice, at most ``topk - init_blocks - window_blocks - 1`` whole blocks
below its band, stays a table of its own (:func:`free_tables`).
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


def forced_blocks(pos, *, block_size: int, init_blocks: int,
                  window_blocks: int):
    """``(N,)``: how many of a row's chosen blocks are forced — the
    first ``init_blocks``, the ``window_blocks`` before its own and its
    own, or every block it can see where those meet."""
    own = (pos // block_size).astype(jnp.int32)
    return jnp.minimum(own + 1, init_blocks + window_blocks + 1)


def free_tables(ids, n, tables, pos, valid, *, block_size: int,
                init_blocks: int, window_blocks: int):
    """The chosen pages that are NOT forced, one virtual slot a (row, kv
    head) like :func:`virtual_tables`: ``(tables (N * hkv, F), q_offset
    (N * hkv,), live (N * hkv,))`` with ``F = topk - init_blocks -
    window_blocks - 1`` lanes. ``ids`` ascend, so a row's free blocks
    follow its ``init_blocks`` first and lie wholly below its band:
    every key of them is seen (``q_offset`` is their last). A row
    that is not ``valid`` or has no free block is not ``live``: the
    paged call gives it the empty part."""
    N, hkv, topk = ids.shape
    W = tables.shape[1]
    F = topk - init_blocks - window_blocks - 1
    nf = n - forced_blocks(pos, block_size=block_size,
                           init_blocks=init_blocks,
                           window_blocks=window_blocks)
    fid = ids[:, :, init_blocks:init_blocks + F]
    blocks = jnp.take_along_axis(
        tables[:, None, :], jnp.minimum(fid, W - 1), axis=-1)
    pages = blocks * hkv + jnp.arange(hkv, dtype=blocks.dtype)[None, :, None]
    lane = jnp.arange(F, dtype=jnp.int32)[None, None, :]
    pages = jnp.where(lane < nf[:, None, None], pages, 0)

    def heads(x):                     # (N,) -> (N * hkv,)
        return jnp.broadcast_to(x[:, None], (N, hkv)).reshape(-1)

    return pages.reshape(N * hkv, F).astype(jnp.int32), \
        heads((nf * block_size - 1).astype(jnp.int32)), \
        heads(valid & (nf > 0))


def band_lanes(tile_rows: int, *, block_size: int, init_blocks: int,
               window_blocks: int) -> int:
    """Lanes of a tile's band table: the first blocks, the window
    before the first row's block, and the blocks ``tile_rows``
    consecutive positions can touch."""
    return init_blocks + window_blocks + 1 \
        + (tile_rows + block_size - 2) // block_size


def band_tables(tile_map, slot_tables, *, hkv: int, cells: int,
                tile_rows: int, block_size: int, init_blocks: int,
                window_blocks: int):
    """The forced blocks of a pack's tiles as the banded tiled call's
    operands (``ops.paged_pallas.paged_history_attention`` under
    ``band=``), one virtual tile a (kv head, tile), head-major:
    ``(tables (hkv * G, B), tiles {name: (hkv * G,)})``.

    ``tile_map`` is the pack's map with every run cut into tiles
    (``pack_history_tiles(every_run=True)``), ``slot_tables`` ``(S,
    W)`` the slots' block tables over pages of ONE kv head, head-minor
    (:func:`virtual_tables`). A tile's table is its slot's first
    ``init_blocks`` lanes, then the lanes from the band of its first
    row on (:func:`band_lanes` of them): the rows stand a whole number
    of blocks lower than they are (``off``), so the call's mask, which
    floors the band's edge to a block, is the selection's. Where the
    band reaches the first blocks the table is the slot's own and no
    block is named twice. The lanes are read by compares and one-hot
    sums over the slots and the table's width, a few dozen entries a
    tile (a gather costs the TPU's compiler about a second, whatever
    its size). Head ``h``'s tiles read the cells ``h * cells ..`` of a
    pack laid out a head at a time; a dead tile owns no row of the
    cell before it (the kernel's output block stays put: dead tiles
    lie BETWEEN the heads' live ones)."""
    slot, cell, lo, hi, off, cap = tile_map
    G = slot.shape[0]
    S, W = slot_tables.shape
    B = band_lanes(tile_rows, block_size=block_size,
                   init_blocks=init_blocks, window_blocks=window_blocks)
    first = jnp.maximum((off + lo) // block_size - window_blocks,
                        init_blocks)
    shift = first - init_blocks                               # blocks
    lane = jnp.arange(B, dtype=jnp.int32)[None, :]
    true = jnp.where(lane < init_blocks, lane, lane + shift[:, None])
    mine = jnp.sum(jnp.where(
        slot[:, None, None] == jnp.arange(S)[None, :, None],
        slot_tables[None], 0), axis=1)                        # (G, W)
    blocks = jnp.sum(jnp.where(
        true[:, :, None] == jnp.arange(W)[None, None, :],
        mine[:, None, :], 0), axis=2)                         # (G, B)
    head = jnp.arange(hkv, dtype=jnp.int32)[:, None]
    live = cap >= 0
    cell = jnp.where(live, cell, jnp.max(jnp.where(live, cell, 0)))

    def heads(x):                     # (G,) -> (hkv * G,), head-major
        return jnp.broadcast_to(x[None], (hkv, G)).reshape(-1)

    return (blocks[None] * hkv + head[:, :, None]).reshape(
        hkv * G, B).astype(jnp.int32), {
        "cell": (cell[None] + head * cells).reshape(-1),
        "lo": heads(lo), "hi": heads(jnp.where(live, hi, lo)),
        "off": heads(off - shift * block_size), "cap": heads(cap)}
