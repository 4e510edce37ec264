"""Pallas TPU flash attention: fused fwd + bwd with custom_vjp.

TPU-native replacement for the reference's FlashAttention wrapper
(``hetu/impl/kernel/FlashAttention.cu:1-50``, which marshals into the vendored
``third_party/flash_attn`` CUDA kernels) and the cp=1 fast path of
``ParallelAttentionOp`` (``hetu/graph/ops/ParallelAttention.h:711``).

Design (TPU-first, not a translation):
- The streamed axis of each kernel is NOT a grid axis. A grid step holds a
  MAJOR block of the streamed operands resident in VMEM — the whole of a
  (batch, kv head)'s K and V for the forward and dq (grid ``(batch,
  q_heads, q_blocks, kv_majors)``), the whole of a head's Q, dO, LSE and
  delta for dk/dv (grid ``(batch, q_heads, kv_blocks, q_majors)``) —
  wherever they fit ``_RESIDENT_BYTES`` (double buffered, lane padded), a
  rule on the operands' SHAPES: a 1k row gets one major block, whose index
  does not depend on the other block axis, so it is fetched once a head; a
  32k ring hop streams major blocks ("arbitrary" axis, running state in
  VMEM scratch), and a major block wholly above the diagonal is not
  fetched (its index is clamped to the last live one).
- An in-kernel ``fori_loop`` walks the compute sub-tiles (``block_q`` x
  ``block_k``) of the resident block BY CLASS (:func:`tile_classes`):
  tiles wholly below the causal diagonal first, with no causal mask built;
  then the ones that cross it; tiles above it are outside the loops'
  bounds — no grid step, no DMA, no branch. With segment ids the wrapper
  reduces each block's ids to a (min, max) range, handed over by scalar
  prefetch: two blocks whose ranges do not meet are skipped on one scalar
  test (true for ANY ids), two blocks of one equal id build no segment
  mask, the rest read the full-width id operands.
- A visited tile costs ~0.4 µs before its first element (two dependent
  matmuls' latency; nothing overlaps across the loop's iterations), so
  tiles are LARGE (``_default_blocks``: up to 1024 a side, the whole row
  of a 1k batch) and each is worked in static strips of 128 queries
  (:func:`_pieces`): straight-line code whose matmuls overlap, and in the
  tile that starts on the diagonal a strip stops at its last row's key —
  the dead half of that tile is not computed either.
- Forward: the exact softmax of a (query block, major block) in two passes
  over its live sub-tiles — scores kept in VMEM and a per-lane running max;
  then probs, per-lane sums and the value product — merged into the
  running (max, sum, accumulator) once a MAJOR block: one online-softmax
  update and two cross-lane reductions a query block, not a sub-tile.
- GQA without materializing repeated KV: the K/V BlockSpec index_map divides
  the q-head program id by the group size.
- Packing / varlen is expressed with segment ids (TPU formulation of the
  reference's cu_seqlens varlen path): q ids broadcast to 128 lanes, kv ids
  to 8 sublanes, the same layout the proven TPU kernels use.
- Backward = two kernels: dq loops key sub-tiles per Q block; dK/dV loop
  query sub-tiles per KV block, from the diagonal on, with tiles KEYS DOWN
  (``k qᵀ``: LSE and delta ride as rows of 8 sublanes, and neither dV =
  Pᵀ dO nor dK = dSᵀ Q transposes a tile); dK/dV produced per q-head then
  group-summed for GQA.
- ``q_offset``/``kv_offset`` shift absolute positions for the causal mask
  and the loops' bounds so ring-attention CP
  (``hetu_tpu.parallel.ring_attention``) can reuse these kernels per hop
  and combine with the returned LSE.

The softmax scale is folded into Q once on entry; masked logits use a finite
``NEG_INF`` so fully-masked rows stay NaN-free (output 0, LSE = NEG_INF),
matching ``attention_reference``.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.bits import fmix32

NEG_INF = -1e30
NUM_LANES = 128
NUM_SUBLANES = 8
# VMEM the resident (major) blocks of one grid step's streamed operands may
# take, double buffered and lane padded: K and V of a head up to 8k keys of
# width 128 in bf16; a 32k ring hop streams major blocks of that size
_RESIDENT_BYTES = 8 * 2 ** 20
# queries (or keys) a strip of a compute tile: see ``_pieces``
_STRIP = 128
# the compute tile's side where the lengths allow: see ``_default_blocks``
_TILE = 1024


def _pick_block(n: int, target: int = 512) -> int:
    for b in (target, 512, 256, 128):
        if n % b == 0 and b <= n and b <= target:
            return b
    return n


def _default_blocks(sq: int, sk: int, kind: str) -> tuple:
    """The compute tile (block_q, block_k) of one kernel — ``kind``:
    "fwd" | "dq" | "dkv" —: the largest of 1024 / 512 / 256 / 128 that
    divides each length, for all three kernels. Swept on a v5e over
    {128, 256, 512, 1024}² at (32, 1024, 12, 64) and (4, 2048, 16, 64)
    on rows packed as ``pretrain-packed-1k`` packs them
    (``workloads/flash_tune.py``; PERF.md, PR 40): a visited tile costs
    ~0.4 µs whatever its size, so at 1k rows 128² tiles — 55 % of the
    square dead — take 2.5–3.7 x the time of one 1024² tile a head
    worked in strips."""
    return _pick_block(sq, _TILE), _pick_block(sk, _TILE)


def _interpret_default() -> bool:
    """Interpret-mode default for the Pallas kernels (flash + fused CE).

    ``HETU_PALLAS_INTERPRET=0|1`` overrides: AOT topology compilation
    (``workloads/aot_check.py``) targets real TPU from a CPU-backend
    process, where the backend heuristic would silently swap in the
    interpret lowering and validate nothing."""
    env = os.environ.get("HETU_PALLAS_INTERPRET")
    if env is not None:
        if env not in ("0", "1"):
            raise ValueError(
                f"HETU_PALLAS_INTERPRET={env!r}: use '0' (real Mosaic "
                "lowering) or '1' (interpret mode)")
        return env == "1"
    return jax.default_backend() != "tpu"


def _expand_q_ids(seg: jnp.ndarray) -> jnp.ndarray:
    # (b, sq) -> (b, sq, NUM_LANES)
    return jax.lax.broadcast_in_dim(
        seg, (*seg.shape, NUM_LANES), (0, 1))


def _expand_kv_ids(seg: jnp.ndarray) -> jnp.ndarray:
    # (b, sk) -> (b, NUM_SUBLANES, sk)
    return jax.lax.broadcast_in_dim(
        seg, (seg.shape[0], NUM_SUBLANES, seg.shape[1]), (0, 2))


def _dropout_keep(seed, ib, ih, iq, ik, *, rate, block_q, block_k,
                  q_offset, kv_offset):
    """(block_q, block_k) bool keep-mask of block (iq, ik): see
    :func:`_keep_at`."""
    return _keep_at(seed, ib, ih, iq * block_q + q_offset,
                    ik * block_k + kv_offset, block_q, block_k, rate=rate)


def _keep_at(seed, ib, ih, q0, k0, nq, nk, *, rate, transposed=False):
    """(nq, nk) bool keep-mask from a counter-based RNG for the queries
    from absolute position ``q0`` and the keys from ``k0``
    (``transposed``: the same mask as (nk, nq), keys down).

    Addressed by ABSOLUTE (q, k) position + (batch, head) + seed — not by
    block indices — so the forward and both backward kernels regenerate
    the IDENTICAL mask even when their tuned block sizes differ (the
    same property the reference gets from flash-attn's philox offsets,
    ``hetu/impl/kernel/FlashAttention.cu:1-50``). Pure uint32 jnp ops:
    one code path for Mosaic and interpret modes.
    """
    shape = (nk, nq) if transposed else (nq, nk)
    qpos = jnp.uint32(q0) + jax.lax.broadcasted_iota(
        jnp.uint32, shape, int(transposed))
    kpos = jnp.uint32(k0) + jax.lax.broadcasted_iota(
        jnp.uint32, shape, int(not transposed))
    salt = fmix32(jnp.uint32(seed)
                   ^ (jnp.uint32(ib) * jnp.uint32(0x27D4EB2F))
                   ^ (jnp.uint32(ih) * jnp.uint32(0x165667B1)))
    u = fmix32((qpos * jnp.uint32(0x9E3779B1))
                ^ (kpos * jnp.uint32(0x85EBCA77)) ^ salt)
    threshold = jnp.uint32(min(2 ** 32 - 1, int(rate * 2 ** 32)))
    return u >= threshold


def dropout_keep_bh(seed, nb, nh, sq, sk, *, rate):
    """(nb, nh, sq, sk) keep mask — the full-array twin of
    ``_dropout_keep`` drawing the SAME stream (batch/head indices become
    iota dims; positions are the whole matrix at block origin 0). Used
    by the ring-attention reference hops and by tests to predict the
    kernel's masks."""
    bi = jax.lax.broadcasted_iota(jnp.uint32, (nb, nh, 1, 1), 0)
    hi = jax.lax.broadcasted_iota(jnp.uint32, (nb, nh, 1, 1), 1)
    salt = fmix32(jnp.uint32(seed)
                  ^ (bi * jnp.uint32(0x27D4EB2F))
                  ^ (hi * jnp.uint32(0x165667B1)))
    qpos = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, sq, sk), 2)
    kpos = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, sq, sk), 3)
    u = fmix32((qpos * jnp.uint32(0x9E3779B1))
               ^ (kpos * jnp.uint32(0x85EBCA77)) ^ salt)
    threshold = jnp.uint32(min(2 ** 32 - 1, int(rate * 2 ** 32)))
    return u >= threshold


def _major_block(n: int, tile: int, row_bytes: int) -> int:
    """Rows of a streamed operand one grid step holds resident: all ``n``
    when they fit ``_RESIDENT_BYTES`` double buffered, else the largest
    divisor of ``n`` made of whole compute tiles that does."""
    tiles = n // tile
    for g in range(tiles, 1, -1):
        if tiles % g == 0 and 2 * g * tile * row_bytes <= _RESIDENT_BYTES:
            return g * tile
    return tile


def _row_bytes(d: int, dtype, n: int) -> int:
    """VMEM bytes a row of ``n`` (rows, d) operands takes, lane padded."""
    return n * -(-d // NUM_LANES) * NUM_LANES * jnp.dtype(dtype).itemsize


def _block_ranges(seg, block: int):
    """Each block's (min, max) id: ``seg`` (b, s) -> two (b, s // block)
    arrays. numpy in, numpy out; jax in, jax out. Two blocks whose ranges
    do not meet hold no equal pair of ids, whatever the order of ids."""
    blocks = seg.reshape(seg.shape[0], seg.shape[1] // block, block)
    return blocks.min(axis=-1), blocks.max(axis=-1)


def tile_classes(q_seg, kv_seg, *, sq, sk, block_q, block_k, causal,
                 q_offset=0, kv_offset=0):
    """How many (block_q, block_k) tiles of one head's call are ``dead``
    (above the diagonal, or the two blocks' id ranges do not meet: never
    computed), ``interior`` (wholly below the diagonal and one id on both
    sides: no mask built) and ``edge`` (the rest). numpy in, ``(dead,
    interior, edge)`` out, summed over the batch; ``q_seg`` / ``kv_seg``
    ``None`` = no ids (one row, causal classes only). The kernels' loop
    bounds (:func:`_key_bounds`, :func:`_query_bounds`) and their test on
    the prefetched ranges walk exactly these classes."""
    q0 = np.arange(sq // block_q)[:, None] * block_q + q_offset
    k0 = np.arange(sk // block_k)[None, :] * block_k + kv_offset
    above = (k0 > q0 + block_q - 1) if causal else np.zeros(
        (sq // block_q, sk // block_k), bool)
    below = (k0 + block_k - 1 <= q0) if causal else ~above
    if q_seg is None:
        meet, one = ~above[None], below[None]
    else:
        qlo, qhi = (x[:, :, None] for x in _block_ranges(q_seg, block_q))
        klo, khi = (x[:, None, :] for x in _block_ranges(kv_seg, block_k))
        meet = (qlo <= khi) & (klo <= qhi) & ~above
        one = meet & below & (qlo == qhi) & (klo == khi)
    dead, interior = int((~meet).sum()), int(one.sum())
    return np.array([dead, interior, meet.size - dead - interior])


def train_tile_classes(segment_ids) -> dict:
    """``{"fwd": (dead, interior, edge), "bwd": ...}`` of one head's
    causal self-attention over a packed batch at the kernels' default
    tiles (``bwd`` = the dq call's tiles + the dk/dv call's)."""
    s = segment_ids.shape[1]
    out = {}
    for kind in ("fwd", "dq", "dkv"):
        bq, bk = _default_blocks(s, s, kind)
        n = tile_classes(segment_ids, segment_ids, sq=s, sk=s, block_q=bq,
                         block_k=bk, causal=True)
        key = "fwd" if kind == "fwd" else "bwd"
        out[key] = out.get(key, 0) + n
    return out


def _floordiv(x, n: int):
    """floor(max(x, 0) / n) of an int32 scalar, traced or a Python int
    (a grid axis of one step: the loops' bounds are then static)."""
    if isinstance(x, int):
        return max(x, 0) // n
    return jax.lax.div(jnp.maximum(x, 0), jnp.int32(n))


def _at_most(x, n: int):
    return min(x, n) if isinstance(x, int) else jnp.minimum(x, n)


def _grid_index(axis: int, steps: int):
    """This grid step's index along ``axis``; the Python 0 where the axis
    has one step."""
    return pl.program_id(axis) if steps > 1 else 0


def _key_bounds(q_first, k_base, *, block_q, block_k, n_tiles, causal):
    """Key tiles ``[0, full)`` of a major block that starts at absolute
    key ``k_base`` lie wholly below the diagonal of the query block that
    starts at ``q_first``; ``[full, live)`` cross it; the rest lie above
    it and are outside the loops."""
    if not causal:
        return n_tiles, n_tiles
    full = _at_most(_floordiv(q_first - k_base + 1, block_k), n_tiles)
    live = _at_most(
        _floordiv(q_first + block_q - 1 - k_base + block_k, block_k),
        n_tiles)
    return full, live


def _query_bounds(k_first, q_base, *, block_q, block_k, n_tiles, causal):
    """Query tiles ``[first, full)`` of a major block that starts at
    absolute query ``q_base`` cross the diagonal of the key block that
    starts at ``k_first``; ``[full, n_tiles)`` lie wholly below it; the
    ones before ``first`` lie above it and are outside the loops."""
    if not causal:
        return 0, 0
    first = _at_most(_floordiv(k_first - q_base, block_q), n_tiles)
    full = _at_most(
        _floordiv(k_first + block_k - 1 - q_base + block_q - 1, block_q),
        n_tiles)
    return first, full


def _walk(start, stop, tile, ranges, causal_mask: bool, seg_classes=True):
    """Run ``tile(j, causal_mask, seg_mask)`` for sub-tiles ``[start,
    stop)`` by class: ``ranges(j)`` gives the two blocks' prefetched id
    ranges — disjoint: skipped; below the diagonal (``causal_mask``
    False) with one id on both sides: interior, no mask at all; else an
    edge tile with the segment mask too. ``seg_classes`` False: the tile
    does the same for both (one body). A range that is empty when traced
    costs nothing."""
    if isinstance(start, int) and isinstance(stop, int) and stop <= start:
        return

    def body(j, carry):
        if ranges is None:
            tile(j, causal_mask, False)
            return carry
        qlo, qhi, klo, khi = ranges(j)
        meet = (qlo <= khi) & (klo <= qhi)
        if causal_mask or not seg_classes:
            pl.when(meet)(lambda: tile(j, causal_mask, True))
            return carry
        one = (qlo == qhi) & (klo == khi) & (qlo == klo)
        pl.when(one)(lambda: tile(j, False, False))
        pl.when(meet & jnp.logical_not(one))(lambda: tile(j, False, True))
        return carry

    jax.lax.fori_loop(start, stop, body, 0)


def _id_ranges(rng, q_at, k_at):
    """``ranges(j)`` for :func:`_walk` from the prefetched (q min, q max,
    kv min, kv max) arrays: ``q_at(j)`` / ``k_at(j)`` index sub-tile
    ``j``'s two blocks; None without ids."""
    if rng is None:
        return None
    return lambda j: (rng[0][q_at(j)], rng[1][q_at(j)],
                      rng[2][k_at(j)], rng[3][k_at(j)])


def _pieces(block_q, block_k, diagonal: bool, keys_down: bool = False):
    """Static windows ``(q0, nq, k0, nk)`` that cover a tile's live
    pairs: strips of ``_STRIP`` queries against the tile's keys
    (``keys_down``: strips of keys against its queries) — straight-line
    code whose matmuls overlap and whose temporaries stay a strip's,
    whatever the tile. In a tile whose first query and first key are the
    SAME position (``diagonal``) a strip of queries stops at the keys of
    its last row (a strip of keys starts at the queries of its first):
    the half of the tile above the diagonal is neither scored nor
    exponentiated."""
    down, across = (block_k, block_q) if keys_down else (block_q, block_k)
    strip = _STRIP if down % _STRIP == 0 else down
    diagonal = diagonal and block_q == block_k
    if keys_down:
        return [(i if diagonal else 0, across - (i if diagonal else 0), i,
                 strip) for i in range(0, down, strip)]
    return [(i, strip, 0, i + strip if diagonal else across)
            for i in range(0, down, strip)]


def _at(j, block, start, size):
    """``size`` rows from row ``start`` of sub-tile ``j`` of a resident
    operand (lane aligned whenever a tile is cut into strips)."""
    align = NUM_LANES if (block % NUM_LANES == 0
                          and start % NUM_LANES == 0) else block
    return pl.ds(pl.multiple_of(j * block + start, align), size)


def _tile_mask(q0, k0, block_q, block_k, *, causal, q_ids, kv_ids,
               transposed=False, bound: int = 1):
    """bool (block_q, block_k) for the tile whose first query is absolute
    position ``q0`` and first key ``k0`` (``transposed``: (block_k,
    block_q), keys down); None if nothing masks. ``bound`` > 1: the
    block bound — a query sees the keys up to the end of its block of
    ``bound`` positions (``ops.attention.block_bound``)."""
    mask = None
    if causal:
        shape = (block_k, block_q) if transposed else (block_q, block_k)
        q_at = jax.lax.broadcasted_iota(jnp.int32, shape, int(transposed))
        k_at = jax.lax.broadcasted_iota(jnp.int32, shape,
                                        int(not transposed))
        if bound == 1:
            mask = q_at - k_at >= k0 - q0
        else:
            mask = ((q_at + q0) | (bound - 1)) >= k_at + k0
    if q_ids is not None:
        # (block_q,1) == (1,block_k), or (1,block_q) == (block_k,1)
        smask = q_ids == kv_ids
        mask = smask if mask is None else mask & smask
    return mask


def _split_refs(refs, n_tensor, has_seg, has_drop):
    """(ranges, tensors, qseg, kseg, seed, outs and scratch) of a call:
    pallas passes the prefetched scalars first and only the refs that
    were given specs, in order."""
    refs = list(refs)
    rng = [refs.pop(0) for _ in range(4)] if has_seg else None
    tensors = [refs.pop(0) for _ in range(n_tensor)]
    qseg, kseg = (refs.pop(0), refs.pop(0)) if has_seg else (None, None)
    seed = refs.pop(0) if has_drop else None
    return rng, tensors, qseg, kseg, seed, refs


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _lane_fold(x, op, pad):
    """(rows, cols) -> (rows, NUM_LANES): ``op`` over the column chunks of
    128, elementwise — what is left for ONE cross-lane reduction a query
    block. A width that is no multiple of 128 is reduced at once into
    lane 0 (the other lanes hold ``pad``, the reduction's identity)."""
    cols = x.shape[1]
    if cols % NUM_LANES == 0:
        return functools.reduce(op, [x[:, c:c + NUM_LANES]
                                     for c in range(0, cols, NUM_LANES)])
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], NUM_LANES), 1)
    red = jnp.max if op is jnp.maximum else jnp.sum
    return jnp.where(lane == 0, red(x, axis=1, keepdims=True), pad)


def _fwd_score_tile(q, k, s_out, mpart_scr, mask):
    """First pass of a sub-tile: its masked scores kept in VMEM, the
    running per-lane max updated (no cross-lane work)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    s_out[...] = s
    mpart_scr[...] = jnp.maximum(mpart_scr[...],
                                 _lane_fold(s, jnp.maximum, NEG_INF))


def _fwd_value_tile(s, v, m, lpart_scr, acc_scr, keep, dropout_rate):
    """Second pass of a sub-tile: probs against the block's row max
    ``m`` (a masked score is NEG_INF and ``m`` at least NEG_INF / 2: its
    prob is an exact zero), the per-lane sums and the value product."""
    p = jnp.exp(s - m)
    lpart_scr[...] += _lane_fold(p, jnp.add, 0.0)
    if keep is not None:
        # dropout on the (later-normalized) probs: mask only the VALUE
        # accumulation — the denominator l stays un-dropped, so
        # out = Σ mask∘softmax∘V / keep and LSE is unchanged
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    acc_scr[...] += jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_kernel(*refs, causal, has_seg, block_q, block_k, kv_major,
                q_blocks, k_blocks, q_offset, kv_offset, dropout_rate,
                bound=1):
    """One query block against one resident major block of K and V: the
    exact softmax of the block in two passes over its live sub-tiles
    (scores and the row max; probs, sums and values), merged into the
    running (max, sum, accumulator) once a MAJOR block — the online
    update and both cross-lane reductions leave the per-tile path."""
    rng, (q_ref, k_ref, v_ref), qseg_ref, kseg_ref, seed_ref, \
        (o_ref, lse_ref, m_scr, mpart_scr, lpart_scr, acc_scr, s_scr) = \
        _split_refs(refs, 3, has_seg, dropout_rate > 0.0)
    n_tiles = kv_major // block_k
    ib, ih = pl.program_id(0), pl.program_id(1)
    iq, ikm = _grid_index(2, q_blocks), _grid_index(3, k_blocks // n_tiles)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        lpart_scr[...] = jnp.zeros_like(lpart_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    mpart_scr[...] = jnp.full_like(mpart_scr, NEG_INF)

    # with equal offsets and square tiles the one tile that crosses the
    # diagonal starts ON it
    aligned = q_offset == kv_offset

    def score_tile(j, causal_mask, seg_mask):
        for q0, nq, k0, nk in _pieces(block_q, block_k,
                                      causal_mask and aligned):
            mask = _tile_mask(
                iq * block_q + q_offset + q0,
                (ikm * n_tiles + j) * block_k + kv_offset + k0, nq, nk,
                causal=causal_mask, bound=bound,
                q_ids=qseg_ref[0, q0:q0 + nq, :1] if seg_mask else None,
                kv_ids=kseg_ref[0, :1, _at(j, block_k, k0, nk)]
                if seg_mask else None)
            # q has the scale folded in already
            _fwd_score_tile(
                q_ref[0, 0, q0:q0 + nq, :],
                k_ref[0, 0, _at(j, block_k, k0, nk), :],
                s_scr.at[j, q0:q0 + nq, k0:k0 + nk],
                mpart_scr.at[q0:q0 + nq, :], mask)

    ranges = _id_ranges(rng, lambda j: ib * q_blocks + iq,
                        lambda j: ib * k_blocks + ikm * n_tiles + j)

    full, live = _key_bounds(
        iq * block_q + q_offset, ikm * kv_major + kv_offset,
        block_q=block_q, block_k=block_k, n_tiles=n_tiles, causal=causal)

    def walk(tile, seg_classes=True):
        _walk(0, full, tile, ranges, False, seg_classes)
        if causal:
            _walk(full, live, tile, ranges, True)

    walk(score_tile)
    m_prev = m_scr[:, :1]
    m_next = jnp.maximum(m_prev, jnp.max(mpart_scr[...], axis=1,
                                         keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
    lpart_scr[...] = lpart_scr[...] * alpha
    acc_scr[...] = acc_scr[...] * alpha
    m_safe = jnp.maximum(m_next, NEG_INF / 2)

    def value_tile(j, causal_mask, seg_mask):
        for q0, nq, k0, nk in _pieces(block_q, block_k,
                                      causal_mask and aligned):
            keep = None
            if dropout_rate > 0.0:
                keep = _keep_at(
                    seed_ref[0], ib, ih, iq * block_q + q_offset + q0,
                    (ikm * n_tiles + j) * block_k + kv_offset + k0, nq, nk,
                    rate=dropout_rate)
            _fwd_value_tile(
                s_scr[j, q0:q0 + nq, k0:k0 + nk],
                v_ref[0, 0, _at(j, block_k, k0, nk), :],
                m_safe[q0:q0 + nq], lpart_scr.at[q0:q0 + nq, :],
                acc_scr.at[q0:q0 + nq, :], keep, dropout_rate)

    walk(value_tile, seg_classes=False)     # the scores are masked already

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _finalize():
        l = jnp.sum(lpart_scr[...], axis=1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _resolve_blocks(sq, sk, kind, block_q, block_k):
    if block_q is None and block_k is None:
        return _default_blocks(sq, sk, kind)
    return (block_q or _pick_block(sq, _TILE),
            block_k or _pick_block(sk, _TILE))


def _seg_ranges(q_seg, kv_seg, block_q, block_k):
    """The prefetched per-block id ranges: flat int32, q's (min, max)
    then kv's; none without ids."""
    if q_seg is None:
        return []
    return [r.reshape(-1).astype(jnp.int32)
            for r in (*_block_ranges(q_seg, block_q),
                      *_block_ranges(kv_seg, block_k))]


def _seed_operand(seed, dropout_rate):
    if dropout_rate <= 0.0:
        return [], []
    return ([pl.BlockSpec(memory_space=pltpu.SMEM)],
            [jnp.asarray(seed, jnp.int32).reshape(1)])


def _last_live_major(iq, ikm, *, causal, block_q, kv_major, q_offset,
                     kv_offset):
    """The major key block grid step ``(iq, ikm)`` reads: ``ikm``, or —
    when that one lies wholly above the query block's diagonal — the last
    that does not (a block whose index did not change is not copied)."""
    if not causal:
        return ikm
    return jnp.minimum(ikm, _floordiv(
        iq * block_q + block_q - 1 + q_offset - kv_offset, kv_major))


def _flash_fwd(q, k, v, q_seg, kv_seg, *, causal, scale,
               q_offset=0, kv_offset=0, interpret=None,
               block_q=None, block_k=None,
               dropout_rate=0.0, seed=None, block: int = 1):
    """q (b,hq,sq,d); k/v (b,hkv,sk,d); seg ids (b,s) or None.

    Returns out (b,hq,sq,d) and lse (b,hq,sq) (natural-log-sum-exp of the
    scaled, masked logits — fp32).

    ``block`` > 1 (causal; forward only): the block bound. Tiles, strips
    and offsets are whole blocks, so a tile's class — dead, interior,
    crossing the diagonal — is the causal one's and only the mask of a
    crossing tile changes: the last row of a tile or strip is the last
    of its block and sees what it saw.
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    block_q, block_k = _resolve_blocks(sq, sk, "fwd", block_q, block_k)
    if block != 1:
        strip = _STRIP if block_q % _STRIP == 0 else block_q
        if not causal or block & (block - 1) or any(
                n % block for n in (block_q, block_k, strip, q_offset,
                                    kv_offset)):
            raise ValueError(
                f"a block bound of {block} needs causal attention and "
                f"tiles ({block_q}, {block_k}), strips and offsets "
                f"({q_offset}, {kv_offset}) of whole blocks")
    has_seg = q_seg is not None
    # resident a key: K and V rows, its ids, and (not double buffered) a
    # query block's float32 scores
    kv_major = _major_block(
        sk, block_k, _row_bytes(d, k.dtype, 2)
        + (NUM_SUBLANES * 4 if has_seg else 0) + block_q * 2)
    interpret = _interpret_default() if interpret is None else interpret

    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    grid = (b, hq, sq // block_q, sk // kv_major)
    major = functools.partial(
        _last_live_major, causal=causal, block_q=block_q,
        kv_major=kv_major, q_offset=q_offset, kv_offset=kv_offset)

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda ib, ih, iq, ikm, *_: (ib, ih, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, kv_major, d),
        lambda ib, ih, iq, ikm, *_: (ib, ih // rep, major(iq, ikm), 0))
    ranges = _seg_ranges(q_seg, kv_seg, block_q, block_k)
    # the full-width ids the edge tiles read: q's a column, kv's a row
    seg_args = [_expand_q_ids(q_seg), _expand_kv_ids(kv_seg)] \
        if has_seg else []
    seg_specs = [
        pl.BlockSpec((1, block_q, NUM_LANES),
                     lambda ib, ih, iq, ikm, *_: (ib, iq, 0)),
        pl.BlockSpec((1, NUM_SUBLANES, kv_major),
                     lambda ib, ih, iq, ikm, *_: (ib, 0, major(iq, ikm))),
    ] if has_seg else []
    seed_specs, seed_args = _seed_operand(seed, dropout_rate)

    lane_spec = pl.BlockSpec((1, 1, block_q, NUM_LANES),
                             lambda ib, ih, iq, ikm, *_: (ib, ih, iq, 0))
    with jax.named_scope("hetu.flash_fwd"):
        out, lse_l = pl.pallas_call(
            functools.partial(
                _fwd_kernel, causal=causal, has_seg=has_seg,
                block_q=block_q, block_k=block_k, kv_major=kv_major,
                q_blocks=sq // block_q, k_blocks=sk // block_k,
                q_offset=q_offset, kv_offset=kv_offset,
                dropout_rate=dropout_rate,
                **({"bound": block} if block != 1 else {})),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(ranges),
                grid=grid,
                in_specs=[q_spec, kv_spec, kv_spec] + seg_specs
                + seed_specs,
                out_specs=[q_spec, lane_spec],
                scratch_shapes=[
                    pltpu.VMEM((block_q, NUM_LANES), jnp.float32),  # max
                    pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                    pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                    pltpu.VMEM((block_q, d), jnp.float32),
                    pltpu.VMEM((kv_major // block_k, block_q, block_k),
                               jnp.float32),
                ]),
            out_shape=[
                jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
                jax.ShapeDtypeStruct((b, hq, sq, NUM_LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="hetu_flash_fwd",
        )(*ranges, qf, k, v, *seg_args, *seed_args)
    return out, lse_l[..., 0]


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _bwd_tile(x, y, dx, dy, lse, delta, mask, keep, dropout_rate):
    """The recomputed probs as dV sees them and dS of one tile, fp32:
    ``(p_v, ds)`` as (rows of x, rows of y) — dq passes (q, k, dO, v) with
    ``lse`` / ``delta`` columns, dk/dv (k, q, v, dO) with rows, so its
    tiles come out keys down and neither product needs a transpose."""
    nt = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(x, y, nt, preferred_element_type=jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(dx, dy, nt, preferred_element_type=jnp.float32)
    p_v = p
    if keep is not None:
        # dA = mask ∘ (dO Vᵀ) / keep; delta = Σ dO∘O is invariant under
        # dropout (see _flash_bwd docnote), so ds keeps its form; dV
        # mixes the DROPPED probs — what the forward output mixed
        dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        p_v = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return p_v, p * (dp - delta)


def _bwd_dq_kernel(*refs, causal, has_seg, block_q, block_k, kv_major,
                   q_blocks, k_blocks, q_offset, kv_offset, dropout_rate,
                   scale):
    rng, (q_ref, k_ref, v_ref, do_ref, col_ref), qseg_ref, \
        kseg_ref, seed_ref, (dq_ref, dq_scr) = _split_refs(
            refs, 5, has_seg, dropout_rate > 0.0)
    n_tiles = kv_major // block_k
    ib, ih = pl.program_id(0), pl.program_id(1)
    iq, ikm = _grid_index(2, q_blocks), _grid_index(3, k_blocks // n_tiles)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    aligned = q_offset == kv_offset

    def tile(j, causal_mask, seg_mask):
        for q0, nq, k0, nk in _pieces(block_q, block_k,
                                      causal_mask and aligned):
            q_at = iq * block_q + q_offset + q0
            k_at = (ikm * n_tiles + j) * block_k + kv_offset + k0
            mask = _tile_mask(
                q_at, k_at, nq, nk, causal=causal_mask,
                q_ids=qseg_ref[0, q0:q0 + nq, :1] if seg_mask else None,
                kv_ids=kseg_ref[0, :1, _at(j, block_k, k0, nk)]
                if seg_mask else None)
            keep = None
            if dropout_rate > 0.0:
                keep = _keep_at(seed_ref[0], ib, ih, q_at, k_at, nq, nk,
                                rate=dropout_rate)
            k = k_ref[0, 0, _at(j, block_k, k0, nk), :]
            cols = col_ref[0, 0, q0:q0 + nq, :]  # lane 0: LSE, then delta
            _, ds = _bwd_tile(
                q_ref[0, 0, q0:q0 + nq, :], k, do_ref[0, 0, q0:q0 + nq, :],
                v_ref[0, 0, _at(j, block_k, k0, nk), :], cols[:, :1],
                cols[:, 1:2], mask, keep, dropout_rate)
            dq_scr[q0:q0 + nq, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    ranges = _id_ranges(rng, lambda j: ib * q_blocks + iq,
                        lambda j: ib * k_blocks + ikm * n_tiles + j)

    full, live = _key_bounds(
        iq * block_q + q_offset, ikm * kv_major + kv_offset,
        block_q=block_q, block_k=block_k, n_tiles=n_tiles, causal=causal)
    _walk(0, full, tile, ranges, False)
    if causal:
        _walk(full, live, tile, ranges, True)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _finalize():
        # undo the q-scale folding
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal, has_seg, block_q, block_k, q_major,
                    q_blocks, k_blocks, q_offset, kv_offset, dropout_rate):
    rng, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qseg_ref, \
        kseg_ref, seed_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = _split_refs(
            refs, 6, has_seg, dropout_rate > 0.0)
    n_tiles = q_major // block_q
    ib, ih = pl.program_id(0), pl.program_id(1)
    ik, iqm = _grid_index(2, k_blocks), _grid_index(3, q_blocks // n_tiles)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    aligned = q_offset == kv_offset

    def tile(j, causal_mask, seg_mask):
        # keys down, queries across: (nk, nq) pieces
        for q0, nq, k0, nk in _pieces(block_q, block_k,
                                      causal_mask and aligned,
                                      keys_down=True):
            q_at = (iqm * n_tiles + j) * block_q + q_offset + q0
            k_at = ik * block_k + kv_offset + k0
            at = _at(j, block_q, q0, nq)
            mask = _tile_mask(
                q_at, k_at, nq, nk, causal=causal_mask, transposed=True,
                q_ids=qseg_ref[0, :1, at] if seg_mask else None,
                kv_ids=kseg_ref[0, k0:k0 + nk, :1] if seg_mask else None)
            keep = None
            if dropout_rate > 0.0:
                keep = _keep_at(seed_ref[0], ib, ih, q_at, k_at, nq, nk,
                                rate=dropout_rate, transposed=True)
            q, do = q_ref[0, 0, at, :], do_ref[0, 0, at, :]
            p_v, ds = _bwd_tile(
                k_ref[0, 0, k0:k0 + nk, :], q, v_ref[0, 0, k0:k0 + nk, :],
                do, lse_ref[0, 0, :1, at], delta_ref[0, 0, :1, at], mask,
                keep, dropout_rate)
            # dV += Ad^T @ dO;  dK += dS^T @ Q
            dv_scr[k0:k0 + nk, :] += jax.lax.dot_general(
                p_v.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[k0:k0 + nk, :] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    ranges = _id_ranges(rng, lambda j: ib * q_blocks + iqm * n_tiles + j,
                        lambda j: ib * k_blocks + ik)

    first, full = _query_bounds(
        ik * block_k + kv_offset, iqm * q_major + q_offset,
        block_q=block_q, block_k=block_k, n_tiles=n_tiles, causal=causal)
    if causal:
        _walk(first, full, tile, ranges, True)
    _walk(full, n_tiles, tile, ranges, False)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, q_seg, kv_seg, out, lse, do, *, causal, scale,
               q_offset=0, kv_offset=0, interpret=None, delta=None,
               block_q=None, block_k=None, dropout_rate=0.0, seed=None):
    """Returns (dq, dk, dv) in input dtypes/shapes ((b,h,s,d) layout).

    ``delta`` (b,hq,sq) fp32 may be precomputed by the caller (ring
    attention passes the globally-combined value); defaults to
    sum(out*do, -1). Dropout note: delta = Σ dO∘O equals
    Σ dA∘A even with dropout (dAd∘Ad = M∘dAd∘A/keep = dA∘A since the
    0/1 mask is idempotent), so the delta trick needs no correction —
    the kernels regenerate the forward's position-hashed mask and apply
    it to dO·Vᵀ (dq/dk) and to the dV-side probs."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    has_seg = q_seg is not None
    interpret = _interpret_default() if interpret is None else interpret

    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    if delta is None:
        delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)                                # (b,hq,sq)
    # the per-query scalars: COLUMNS for dq (queries down: LSE in lane 0
    # and delta in the other lanes of ONE 128-lane operand, one fused
    # write), ROWS for dk/dv (queries across: 8 sublanes each)
    lane = jax.lax.broadcasted_iota(jnp.int32, (*lse.shape, NUM_LANES), 3)
    columns = jnp.where(lane == 0, lse[..., None], delta[..., None])
    rows = [jax.lax.broadcast_in_dim(x, (b, hq, NUM_SUBLANES, sq), (0, 1, 3))
            for x in (lse, delta)]
    seed_specs, seed_args = _seed_operand(seed, dropout_rate)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    # ---- dQ: grid (b, hq, q_blocks, kv_majors); K and V resident, an
    # in-kernel loop over key sub-tiles by class ----
    bq, bk = _resolve_blocks(sq, sk, "dq", block_q, block_k)
    kv_major = _major_block(
        sk, bk, _row_bytes(d, k.dtype, 2)
        + (NUM_SUBLANES * 4 if has_seg else 0))
    major = functools.partial(
        _last_live_major, causal=causal, block_q=bq, kv_major=kv_major,
        q_offset=q_offset, kv_offset=kv_offset)
    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda ib, ih, iq, ikm, *_: (ib, ih, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, kv_major, d),
        lambda ib, ih, iq, ikm, *_: (ib, ih // rep, major(iq, ikm), 0))
    lane_spec = pl.BlockSpec((1, 1, bq, NUM_LANES),
                             lambda ib, ih, iq, ikm, *_: (ib, ih, iq, 0))
    ranges = _seg_ranges(q_seg, kv_seg, bq, bk)
    seg_args = [_expand_q_ids(q_seg), _expand_kv_ids(kv_seg)] \
        if has_seg else []
    seg_specs = [
        pl.BlockSpec((1, bq, NUM_LANES),
                     lambda ib, ih, iq, ikm, *_: (ib, iq, 0)),
        pl.BlockSpec((1, NUM_SUBLANES, kv_major),
                     lambda ib, ih, iq, ikm, *_: (ib, 0, major(iq, ikm))),
    ] if has_seg else []
    with jax.named_scope("hetu.flash_bwd"):
        dq = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, causal=causal, has_seg=has_seg,
                block_q=bq, block_k=bk, kv_major=kv_major,
                q_blocks=sq // bq, k_blocks=sk // bk, q_offset=q_offset,
                kv_offset=kv_offset, dropout_rate=dropout_rate,
                scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(ranges),
                grid=(b, hq, sq // bq, sk // kv_major),
                in_specs=[q_spec, kv_spec, kv_spec, q_spec, lane_spec]
                + seg_specs + seed_specs,
                out_specs=q_spec,
                scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            compiler_params=params,
            interpret=interpret,
            name="hetu_flash_bwd_dq",
        )(*ranges, qf, k, v, do, columns, *seg_args, *seed_args)

    # ---- dK/dV: grid (b, hq, kv_blocks, q_majors); Q, dO, lse, delta
    # resident, an in-kernel loop over query sub-tiles from the diagonal
    # on. Produced per *q* head (GQA read via index_map), then
    # group-summed down to kv heads ----
    bq, bk = _resolve_blocks(sq, sk, "dkv", block_q, block_k)
    q_major = _major_block(
        sq, bq, _row_bytes(d, q.dtype, 2)
        + (3 if has_seg else 2) * NUM_SUBLANES * 4)

    def major(ik, iqm):
        # the first major query block not wholly above the key block's
        # diagonal, for the grid steps before it
        if not causal:
            return iqm
        return jnp.maximum(iqm, jnp.minimum(
            _floordiv(ik * bk + kv_offset - q_offset, q_major),
            sq // q_major - 1))

    q_spec = pl.BlockSpec(
        (1, 1, q_major, d),
        lambda ib, ih, ik, iqm, *_: (ib, ih, major(ik, iqm), 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda ib, ih, ik, iqm, *_: (ib, ih // rep, ik, 0))
    row_spec = pl.BlockSpec(
        (1, 1, NUM_SUBLANES, q_major),
        lambda ib, ih, ik, iqm, *_: (ib, ih, 0, major(ik, iqm)))
    # the ids the other way round: q's as a row, kv's as a column
    ranges = _seg_ranges(q_seg, kv_seg, bq, bk)
    seg_args = [_expand_kv_ids(q_seg), _expand_q_ids(kv_seg)] \
        if has_seg else []
    seg_specs = [
        pl.BlockSpec((1, NUM_SUBLANES, q_major),
                     lambda ib, ih, ik, iqm, *_: (ib, 0, major(ik, iqm))),
        pl.BlockSpec((1, bk, NUM_LANES),
                     lambda ib, ih, ik, iqm, *_: (ib, ik, 0)),
    ] if has_seg else []
    kv_out_spec = pl.BlockSpec(
        (1, 1, bk, d), lambda ib, ih, ik, iqm, *_: (ib, ih, ik, 0))
    with jax.named_scope("hetu.flash_bwd"):
        dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_dkv_kernel, causal=causal, has_seg=has_seg,
                block_q=bq, block_k=bk, q_major=q_major,
                q_blocks=sq // bq, k_blocks=sk // bk, q_offset=q_offset,
                kv_offset=kv_offset, dropout_rate=dropout_rate),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(ranges),
                grid=(b, hq, sk // bk, sq // q_major),
                in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec,
                          row_spec] + seg_specs + seed_specs,
                out_specs=[kv_out_spec, kv_out_spec],
                scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                                pltpu.VMEM((bk, d), jnp.float32)]),
            # fp32 only where the GQA group sum still follows
            out_shape=[jax.ShapeDtypeStruct(
                (b, hq, sk, d), jnp.float32 if rep > 1 else x.dtype)
                for x in (k, v)],
            compiler_params=params,
            interpret=interpret,
            name="hetu_flash_bwd_dkv",
        )(*ranges, qf, k, v, do, *rows, *seg_args, *seed_args)
    if rep > 1:
        dk = dk.reshape(b, hkv, rep, sk, d).sum(axis=2)
        dv = dv.reshape(b, hkv, rep, sk, d).sum(axis=2)
    # dk carries the q-scale through s = (q*scale) k^T — already correct.
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# Public custom_vjp entry point — (b, s, h, d) layout like ops.attention
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_core(q, k, v, q_seg, kv_seg, seed, causal, scale, interpret,
                blocks, dropout_rate):
    out, _ = _flash_fwd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), q_seg, kv_seg,
                        causal=causal, scale=scale, interpret=interpret,
                        block_q=blocks[0], block_k=blocks[1],
                        dropout_rate=dropout_rate, seed=seed)
    return jnp.swapaxes(out, 1, 2)


def _flash_core_fwd(q, k, v, q_seg, kv_seg, seed, causal, scale,
                    interpret, blocks, dropout_rate):
    from jax.ad_checkpoint import checkpoint_name
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out, lse = _flash_fwd(qh, kh, vh, q_seg, kv_seg, causal=causal,
                          scale=scale, interpret=interpret,
                          block_q=blocks[0], block_k=blocks[1],
                          dropout_rate=dropout_rate, seed=seed)
    # Name the kernel residuals so remat policies can pin them: without
    # these tags, ``remat="selective"`` recomputes the whole forward
    # kernel inside the backward (saving dots doesn't cover a Pallas
    # custom call). ``remat_policy`` adds save_only_these_names on top of
    # the dots policy; cost is one (b,s,h,d) bf16 + one (b,h,s) fp32 per
    # layer.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return jnp.swapaxes(out, 1, 2), (qh, kh, vh, q_seg, kv_seg, seed,
                                     out, lse)


def _flash_core_bwd(causal, scale, interpret, blocks, dropout_rate,
                    res, g):
    qh, kh, vh, q_seg, kv_seg, seed, out, lse = res
    dq, dk, dv = _flash_bwd(qh, kh, vh, q_seg, kv_seg, out, lse,
                            jnp.swapaxes(g, 1, 2), causal=causal,
                            scale=scale, interpret=interpret,
                            block_q=blocks[0], block_k=blocks[1],
                            dropout_rate=dropout_rate, seed=seed)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2), None, None, None)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_pallas(q, k, v, *, causal: bool = False,
                           segment_ids: Optional[jnp.ndarray] = None,
                           kv_segment_ids: Optional[jnp.ndarray] = None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           dropout_rate: float = 0.0,
                           dropout_key: Optional[jax.Array] = None):
    """Flash attention, (batch, seq, heads, head_dim) layout, GQA allowed.

    Differentiable via fused Pallas backward kernels. ``segment_ids`` enables
    packed/varlen batches (positions attend only within equal ids).
    ``block_q``/``block_k`` override the default tiling (must divide the
    seq lens) — see ``workloads/flash_tune.py`` for the autotune sweep.

    ``dropout_rate``/``dropout_key``: in-kernel attention-prob dropout
    (reference flash wrapper's p_dropout). The key collapses to a uint32
    seed feeding a position-addressable counter RNG (``_dropout_keep``),
    so the backward kernels regenerate the identical mask with no stored
    mask tensor and independently tuned block sizes.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    drop_active = dropout_rate > 0.0 and dropout_key is not None
    seed = jax.random.bits(dropout_key, (1,), jnp.uint32
                           ).astype(jnp.int32) if drop_active else None
    return _flash_core(q, k, v, segment_ids, kv_segment_ids, seed,
                       causal, scale, interpret, (block_q, block_k),
                       dropout_rate if drop_active else 0.0)
