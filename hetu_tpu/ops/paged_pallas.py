"""Pallas TPU paged-attention decode kernel.

The serving fleet's hottest per-token op is decode attention over the
block-paged KV arena. Until this kernel, every path paid the GATHER TAX:
``ops.attention.gather_block_rows`` materializes each row's full
``(table_width * block_size, hkv, d)`` KV view per layer per step — HBM
traffic proportional to the TABLE WIDTH, not the live context, plus a
same-size scratch allocation the XLA gather writes before attention
reads it back. This module is the TPU-native PagedAttention shape
(vLLM, SOSP'23) mapped onto the Pallas idioms the flash kernels already
use:

- **block-table-indexed async copies per KV tile**: the per-slot block
  tables and positions ride a ``PrefetchScalarGridSpec`` scalar-prefetch
  operand, so each grid step's K/V BlockSpec ``index_map`` reads the
  table and DMAs the *physical* arena page straight into VMEM — the
  indirection costs an SMEM lookup, not a materialized gather;
- **layer-indexed pages**: the arena operand is the STACKED leaf
  ``(layers, n_blocks, block_size, hkv*d)`` and the layer is one more
  scalar-prefetch operand, so a page's ``index_map`` is ``(layer,
  table[s, w], 0, 0)`` — the layer scan of the fused serving step
  carries the whole arena and never slices a layer out for the kernel
  (a slice is a copy of the layer's leaf, per layer, per lane). A 3-D
  ``(n_blocks, block_size, hkv*d)`` arena is the one-layer case of the
  same call;
- **a grid that is the work**: the decode and verify rows' call walks
  the LIST of live (slot, table chunk) pairs (:func:`decode_work_list`,
  made on the device with the call from the offsets, the window and
  the slots' ``live`` flags; its length is the grid's one bound, data).
  A freed or prefilling slot — whatever position it was left at — and
  a chunk above a slot's context or wholly below its window cost no
  grid step, no index map, no fetch and no compute: cost scales with
  ``ceil(context / block_size)`` pages of the slots that decode, not
  with ``slots x table_width`` (the long-prompt lane's wide tables ride
  free). Rows nobody computes come back as zeros with the empty part's
  LSE;
- **online softmax** over a slot's chunks (successive grid steps):
  running max / denominator / accumulator live in VMEM scratch exactly
  like ``flash_pallas``, begun at a slot's first pair and written out
  at its last;
- **one tile a (head, chunk)**: inside a grid step the chunk's
  ``pages_per_step`` pages are ONE key tile per kv head — the head's
  lane slice of each page block, joined along the key axis in VMEM —
  so a head costs one ``(rows, pages * block_size)`` score product,
  one mask, one online-softmax update and one value product a chunk,
  not one of each a page (a decode row against a page of 16 keys fills
  16 of the MXU's 128 lanes and pays a full update for it). The pages
  of a slot's last chunk that lie beyond its context are MASKED with
  the rest of the page its context ends in, not skipped: they hold
  whatever finite numbers the pages the table names hold. With a
  ``window`` (static ``None`` on models that have none) the pages of
  its first chunk wholly below the window are masked too, and not
  fetched. A whole chunk nobody sees is no grid step (the work list)
  or a skipped one (a tile under a lower cap than the deepest);
- **per-row ``q_offset`` semantics**: q row ``i`` of slot ``s`` attends
  absolute positions ``<= q_offset[s] + i`` — the speculative verify
  lane's k+1 rows (PR 11) are the contract
  ``attention_reference(q_offset=array)`` speaks;
- **tiles of a prefill pack** (:func:`paged_history_attention`): the
  packed-prefill lane's read of each request's RESIDENT history. The
  tokens of one request's run share one pass over that request's pages
  per tile of the chunk — the same kernel under a key cap, on a grid
  whose two bounds are data (the tiles that have history x the table
  chunks under the deepest cap). A grid step holds a key tile of
  SEVERAL pages, joined as the decode call joins them
  (:func:`history_tile_pages`: as many as fit beside the tile's rows,
  up to 512 keys), and its mask is the cap and the window's lower edge
  alone — a tile's rows stand above every key they read. The chip's
  sweep of this body (PERF.md, PR 42; ms a call at 1 / 2 / 4 / 8
  pages): 8.13 / 4.37 / 2.52 / 1.97 at 16 heads over a 640-wide latent
  row (one page a step with the causal compare: 8.61), 19.0 / 10.0 /
  5.71 / 4.44 at 32 heads, 6.09 / 3.58 / 2.22 / 1.99 at 128 q heads
  over 8 kv heads under a window, 0.305 / 0.197 / 0.128 / 0.089 at
  GPT-2 large's 20 heads over 16-key pages. A second body without any
  mask for the chunks wholly under the cap and inside the window
  changed no time by more than 1 % at any shape and is not there;
- **arena-layout lanes**: fp32/bf16 arenas stream directly; the int8
  arena streams quantized pages + their fp32 scales and dequantizes
  per tile in VMEM (1/4 the HBM bytes of a dequantized gather).

``pages_per_step`` (how many table lanes one grid step streams and
joins: the keys of a tile) is the kernel's tunable:
``workloads/paged_tune.py`` measures winners per block size on the real
chip into ``workloads/out/paged_blocks.json``
(``core.measured.read_measured``, the same persistence the flash block
sweep uses).

The XLA-gather path (``paged_attention_reference``) remains the CPU
path and the parity oracle; dispatch lives in
``ParallelAttention._decode`` behind ``attn_kernel="paged"|"reference"``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.flash_pallas import _interpret_default

NEG_INF = -1e30
NUM_LANES = 128


def _tuned_pages(block_size: int) -> Optional[int]:
    """Measured ``pages_per_step`` winner for this block size
    (``workloads/paged_tune.py`` → ``paged_blocks.json``), or None."""
    if jax.default_backend() != "tpu":
        return None
    from hetu_tpu.core.measured import read_measured
    data = read_measured("paged_blocks.json")
    try:
        for e in data["entries"]:
            if int(e["block_size"]) == int(block_size):
                return int(e["pages_per_step"])
    except (KeyError, TypeError, ValueError):
        pass
    return None


def default_pages_per_step(block_size: int) -> int:
    """Tuned winner when measured, else the pages of a head's key tile:
    at most 128 keys (one lane tile of scores), from at most 3 pages.
    The cap is the benchmark's, not the chip's: the sweep of the joined
    body (PERF.md, PR 34) has a 16-key page's call faster with every
    page joined up to 8 — 1,818 / 1,224 / 882 / 738 / 461 us a layer
    call at 1 / 2 / 3 / 4 / 8 pages in ``gpt2-large.backlog``'s shape —
    and at 4 that cell serves its 400-request backlog to within 7
    requests of empty, at 8 it empties it before the window ends and
    the run cannot be judged. ``min(8, 128 // block_size)`` is for the
    PR after the benchmark deepens that backlog."""
    tuned = _tuned_pages(block_size)
    if tuned is not None:
        return max(1, tuned)
    return max(1, min(3, 128 // max(1, int(block_size))))


def table_chunks(table_width: int, block_size: int,
                 pages_per_step: Optional[int] = None) -> tuple[int, int]:
    """``(pages a grid step streams, steps over a table)``: how the
    paged call cuts a ``table_width``-lane block table into chunks."""
    pages = pages_per_step or default_pages_per_step(block_size)
    pages = max(1, min(pages, table_width))
    return pages, -(-table_width // pages)


def _paged_kernel(tbl_ref, off_ref, lyr_ref, *refs, rows, g, bs, L, hkv,
                  quant, windowed, tiled=False, v_width=None, block=1,
                  band=None):
    """One grid step: slot ``s``, table-lane chunk ``w`` (L whole pages
    ``(bs, hkv*d)`` of the layer the index maps picked — the layer and
    page dims are squeezed out of the block — every kv head: a TPU
    block's last two dims must be (8, 128)-tiled or span the array's,
    so heads are lane slices taken inside the kernel). Per kv head the
    chunk is ONE ``(L*bs, d)`` key tile (the head's slice of the L
    pages, joined): one score product, one mask over the chunk's
    positions, one online-softmax update, one value product — at
    ``L`` = 1 there is nothing to join and the page is the tile. Online
    softmax across a slot's chunks, which are successive grid steps.
    ``windowed``: a fourth scalar operand holds each slot's attention
    window. The decode and verify rows' call (not ``tiled``): the grid
    is the LIST of live (slot, chunk) pairs, two more scalar operands
    (:func:`decode_work_list`) — step ``i`` is pair ``i``, a slot's
    first pair is the one whose neighbour below is another slot's, its
    last the one whose neighbour above is. ``tiled``: slot ``s`` is a
    TILE of a prefill pack — four more scalar operands hold its key
    cap, the q cell it reads (the index maps' business) and the cell's
    rows ``[lo, hi)`` that are its own; the grid is (tiles, chunks)
    and its bounds are data; a tile's rows stand ABOVE every key they
    read, so its mask is the cap (and a window's lower edge) with no
    causal compare.
    ``v_width`` (a latent arena): there are
    no value pages — a key row's first ``v_width`` columns are its
    value, taken from the key page the step already holds.
    ``block`` > 1 (the decode rows' call, no window): the block bound —
    a row at position ``p`` sees the keys ``<= p | (block - 1)``, its
    own block whole (``ops.attention.block_bound``).
    ``band`` ``(band_blocks, init_blocks)`` (a ``tiled`` call, no
    window): the tile's table holds its rows' OWN keys — a row at
    ``t`` sees the keys ``<= t`` of the ``band_blocks`` blocks before
    its own, of its own, and of the table's first ``init_blocks``
    lanes; the cap says only where the tile's last row stands."""
    del lyr_ref                     # read by the page index maps only
    win_ref = None
    if windowed:
        win_ref, *refs = refs
    if tiled:
        cap_ref, _, lo_ref, hi_ref, *refs = refs
        n_steps = pl.num_programs(1)
    else:
        slot_ref, chunk_ref, *refs = refs
    q_ref, *refs = refs
    if tiled:
        s_i = pl.program_id(0)
        w = pl.program_id(1)

        def first():
            return w == 0

        def last():
            return w == n_steps - 1
    else:
        i = pl.program_id(0)
        s_i, w = slot_ref[i], chunk_ref[i]

        def first():
            return (i == 0) | (slot_ref[jnp.maximum(i - 1, 0)] != s_i)

        def last():
            above = jnp.minimum(i + 1, slot_ref.shape[0] - 1)
            return (i == pl.num_programs(0) - 1) | (slot_ref[above] != s_i)

    # static ref layout: L k pages, L v pages, [L k scales, L v scales],
    # then outputs (o, lse) and scratch (m, l, acc)
    k_pages = refs[:L]
    if v_width is None:
        v_pages = refs[L:2 * L]
        idx = 2 * L
    else:
        v_pages, idx = k_pages, L
    if quant:
        ks_pages = refs[idx:idx + L]
        vs_pages = refs[idx + L:idx + 2 * L]
        idx += 2 * L
    o_ref, lse_ref = refs[idx], refs[idx + 1]
    m_scr, l_scr, acc_scr = refs[idx + 2], refs[idx + 3], refs[idx + 4]

    @pl.when(first())
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    d = q_ref.shape[-1]
    span = L * bs                   # the chunk's keys: ONE tile a head
    off = off_ref[s_i]
    if not tiled or windowed:
        # q row r of the (rows = R*g) tile belongs to verify row r // g
        # and attends absolute positions <= off + r // g (a tile's rows
        # need theirs for a window's lower edge only)
        qpos = off + jax.lax.broadcasted_iota(
            jnp.int32, (rows, span), 0) // g
    if band is not None:
        # a row's position and the first key of its band: a column
        band_blocks, init_blocks = band
        qpos = off + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // g
        edge = (qpos // bs - band_blocks) * bs
    last_q = off + (rows // g - 1)
    if block != 1:
        qpos, last_q = qpos | (block - 1), last_q | (block - 1)
    if tiled:
        # the tile's own rows are [lo, hi) of the cell and none sees a
        # key above the cap (they sit ABOVE the keys they read)
        cap, lo, hi = cap_ref[s_i], lo_ref[s_i], hi_ref[s_i]
        last_q = jnp.minimum(off + hi - 1, cap)
        off = off + lo
    if windowed:
        # the first key the slot's FIRST row sees (the lowest any sees)
        first_k = off - win_ref[s_i] + 1
    if band is not None:
        # ... and the tile's first row's, past the leading lanes
        first_k = (off // bs - band_blocks) * bs

    # the online-softmax lines below speak lax, not jnp: this body is
    # traced hkv times and each jnp call pays the jit machinery again
    # (a good part of this kernel's trace time: seconds of every
    # serving cell's warm-up); the jaxpr is the same
    def rowwise(x):                 # (rows,) -> (rows, 1)
        return lax.broadcast_in_dim(x, (rows, 1), (0,))

    def lanes(x):                   # (rows, 1) -> (rows, NUM_LANES)
        return lax.broadcast_in_dim(x, (rows, NUM_LANES), (0, 1))

    def joined(pages, h, scales=None):
        """Head ``h``'s ``(span, d)`` tile of the chunk: its lane slice
        of the L pages, joined along the key axis (an int8 page
        dequantised in VMEM on the way)."""
        parts = []
        for j, page in enumerate(pages):
            x = page[:, h * d:(h + 1) * d]       # (bs, d)
            if scales is not None:
                x = x.astype(jnp.float32) * scales[j][:, h:h + 1]
            parts.append(x)
        return jnp.concatenate(parts, axis=0)    # one page: itself

    # the chunk's first position, written as the start of its page
    # j = 0 was when the body looped over pages: the decode call's
    # jaxpr stays the pinned one to the character
    chunk_start = (w * L + 0) * bs

    def compute():
        # ONE mask over the chunk's positions: the pages of a slot's
        # last chunk above its context (and of its first below the
        # window) are masked like the rest of the page its context
        # ends in; what they hold is never seen. A tile's rows stand
        # above the cap: the causal compare would be true everywhere
        kpos = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, span), 1)
        if band is not None:
            # the tile's own keys: causal, from the row's band's first
            # block (FLOORED to a block, the selection's rule) or in
            # the table's leading lanes
            mask = (kpos <= qpos) & (
                (kpos >= edge) | (kpos < init_blocks * bs))
        else:
            mask = kpos <= cap if tiled else kpos <= qpos
        if windowed:
            mask &= kpos > qpos - win_ref[s_i]
        # a row whose keys of this chunk are ALL masked would weigh
        # them 1 (exp(NEG_INF - NEG_INF)): p is masked again — but a
        # live chunk's first key is under the cap, so without a window
        # every row of a tile sees one and exp(NEG_INF - m) is 0
        # (under a band a chunk may lie above a row of the tile)
        remask = not tiled or windowed or band is not None
        # a head's index as an array, made once a chunk: an int index
        # is converted again at each of its eight uses (a literal
        # either way)
        heads = [jnp.asarray(h) for h in range(hkv)]
        for h, at in enumerate(heads):
            q = q_ref[heads[0], at]              # (rows, d), scale folded
            if quant:
                k = joined(k_pages, h, ks_pages)
                v = joined(v_pages, h, vs_pages)
            elif v_width is not None:
                k = joined(k_pages, h)           # (span, d), fetched once
                v = k[:, :v_width]
            else:
                k = joined(k_pages, h)           # (span, d)
                v = joined(v_pages, h)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[at, :, :1]
            l_prev = l_scr[at, :, :1]
            m_next = lax.max(m_prev, rowwise(lax.reduce_max(s, (1,))))
            p = lax.exp(lax.sub(s, m_next))
            if remask:
                p = jnp.where(mask, p, 0.0)
            l_cur = rowwise(lax.reduce_sum(p, (1,)))
            alpha = lax.exp(lax.sub(m_prev, m_next))
            m_scr[at] = lanes(m_next)
            l_scr[at] = lanes(lax.add(lax.mul(alpha, l_prev), l_cur))
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[at] = lax.add(lax.mul(acc_scr[at], alpha), pv)

    # a chunk wholly beyond the last live position, or wholly below the
    # window, never touches the MXU. The decode rows' work list holds
    # no such chunk; a tile of a prefill pack under a lower cap than
    # the deepest (the grid's bound) has them
    live = chunk_start <= last_q
    if windowed:
        live &= chunk_start + span > first_k
    if band is not None:
        live &= (chunk_start + span > first_k) \
            | (chunk_start < init_blocks * bs)
    pl.when(live)(compute)

    @pl.when(last())
    def _finalize():
        l = l_scr[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        if tiled:
            # the cell's other rows are another tile's (the block stays
            # put between tiles of one cell) or nobody's
            row = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
            own = (row >= lo * g) & (row < hi * g)

            def put(ref, new):
                ref[0] = jnp.where(own, new.astype(ref.dtype), ref[0])
        else:
            def put(ref, new):
                ref[0] = new.astype(ref.dtype)
        put(o_ref, acc_scr[...] / l_safe)
        lse = jnp.where(l == 0.0, NEG_INF,
                        m_scr[:, :, :1] + jnp.log(l_safe))
        put(lse_ref, jnp.broadcast_to(lse, lse_ref.shape[1:]))


def _stacked(x):
    """An arena operand as a stacked ``(layers, n_blocks, block_size,
    H)`` leaf: one layer's 3-D leaf is the one-layer stack."""
    if x.ndim not in (3, 4):
        raise ValueError(
            f"a paged arena operand is (n_blocks, block_size, H) or "
            f"(layers, n_blocks, block_size, H); got {x.shape}")
    return x[None] if x.ndim == 3 else x


def decode_work_list(q_offset, live=None, *, rows: int, span: int,
                     n_steps: int, window=None, block: int = 1):
    """The decode and verify rows' work: the live (slot, table chunk)
    pairs, in slot order, chunks ascending — the paged call's grid.
    (``block``: the rows' block bound; a slot's last chunk is the one
    that holds the END of its last row's block.)

    A chunk is ``span`` positions of a slot's table (``pages_per_step``
    pages). Slot ``s`` with its ``rows`` q rows at ``q_offset[s] ..``
    has the chunks from the one that holds the first key its first row
    sees (position 0, or ``q_offset[s] - window + 1`` under a
    ``window``: a scalar or one a slot) to the one that holds its last
    row's position; a slot that is not ``live`` (``None``: all are) has
    none, whatever its offset says. Returns ``(slot, chunk, n)``: two
    ``(S * n_steps,)`` int32 arrays whose first ``n`` entries are the
    pairs (the rest 0) and the count. Made on the device with the
    call: a cumsum over the slots and one ``(pairs, slots)`` compare,
    no gather, no sort."""
    off = jnp.asarray(q_offset, jnp.int32)
    S = off.shape[0]
    last_q = off + (rows - 1)
    if block != 1:
        last_q = last_q | (block - 1)
    last = jnp.clip(last_q // span, 0, n_steps - 1)
    if window is None:
        first = jnp.zeros_like(last)
    else:
        first = jnp.minimum(
            jnp.maximum(off - jnp.asarray(window, jnp.int32) + 1, 0)
            // span, last)
    count = last - first + 1
    if live is not None:
        count = jnp.where(live, count, 0)
    end = jnp.cumsum(count)
    i = jnp.arange(S * n_steps, dtype=jnp.int32)
    # pair i is slot s's when the slots before s end at or below i: the
    # slot is how many ends lie at or below i, and base[slot] (a
    # slot's first chunk less its first pair's index) is base[0] plus
    # the steps of base over those slots
    below = end[None, :] <= i[:, None]
    base = first - (end - count)
    step = jnp.diff(base, append=base[-1:])
    slot = jnp.sum(below, axis=1, dtype=jnp.int32)
    chunk = i + base[0] + jnp.sum(jnp.where(below, step[None, :], 0),
                                  axis=1, dtype=jnp.int32)
    n = end[-1]
    return jnp.where(i < n, slot, 0), jnp.where(i < n, chunk, 0), n


def paged_attention_pallas(q, k, v, block_tables, q_offset, *,
                           layer=None, k_scale=None, v_scale=None,
                           scale: Optional[float] = None,
                           pages_per_step: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           return_lse: bool = False, window=None,
                           live=None, tiles=None,
                           v_width: Optional[int] = None,
                           block: int = 1, band=None):
    """Decode attention through per-slot block tables, in-kernel.

    - ``q``: ``(S, R, hq, d)`` — S slots × R rows (1 for classic decode,
      k+1 for the speculative verify lane); row ``i`` of slot ``s``
      attends absolute positions ``<= q_offset[s] + i``.
    - ``k``/``v``: the paged arena as stored — the STACKED leaves
      ``(layers, n_blocks, block_size, hkv*d)`` of
      ``models.generation.init_paged_caches`` with ``layer`` (an int32
      scalar, traced inside the layer scan) naming the layer to read,
      or one layer's ``(n_blocks, block_size, hkv*d)`` without
      ``layer``. The minor dim is the ONE layout: a ``(hkv, d)`` pair
      would make the TPU re-tile the whole arena around the kernel.
      int8 when ``k_scale``/``v_scale`` (minor ``hkv``, fp32) are
      given — pages dequantize per tile in VMEM. Each operand's rank
      says whether ``layer`` indexes it: the scales may be ONE layer's
      3-D leaves beside a stacked ``k``/``v`` (their minor dim is under
      a lane tile, so the TPU stores the stack blocks-minor and only a
      re-tiled copy has whole pages — ``ParallelAttention._decode``
      re-tiles one layer, not the stack).
    - ``block_tables``: ``(S, W)`` int32 — logical lane ``w`` of slot
      ``s`` holds positions ``[w*block_size, (w+1)*block_size)`` at
      physical page ``block_tables[s, w]``.
    - ``q_offset``: ``(S,)`` int32 per-slot base position.
    - ``window`` (``None`` = none; a static choice): row ``i`` of slot
      ``s`` sees only keys ``> q_offset[s] + i - window`` — an int32
      scalar or one value per slot ``(S,)``, traced (the layers of a
      scanned block differ by it), a FOURTH scalar-prefetch
      operand. Pages wholly below a slot's window are neither computed
      nor fetched: their index maps name the window's first page again,
      and a block whose index did not change is not copied. A
      full-attention layer of a model that has window layers passes a
      window no key is ever below (``2 ** 30``).
    - ``live`` (``None`` = every slot): ``(S,)`` bool, the slots that
      decode. The grid is the list of live (slot, table chunk) pairs
      (:func:`decode_work_list`), so a slot that is not live costs
      nothing — its ``q_offset`` may be stale and its table row
      anything that names pages of the arena — and its rows come back
      as zeros, its LSE ``NEG_INF``. Not with ``tiles``.
    - ``tiles`` (``None`` = none; a static choice like ``window``): the
      slots are TILES of a prefill pack, cut on the host
      (:func:`pack_history_tiles`). ``q`` is then the pack in CELLS of
      R rows ``(cells, R, hq, d)`` and every other per-slot operand is
      per tile ``(G, ...)``: tile ``t`` reads cell ``tiles["cell"][t]``,
      owns its rows ``[lo[t], hi[t])``, sits with the cell's row 0 at
      ``q_offset[t]`` and sees keys ``<= min(q_offset[t] + i,
      cap[t])`` — the rows stand at their true positions ABOVE the keys
      they read, so a ``window`` is the layer's own. Four more
      scalar-prefetch operands. The grid is DATA: tiles ``[0, last
      tile with cap >= 0]`` x the table chunks under the deepest cap,
      and above a tile's cap no page is fetched. A tile writes its own
      rows only (tiles of one cell are adjacent: the block stays put
      between them); rows no tile owns are never written, the caller
      masks them.
    - ``v_width`` (``None`` = none; a static choice): a LATENT arena —
      ``v`` is ``None`` and a key row's first ``v_width`` columns are
      also its value (MLA: ONE key head ``[c ‖ k_rope ‖ pad]`` that all
      query heads share, ``q`` in the absorbed form of the same width).
      A page is fetched once; the result is ``v_width`` wide. ``scale``
      must be given: the row's width says nothing about it. No int8
      form.
    - ``block`` (1 = causal; a static power of two): the block bound of
      a block-diffusion model's lane — row ``i`` of slot ``s`` sees the
      keys ``<= (q_offset[s] + i) | (block - 1)``, every key of its own
      block. Not with ``window`` or ``tiles`` (a tile's rows stand above
      their keys: the history read needs no bound).
    - ``band`` (``None`` = none; static ``(band_blocks, init_blocks)``,
      with ``tiles`` and no ``window``): the tiles' tables hold their
      rows' OWN keys, and row ``i`` of tile ``t`` at ``p = q_offset[t]
      + i`` sees key ``j`` of the table iff ``j <= p`` and (``j //
      block_size >= p // block_size - band_blocks`` or ``j <
      init_blocks * block_size``): causal, from a lower edge FLOORED
      to a block (a block-sparse selection's forced band, not the
      sliding ``window``), plus the table's leading lanes. Of
      ``tiles["cap"]`` only the sign is read (< 0: a dead tile); pages
      wholly above a tile's last row, or below its first row's edge
      and past the leading lanes, are neither fetched nor computed.

    Returns ``(S, R, hq, d)`` in q's dtype (plus the fp32
    ``(S, R*… )``-shaped LSE ``(S, hq, R)`` when ``return_lse`` — the
    packed-prefill lane's LSE-combine consumes it). Matches
    :func:`paged_attention_reference` up to fp associativity.
    """
    S, R, hq, d = q.shape
    latent = v_width is not None
    if latent:
        if v is not None or k_scale is not None or scale is None \
                or not 0 < v_width <= d:
            raise ValueError(
                "v_width= is the latent arena's call: v=None (the value "
                "is a prefix of the key row), no int8 scales, scale= "
                f"given, 0 < v_width <= {d}; got v_width={v_width}")
        v = k
    dv = v_width if latent else d
    if (layer is None) != (k.ndim == 3) or k.ndim != v.ndim:
        raise ValueError(
            f"layer= goes with stacked (layers, n_blocks, block_size, "
            f"hkv*d) k/v and only with them; got k {k.shape}, v "
            f"{v.shape}, layer={layer!r}")
    layer = jnp.asarray(0 if layer is None else layer,
                        jnp.int32).reshape(1)
    bs, hkv = k.shape[-2], k.shape[-1] // d
    g = hq // hkv
    rows = R * g
    quant = k_scale is not None
    W = block_tables.shape[1]
    L, n_steps = table_chunks(W, bs, pages_per_step)
    Wp = n_steps * L
    if Wp != W:
        # pad lanes point at the null block; their positions start at
        # W*bs > any live q position, so the mask keeps them inert
        block_tables = jnp.pad(block_tables, ((0, 0), (0, Wp - W)))
    block_tables = block_tables.astype(jnp.int32)
    T = block_tables.shape[0]       # slots; tiles, where q is in cells
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(T)
    scalars = (block_tables, q_offset, layer)
    windowed = window is not None
    if windowed:
        scalars += (jnp.broadcast_to(jnp.asarray(window, jnp.int32),
                                     (T,)),)
    tiled = tiles is not None
    if block != 1 and (block < 1 or block & (block - 1) or windowed
                       or tiled):
        raise ValueError(
            f"block={block}: a block bound is a power of two, and goes "
            f"with neither a window nor tiles")
    if band is not None and (not tiled or windowed):
        raise ValueError(
            f"band={band}: the banded causal mask is the tiled call's "
            f"(tiles=), and its lower edge takes a window's place")
    if tiled:
        if live is not None:
            raise ValueError("live= is per slot; a dead tile says so "
                             "in its cap")
        cap = jnp.asarray(tiles["cap"], jnp.int32)
        if band is not None:
            # a live tile's last key is its last row's own
            cap = jnp.where(cap >= 0, q_offset + jnp.asarray(
                tiles["hi"], jnp.int32) - 1, -1)
        scalars += (cap,) + tuple(jnp.asarray(tiles[n], jnp.int32)
                                  for n in ("cell", "lo", "hi"))
        # tiles with history come first (the host packs them so) and
        # table chunks above the deepest cap hold nothing for anyone
        grid = (jnp.max(jnp.where(cap >= 0, jnp.arange(T) + 1, 1)),
                jnp.clip(jnp.max(cap) // (L * bs) + 1, 1, n_steps))
    else:
        # the grid is the list of live (slot, chunk) pairs, made here
        # on the device: no step for a dead slot or above a context.
        # No live slot at all: one step, pair (0, 0), whose rows the
        # mask below zeroes like every dead slot's.
        with jax.named_scope("hetu.paged_attn"):
            slot, chunk, n = decode_work_list(
                q_offset, live, rows=R, span=L * bs, n_steps=n_steps,
                window=window, block=block)
        scalars += (slot, chunk)
        grid = (jnp.maximum(n, 1),)
    interpret = _interpret_default() if interpret is None else interpret
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    # (S, R, hkv*g, d) → (S, hkv, R*g, d): tile row r = (row r//g,
    # group member r%g) so one kv head serves its whole q group
    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qh = qf.reshape(S, R, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(S, hkv, rows, d)

    def pair(at):
        """(slot or tile, chunk, the scalar operands) of a grid step."""
        if tiled:
            return at[0], at[1], at[2:]
        return at[-2][at[0]], at[-1][at[0]], at[1:]

    def whole(*at):
        s, _, scalars = pair(at)
        if tiled:
            s = scalars[-3][s]              # (.., cap, CELL, lo, hi)
        return (s, 0, 0, 0)

    def page_spec(j, x):
        # one whole page of one layer, all kv heads: the block's last
        # two dims span the arena's (block_size, hkv*d) — a one-head
        # block would slice the minor dims below the TPU's (8, 128)
        # tile. A 3-D operand is one layer already: its pages are read
        # at layer 0 of the one-layer stack.
        stacked = x.ndim == 4

        def index(*at):
            s, w, (tbl, off, lyr, *more) = pair(at)
            lane = w * L + j
            first = off[s]
            if tiled:
                cap, _, lo, _ = more[-4:]
                first += lo[s]      # a tile's first row: the cell's lo
            if windowed:
                # table lanes below the slot's window name its first
                lane = jnp.maximum(
                    lane, jnp.maximum(first - more[0][s] + 1, 0) // bs)
            if band is not None:
                # ... and those below the band of the tile's first
                # row, past the table's leading lanes, the band's first
                lane = jnp.where(lane < band[1], lane, jnp.maximum(
                    lane, first // bs - band[0]))
            if tiled:
                # ... and lanes above its cap its last: a block whose
                # index did not change is not copied
                lane = jnp.minimum(lane, jnp.maximum(cap[s], 0) // bs)
            return (lyr[0] if stacked else 0, tbl[s, lane], 0, 0)

        return pl.BlockSpec((None, None, bs, x.shape[-1]), index)

    in_specs = [pl.BlockSpec((1, hkv, rows, d), whole)]
    args = [qh]
    for x in ((k,) if latent else (k, v)) \
            + ((k_scale, v_scale) if quant else ()):
        in_specs += [page_spec(j, x) for j in range(L)]
        args += [_stacked(x)] * L

    out_specs = [pl.BlockSpec((1, hkv, rows, dv), whole),
                 pl.BlockSpec((1, hkv, rows, NUM_LANES), whole)]
    out_shape = [
        jax.ShapeDtypeStruct((S, hkv, rows, dv), q.dtype),
        jax.ShapeDtypeStruct((S, hkv, rows, NUM_LANES), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, NUM_LANES), jnp.float32),
            pltpu.VMEM((hkv, rows, NUM_LANES), jnp.float32),
            pltpu.VMEM((hkv, rows, dv), jnp.float32),
        ],
    )
    with jax.named_scope("hetu.paged_attn"):
        out, lse_l = pl.pallas_call(
            functools.partial(_paged_kernel, rows=rows, g=g, bs=bs, L=L,
                              hkv=hkv, quant=quant, windowed=windowed,
                              tiled=tiled, v_width=v_width,
                              **({"block": block} if block != 1 else {}),
                              **({} if band is None
                                 else {"band": tuple(band)})),
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                # a slot's pairs, and the tiles of one cell, share an
                # output block: in order
                dimension_semantics=("arbitrary",) * len(grid)),
            interpret=interpret,
            name="hetu_paged_attn",
        )(*scalars, *args)

    # (S, hkv, R*g, d) → (S, R, hq, d)
    out = out.reshape(S, hkv, R, g, dv).transpose(0, 2, 1, 3, 4) \
        .reshape(S, R, hq, dv)
    if live is not None:
        # a dead slot's blocks were never written: nobody's rows are
        # zeros, with the empty part's LSE
        out = jnp.where(live[:, None, None, None], out, 0)
    if return_lse:
        # (S, hkv, R*g) rows (i*g + gj) → (S, hq, R) with head
        # h = kh*g + gj — the attention_reference LSE layout
        lse = lse_l[..., 0].reshape(S, hkv, R, g) \
            .transpose(0, 1, 3, 2).reshape(S, hq, R)
        if live is not None:
            lse = jnp.where(live[:, None, None], lse, NEG_INF)
        return out, lse
    return out


def paged_attention_auto(q, k, v, block_tables, q_offset, *,
                         layer=None, k_scale=None, v_scale=None,
                         scale: Optional[float] = None,
                         pages_per_step: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         return_lse: bool = False, window=None,
                         live=None, tiles=None,
                         v_width: Optional[int] = None, block: int = 1,
                         band=None):
    """:func:`paged_attention_pallas`, tp-aware.

    Mosaic kernels cannot be GSPMD-auto-partitioned, so under a
    tp-sharded activation context the raw call would not compile — the
    historical fallback was the gather path (the ``tp`` fallback site).
    This wrapper closes that gap: when the current plan binds a tp axis
    of size > 1 and both head counts divide it, the kernel call is
    wrapped in ``shard_map`` over that axis — each shard streams only
    its LOCAL head slice of the stacked arena (block tables, offsets,
    ``live`` and the layer ride replicated; the GQA group layout is
    head-major, so an even hkv split keeps q-head groups contiguous per
    shard).
    Everything else (no context, tp == 1, ragged heads — which
    ``resolve_decode_kernel`` already degrades) is the plain call."""
    from hetu_tpu.parallel.sharding import (
        _axis_size, current_act_sharding,
    )

    def call(q, k, v, tbl, off, layer, ks, vs, live=live, tiles=tiles):
        return paged_attention_pallas(
            q, k, v, tbl, off, layer=layer, k_scale=ks, v_scale=vs,
            scale=scale, pages_per_step=pages_per_step,
            interpret=interpret, return_lse=return_lse, window=window,
            live=live, tiles=tiles, v_width=v_width, block=block,
            band=band)

    ctx = current_act_sharding()
    head_ax = ctx.tp if ctx is not None and isinstance(ctx.tp, str) \
        else None
    nh = _axis_size(ctx.mesh, head_ax) if ctx is not None else 1
    hq, hkv = q.shape[2], k.shape[-1] // q.shape[3]
    if nh <= 1 or hq % nh or hkv % nh:
        # resolve_decode_kernel degrades ragged head counts before the
        # trace ever reaches here; keep the plain call as the safe twin
        return call(q, k, v, block_tables, q_offset, layer, k_scale,
                    v_scale)
    if window is not None or v_width is not None:
        raise NotImplementedError(
            "a windowed or latent paged call under a tp-sharded plan: "
            "the window would have to ride the shard_map as an operand, "
            "and a latent row has one key head to split")

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if layer is None:                # one layer: the one-layer stack
        k, v, layer = k[None], v[None], 0

    def page_spec(x):                # arena: head-major minor dim
        return P(*(None,) * (x.ndim - 1), head_ax)

    head_spec = P(None, None, head_ax, None)     # q/out: heads dim 2
    in_specs = (head_spec, page_spec(k), page_spec(v), P(None, None),
                P(None), P(None))
    args = (q, k, v, block_tables, jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1))
    if k_scale is not None:
        in_specs += (page_spec(k_scale), page_spec(v_scale))
        args += (k_scale, v_scale)
    if live is not None:
        in_specs += (P(None),)
        args += (live,)
    tile_keys = sorted(tiles) if tiles is not None else []
    in_specs += (P(None),) * len(tile_keys)      # the tile map rides
    args += tuple(jnp.asarray(tiles[n], jnp.int32) for n in tile_keys)
    out_specs = (head_spec, P(None, head_ax, None)) if return_lse \
        else head_spec

    def local(q, k, v, tbl, off, lyr, *rest):
        rest = list(rest)
        ks, vs = (rest.pop(0), rest.pop(0)) if k_scale is not None \
            else (None, None)
        lv = rest.pop(0) if live is not None else None
        return call(q, k, v, tbl, off, lyr[0], ks, vs, lv,
                    dict(zip(tile_keys, rest)) or None)

    fn = shard_map(local, mesh=ctx.mesh, in_specs=in_specs,
                   out_specs=out_specs, axis_names=set(ctx.mesh.shape),
                   check_vma=False)
    return fn(*args)


# ---------------------------------------------------------------------------
# the packed-prefill lane's history read: tiles of one request's chunk
# ---------------------------------------------------------------------------

#: what a tile's blocks may take of the 16 MiB a kernel gets of VMEM
#: by default (the rest: the compiler's own temporaries)
_TILE_VMEM_BUDGET = 12 * 2 ** 20


def history_tile_rows(g: int, d: int, hkv: int, block_size: int, *,
                      kv_itemsize: int = 2,
                      head_rows: Optional[int] = None) -> int:
    """Tokens per tile of the prefill lane's history read
    (:func:`paged_history_attention`), from shapes alone: the largest
    power of two up to 128 (a tile of ``Tq`` tokens is ``Tq * g`` rows
    per kv head on the MXU, where a decode row gives it ``g``) whose
    blocks fit the kernel's VMEM — the q cell and the two outputs
    double buffered (priced at float32), the three online-softmax
    scratch buffers, two sets of one K and one V page. 128 for GPT-2
    (``g`` = 1, 12 or 20 heads of 64), 16 for 128 q heads over 8 kv
    heads of 128. ``head_rows``: the most rows of ONE kv head a tile
    may hold, where the caller bounds them — one head's update keeps
    some seven float32 tiles of its rows against a lane tile of keys
    beside the blocks, which this pricing leaves out (a group of 16
    over pages of ONE kv head of 128 prices to 128 tokens = 2,048
    rows: 16.66 MB of the 16 MiB, refused by the chip's compiler), and
    the kernel's compile time grows with them
    (``BlockSparseAttention.BAND_ROWS``)."""
    pages = 2 * 2 * block_size * hkv * d * kv_itemsize
    tq = 128
    while tq > 8 and (_tile_cell_bytes(tq * g, d, hkv) + pages
                      > _TILE_VMEM_BUDGET
                      or head_rows is not None and tq * g > head_rows):
        tq //= 2
    # ... and not so many rows that the key tile beside them is under
    # one lane tile of keys where the pages would give more: a tile of
    # 2,560 rows (20 query heads over ONE kv head of 128) leaves room
    # for one 64-key page a step — half a lane tile of scores, the
    # accumulator rescaled as often as they are computed (PERF.md
    # section 6, PR 55: 21.2 ms a layer and iteration where 1,280 rows
    # x 512 keys read 4.6); every other shape the engines have met keeps
    # its tile
    while tq > 8 and _room_keys(tq * g, d, hkv, block_size, kv_itemsize) \
            < min(_TILE_MIN_KEYS, _TILE_PAGES * block_size):
        tq //= 2
    return tq


def _room_keys(rows: int, d: int, hkv: int, block_size: int,
               kv_itemsize: int) -> int:
    """Keys of the key tile that fits beside a cell of ``rows`` rows a
    kv head (:func:`history_tile_pages`' pricing, K and V leaves)."""
    return block_size * history_tile_pages(
        1, d, hkv, block_size, tile_rows=rows, kv_itemsize=kv_itemsize)


def _tile_cell_bytes(rows: int, d: int, hkv: int) -> int:
    """What a tile's cell of ``rows`` rows a kv head holds in VMEM,
    priced at float32: the q cell and the two outputs double buffered,
    the three online-softmax scratch buffers."""
    return hkv * rows * 4 * (2 * d + 2 * d          # q, out: x2
                             + 2 * NUM_LANES        # lse: x2
                             + 2 * NUM_LANES + d)   # m, l, acc


#: the most keys a tile of the history read scores a grid step (one
#: score tile a kv head), from at most this many pages
_TILE_KEYS, _TILE_PAGES = 512, 8
#: ... and the fewest keys :func:`history_tile_rows` leaves room for
#: (one whole lane tile of scores a step), where the pages reach that
#: many
_TILE_MIN_KEYS = NUM_LANES


def history_tile_pages(g: int, d: int, hkv: int, block_size: int, *,
                       tile_rows: int, latent: bool = False,
                       kv_itemsize: int = 2) -> int:
    """Pages a grid step of the history read streams and joins into
    ONE key tile a kv head, from shapes alone — the rows came first
    (:func:`history_tile_rows`, the same pricing of the cell): the
    largest power of two up to 8 pages and 512 keys whose blocks fit
    next to the cell — the pages double buffered (one set under a
    ``latent`` arena, whose value is its key), one head's joined key
    and value tiles and its score and probability tiles ``(tile_rows x
    g, keys)``, priced at float32. 8 pages of 64 keys beside the latent
    cells (16 or 32 heads over one 640-wide row), 4 beside 128 q heads
    over 8 kv heads of 128, 8 of GPT-2's 16-key pages."""
    rows = tile_rows * g
    room = _TILE_VMEM_BUDGET - _tile_cell_bytes(rows, d, hkv)
    leaves = 1 if latent else 2

    def blocks(keys):
        return 2 * leaves * keys * hkv * d * kv_itemsize \
            + 4 * keys * (leaves * d + 2 * rows)

    pages = _TILE_PAGES
    while pages > 1 and (pages * block_size > _TILE_KEYS
                         or blocks(pages * block_size) > room):
        pages //= 2
    return pages


def history_tile_count(chunk: int, tile_rows: int, max_runs: int) -> int:
    """The most tiles a pack of ``chunk`` tokens in at most ``max_runs``
    runs can cut into: its cells, and every run after the first may
    split one more cell."""
    return -(-chunk // tile_rows) + max(1, max_runs) - 1


#: the rows of a tile map (:func:`pack_history_tiles`): whose block
#: table, which cell of the pack, the cell's rows that are the tile's,
#: the position the cell's row 0 would have in the tile's run, and the
#: last key the tile may see (``hist - 1``; -1: a dead tile)
TILE_FIELDS = ("slot", "cell", "lo", "hi", "off", "cap")


def pack_history_tiles(runs, *, tile_rows: int, n_tiles: int,
                       every_run: bool = False):
    """The tile map of one prefill pack, on the host (numpy).

    The pack lies in CELLS of ``tile_rows`` rows; ``runs`` is ``(slot,
    first pack row, tokens, hist)`` per request of the pack (one
    contiguous run each, positions ascending from ``hist``: what lies
    below a run's first token is its history). A run WITH
    history gives one tile per cell it touches — a tile never straddles
    two runs — in pack order, so the tiles of one cell are adjacent;
    they come first, and every other tile of the static ``n_tiles`` is
    dead (``cap`` -1: the grid ends before it). Returns the map as ONE
    ``(len(TILE_FIELDS), n_tiles)`` int32 array, a row per field (one
    upload a step), and the counts ``(live tiles, empty tiles, rows in
    live tiles)``: an empty tile is one a run WITHOUT history would
    have been. ``every_run``: the map of a read that takes in the
    pack's own keys (:func:`paged_history_attention` under ``band=``)
    — a run without history is cut into tiles like any other, and a
    tile's last key is its last row's own."""
    import numpy as np
    t = np.zeros((len(TILE_FIELDS), n_tiles), np.int32)
    t[-1] = -1
    live = empty = rows = 0
    for slot, first, n, hist in runs:
        cells = range(first // tile_rows,
                      (first + n - 1) // tile_rows + 1)
        if hist <= 0 and not every_run:
            empty += len(cells)
            continue
        for c in cells:
            base = c * tile_rows
            lo = max(first, base) - base
            hi = min(first + n, base + tile_rows) - base
            off = hist + base - first
            t[:, live] = (slot, c, lo, hi, off,
                          off + hi - 1 if every_run else hist - 1)
            live += 1
        rows += n
    return t, (live, empty, rows)


def paged_history_attention(q, k, v, tile_tables, hist, tiles, *,
                            tile_rows: int, layer=None, k_scale=None,
                            v_scale=None, window=None,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            v_width: Optional[int] = None,
                            pages_per_step: Optional[int] = None,
                            band=None):
    """Each pack token's attention over its request's RESIDENT history
    (arena positions ``< hist[t]``: earlier chunks, prefix-cache hits),
    one pass over a request's pages per TILE of its chunk.

    - ``q``: ``(C, hq, d)`` pack rows; ``hist`` ``(C,)`` each token's
      run's start offset (0: no history);
    - ``tiles``: the pack's tile map (:func:`pack_history_tiles`, the
      array or its rows by name) with ``tile_tables`` ``(G, W)``, each
      tile's request's block table (``bt[tiles["slot"]]``).
      The pack is handed to :func:`paged_attention_pallas` in cells of
      ``tile_rows`` rows, in place — no row is gathered, nothing is
      padded to the tile count.

    ``v_width``: the latent arena's call (``v`` is ``None``; the result
    is ``v_width`` wide; :func:`paged_attention_pallas`).
    ``pages_per_step`` (``None``: :func:`history_tile_pages` of the
    operands' shapes): the pages of a grid step's key tile.
    ``band`` ``(band_blocks, init_blocks)``: the tiles read their
    runs' OWN keys (written before they are read) under the banded
    causal mask of :func:`paged_attention_pallas` — the forced part of
    a block-sparse selection, which the tokens of a tile share. The
    map then has EVERY run cut into tiles (:func:`pack_history_tiles`
    ``every_run=True``: ``off`` the position of a cell's row 0 in the
    tile's table, ``cap`` >= 0 for a live tile) and ``hist`` says only
    which rows are live (> 0).

    Returns ``(C, hq, d)`` and the fp32 LSE ``(C, hq)``; a token
    without history gets the empty part (0, ``NEG_INF``), which
    :func:`combine_attention_lse` weighs 0. The per-token formulation
    (``paged_attention_reference`` with one slot a token at ``hist -
    1``) is the parity oracle."""
    C, hq, d = q.shape
    if not isinstance(tiles, dict):
        tiles = dict(zip(TILE_FIELDS, tiles))
    if pages_per_step is None:
        hkv = k.shape[-1] // d
        pages_per_step = history_tile_pages(
            hq // hkv, d, hkv, k.shape[-2], tile_rows=tile_rows,
            latent=v_width is not None, kv_itemsize=k.dtype.itemsize)
    pad = -C % tile_rows
    cells = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, tile_rows, hq, d)
    out, lse = paged_attention_auto(
        cells, k, v, tile_tables, tiles["off"], layer=layer,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        pages_per_step=pages_per_step, interpret=interpret,
        return_lse=True,
        window=window, v_width=v_width, band=band,
        tiles={n: tiles[n] for n in ("cap", "cell", "lo", "hi")})
    live = hist > 0
    out = out.reshape(-1, hq, out.shape[-1])[:C]
    lse = jnp.moveaxis(lse, 1, 2).reshape(-1, hq)[:C]
    return (jnp.where(live[:, None, None], out, 0),
            jnp.where(live[:, None], lse, NEG_INF))


def paged_attention_reference(q, k, v, block_tables, q_offset, *,
                              k_scale=None, v_scale=None,
                              scale: Optional[float] = None,
                              causal: bool = True,
                              return_lse: bool = False, window=None,
                              v_width: Optional[int] = None,
                              block: int = 1):
    """The XLA-gather twin (and parity oracle): materialize each slot's
    table view with :func:`~hetu_tpu.ops.attention.gather_block_rows`
    and run the dense reference — exactly what ``ParallelAttention.
    _decode`` did before the kernel existed, kept as the CPU
    path. Int8 arenas gather quantized rows + scales (1/4 the
    bytes) and dequantize after, matching the kernel's lanes."""
    from hetu_tpu.ops.attention import (
        attention_reference, gather_block_rows,
    )
    from hetu_tpu.ops.quantization import dequantize_int8

    def rows(buf, w):
        # gather in the stored layout, split heads on the gathered rows
        # only: (S, W*block_size, hkv*w) → (..., hkv, w) — never a
        # reshape of the whole arena
        x = gather_block_rows(buf, block_tables)
        return x.reshape(x.shape[:2] + (-1, w))

    d = q.shape[-1]
    if v_width is not None:
        # the latent arena: one gather, the value a prefix of the key
        k_buf = rows(k, d)
        v_buf = k_buf[..., :v_width]
    elif k_scale is not None:
        k_buf = dequantize_int8(rows(k, d), rows(k_scale, 1), q.dtype)
        v_buf = dequantize_int8(rows(v, d), rows(v_scale, 1), q.dtype)
    else:
        k_buf, v_buf = rows(k, d), rows(v, d)
    return attention_reference(q, k_buf, v_buf, causal=causal,
                               q_offset=q_offset, kv_offset=0,
                               scale=scale, return_lse=return_lse,
                               window=window, block=block)


def combine_attention_lse(o1, lse1, o2, lse2):
    """Merge two attention partials computed over DISJOINT KV sets.

    ``o``: ``(b, q, h, d)``; ``lse``: ``(b, h, q)`` natural-log-sum-exp
    of each part's masked logits (``attention_reference(return_lse=
    True)`` / the kernels' lse output). The packed-prefill flash lane
    uses this to fuse the intra-pack flash part with the arena-history
    paged part — the standard flash-decoding split-KV reduction. A part
    with no live keys carries ``lse ≈ NEG_INF`` and weighs 0; two empty
    parts yield exact 0 (the reference's fully-masked-row convention).
    """
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    den = w1 + w2
    den = jnp.where(den == 0.0, 1.0, den)

    def rowwise(w):                      # (b, h, q) → (b, q, h, 1)
        return jnp.moveaxis(w, 1, 2)[..., None]

    out = (o1.astype(jnp.float32) * rowwise(w1 / den)
           + o2.astype(jnp.float32) * rowwise(w2 / den))
    return out.astype(o1.dtype)
