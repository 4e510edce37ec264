"""The two Pallas TPU kernels of the gated delta rule: what
:class:`~hetu_tpu.nn.parallel.KimiDeltaAttention` runs under
``hetu.kda_scan`` — the chunk form over a prefill pack
(:func:`hetu_kda_scan`, below) — and under ``hetu.kda_update`` — the
decode rows' one token a slot on the state leaf IN PLACE
(:func:`hetu_kda_update`: the grid walks the live slots,
:func:`live_list`, by blocks of heads; a live slot's state read once
and written once, nothing else of the leaf touched; its docstring has
the rest). ``ops/kda.py`` holds both kernels' ``jax.numpy`` oracles.

``ops.kda.kda_scan`` (plain ``jax.numpy``, the oracle the tests hold
this to) computed the chunk-local products for all chunks at once —
~30 float32 temporaries the size of the pack through HBM, thousands of
16 x 128 x 64 matmuls — and walked the pieces in a ``fori_loop`` of ~10
small kernels a piece around one 2 MB state (PERF.md, PR 46: 4.95 ms a
layer call where the operands' bytes take 0.14). This is the same
arithmetic as ONE call a layer call:

- **the grid is the work list of the pack's PIECES** (a chunk's rows of
  one run, in pack order) by blocks of heads — data, as the paged
  call's is (:func:`scan_work_list`: made on the device from ``slot``,
  ``pos``, ``valid`` with compares and cumsums — no gather, no sort, no
  ``nonzero`` —, prefetched as scalars, one spare entry behind the
  last). Every chunk has at least one entry (a chunk without a valid
  row has a dead one: its rows of ``o`` are written as zeros). A
  chunk is ``CHUNK`` rows; a pack shorter than that is one chunk of its
  rows in whole sub-chunks (:func:`scan_chunk`: the tiny engines' packs
  of 8 rows solve 16, not 64 of which 56 are pad — interpreted on the
  CPU that is the difference between 1.3 and 0.3 ms a call);
- **a step loads its chunk's q, k, v, g, beta rows once** (the index map
  goes piece -> chunk: consecutive pieces of one chunk keep the block)
  and masks them by the piece's ``[lo, hi)`` from scalars; the
  reference row of a sub-chunk is one dynamic sublane read;
- **a run's float32 state** ``(heads, dk, dv)`` **stays in VMEM from the
  run's first piece to its last**: the leaf ``(layers, slots, H, dk,
  dv)`` stays in HBM (``memory_space=ANY``, aliased to the result); the
  step that opens a run copies ``[layer, slot]``'s heads in (or zeroes
  the scratch where the run begins at position 0), the run's last step
  copies them out. No layer's slab and no slot's state is sliced out of
  or concatenated into the leaf by XLA;
- **heads a step from the shapes** (:func:`kda_head_block`), no knob,
  and the step's work **a stage at a time over all its heads**: the
  compiler does not interleave one head's chain of products with
  another's, it does run a stage's independent products back to back
  (the chip, PERF.md PR 46: 2.95 ms a call a head at a time, 1.86 a
  stage at a time; 2.20 / 1.89 / 1.86 at 2 / 4 / 8 heads a step);
- **two heads' chunk-square matrices side by side in the lanes**: the
  MXU's cost is the rows pushed through a weight tile, and a 64 x 64
  matrix fills half a tile — ``[X_1 | X_2] diag(Y_1, Y_2) = [X_1 Y_1 |
  X_2 Y_2]`` costs one head's rows for two (the kernel alone 1.41 ->
  1.14 ms a call; in the cell +0 to 2 %, inside what two calls of
  one body differ by).

For a piece, per head (``ops/kda.py`` has the equations): the running
sum of ``g`` from the piece's first row; the decay-scaled lower
products against a reference row between each pair (``SUB = 16``: a
kept pair's exponent is at most ``(SUB - 1) x 5 = 75 < 88``; masked
pairs are cut at ``_EXP_MAX``); ``(I + A)^-1`` from the diagonal
sub-chunks' Neumann products (``kda._neumann_inverse``'s, the four of a
chunk as one block-diagonal matrix) and the block-nilpotent rest
(``(I + E)^-1 = (I - E)(I + E^2)``, ``E = T L``, ``E^4 = 0`` — forward
substitution over the sub-chunks, as products); ``W S_0``, ``(Q e^G)
S_0``, ``P U``, ``S_C``.

**Precision is the configuration's**: float32 operands and
``precision=jax.lax.Precision.HIGHEST`` on EVERY dot (:func:`_dot`;
Mosaic lowers it as ``contract_precision<fp32>``), the state, the
decays and ``beta`` float32. Interpreted on the CPU
(``flash_pallas._interpret_default``) it takes any head size; compiled
for a TPU it refuses by name a head size that is not whole lane tiles.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.flash_pallas import _interpret_default
from hetu_tpu.ops.kda import (
    CHUNK, SUB, _EXP_MAX, _f32, _segments, _stacked, widen,
)

_HI = jax.lax.Precision.HIGHEST
#: what a step's head block may hold in VMEM: the state, and the chunk's
#: q, k, v, g rows and its rows of o double buffered
_STEP_BYTES = 4 * 2 ** 20
_VMEM_SPARE = 24 * 2 ** 20
#: flag bits of a work-list entry
_OPENS, _ZERO, _CLOSES = 1, 2, 4


def kda_head_block(H: int, dk: int, dv: int) -> int:
    """Heads a grid step holds, from shapes alone: the largest divisor
    of ``H`` whose float32 state ``(heads, dk, dv)`` and double-buffered
    chunk rows (q, k, g ``dk`` wide; v, o ``dv`` wide) fit 4 MiB, at
    most 8 (a stage's products are unrolled over the heads). 8 at
    Ling's ``(32, 128, 128)``: 0.5 MB of state beside 2.5 MB of rows
    (the chip's sweep: 1.74 ms a call at 4, 1.59 at 8)."""
    per_head = 4 * (dk * dv + 2 * CHUNK * (3 * dk + 2 * dv))
    best = 1
    for hb in range(1, min(H, 8) + 1):
        if H % hb == 0 and hb * per_head <= _STEP_BYTES:
            best = hb
    return best


class ScanWork(NamedTuple):
    """The pieces of one pack (:func:`scan_work_list`): ``(n_max,)``
    int32 arrays whose first ``n`` entries are the pieces in pack order
    (``n_max`` has one spare entry behind a full list)."""
    chunk: jax.Array     # the piece's chunk
    lo: jax.Array        # its first row in the chunk
    hi: jax.Array        # behind its last valid row (== lo: a dead piece)
    slot: jax.Array      # its run's slot
    flags: jax.Array     # _OPENS | _ZERO | _CLOSES
    n: jax.Array         # () pieces

    @property
    def live(self):
        """Pieces that hold a valid row."""
        return jnp.sum(self.hi > self.lo, dtype=jnp.int32)


def scan_chunk(rows: int) -> int:
    """Rows of a chunk, from the pack's rows alone: ``CHUNK``, or — a
    pack shorter than that is ONE shorter chunk — its rows in whole
    sub-chunks (the tiny engines' packs of 8 or 16 rows: a 16-row
    solve, not a 64-row one of which 48 rows are pad)."""
    return CHUNK if rows >= CHUNK else -(-rows // SUB) * SUB


def scan_pieces_max(rows: int, slots: int, chunk: int = CHUNK) -> int:
    """The static bound of the piece list of a pack of ``rows`` (whole
    chunks) over ``slots``: every chunk opens a piece, every run (at
    most one a slot) may open one more, and one spare entry."""
    return rows // chunk + min(slots, rows) + 1


def scan_work_list(slot, pos, valid, *, slots: int,
                   chunk: int = CHUNK) -> ScanWork:
    """The pieces of a pack whose rows are whole chunks: a piece opens
    at every chunk's first row and at every row that opens a RUN (a
    valid row whose predecessor is not the token before it of the same
    slot); its rows are the valid ones up to the next opening. A live
    piece ``_OPENS`` its run where its first row does (``_ZERO``: the
    run begins at position 0, so from a zero state) and ``_CLOSES`` it
    where the next piece does not continue it. Compares and sums over
    ``(pieces, rows)`` — no gather, no sort, no ``nonzero``."""
    C = slot.shape[0]
    slot, pos = slot.astype(jnp.int32), pos.astype(jnp.int32)
    idx = jnp.arange(C, dtype=jnp.int32)
    start = _segments(slot, pos, valid)[0]
    opens = start | (idx % chunk == 0)
    piece = jnp.cumsum(opens.astype(jnp.int32)) - 1      # a row's piece
    n_max = scan_pieces_max(C, slots, chunk)
    p = jnp.arange(n_max, dtype=jnp.int32)
    mine = piece[None, :] == p[:, None]                  # (n_max, C)
    first = mine & opens[None, :]                        # one-hot, or none

    def pick(a):
        return jnp.sum(jnp.where(first, a.astype(jnp.int32)[None, :], 0),
                       axis=1, dtype=jnp.int32)
    r0 = pick(idx)
    n_valid = jnp.sum(mine & valid[None, :], axis=1, dtype=jnp.int32)
    live = n_valid > 0
    opens_run = live & (pick(start) > 0)
    nxt = jnp.concatenate([(live & ~opens_run)[1:], jnp.zeros((1,), bool)])
    flags = (opens_run * _OPENS + (opens_run & (pick(pos) == 0)) * _ZERO
             + (live & ~nxt) * _CLOSES).astype(jnp.int32)
    n = piece[-1] + 1
    there = p < n
    lo = r0 % chunk
    return ScanWork(
        # (a spare or dead entry names the last chunk: no fetch of its own)
        chunk=jnp.where(there, r0 // chunk, C // chunk - 1),
        lo=jnp.where(there, lo, 0), hi=jnp.where(there, lo + n_valid, 0),
        slot=pick(slot), flags=jnp.where(there, flags, 0), n=n)


def _dot(a, b, dims=((2,), (1,))):
    """EVERY matmul of the scan, over the step's heads (the leading
    axis of both): float32 at the highest precision."""
    return jax.lax.dot_general(a, b, (dims, ((0,), (0,))), precision=_HI,
                               preferred_element_type=jnp.float32)


def _running_sum(x, rows):
    """The inclusive running sum down the rows of ``x (c, n)`` (``rows``
    ``(c, 1)`` their indices): log2(c) shifted adds."""
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(rows >= shift, jnp.roll(x, shift, axis=0), 0.0)
        shift *= 2
    return x


def _heads(ref, rows, d, hb):
    """``ref[rows]`` ``(n, hb * d)`` -> ``(hb, n, d)``: a head's lanes
    are whole tiles, so this moves nothing."""
    return jnp.stack([ref[rows, j * d:(j + 1) * d] for j in range(hb)])


def _pair(x, gs):
    """Heads ``(hb, n, d)`` -> groups of ``gs`` side by side in the
    lanes ``(hb / gs, n, gs d)``."""
    x = x.reshape((x.shape[0] // gs, gs) + x.shape[1:])
    return jnp.concatenate([x[:, i] for i in range(gs)], axis=2)


def _unpair(x, gs):
    """``_pair``'s inverse."""
    d = x.shape[2] // gs
    x = jnp.stack([x[:, :, i * d:(i + 1) * d] for i in range(gs)], axis=1)
    return x.reshape((-1,) + x.shape[2:])


def _blockdiag(x, gs):
    """Heads ``(hb, n, d)`` -> a group's ``gs`` heads down the diagonal
    ``(hb / gs, gs n, gs d)``: what a product of the PAIRED rows ``[X_1 |
    X_2]`` takes on its other side to give ``[X_1 Y_1 | X_2 Y_2]``."""
    if gs == 1:
        return x
    x = x.reshape((x.shape[0] // gs, gs) + x.shape[1:])
    zero = jnp.zeros_like(x[:, 0])
    return jnp.concatenate([
        jnp.concatenate([x[:, i] if i == j else zero for j in range(gs)],
                        axis=2) for i in range(gs)], axis=1)


def _piece(lo, hi, q_ref, k_ref, v_ref, beta_ref, gs_ref, row_ref, s_ref,
           o_ref, *, c, hb, dk, dv, scalar=False):
    """The step's heads on the piece ``[lo, hi)`` of a chunk of ``c``
    rows, a stage at a time
    over all of them (independent products back to back, not a head's
    chain after another's): their rows of ``o`` added, their states
    advanced. ``gs_ref`` holds the running sum of the piece's ``g``
    (zeros above the piece), ``row_ref`` its reference row for each row
    block and, behind them, its last row.

    The chunk-square matrices (A, the products of the solve) of TWO
    heads lie side by side in the lanes, ``[X_1 | X_2]`` ``(c, 2 c)``:
    a product with ``diag(Y_1, Y_2)`` gives ``[X_1 Y_1 | X_2 Y_2]`` at
    the rows of ONE head's product (the MXU's cost is the rows pushed
    through a weight tile, and a 64-wide tile is half empty).

    ``scalar``: ONE decay a head (Gated DeltaNet; the channels of
    ``gs_ref`` are alike): the lower products are ``Q K^T`` and ``K
    K^T`` as they are, ONE dot of all the chunk's rows, times
    ``e^{G_i - G_j}`` a pair — no exponent above 0 whatever ``g``, no
    reference row, ``c x c`` exponentials a head in place of ``2 c dk``
    a row block."""
    nb = c // SUB
    gs = 2 if hb % 2 == 0 else 1
    w = gs * c
    every = slice(None)
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, c, 1), 1)
    keep = (rows >= lo) & (rows < hi)
    q = jnp.where(keep, _heads(q_ref, every, dk, hb), 0.0)
    k = jnp.where(keep, _heads(k_ref, every, dk, hb), 0.0)
    v = jnp.where(keep, _heads(v_ref, every, dv, hb), 0.0)
    beta = jnp.where(keep, _heads(beta_ref, every, 1, hb), 0.0)
    Gs = _heads(gs_ref, every, dk, hb)
    prods = []

    def lanes():             # a paired matrix's rows, lanes and columns
        ri = jax.lax.broadcasted_iota(jnp.int32, (1, c, w), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, c, w), 2)
        return ri, lane, lane % c

    def half(a, b):          # a in the first head's lanes, b in the other's
        return jnp.where(lane < c, a, b)
    if scalar:
        ri, lane, ci = lanes()
        # G down the rows is a lane of Gs; G along the columns is its
        # transpose: the mean over a head's (alike) channels as a dot
        mean = jnp.full((hb, 8, dk), 1.0 / dk, jnp.float32)
        g_cols = _pair(_dot(mean, Gs, ((2,), (2,)))[:, :1], gs)  # (., 1, w)
        g_rows = Gs[:, :, :1].reshape((-1, gs, c, 1))
        dec = jnp.exp(jnp.minimum(
            half(g_rows[:, 0], g_rows[:, gs - 1]) - g_cols, 0.0))
        both = _dot(_pair(jnp.concatenate([q, k], axis=1), gs),
                    _blockdiag(k, gs), ((2,), (2,)))       # (., 2 c, w)
        prods = [jnp.concatenate([both[:, :c] * dec, both[:, c:] * dec],
                                 axis=1)]
    else:
        # row block b against every column, scaled about the reference
        # row between them: the first row of the block, or of the piece
        for b in range(nb):
            sl = slice(b * SUB, (b + 1) * SUB)
            ref = _heads(row_ref, slice(b, b + 1), dk, hb)    # (hb, 1, dk)
            kc = k * jnp.exp(jnp.minimum(ref - Gs, _EXP_MAX))
            rf = jnp.exp(jnp.minimum(Gs[:, sl] - ref, _EXP_MAX))
            prods.append(_dot(
                _pair(jnp.concatenate([q[:, sl] * rf, k[:, sl] * rf],
                                      axis=1), gs),
                _blockdiag(kc, gs), ((2,), (2,))))     # (., 2 SUB, w)
        ri, lane, ci = lanes()
    part = c if scalar else SUB          # rows of q, then of k, a product
    Pq = jnp.where(ci <= ri, jnp.concatenate(
        [x[:, :part] for x in prods], axis=1), 0.0)
    by_group = beta.reshape((-1, gs) + beta.shape[1:])
    A = jnp.where(ci < ri, jnp.concatenate(
        [x[:, part:] for x in prods], axis=1), 0.0) \
        * half(by_group[:, 0], by_group[:, gs - 1])

    def mm(x, y):            # [X_1 Y_1 | X_2 Y_2] of two paired matrices
        return _dot(x, y if gs == 1 else jnp.concatenate(
            [half(y, 0.0), half(0.0, y)], axis=1))
    # (I + A)^-1: the diagonal sub-chunks by their Neumann products (one
    # block-diagonal matrix), the strictly block-lower rest L by (I +
    # T L)^-1 = (I - E)(I + E^2), E^4 = 0
    eye = (ri == ci).astype(jnp.float32)
    diag = (ri // SUB) == (ci // SUB)
    n = jnp.where(diag, A, 0.0)
    T, pw, reach = eye - n, n, 2
    while reach < SUB:
        pw = mm(pw, pw)
        T = mm(T, eye + pw)
        reach *= 2
    M = T
    if nb > 1:               # (a short pack's one sub-chunk has no L)
        E = mm(T, jnp.where(diag, 0.0, A))
        M = mm(mm(eye - E, eye + mm(E, E)), T)
    eG = jnp.exp(Gs)
    W = _unpair(_dot(M, _blockdiag(beta * k * eG, gs)), gs)
    u = _unpair(_dot(M, _blockdiag(beta * v, gs)), gs)
    s_in = s_ref[...]                                     # (hb, dk, dv)
    ws = _dot(jnp.concatenate([W, q * eG], axis=1), s_in)
    u = u - ws[:, :c]
    out = ws[:, c:] + _unpair(_dot(Pq, _blockdiag(u, gs)), gs)
    for j in range(hb):
        o_ref[:, j * dv:(j + 1) * dv] += out[j]
    g_last = _heads(row_ref, slice(nb, nb + 1), dk, hb)       # (hb, 1, dk)
    kd = k * jnp.exp(jnp.minimum(g_last - Gs, 0.0))
    # e^{g_last} down the state's rows: the lane row through a diagonal
    dd = jax.lax.broadcasted_iota(jnp.int32, (1, dk, dk), 1) \
        == jax.lax.broadcasted_iota(jnp.int32, (1, dk, dk), 2)
    decay = jnp.sum(jnp.where(dd, jnp.exp(g_last), 0.0), axis=2,
                    keepdims=True)                        # (hb, dk, 1)
    s_ref[...] = decay * s_in + _dot(kd, u, ((1,), (1,)))


def _kernel(layer_ref, chunk_ref, lo_ref, hi_ref, slot_ref, flag_ref,
            q_ref, k_ref, v_ref, g_ref, beta_ref, state_in, o_ref,
            state_out, s_ref, gs_ref, row_ref, sem, *, c, hb, dk, dv,
            scalar=False):
    del chunk_ref, state_in          # (the index maps'; aliased to out)
    p = pl.program_id(1)
    lo, hi, flags = lo_ref[p], hi_ref[p], flag_ref[p]
    mine = state_out.at[layer_ref[0], slot_ref[p],
                        pl.ds(pl.program_id(0) * hb, hb)]

    @pl.when((flags & _OPENS != 0) & (flags & _ZERO == 0))
    def _():
        copy = pltpu.make_async_copy(mine, s_ref, sem)
        copy.start()
        copy.wait()

    @pl.when(flags & _ZERO != 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(lo == 0)                # the chunk's first piece
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(hi > lo)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        keep = (rows >= lo) & (rows < hi)
        gs_ref[...] = _running_sum(jnp.where(keep, g_ref[...], 0.0), rows)
        # the reference row of a row block: its first row, or the piece's
        # (each one dynamic sublane read, all heads wide)
        for b in range(c // SUB):
            row_ref[b:b + 1, :] = gs_ref[
                pl.ds(jnp.maximum(b * SUB, lo), 1), :]
        row_ref[c // SUB:c // SUB + 1, :] = gs_ref[
            pl.ds(hi - 1, 1), :]
        _piece(lo, hi, q_ref, k_ref, v_ref, beta_ref, gs_ref, row_ref,
               s_ref, o_ref, c=c, hb=hb, dk=dk, dv=dv, scalar=scalar)

    @pl.when(flags & _CLOSES != 0)
    def _():
        copy = pltpu.make_async_copy(s_ref, mine, sem)
        copy.start()
        copy.wait()


def hetu_kda_scan(q, k, v, g, beta, state, slot, pos, valid, *, layer=None,
                  head_block: Optional[int] = None,
                  interpret: Optional[bool] = None,
                  return_steps: bool = False):
    """``ops.kda.kda_scan``'s contract, as one Pallas call: a pack of
    ``C`` tokens ``q``, ``k``, ``g`` ``(C, H, dk)``, ``v`` ``(C, H,
    dv)``, ``beta`` ``(C, H)``; ``slot``, ``pos`` ``(C,)`` int32 and
    ``valid`` ``(C,)`` bool — the tokens of one slot contiguous with
    ascending positions; ``state`` ``(S, H, dk, dv)`` float32, or the
    STACKED leaf ``(layers, S, H, dk, dv)`` with ``layer=`` (an int32
    scalar, traced inside the layer scan) — read and written in place
    at ``[layer, slot]`` of the slots with a run here, nothing else of
    it touched. A run whose first token stands at position 0 starts
    from zeros. Gated DeltaNet's form — ``g (C, H)``, ``q`` and ``k``
    of fewer heads than ``v`` — is widened onto these shapes
    (``ops.kda.widen``) in front of the same call, whose steps then
    form a pair's decay ``e^{G_i - G_j}`` directly (``_piece``'s
    ``scalar``): exact for any ``g``, where the per-channel form needs
    ``g >= -5``.

    Returns ``(o (C, H, dv) float32 — zeros on rows that are not valid
    —, new state)`` and, with ``return_steps``, ``[live, computed]``
    int32: the grid steps that held a valid row and the steps run."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    scalar = g.ndim == 2         # one decay a head: exact pair factors
    q, k, g = widen(q, k, v, g)
    C, H, dk = q.shape
    dv = v.shape[-1]
    interpret = _interpret_default() if interpret is None else interpret
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"hetu_kda_scan compiled for a TPU takes head sizes of whole "
            f"lane tiles (multiples of 128); got dk={dk}, dv={dv}")
    hb = kda_head_block(H, dk, dv) if head_block is None else head_block
    if H % hb:
        raise ValueError(f"head_block {hb} does not divide {H} heads")
    buf, layer = _stacked(state, layer)
    chunk = scan_chunk(C)
    pad = -C % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
        slot, pos = (jnp.pad(a, (0, pad)) for a in (slot, pos))
        valid = jnp.pad(valid, (0, pad))
    Cp = C + pad
    work = scan_work_list(slot, pos, valid, slots=buf.shape[1],
                          chunk=chunk)
    scalars = (layer.reshape(1), work.chunk, work.lo, work.hi, work.slot,
               work.flags)

    def rows_at(i, p, layer, chunk, *_):
        return (chunk[p], i)

    def wide(d):
        return pl.BlockSpec((chunk, hb * d), rows_at)
    o, buf = pl.pallas_call(
        functools.partial(_kernel, c=chunk, hb=hb, dk=dk, dv=dv,
                          scalar=scalar),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(H // hb, work.n),
            in_specs=[wide(dk), wide(dk), wide(dv), wide(dk),
                      pl.BlockSpec((None, chunk, hb),
                                   lambda i, p, layer, chunk, *_:
                                   (i, chunk[p], 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[wide(dv), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32),
                            pltpu.VMEM((chunk, hb * dk), jnp.float32),
                            pltpu.VMEM((8, hb * dk), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((Cp, H * dv), jnp.float32),
                   jax.ShapeDtypeStruct(buf.shape, jnp.float32)],
        input_output_aliases={len(scalars) + 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * _STEP_BYTES + _VMEM_SPARE),
        interpret=interpret,
        name="hetu_kda_scan",
    )(*scalars, q.reshape(Cp, H * dk), k.reshape(Cp, H * dk),
      v.reshape(Cp, H * dv), g.reshape(Cp, H * dk),
      jnp.moveaxis(beta.reshape(Cp, H // hb, hb), 1, 0), buf)
    out = (o.reshape(Cp, H, dv)[:C], buf if state.ndim == 5 else buf[0])
    if return_steps:
        out += (jnp.stack([work.live, work.n]) * (H // hb),)
    return out


# -- the decode rows -----------------------------------------------------------
def live_list(live):
    """``live (S,)`` bool -> ``(ids (S,) int32, n (1,) int32)``: the
    live slots in order, then zeros — compares and sums, no ``nonzero``,
    no sort."""
    S = live.shape[0]
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    at = live[None, :] & (rank[None, :] == jnp.arange(S)[:, None])
    ids = jnp.sum(jnp.where(at, jnp.arange(S, dtype=jnp.int32)[None, :], 0),
                  axis=1, dtype=jnp.int32)
    return ids, jnp.sum(live, dtype=jnp.int32).reshape(1)


def _update_kernel(layer_ref, ids_ref, n_ref, fresh_ref, beta_ref, g_ref,
                   k_ref, q_ref, v_ref, state_in, o_ref, state_out, x_ref,
                   *, H, hb, at):
    del layer_ref
    r, first = pl.program_id(0), pl.program_id(1) * hb
    n = n_ref[0]

    @pl.when(r < n)
    def _():
        slot = ids_ref[r]
        # e^g, k and q run along the lanes; the formula needs them down
        # the state's rows: one transpose a step turns them all
        x_ref[0:hb, :] = jnp.exp(g_ref[...])
        x_ref[at:at + hb, :] = k_ref[...]
        x_ref[2 * at:2 * at + hb, :] = q_ref[...]
        cols = x_ref[...].T                                 # (dk, rows)
        fresh = fresh_ref[slot] != 0
        head = slot * H + first
        for j in range(hb):
            def col(i):
                return cols[:, i * at + j:i * at + j + 1]   # (dk, 1)
            s = col(0) * jnp.where(fresh, 0.0, state_in[j])
            rest = v_ref[j:j + 1, :] - jnp.sum(col(1) * s, axis=0,
                                               keepdims=True)
            s = s + col(1) * (beta_ref[head + j] * rest)
            state_out[j] = s
            o_ref[j:j + 1, :] = jnp.sum(col(2) * s, axis=0, keepdims=True)

    @pl.when(n == 0)         # (no live row: the one block named, as it is)
    def _():
        state_out[...] = state_in[...]


def hetu_kda_update(q, k, v, g, beta, state, live, *, layer=None,
                    fresh=None, head_block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    return_steps: bool = False):
    """``ops.kda.kda_update``'s contract, as one Pallas call on the
    state IN PLACE: one token a slot, ``q``, ``k``, ``g`` ``(S, H,
    dk)``, ``v`` ``(S, H, dv)``, ``beta`` ``(S, H)``; ``state`` ``(S, H,
    dk, dv)`` float32, or the STACKED leaf ``(layers, S, H, dk, dv)``
    with ``layer=`` — aliased to the result. The grid walks the ``live``
    slots (:func:`live_list`) by blocks of heads
    (:func:`kda_head_block`): a live slot's state is read once and
    written once, ``S <- Diag(e^g) S; r = v - k^T S; S <- S + (beta k)
    r^T; o = S^T q`` on its tile in VMEM, all of it float32 on the VPU
    (no dot). A slot that is not live is never fetched — the steps
    behind the live ones name the last live block again, which moves
    nothing — and its row of ``o`` is zeros; a ``fresh`` slot starts
    from a zero state whatever it held. Gated DeltaNet's form (``g (S,
    H)``, fewer key heads): ``ops.kda.widen``, as the scan.

    Returns ``(o (S, H, dv) float32, new state)`` and, with
    ``return_steps``, ``[live, stepped]`` int32: the slots advanced and
    the slot steps of the grid."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    q, k, g = widen(q, k, v, g)
    S, H, dk = q.shape
    dv = v.shape[-1]
    interpret = _interpret_default() if interpret is None else interpret
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"hetu_kda_update compiled for a TPU takes head sizes of whole "
            f"lane tiles (multiples of 128); got dk={dk}, dv={dv}")
    hb = kda_head_block(H, dk, dv) if head_block is None else head_block
    if H % hb:
        raise ValueError(f"head_block {hb} does not divide {H} heads")
    buf, layer = _stacked(state, layer)
    nh = H // hb
    ids, n = live_list(live)
    fresh = jnp.zeros((S,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    scalars = (layer.reshape(1), ids, n, fresh, beta.reshape(S * H))

    def block(r, i, layer, ids, n, *_):
        """The (slot, head block) of a step: behind the live slots, the
        last live one's last again."""
        return (ids[jnp.minimum(r, jnp.maximum(n[0] - 1, 0))],
                jnp.where(r < n[0], i, nh - 1))

    def rows(d):
        return pl.BlockSpec((None, None, hb, d),
                            lambda *a: block(*a) + (0, 0))
    leaf = pl.BlockSpec((None, None, hb, dk, dv),
                        lambda r, i, layer, *a: (layer[0],) + block(
                            r, i, layer, *a) + (0, 0))
    # the rows of e^g, k, q as one matrix of whole tiles to transpose:
    # each quantity's rows start a sublane tile
    at = -(-hb // 8) * 8
    o, buf = pl.pallas_call(
        functools.partial(_update_kernel, H=H, hb=hb, at=at),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S, nh),
            in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), leaf],
            out_specs=[rows(dv), leaf],
            scratch_shapes=[pltpu.VMEM((-(-3 * at // 128) * 128, dk),
                                       jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((S, nh, hb, dv), jnp.float32),
                   jax.ShapeDtypeStruct(buf.shape, jnp.float32)],
        input_output_aliases={len(scalars) + 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="hetu_kda_update",
    )(*scalars, *(a.reshape(S, nh, hb, -1) for a in (g, k, q, v)), buf)
    o = jnp.where(live[:, None, None], o.reshape(S, H, dv), 0.0)
    out = (o, buf if state.ndim == 5 else buf[0])
    if return_steps:
        out += (jnp.stack([n[0], jnp.int32(S)]),)
    return out
