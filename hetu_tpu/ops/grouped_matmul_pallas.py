"""Pallas TPU grouped matmul: sorted rows through their groups' matrices.

The routed-expert layer of the serving step
(:class:`hetu_tpu.nn.moe.ExpertShareMoE`) sorts its (token, choice)
pairs by expert and runs its experts over the sorted rows, each group
of rows against its own expert's matrices: ONE call a layer call and
lane where an expert's three matrices fit a grid step
(:func:`grouped_swiglu`, below), else three matmuls
(:func:`grouped_matmul`). Until this
kernel they were ``jax.lax.ragged_dot``, which takes no tile sizes: on
the chip it read the experts' weights at 19-46 % of the bandwidth
wherever the rows were many or the width was not a multiple of 512
(PERF.md, PR 43) — a row tile that straddles two groups, or a group
that straddles two tiles, reads an expert again. This module is the
same product in the house style of ``ops/paged_pallas.py``:

- **row tiles that belong to one group**: the sorted rows are laid out
  with each group's start rounded up to the row tile
  (:func:`grouped_layout`, plain ``jnp`` in the caller's route scope:
  at most ``rows + groups x (tile - 1)`` rows, a static bound), so a
  grid step is (one tile of one group) x (a block of that group's
  matrix) — no mask, no partial sum across steps, no accumulator;
- **a group's matrix read once a call**: the grid is a LIST of steps,
  one per (live tile, column block), ordered by group, then column
  block, then the group's tiles — the weight block's index changes
  only when (group, column block) does, and a block whose index did
  not change is not copied. Groups without a row have no tile and are
  never fetched; tiles past the last live one are no grid step (the
  grid's one bound is data);
- **the stacked leaf indexed, never sliced**: inside the layer scan the
  weights are ``(layers, groups, K, N)`` with a traced ``layer``, one
  more scalar-prefetch operand — the index map reads ``layer x groups
  + group`` and no layer's experts are sliced out (a copy of 1.6 GB a
  layer: PERF.md, PR 26);
- **tiles from the shapes** (:func:`grouped_tile_rows`,
  :func:`grouped_block_cols`): a sublane pack of rows where a call has
  a few rows a group (the decode lanes: a pure read of the weights), a
  hundred or more where it has hundreds; the whole matrix a block where
  it fits the budget, column blocks where it does not;
- **an epilogue, not a fourth call**: with ``gate=`` the step writes
  ``silu(gate) * (x @ w)`` in the result's dtype — the SwiGLU product
  on the float32 accumulator, as the layer computed it in XLA, without
  a pass over the padded rows.

**A small expert is one grid step** (:func:`grouped_swiglu`, PR 62):
where an expert's gate, up and down matrices fit VMEM double buffered
(:func:`grouped_swiglu_fits`: 13-44 MB for the benchmark's experts of
2048 x 512 to 2048 x 1408; Command A+'s 4096 x 4096 stay three calls in
column blocks) the step of (one row tile of one expert) computes ``g =
x @ wg``, ``u = x @ wi``, ``h = silu(g) * u`` rounded to the operands'
dtype and ``y = h @ wo`` — the three calls' roundings in the same
places, the gate and ``h`` never in HBM — and, with the combine tables
(:func:`grouped_combine`), weights its rows and ADDS each live one into
its token's row of a float32 result that stays in VMEM for the whole
call: the layer's weighted sum over choices, with no result in the
aligned layout and no gather back. The row loop runs while the next
expert's matrices stream in, so it costs nothing a call: at Qwen3-Next's
pack (2,560 live rows of 20,480 pairs over 64 experts of 6.3 MB) the
three calls took 0.68 ms and the gather back with its sum 1.30; the one
call takes 0.57 (PERF.md, PR 62).

The chip's sweep of the matmul alone (PERF.md, PR 43; ms a call,
``ragged_dot`` -> the rule's tile): 1.27 -> 0.52 at 288 rows over 64
experts of 2048 x 1408 (715 GB/s of weights), 3.76 -> 0.76 at 12,288
rows; 0.86 -> 0.67 and 1.84 -> 0.76 at 16 experts of 4096 x 4096 in a
128- and a 1,024-row window; 0.57 -> 0.27 and 1.23 -> 0.39 at 64
experts of 2560 x 768. Column chunks inside the body changed nothing.

bf16 (or the operands') inputs, float32 accumulation; ``interpret`` on
the CPU. :func:`grouped_matmul_reference` is the per-group loop the
tests hold it to.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.flash_pallas import _interpret_default

#: what one weight block may take (two of them stream double buffered
#: beside the row tile, the result tile and the accumulator)
_BLOCK_BYTES = 8 * 2 ** 20
#: the kernel's VMEM limit is what its blocks take and this much for
#: the compiler's own, within what a v5e core has (128 MiB; the default
#: scope is 16 — two whole experts of Kimi's are 11.5 already)
_VMEM_SPARE, _VMEM_MOST = 16 * 2 ** 20, 100 * 2 ** 20
#: the most rows a tile takes: beyond it a group's last tile wastes
#: more rows than a larger product saves
_MAX_TILE_ROWS = 256


def grouped_tile_rows(rows: int, groups: int) -> int:
    """Rows of a tile, from shapes alone: the smallest power of two
    that holds a group's mean share of a call's ``rows`` (a group is
    one tile, give or take its imbalance), between one sublane pack of
    bf16 rows (16; two of float32) and 256. 16 where a call has 4.5
    rows a group (Kimi's decode lane: 288 over 64) or 8 (Command A+'s
    128-row window over 16), 64 at 64 a group (the 1,024- and 4,096-row
    windows), 256 at 192 (12,288 over 64)."""
    tile = 16
    while tile < _MAX_TILE_ROWS and tile * groups < rows:
        tile *= 2
    return tile


def grouped_block_cols(k: int, n: int, itemsize: int = 2) -> int:
    """Columns of a weight block, from shapes alone: all ``n`` where a
    whole ``(k, n)`` matrix is within the block budget (8 MiB: Kimi's
    2048 x 1408 and Ling's 2560 x 768 in bf16 are one contiguous block
    of 5.8 and 3.9 MB), else the largest multiple of 128 that divides
    ``n`` and fits (Command A+'s 4096 x 4096: 1024 columns, four blocks
    of 8 MiB)."""
    if k * n * itemsize <= _BLOCK_BYTES or n % 128:
        return n
    lanes = n // 128
    best = 1
    for c in range(1, lanes + 1):
        if lanes % c == 0 and k * c * 128 * itemsize <= _BLOCK_BYTES:
            best = c
    return best * 128


def grouped_padded_rows(rows: int, groups: int, tile: int) -> int:
    """The static bound of the aligned layout: every group may waste
    ``tile - 1`` rows, in whole tiles."""
    return -(-(rows + groups * (tile - 1)) // tile) * tile


def grouped_rows_computed(sizes, tile: int, window: Optional[int] = None):
    """Rows of the tiles one call visits, on the host (numpy): every
    group's rows rounded up to the tile; with ``window`` the sorted
    rows are walked in windows of that many rows, each laid out on its
    own (a group that straddles two windows rounds up in both)."""
    import numpy as np
    sizes = np.asarray(sizes, np.int64)
    hi = np.cumsum(sizes)
    lo = hi - sizes
    total = int(hi[-1]) if hi.size else 0
    window = window or max(total, 1)
    computed = 0
    for start in range(0, total, window):
        inside = np.clip(np.minimum(hi, start + window)
                         - np.maximum(lo, start), 0, None)
        computed += int((-(-inside // tile) * tile).sum())
    return computed


def _count_le(ends, at):
    """``searchsorted(ends, at, side="right")`` as one compare of every
    pair: ``ends`` is a few dozen long, and the TPU compiler takes a
    second to compile a scan or a gather, whatever its size."""
    return jnp.sum(ends[None, :] <= at[:, None], axis=1, dtype=jnp.int32)


def _pick(table, at):
    """``table[at]`` of a table a few dozen long, as a one-hot sum."""
    hot = at[:, None] == jnp.arange(table.shape[0], dtype=at.dtype)[None]
    return jnp.sum(jnp.where(hot, table[None, :], 0), axis=1,
                   dtype=table.dtype)


class GroupedLayout(NamedTuple):
    """Where the sorted rows of one call lie (:func:`grouped_layout`):
    arrays only, so that it crosses a ``jit`` or a loop as it is."""
    src: jax.Array       # (padded rows,) each laid-out row's sorted row
    dst: jax.Array       # (rows,) each sorted row's laid-out row
    tile_group: jax.Array    # (tiles + 1,) each tile's group
    group_first: jax.Array   # (groups,) each group's first tile
    group_tiles: jax.Array   # (groups,) its tiles
    n_tiles: jax.Array       # () live tiles

    @property
    def rows(self) -> int:
        """The laid-out rows: the static bound, in whole tiles."""
        return self.src.shape[0]

    @property
    def tile(self) -> int:
        """Rows of a tile (``tile_group`` has one spare entry)."""
        return self.rows // (self.tile_group.shape[0] - 1)


def grouped_layout(sizes, *, rows: int, tile: int) -> GroupedLayout:
    """The aligned layout of ``rows`` sorted rows in groups of ``sizes``
    ``(groups,)`` (the live rows come first, ``sizes.sum() <= rows``):
    group ``g`` starts at a multiple of ``tile``. Plain ``jnp``,
    compares and sums on a few hundred integers a table — call it in
    the scope that routes. ``src`` of a row no group owns is clipped
    into the sorted rows (its product is never read), ``dst`` of a
    sorted row behind the last group likewise."""
    sizes = sizes.astype(jnp.int32)
    groups = sizes.shape[0]
    padded = grouped_padded_rows(rows, groups, tile)
    n_tiles_max = padded // tile
    tiles = -(-sizes // tile)                       # (groups,)
    t_hi = jnp.cumsum(tiles)
    t_lo = t_hi - tiles
    hi = jnp.cumsum(sizes)
    # how far the layout moves a group's rows
    shift = t_lo * tile - (hi - sizes)
    # a spare entry behind the last tile: a full list is then no edge
    tile_group = jnp.minimum(
        _count_le(t_hi, jnp.arange(n_tiles_max + 1, dtype=jnp.int32)),
        groups - 1)
    src = jnp.arange(padded, dtype=jnp.int32) - jnp.broadcast_to(
        _pick(shift, tile_group[:-1])[:, None],
        (n_tiles_max, tile)).reshape(padded)
    p = jnp.arange(rows, dtype=jnp.int32)
    dst = p + _pick(shift, jnp.minimum(_count_le(hi, p), groups - 1))
    return GroupedLayout(
        src=jnp.clip(src, 0, rows - 1),
        dst=jnp.clip(dst, 0, padded - 1), tile_group=tile_group,
        group_first=t_lo, group_tiles=tiles, n_tiles=t_hi[-1])


def _step_list(layout: GroupedLayout, nb: int):
    """The grid's steps ``(tile, column block)``, one per (live tile,
    column block), ordered by group, then column block, then the
    group's tiles: the weight block ``(group, column block)`` is the
    same for the tiles of a group in a row. One spare entry behind the
    last (the full list that halted the paged call, PERF.md PR 39, is
    no edge here); dead entries name tile 0."""
    n_max = layout.rows // layout.tile
    first, tiles = layout.group_first, layout.group_tiles
    s = jnp.arange(n_max * nb + 1, dtype=jnp.int32)
    if nb == 1:
        return jnp.minimum(s, n_max - 1), jnp.zeros_like(s)
    g = jnp.minimum(_count_le((first + tiles) * nb, s),
                    tiles.shape[0] - 1)
    start, n = _pick(first, g), jnp.maximum(_pick(tiles, g), 1)
    at = s - start * nb                 # the step's place in its group
    live = s < layout.n_tiles * nb
    return (jnp.where(live, start + at % n, 0),
            jnp.where(live, at // n, 0))


def _kernel(*refs, gated: bool):
    x_ref, w_ref = refs[-4:-2] if gated else refs[-3:-1]
    o_ref = refs[-1]
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=jnp.float32)
    if gated:
        acc = jax.nn.silu(refs[-2][...]) * acc
    o_ref[...] = acc.astype(o_ref.dtype)


def grouped_matmul(x, w, layout: GroupedLayout, *, layer=None, gate=None,
                   out_dtype=jnp.float32, block_cols: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``out[r] = x[r] @ w[group of r]`` over the laid-out rows.

    - ``x``: ``(layout.rows, K)`` rows in the aligned layout
      (``jnp.take(sorted rows, layout.src)``);
    - ``w``: ``(groups, K, N)``, or the STACKED leaf ``(layers, groups,
      K, N)`` with ``layer`` (an int32 scalar, traced inside the layer
      scan) naming the layer to read — indexed, never sliced;
    - ``gate`` (``None`` = none; a static choice): ``(layout.rows, N)``
      float32, the step then writes ``silu(gate) * (x @ w)``;
    - ``block_cols`` (``None``: :func:`grouped_block_cols` of the
      operands' shapes): columns of a weight block.

    Returns ``(layout.rows, N)`` in ``out_dtype``. Rows of tiles no
    group owns are never written: the caller reads through
    ``layout.dst`` alone. Matches :func:`grouped_matmul_reference` up
    to the order of float32 partial sums."""
    if (layer is None) != (w.ndim == 3):
        raise ValueError(
            f"layer= goes with a stacked (layers, groups, K, N) w and "
            f"only with it; got w {w.shape}, layer={layer!r}")
    groups, K, N = w.shape[-3:]
    rows, tile = layout.rows, layout.tile
    if x.shape != (rows, K) or x.dtype != w.dtype:
        raise ValueError(f"x {x.shape} {x.dtype} against a layout of "
                         f"{rows} rows and w {w.shape} {w.dtype}")
    if gate is not None and gate.shape != (rows, N):
        raise ValueError(f"gate {gate.shape}, result {(rows, N)}")
    bn = grouped_block_cols(K, N, w.dtype.itemsize) \
        if block_cols is None else block_cols
    if N % bn or (bn != N and bn % 128):
        raise ValueError(f"block_cols {bn} does not cut {N} columns "
                         "into whole lane tiles")
    nb = N // bn
    step_tile, step_col = _step_list(layout, nb)
    base = jnp.asarray(0 if layer is None else layer, jnp.int32) \
        .reshape(1) * groups
    scalars = (base, layout.tile_group, step_tile, step_col)

    def rows_at(s, base, grp, st, sc):
        return (st[s], 0)

    def weight_at(s, base, grp, st, sc):
        return (base[0] + grp[st[s]], 0, sc[s])

    def result_at(s, base, grp, st, sc):
        return (st[s], sc[s])

    in_specs = [pl.BlockSpec((tile, K), rows_at),
                pl.BlockSpec((None, K, bn), weight_at)]
    args = [x, w.reshape((-1, K, N))]
    if gate is not None:
        in_specs.append(pl.BlockSpec((tile, bn), result_at))
        args.append(gate.astype(jnp.float32))
    interpret = _interpret_default() if interpret is None else interpret
    # double-buffered blocks and the float32 accumulator
    vmem = 2 * (K * bn + tile * K) * w.dtype.itemsize + tile * bn * (
        2 * jnp.dtype(out_dtype).itemsize + 4 + (8 if gate is not None
                                                 else 0))
    return pl.pallas_call(
        functools.partial(_kernel, gated=gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(jnp.maximum(layout.n_tiles, 1) * nb,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, bn), result_at)),
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + _VMEM_SPARE, _VMEM_MOST)),
        interpret=interpret,
        name="hetu_grouped_matmul",
    )(*scalars, *args)


class GroupedCombine(NamedTuple):
    """Where the laid-out rows of one call go (:func:`grouped_combine`):
    :func:`grouped_swiglu` adds each live row, weighted, into its
    token's row of the result."""
    token: jax.Array    # (layout.rows,) int32 each laid-out row's token
    weight: jax.Array   # (layout.rows, 1) float32 its weight
    live: jax.Array     # (tiles + 1,) int32 each tile's live rows


def grouped_combine(layout: GroupedLayout, sizes, token, weight):
    """The combine tables of one call: ``token`` and ``weight``
    ``(layout.rows,)`` of the LAID-OUT rows (each row's token and
    routing weight, gathered through ``layout.src`` as the rows were),
    and each tile's live rows from the groups' ``sizes`` (a group's rows
    fill its tiles from the first; the rest of its last tile is dead and
    is never added). Plain ``jnp`` on a few hundred integers — call it
    in the scope that routes."""
    tile = layout.tile
    j = jnp.arange(layout.tile_group.shape[0], dtype=jnp.int32)
    g = layout.tile_group
    live = _pick(sizes.astype(jnp.int32), g) \
        - (j - _pick(layout.group_first, g)) * tile
    return GroupedCombine(
        token=token.astype(jnp.int32),
        weight=weight.astype(jnp.float32)[:, None],
        live=jnp.where(j < layout.n_tiles, jnp.clip(live, 0, tile), 0))


def grouped_swiglu_vmem(k: int, n: int, tile: int, tokens: int,
                        itemsize: int = 2) -> int:
    """Bytes of VMEM a step of :func:`grouped_swiglu` holds: an expert's
    three whole matrices, the row tile and its weights double buffered,
    the body's float32 gate, up and product, the rounded ``h``, the
    float32 result of the tile and its weighted copy, and the ``(tokens,
    k)`` float32 result that stays resident (two buffers of it)."""
    return 2 * (3 * k * n + tile * k) * itemsize + 2 * tile * 128 * 4 \
        + tile * n * (12 + itemsize) + 2 * tile * k * 4 \
        + 2 * (-(-tokens // 8) * 8) * k * 4


def grouped_swiglu_fits(k: int, n: int, tokens: int,
                        itemsize: int = 2) -> bool:
    """Whether an expert of ``(k, n)`` gate and up and ``(n, k)`` down
    matrices can be ONE grid step (:func:`grouped_swiglu`) of a call
    over ``tokens`` tokens, from shapes alone: its three matrices,
    double buffered, beside the largest row tile and the resident result
    within the kernel's VMEM limit (100 MiB less the compiler's 16: bf16
    experts of 2048 x 512, 2048 x 768, 2560 x 768 and 2048 x 1408 take
    13-44 MB and a 2,048-token float32 result 34-42 more; Command A+'s
    4096 x 4096 would take 201 and stays three calls in column
    blocks)."""
    return grouped_swiglu_vmem(k, n, _MAX_TILE_ROWS, tokens, itemsize) \
        + _VMEM_SPARE <= _VMEM_MOST


def _swiglu_kernel(base_ref, grp_ref, st_ref, tok_ref, live_ref, x_ref,
                   wg_ref, wi_ref, wo_ref, w_ref, o_ref, y_ref, *,
                   tile: int):
    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wi_ref[...], preferred_element_type=jnp.float32)
    # h is rounded where the split path's second call rounds it
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y_ref[...] = jnp.dot(h, wo_ref[...],
                         preferred_element_type=jnp.float32) * w_ref[...]
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    first = st_ref[s] * tile

    def add(r, carry):
        at = pl.ds(tok_ref[first + r], 1)
        o_ref[at, :] = o_ref[at, :] + y_ref[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, live_ref[st_ref[s]], add, 0)


def grouped_swiglu(x, wg, wi, wo, layout: GroupedLayout,
                   combine: GroupedCombine, *, tokens: int, layer=None,
                   interpret: Optional[bool] = None):
    """``out[t] = sum over the live rows r of token t of weight[r] *
    y[r]``, ``y[r] = (silu(x[r] @ wg[e]) * (x[r] @ wi[e])) @ wo[e]``
    with ``e`` the group of ``r``, over the laid-out rows, in ONE call
    whose grid step is (one row tile of one group): what three
    :func:`grouped_matmul` calls (gate; up with the ``gate=`` epilogue
    and ``out_dtype=x.dtype``; down), the gather back and the layer's
    weighted sum over choices compute, with the same roundings in the
    same places — float32 accumulators, the SwiGLU product rounded to
    ``x.dtype``, float32 products and sums — and neither the gate, ``h``
    nor a result in the aligned layout ever in HBM. The step weights its
    tile's rows and ADDS each live one into its token's row of the
    ``(tokens, K)`` float32 result, which stays in VMEM for the whole
    call; the adds run in the rows' order while the next group's
    matrices stream in. For experts whose three matrices fit beside that
    result (:func:`grouped_swiglu_fits`; refused otherwise).

    - ``x``: ``(layout.rows, K)`` rows in the aligned layout;
    - ``wg``, ``wi``: ``(groups, K, N)`` and ``wo``: ``(groups, N, K)``,
      or the STACKED leaves ``(layers, groups, ...)`` with ``layer`` (an
      int32 scalar, traced inside the layer scan) — all three indexed by
      ``layer x groups + group``, never sliced. A group's blocks are not
      copied again for its consecutive tiles; a group without a row is
      never fetched;
    - ``combine`` (:func:`grouped_combine`): each laid-out row's token
      and weight, each tile's live rows.

    Returns ``(tokens, K)`` float32; a token without a live row reads
    zeros. Matches the three-call path up to the order of float32
    partial sums."""
    if (layer is None) != (wg.ndim == 3):
        raise ValueError(
            f"layer= goes with stacked (layers, groups, K, N) weights "
            f"and only with them; got wg {wg.shape}, layer={layer!r}")
    groups, K, N = wg.shape[-3:]
    rows, tile = layout.rows, layout.tile
    if wi.shape != wg.shape or wo.shape != wg.shape[:-2] + (N, K) \
            or not wg.dtype == wi.dtype == wo.dtype:
        raise ValueError(f"wg {wg.shape} {wg.dtype}, wi {wi.shape} "
                         f"{wi.dtype}, wo {wo.shape} {wo.dtype}: not one "
                         "expert's gate, up and down")
    if x.shape != (rows, K) or x.dtype != wg.dtype:
        raise ValueError(f"x {x.shape} {x.dtype} against a layout of "
                         f"{rows} rows and wg {wg.shape} {wg.dtype}")
    if combine.token.shape != (rows,) or combine.weight.shape != (rows, 1):
        raise ValueError(f"combine tables {combine.token.shape}, "
                         f"{combine.weight.shape} against a layout of "
                         f"{rows} rows")
    vmem = grouped_swiglu_vmem(K, N, tile, tokens, wg.dtype.itemsize)
    if vmem + _VMEM_SPARE > _VMEM_MOST:
        raise ValueError(
            f"three {K} x {N} {wg.dtype} matrices, a {tile}-row tile and "
            f"a result of {tokens} tokens take {vmem} bytes, over the "
            f"{_VMEM_MOST} of the kernel's limit: use three "
            f"grouped_matmul calls")
    step_tile, _ = _step_list(layout, 1)
    base = jnp.asarray(0 if layer is None else layer, jnp.int32) \
        .reshape(1) * groups
    # (the step WRITES by these: a token outside the result is clipped
    # into it, never an address outside the kernel's memory)
    scalars = (base, layout.tile_group, step_tile,
               jnp.clip(combine.token, 0, tokens - 1), combine.live)

    def rows_at(s, base, grp, st, tok, live):
        return (st[s], 0)

    def weight_at(s, base, grp, st, tok, live):
        return (base[0] + grp[st[s]], 0, 0)

    interpret = _interpret_default() if interpret is None else interpret
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(jnp.maximum(layout.n_tiles, 1),),
            in_specs=[pl.BlockSpec((tile, K), rows_at),
                      pl.BlockSpec((None, K, N), weight_at),
                      pl.BlockSpec((None, K, N), weight_at),
                      pl.BlockSpec((None, N, K), weight_at),
                      pl.BlockSpec((tile, 1), rows_at)],
            out_specs=pl.BlockSpec((tokens, K), lambda s, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((tile, K), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, K), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + _VMEM_SPARE),
        interpret=interpret,
        name="hetu_grouped_swiglu",
    )(*scalars, x, wg.reshape((-1, K, N)), wi.reshape((-1, K, N)),
      wo.reshape((-1, N, K)), combine.weight)


def grouped_matmul_reference(x, w, sizes):
    """The per-group loop over DENSE sorted rows ``x (rows, K)``: group
    ``g``'s ``sizes[g]`` rows against ``w[g]``, float32 accumulation;
    rows behind the last group are zeros (numpy sizes: a test's
    oracle)."""
    out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    start = 0
    for g, n in enumerate(int(s) for s in sizes):
        out = out.at[start:start + n].set(jnp.matmul(
            x[start:start + n], w[g],
            preferred_element_type=jnp.float32))
        start += n
    return out
