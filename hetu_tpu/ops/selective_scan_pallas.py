"""The two Pallas TPU kernels of the selective scan: what
:class:`~hetu_tpu.nn.parallel.MambaMixer` runs under ``hetu.ssm_scan``
— a prefill pack's tokens (:func:`hetu_selective_scan`) — and under
``hetu.ssm_update`` — the decode rows' one token a slot
(:func:`hetu_selective_update`) —, both on the state leaf IN PLACE.
``ops/selective_scan.py`` holds the equations and both kernels'
``jax.numpy`` oracles; nothing here falls back to them.

No step of this recurrence is a matrix product (the decay ``exp(dt_t[c]
A[n, c])`` is the pair's and the token's own), so both kernels are
vector-unit work and the layout is chosen for it:

- **a state's channels fill whole vector registers.** The leaf is
  ``(layers, slots, N, R, L)`` float32 — :func:`state_tiles`: ``L =
  128`` lanes, ``R = D / 128`` rows, 5120 channels = 40 rows = 5
  register tiles of ``(8, 128)`` for each of the ``N = 16`` states; the
  same 327,680 B a layer and slot as ``(N, D)``, no padding. One
  register holds ONE state ``n`` of 1,024 channels, so ``B_t[n]`` and
  ``C_t[n]`` are SCALARS (read from SMEM, splat by the vector-scalar
  operand) and the 16-way sum of ``y`` is 16 multiply-adds of whole
  registers — no broadcast across lanes, no reduction across sublanes.
  ``x``, ``dt`` and ``y`` ride as ``(tokens, R, L)``: a token's 1,024
  channels are one register.
- **the scan's grid is (blocks of 8 rows) x (the pack's PIECES)**
  (``ops.kda_pallas.scan_work_list`` at :data:`CHUNK` rows: a chunk's
  rows of one run, in pack order, made on the device from ``slot``,
  ``pos``, ``valid``). A run's state ``(N, 8, 128)`` — 16 registers —
  is carried in REGISTERS across the tokens of a piece (the loop's
  carry) and in a VMEM scratch from the run's first piece to its last:
  the step that opens a run copies ``[layer, slot]``'s block in (or
  zeroes the scratch where the run begins at position 0), the run's
  last step copies it out. The walk over a piece's tokens is
  sequential: per token and register ``dt A_n`` (a multiply), ``exp``,
  the decay, ``(dt x) B_n``, an add, ``h C_n`` and an add — 6 vector
  operations and one ``exp`` a (state, 1,024 channels).
- **the update's grid is the LIVE slots** (``ops.kda_pallas.
  live_list``): a live slot's whole state block ``(N, R, L)`` is read
  once and written once through the aliased leaf; a slot that is not
  live is never fetched (the steps behind the live ones name the last
  live block again, which moves nothing) and its row of ``y`` is zeros.

Everything is float32. Interpreted on the CPU
(``flash_pallas._interpret_default``) it takes any channel count that
:func:`state_tiles` accepts; compiled for a TPU it refuses by name one
whose rows are not whole register tiles (``D % 1024``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.flash_pallas import _interpret_default
from hetu_tpu.ops.kda_pallas import (
    _CLOSES, _OPENS, _ZERO, live_list, scan_work_list,
)
from hetu_tpu.ops.selective_scan import _f32, _stacked

LANES, SUBLANES = 128, 8
#: pack rows a chunk of the scan's work list: a piece is a chunk's rows
#: of one run. 256 rows of x, dt and y are 1 MB each a step's 1,024
#: channels, double buffered 6 MB
CHUNK = 256


def state_tiles(D: int) -> tuple:
    """``(R, L)``: the rows and lanes ``D`` channels lie in — 128 lanes
    where they divide, one row of ``D`` lanes below that (the tiny
    models of the tests)."""
    if D % LANES == 0:
        return D // LANES, LANES
    if D < LANES:
        return 1, D
    raise ValueError(f"{D} channels: whole rows of {LANES} lanes, or "
                     f"fewer than {LANES}")


def _row_block(R: int) -> int:
    """Rows of a state a grid step of the scan holds: one register tile
    where the rows divide into them."""
    return SUBLANES if R % SUBLANES == 0 else R


def _refuse(name: str, D: int, interpret: bool) -> None:
    if not interpret and D % (SUBLANES * LANES):
        raise ValueError(
            f"{name} compiled for a TPU takes channels in whole register "
            f"tiles (multiples of {SUBLANES * LANES}); got {D}")


def _advance(h, dt, u, a_ref, bc_ref, k, N):
    """One token on the states ``h`` (``N`` arrays ``(rows, L)``):
    ``dt``, ``u = dt x`` ``(rows, L)``; ``bc_ref[k + n]`` is ``B[n]``
    and ``bc_ref[k + N + n]`` ``C[n]``. Returns ``(h_t, y_t)``."""
    out, y = [], jnp.zeros_like(dt)
    for n in range(N):
        hn = jnp.exp(dt * a_ref[n]) * h[n] + u * bc_ref[k + n]
        y = y + hn * bc_ref[k + N + n]
        out.append(hn)
    return tuple(out), y


def _scan_kernel(layer_ref, chunk_ref, lo_ref, hi_ref, slot_ref, flag_ref,
                 bc_ref, x_ref, dt_ref, a_ref, state_in, y_ref, state_out,
                 h_ref, sem, *, c, N, rb):
    del state_in                     # (aliased to state_out)
    p = pl.program_id(1)
    lo, hi, flags = lo_ref[p], hi_ref[p], flag_ref[p]
    mine = state_out.at[layer_ref[0], slot_ref[p], pl.ds(0, N),
                        pl.ds(pl.program_id(0) * rb, rb)]

    @pl.when((flags & _OPENS != 0) & (flags & _ZERO == 0))
    def _():
        copy = pltpu.make_async_copy(mine, h_ref, sem)
        copy.start()
        copy.wait()

    @pl.when(flags & _ZERO != 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(lo == 0)                # the chunk's first piece
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(hi > lo)
    def _():
        base = chunk_ref[p] * c

        def token(t, h):
            dt = dt_ref[t]
            h, y = _advance(h, dt, dt * x_ref[t], a_ref, bc_ref,
                            (base + t) * 2 * N, N)
            y_ref[t] = y
            return h
        h = jax.lax.fori_loop(lo, hi, token,
                              tuple(h_ref[n] for n in range(N)))
        for n in range(N):
            h_ref[n] = h[n]

    @pl.when(flags & _CLOSES != 0)
    def _():
        copy = pltpu.make_async_copy(h_ref, mine, sem)
        copy.start()
        copy.wait()


def hetu_selective_scan(x, dt, A, B, C, state, slot, pos, valid, *,
                        layer=None, chunk: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        return_steps: bool = False):
    """``ops.selective_scan.selective_scan``'s contract, as one Pallas
    call: a pack of ``P`` tokens ``x``, ``dt`` ``(P, D)``, ``B``, ``C``
    ``(P, N)``, ``A (N, D)``; ``slot``, ``pos`` ``(P,)`` int32 and
    ``valid`` ``(P,)`` bool — the tokens of one slot contiguous with
    ascending positions; ``state`` ``(S, N, R, L)`` float32
    (:func:`state_tiles`), or the STACKED leaf ``(layers, S, N, R, L)``
    with ``layer=`` (an int32 scalar, traced inside the layer scan) —
    read and written in place at ``[layer, slot]`` of the slots with a
    run here, nothing else of it touched. A run whose first token
    stands at position 0 starts from zeros.

    Returns ``(y (P, D) float32 — zeros on rows that are not valid —,
    new state)`` and, with ``return_steps``, ``[live, computed]`` int32:
    the grid steps that held a valid row and the steps run."""
    x, dt, A, B, C = _f32(x, dt, A, B, C)
    P, D = x.shape
    N = A.shape[0]
    interpret = _interpret_default() if interpret is None else interpret
    _refuse("hetu_selective_scan", D, interpret)
    R, L = state_tiles(D)
    rb = _row_block(R)
    buf, layer = _stacked(state, layer)
    # a pack shorter than a chunk is one chunk of its rows
    c = min(CHUNK if chunk is None else chunk,
            -(-P // SUBLANES) * SUBLANES)
    pad = -P % c
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, dt, B, C))
        slot, pos = (jnp.pad(a, (0, pad)) for a in (slot, pos))
        valid = jnp.pad(valid, (0, pad))
    Pp = P + pad
    work = scan_work_list(slot, pos, valid, slots=buf.shape[1], chunk=c)
    scalars = (layer.reshape(1), work.chunk, work.lo, work.hi, work.slot,
               work.flags, jnp.concatenate([B, C], axis=1).reshape(-1))

    rows = pl.BlockSpec((c, rb, L),
                        lambda g, p, layer, chunk, *_: (chunk[p], g, 0))
    y, buf = pl.pallas_call(
        functools.partial(_scan_kernel, c=c, N=N, rb=rb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(R // rb, work.n),
            in_specs=[rows, rows,
                      pl.BlockSpec((N, rb, L), lambda g, p, *_: (0, g, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[rows, pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((N, rb, L), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((Pp, R, L), jnp.float32),
                   jax.ShapeDtypeStruct(buf.shape, jnp.float32)],
        input_output_aliases={len(scalars) + 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
        name="hetu_selective_scan",
    )(*scalars, x.reshape(Pp, R, L), dt.reshape(Pp, R, L),
      A.reshape(N, R, L), buf)
    out = (y.reshape(Pp, D)[:P], buf if state.ndim == 5 else buf[0])
    if return_steps:
        out += (jnp.stack([work.live, work.n]) * (R // rb),)
    return out


# -- the decode rows -----------------------------------------------------------
def _update_kernel(layer_ref, ids_ref, n_ref, fresh_ref, bc_ref, x_ref,
                   dt_ref, a_ref, state_in, y_ref, state_out, *, N):
    del layer_ref
    r = pl.program_id(0)
    n_live = n_ref[0]

    @pl.when(r < n_live)
    def _():
        slot = ids_ref[r]
        keep = jnp.where(fresh_ref[slot] != 0, 0.0, 1.0)
        dt = dt_ref[...]
        h, y = _advance(tuple(keep * state_in[n] for n in range(N)), dt,
                        dt * x_ref[...], a_ref, bc_ref, slot * 2 * N, N)
        for n in range(N):
            state_out[n] = h[n]
        y_ref[...] = y

    @pl.when(n_live == 0)    # (no live row: the one block named, as it is)
    def _():
        state_out[...] = state_in[...]


def hetu_selective_update(x, dt, A, B, C, state, live, *, layer=None,
                          fresh=None, interpret: Optional[bool] = None,
                          return_steps: bool = False):
    """``ops.selective_scan.selective_update``'s contract, as one Pallas
    call on the state IN PLACE: one token a slot, ``x``, ``dt`` ``(S,
    D)``, ``B``, ``C`` ``(S, N)``, ``A (N, D)``; ``state`` ``(S, N, R,
    L)`` float32, or the STACKED leaf ``(layers, S, N, R, L)`` with
    ``layer=`` — aliased to the result. The grid walks the ``live``
    slots: a live slot's state is read once and written once, ``h <-
    exp(dt A) h + (dt x) B; y = sum_n h C`` on its block in VMEM, all
    of it float32 on the vector unit. A slot that is not live is never
    fetched and its row of ``y`` is zeros; a ``fresh`` slot starts from
    a zero state whatever it held.

    Returns ``(y (S, D) float32, new state)`` and, with
    ``return_steps``, ``[live, stepped]`` int32: the slots advanced and
    the slot steps of the grid."""
    x, dt, A, B, C = _f32(x, dt, A, B, C)
    S, D = x.shape
    N = A.shape[0]
    interpret = _interpret_default() if interpret is None else interpret
    _refuse("hetu_selective_update", D, interpret)
    R, L = state_tiles(D)
    buf, layer = _stacked(state, layer)
    ids, n = live_list(live)
    fresh = jnp.zeros((S,), jnp.int32) if fresh is None \
        else fresh.astype(jnp.int32)
    scalars = (layer.reshape(1), ids, n, fresh,
               jnp.concatenate([B, C], axis=1).reshape(-1))

    def slot_of(r, layer, ids, n, *_):
        """The slot of a step: behind the live ones, the last again."""
        return ids[jnp.minimum(r, jnp.maximum(n[0] - 1, 0))]

    row = pl.BlockSpec((None, R, L), lambda r, *a: (slot_of(r, *a), 0, 0))
    leaf = pl.BlockSpec((None, None, N, R, L),
                        lambda r, layer, *a:
                        (layer[0], slot_of(r, layer, *a), 0, 0, 0))
    y, buf = pl.pallas_call(
        functools.partial(_update_kernel, N=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S,),
            in_specs=[row, row,
                      pl.BlockSpec((N, R, L), lambda r, *_: (0, 0, 0)),
                      leaf],
            out_specs=[row, leaf]),
        out_shape=[jax.ShapeDtypeStruct((S, R, L), jnp.float32),
                   jax.ShapeDtypeStruct(buf.shape, jnp.float32)],
        input_output_aliases={len(scalars) + 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hetu_selective_update",
    )(*scalars, x.reshape(S, R, L), dt.reshape(S, R, L),
      A.reshape(N, R, L), buf)
    y = jnp.where(live[:, None], y.reshape(S, D), 0.0)
    out = (y, buf if state.ndim == 5 else buf[0])
    if return_steps:
        out += (jnp.stack([n[0], jnp.int32(S)]),)
    return out
