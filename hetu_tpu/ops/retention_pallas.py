"""The two Pallas TPU kernels of power retention (``ops/retention.py``
has the equations and the state's tiled layout ``(d / 2 + 1, R, d)``):
what :class:`~hetu_tpu.nn.parallel.PowerRetention` runs under
``hetu.retention_scan`` and ``hetu.retention_update``. Interpreted on
the CPU (``flash_pallas._interpret_default``); no ``jax.numpy`` form
stands behind them on the chip.

:func:`hetu_retention_scan` — a prefill pack. The grid is (kv heads) x
(the WORK LIST of the pack's pieces — a chunk's rows of one run, in pack
order: ``kda_pallas.scan_work_list``, data). **A run's state tiles stay
in VMEM from the run's first piece to its last** (4.5 MB a kv head at
``d = 128``): the leaf stays in HBM (``memory_space=ANY``, aliased to
the result), the piece that opens a run copies ``[layer, slot, head]``
in (or zeroes the scratch where the run begins at position 0), the
run's last piece copies it out — the state is read once and written
once a run and call. **``phi`` is formed a feature row at a time and
never written to HBM**: the operands come TRANSPOSED (features down the
sublanes, tokens along the lanes), so that row ``a`` of ``phi^T`` is
two sublane broadcasts, two products and a select of ``(d, T)`` — no
lane broadcast, no transpose in the kernel. A piece is

- for each feature row ``a`` (a ``fori_loop``; the squares' row after
  it): the state's ``M_a (R, d)`` once — ``M_a phi_a(q_i)^T`` ``(R, T)``
  added to each of the kv head's ``G`` query heads' sums (numerators
  and, in row ``dv``, the normaliser: one product), then ``M_a <- D
  M_a + ([v, 1] w)^T phi_a(k)`` with ``w_s`` the decay from ``s`` to
  the piece's last token and ``D`` the piece's whole decay;
- the gated quadratic form inside the piece, ``[v, 1]^T ((K q_i^T)^2
  ⊙ decay)``, and ``y^T = intra + decay-to-the-token x inter``.

The kernel hands out numerators and normaliser ``(Hkv, G, R, C)``; the
division is the caller's (XLA fuses it with the transpose back).

:func:`hetu_retention_update` — the decode rows, ON THE LEAF IN PLACE
(``input_output_aliases``): the grid walks the LIVE slots
(``kda_pallas.live_list``: compares and sums), kv heads and tiles of
feature rows; a live slot's state is read once and written once, ``g M
+ [v, 1] phi(k)``, and read out against ``phi(q_i)`` on the VPU as it passes (the MXU
would push a 136-row weight tile for 8 rows of queries). A slot that is
not live is never fetched: the steps behind the live ones name the last
live block again, which moves nothing. ``phi`` of the S decode rows —
``(S, heads, d / 2 + 1, d)``, a few MB beside a GB of state — is formed
by XLA; it is a pack's ``[tokens, 8,256]`` that must not exist, and
does not.

Operands: the MXU takes what the layer computes in (bf16 to serve:
``phi``, the state's copy for the read, the weights of the quadratic
form; float32 accumulation); float32 operands (the tests) take
``Precision.HIGHEST``. The state, the gate's sums, the decays and the
normaliser are float32 always.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.flash_pallas import _interpret_default
from hetu_tpu.ops.kda import _stacked
from hetu_tpu.ops.kda_pallas import (
    _CLOSES, _OPENS, _ZERO, live_list, scan_work_list,
)
from hetu_tpu.ops.retention import (
    feature_rows, phi_tiles, value_rows, values_one,
)

_HI = jax.lax.Precision.HIGHEST
#: tokens a chunk of the scan (a piece never crosses one)
SCAN_CHUNK = 256
#: feature rows a grid step of the update (a divisor of d / 2 + 1)
UPDATE_ROWS = 13
_VMEM = 64 * 2 ** 20


def scan_chunk(rows: int) -> int:
    """Tokens a chunk: ``SCAN_CHUNK``, or a shorter pack's rows in whole
    sublane tiles (the tiny engines' packs)."""
    return SCAN_CHUNK if rows >= SCAN_CHUNK else -(-rows // 8) * 8


def update_rows(A: int) -> int:
    """Feature rows a step of the update: the largest divisor of ``A``
    up to ``UPDATE_ROWS``."""
    return max(n for n in range(1, UPDATE_ROWS + 1) if A % n == 0)


def _mm(a, b, dims, mxu):
    """A product on the MXU in the operands' type, float32 out."""
    return jax.lax.dot_general(
        a.astype(mxu), b.astype(mxu), (dims, ((), ())),
        precision=_HI if mxu == jnp.float32 else None,
        preferred_element_type=jnp.float32)


_NN = ((1,), (0,))
_NT = ((1,), (1,))


def _piece(lo, hi, qT_ref, qrT_ref, kT_ref, krT_ref, k_ref, vT_ref,
           g_ref, gcol_ref, s_ref, acc_ref, o_ref, *, T, G, d, mxu):
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    keep = (cols >= lo) & (cols < hi)
    Gi, Ge = g_ref[0:1, :], g_ref[1:2, :]       # inclusive, exclusive
    base = jnp.sum(jnp.where(cols == lo, Ge, 0.0), axis=1, keepdims=True)
    last = jnp.sum(jnp.where(cols == hi - 1, Gi, 0.0), axis=1,
                   keepdims=True)
    to_tok = jnp.where(keep, jnp.exp(jnp.minimum(Gi - base, 0.0)), 0.0)
    to_end = jnp.where(keep, jnp.exp(jnp.minimum(last - Gi, 0.0)), 0.0)
    whole = jnp.exp(jnp.minimum(last - base, 0.0))          # (1, 1)
    vT = vT_ref[...]                                        # (R, T)
    vw = (vT * to_end).astype(mxu)
    sub = jax.lax.broadcasted_iota(jnp.int32, (d, T), 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def row(a, x, xr, xa, xra):
        return jnp.where(sub > a, xa * x,
                         jnp.where(sub < a, xra * xr, 0.0))

    def advance(a, fq, fk):
        """Feature row ``a``: read against every query head, then
        advanced by the piece's keys."""
        Ma = s_ref[a]                                       # (R, d)
        Mb = Ma.astype(mxu)
        for i in range(G):
            acc_ref[i] += _mm(Mb, fq(i), _NN, mxu)
        s_ref[a] = whole * Ma + _mm(vw, fk(), _NT, mxu)

    def body(a, _):
        advance(
            a,
            lambda i: row(a, qT_ref[i], qrT_ref[i],
                          qT_ref[i, pl.ds(a, 1), :],
                          qrT_ref[i, pl.ds(a, 1), :]),
            lambda: row(a, kT_ref[...], krT_ref[...],
                        kT_ref[pl.ds(a, 1), :], krT_ref[pl.ds(a, 1), :]))
        return 0

    jax.lax.fori_loop(0, d // 2, body, 0)
    # (the operands carry 2^(1/4): a square is sqrt(2) too large)
    half = 0.5 ** 0.5
    advance(d // 2, lambda i: half * qT_ref[i] * qT_ref[i],
            lambda: half * kT_ref[...] * kT_ref[...])
    # inside the piece: key s (down) at or below query t (along)
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    see = (rows >= lo) & (rows < hi) & keep & (rows <= cols)
    decay = jnp.where(see, jnp.exp(jnp.minimum(Gi - gcol_ref[...], 0.0)),
                      0.0)                                  # (T, T)
    kk = k_ref[...]
    for i in range(G):
        sc = _mm(kk, qT_ref[i], _NN, mxu)                   # (T, T)
        y = _mm(vT, 0.5 * sc * sc * decay, _NN, mxu) + to_tok * acc_ref[i]
        o_ref[i] = jnp.where(keep, y, o_ref[i])


def _scan_kernel(layer_ref, chunk_ref, lo_ref, hi_ref, slot_ref, flag_ref,
                 qT_ref, qrT_ref, kT_ref, krT_ref, k_ref, vT_ref, g_ref,
                 gcol_ref, state_in, o_ref, state_out, s_ref, acc_ref, sem,
                 **kw):
    del chunk_ref, state_in          # (the index maps'; aliased to out)
    p = pl.program_id(1)
    lo, hi, flags = lo_ref[p], hi_ref[p], flag_ref[p]
    mine = state_out.at[layer_ref[0], slot_ref[p], pl.program_id(0)]

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
        _piece(lo, hi, qT_ref, qrT_ref, kT_ref, krT_ref, k_ref, vT_ref,
               g_ref, gcol_ref, s_ref, acc_ref, o_ref, **kw)

    @pl.when(flags & _CLOSES != 0)
    def _():
        copy = pltpu.make_async_copy(s_ref, mine, sem)
        copy.start()
        copy.wait()


def hetu_retention_scan(q, k, v, log_g, state, slot, pos, valid, *,
                        eps: float, layer=None,
                        interpret: Optional[bool] = None,
                        return_runs: bool = False):
    """``ops.retention.retention_scan``'s contract as one Pallas call;
    ``state`` ``(S, Hkv, d / 2 + 1, R, d)`` float32 tiles, or the
    STACKED leaf ``(layers, S, ...)`` with ``layer=`` (an int32 scalar,
    traced inside the layer scan) — read and written in place at
    ``[layer, slot]`` of the slots with a run here, nothing else of it
    touched. With ``return_runs`` a third result: the runs the pack
    held (a state is read and written once a run)."""
    C, H, d = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    G, A, R = H // Hkv, feature_rows(d), value_rows(dv)
    mxu = jnp.dtype(q.dtype)
    interpret = _interpret_default() if interpret is None else interpret
    if not interpret and (d % 128 or dv % 128):
        raise ValueError(
            f"hetu_retention_scan compiled for a TPU takes head sizes of "
            f"whole lane tiles (multiples of 128); got d={d}, dv={dv}")
    buf, layer = _stacked(state, layer)
    T = scan_chunk(C)
    pad = -C % T
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        log_g = jnp.pad(log_g, ((0, pad), (0, 0)))
        slot, pos = (jnp.pad(a, (0, pad)) for a in (slot, pos))
        valid = jnp.pad(valid, (0, pad))
    Cp = C + pad
    work = scan_work_list(slot, pos, valid, slots=buf.shape[1], chunk=T)
    scalars = (layer.reshape(1), work.chunk, work.lo, work.hi, work.slot,
               work.flags)
    f32 = jnp.float32
    # phi's scale and its sqrt(2) ride the operands: x (2 / d)^(1/4)
    c = (2.0 / d) ** 0.25
    qT = jnp.transpose(q.astype(f32).reshape(Cp, Hkv, G, d) * c,
                       (1, 2, 3, 0))                        # (Hkv, G, d, C)
    ks = k.astype(f32) * c
    kT = jnp.transpose(ks, (1, 2, 0))                       # (Hkv, d, C)
    vT = jnp.transpose(values_one(v, valid[:, None]), (1, 2, 0))
    # the gate's running sum, from each chunk's first row (a piece never
    # crosses a chunk): inclusive and exclusive, a row; inclusive, a column
    lg = jnp.where(valid[:, None], log_g.astype(f32), 0.0).T    # (Hkv, C)
    Gi = jnp.cumsum(lg.reshape(Hkv, Cp // T, T), axis=-1).reshape(Hkv, Cp)
    grow = jnp.stack([Gi, Gi - lg], axis=1)                 # (Hkv, 2, C)

    def at(*block):
        """A kv head's block at the piece's chunk, tokens last."""
        n = len(block)
        return pl.BlockSpec(
            (None,) + block,
            lambda h, p, layer, chunk, *_: (h,) + (0,) * (n - 1)
            + (chunk[p],))

    o, buf = pl.pallas_call(
        functools.partial(_scan_kernel, T=T, G=G, d=d, mxu=mxu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(Hkv, work.n),
            in_specs=[at(G, d, T), at(G, d, T), at(d, T), at(d, T),
                      pl.BlockSpec((None, T, d),
                                   lambda h, p, layer, chunk, *_:
                                   (h, chunk[p], 0)),
                      at(R, T), at(2, T),
                      pl.BlockSpec((None, T, 1),
                                   lambda h, p, layer, chunk, *_:
                                   (h, chunk[p], 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[at(G, R, T), pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((A, R, d), f32),
                            pltpu.VMEM((G, R, T), f32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((Hkv, G, R, Cp), f32),
                   jax.ShapeDtypeStruct(buf.shape, f32)],
        input_output_aliases={len(scalars) + 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="hetu_retention_scan",
    )(*scalars, qT, qT[:, :, ::-1], kT, kT[:, ::-1],
      jnp.transpose(ks, (1, 0, 2)), vT, grow, Gi[:, :, None], buf)
    n1 = jnp.transpose(o, (3, 0, 1, 2))[:C]                 # (C, Hkv, G, R)
    y = n1[..., :dv] / (n1[..., dv:dv + 1] + eps)
    y = jnp.where(valid[:C, None, None, None], y, 0.0).reshape(C, H, dv)
    out = (y, buf if state.ndim == 6 else buf[0])
    if return_runs:
        out += (jnp.sum((work.flags & _OPENS) != 0, dtype=jnp.int32),)
    return out


def _update_kernel(layer_ref, ids_ref, n_ref, g_ref, fk_ref, fq_ref, v_ref,
                   state_in, o_ref, state_out, acc_ref, *, G, Hkv, nt, d):
    del layer_ref
    r, h, t = (pl.program_id(i) for i in range(3))
    n = n_ref[0]

    @pl.when(r < n)
    def _():
        g = g_ref[ids_ref[r] * Hkv + h]
        vb = jnp.broadcast_to(v_ref[...], v_ref.shape[:1] + (d,))  # (R, d)
        new = g * state_in[...] + vb[None] * fk_ref[...][:, None, :]
        state_out[...] = new

        @pl.when(t == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for i in range(G):
            acc_ref[i] += jnp.sum(new * fq_ref[i][:, None, :], axis=0)

        @pl.when(t == nt - 1)
        def _():
            # the lanes' sum as a product: a lane-dense row of R
            ones = jnp.ones((8, d), jnp.float32)
            for i in range(G):
                o_ref[i:i + 1, :] = jax.lax.dot_general(
                    ones, acc_ref[i], (_NT, ((), ())), precision=_HI,
                    preferred_element_type=jnp.float32)[0:1]

    @pl.when(n == 0)         # (no live row: the one block named, as it is)
    def _():
        state_out[...] = state_in[...]


def hetu_retention_update(q, k, v, log_g, state, live, *, eps: float,
                          layer=None, interpret: Optional[bool] = None):
    """``ops.retention.retention_update``'s contract as one Pallas call
    on the state IN PLACE: ``state`` the tiles of every slot ``(S, Hkv,
    d / 2 + 1, R, d)`` or the stacked leaf with ``layer=``; a live
    slot's state is read once and written once, any other is not
    touched."""
    S, H, d = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    G, A, R = H // Hkv, feature_rows(d), value_rows(dv)
    interpret = _interpret_default() if interpret is None else interpret
    if not interpret and (d % 128 or dv % 128):
        raise ValueError(
            f"hetu_retention_update compiled for a TPU takes head sizes "
            f"of whole lane tiles (multiples of 128); got d={d}, dv={dv}")
    buf, layer = _stacked(state, layer)
    At = update_rows(A)
    nt = A // At
    f32 = jnp.float32
    fk = phi_tiles(k.astype(f32) * d ** -0.25).reshape(S, Hkv, nt, At, d)
    fq = phi_tiles(q.astype(f32).reshape(S, Hkv, G, d) * d ** -0.25)
    fq = jnp.moveaxis(fq.reshape(S, Hkv, G, nt, At, d), 3, 2)
    v1 = values_one(v)[..., None]                       # (S, Hkv, R, 1)
    ids, n = live_list(live)
    scalars = (layer.reshape(1), ids, n,
               jnp.exp(log_g.astype(f32)).reshape(S * Hkv))

    def slot_of(r, ids, n):
        return ids[jnp.minimum(r, jnp.maximum(n[0] - 1, 0))]

    def where_live(r, n, x, last):
        return jnp.where(r < n[0], x, last)

    def rows(*block):
        """A (slot, kv head, tile)'s block of an operand."""
        return pl.BlockSpec(
            (None, None, None) + block,
            lambda r, h, t, layer, ids, n, g: (
                slot_of(r, ids, n), where_live(r, n, h, Hkv - 1),
                where_live(r, n, t, nt - 1)) + (0,) * len(block))

    def heads(*block):
        return pl.BlockSpec(
            (None, None) + block,
            lambda r, h, t, layer, ids, n, g: (
                slot_of(r, ids, n), where_live(r, n, h, Hkv - 1))
            + (0,) * len(block))

    leaf = pl.BlockSpec(
        (None, None, None, At, R, d),
        lambda r, h, t, layer, ids, n, g: (
            layer[0], slot_of(r, ids, n), where_live(r, n, h, Hkv - 1),
            where_live(r, n, t, nt - 1), 0, 0))
    o, buf = pl.pallas_call(
        functools.partial(_update_kernel, G=G, Hkv=Hkv, nt=nt, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(S, Hkv, nt),
            in_specs=[rows(At, d), rows(G, At, d), heads(R, 1), leaf],
            out_specs=[heads(8, R), leaf],
            scratch_shapes=[pltpu.VMEM((G, R, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((S, Hkv, 8, R), f32),
                   jax.ShapeDtypeStruct(buf.shape, f32)],
        input_output_aliases={len(scalars) + 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="hetu_retention_update",
    )(*scalars, fk, fq, v1, buf)
    n1 = o[:, :, :G]                                    # (S, Hkv, G, R)
    y = n1[..., :dv] / (n1[..., dv:dv + 1] + eps)
    y = jnp.where(live[:, None, None, None], y, 0.0).reshape(S, H, dv)
    return y, buf if state.ndim == 6 else buf[0]
