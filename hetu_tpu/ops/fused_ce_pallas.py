"""Fused LM-head + softmax-CE Pallas kernel: streaming over vocab blocks.

TPU-native replacement for the reference's fused projection+CE kernel
(``hetu/impl/kernel/VocabParallelCrossEntropyLoss.cu`` fused with the
column-parallel lm_head): the (N, V) logits are NEVER materialized in
HBM. The forward streams vocab blocks with an online max/denominator
(flash-attention-style) and emits per-token loss ``lse - logit[label]``;
the backward recomputes each logits tile and feeds
``g * (softmax - onehot)`` straight into the dH / dW matmuls.

vs. ``ops.losses.chunked_lm_loss`` (the XLA formulation): chunking bounds
logits memory to ~0.8 GB per chunk and serializes chunks with a barrier;
this kernel bounds it to one VMEM tile (~1 MB) with no barrier, at the
cost of one extra tile recompute in backward (two bwd kernels, same
split as the flash bwd). A/B-able at the whole-step level via
``HETU_LM_LOSS_IMPL=fused`` (see ``vocab_parallel_lm_loss``).

Layout: h (N, E) flattened tokens, w (V, E) vocab-major weight,
labels (N,) int32. N must divide by block_n after caller padding; V is
padded internally to block_v (padded columns masked to NEG_INF).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.flash_pallas import _interpret_default, _pick_block

NEG_INF = -1e30
NUM_LANES = 128


def _expand_lanes(x: jnp.ndarray) -> jnp.ndarray:
    # (N,) -> (N, NUM_LANES)
    return jax.lax.broadcast_in_dim(x, (*x.shape, NUM_LANES), (0,))


def _col_ids(iv, block_n, block_v):
    """Global vocab column ids of this tile, (block_n, block_v)."""
    return iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


# --------------------------------------------------------------------------
# Forward: per-token (lse, target-logit) streamed over vocab blocks
# --------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, lab_ref, tgt_ref, lse_ref,
                m_scr, l_scr, t_scr, *, block_n, block_v, v_blocks, vocab):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    h = h_ref[...]                                  # (block_n, E)
    w = w_ref[...].astype(h.dtype)                  # (block_v, E)
    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    cols = _col_ids(iv, block_n, block_v)
    if vocab % block_v:
        s = jnp.where(cols < vocab, s, NEG_INF)

    lab = lab_ref[:, :1]                            # (block_n, 1)
    t_scr[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(cols == lab, s, 0.0), axis=1, keepdims=True),
        t_scr.shape)

    m_prev = m_scr[:, :1]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    l_cur = jnp.sum(jnp.exp(s - m_next), axis=1, keepdims=True)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(alpha * l_scr[:, :1] + l_cur, l_scr.shape)

    @pl.when(iv == v_blocks - 1)
    def _finalize():
        lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        tgt_ref[...] = jnp.broadcast_to(t_scr[:, :1], tgt_ref.shape)


# --------------------------------------------------------------------------
# Backward: dH streams vocab blocks per token block; dW streams token
# blocks per vocab block (same two-kernel split as the flash backward)
# --------------------------------------------------------------------------

def _p_tile(h, w, lab, lse, glse, gtgt, iv, *, block_n, block_v, vocab):
    """dlogits tile ``glse * exp(s - lse) + gtgt * onehot``, fp32.

    ``glse``/``gtgt`` are the cotangents of this shard's (lse, tgt) —
    the dense loss ``lse - tgt`` gives (g, -g); the vocab-parallel
    psum-combine gives (g * exp(lse_local - lse_global), -g), and the
    chain rule through both lands on g * (softmax - onehot)."""
    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    cols = _col_ids(iv, block_n, block_v)
    p = jnp.exp(s - lse)                            # padded cols: exp(-inf)=0
    if vocab % block_v:
        p = jnp.where(cols < vocab, p, 0.0)
    return glse * p + gtgt * jnp.where(cols == lab, 1.0, 0.0)


def _dh_kernel(h_ref, w_ref, lab_ref, lse_ref, glse_ref, gtgt_ref,
               dh_ref, acc_scr, *, block_n, block_v, v_blocks, vocab):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h = h_ref[...]
    w = w_ref[...].astype(h.dtype)
    p = _p_tile(h, w, lab_ref[:, :1], lse_ref[:, :1], glse_ref[:, :1],
                gtgt_ref[:, :1], iv,
                block_n=block_n, block_v=block_v, vocab=vocab)
    acc_scr[...] += jax.lax.dot_general(
        p.astype(h.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iv == v_blocks - 1)
    def _finalize():
        dh_ref[...] = acc_scr[...].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, lab_ref, lse_ref, glse_ref, gtgt_ref,
               dw_ref, acc_scr, *, block_n, block_v, n_blocks, vocab):
    iv = pl.program_id(0)
    i_n = pl.program_id(1)

    @pl.when(i_n == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h = h_ref[...]
    w = w_ref[...].astype(h.dtype)
    p = _p_tile(h, w, lab_ref[:, :1], lse_ref[:, :1], glse_ref[:, :1],
                gtgt_ref[:, :1], iv,
                block_n=block_n, block_v=block_v, vocab=vocab)
    # (block_v, E) += p^T @ h
    acc_scr[...] += jax.lax.dot_general(
        p.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i_n == n_blocks - 1)
    def _finalize():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


# --------------------------------------------------------------------------
# custom_vjp wrapper
# --------------------------------------------------------------------------




@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_lse_tgt(h, w, labels, block_n, block_v, interpret):
    """Per-token ``(lse, target_logit)`` over THIS weight shard,
    streamed — the differentiable primitive. ``labels`` are local vocab
    ids; out-of-shard tokens should carry an impossible id (e.g. -1),
    contributing 0 to ``tgt``. Composes with a psum logsumexp combine
    for the vocab-parallel path (the custom VJP takes general (glse,
    gtgt) cotangents, so AD through the combine lands on
    ``g * (softmax - onehot)`` per shard)."""
    return _fused_fwd_impl(h, w, labels, block_n, block_v, interpret)


def _fused_fwd_impl(h, w, labels, block_n, block_v, interpret):
    n, e = h.shape
    vocab = w.shape[0]
    v_pad = -vocab % block_v
    wp = jnp.pad(w, ((0, v_pad), (0, 0))) if v_pad else w
    v_blocks = (vocab + v_pad) // block_v
    n_blocks = n // block_n
    lab_l = _expand_lanes(labels.astype(jnp.int32))

    grid = (n_blocks, v_blocks)
    with jax.named_scope("hetu.fused_ce"):
        tgt_l, lse_l = pl.pallas_call(
            functools.partial(_fwd_kernel, block_n=block_n, block_v=block_v,
                              v_blocks=v_blocks, vocab=vocab),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, e), lambda i, j: (i, 0)),
                pl.BlockSpec((block_v, e), lambda i, j: (j, 0)),
                pl.BlockSpec((block_n, NUM_LANES), lambda i, j: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_n, NUM_LANES), lambda i, j: (i, 0)),
                pl.BlockSpec((block_n, NUM_LANES), lambda i, j: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, NUM_LANES), jnp.float32),
                jax.ShapeDtypeStruct((n, NUM_LANES), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((block_n, NUM_LANES), jnp.float32),
                            pltpu.VMEM((block_n, NUM_LANES), jnp.float32),
                            pltpu.VMEM((block_n, NUM_LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="hetu_fused_ce_fwd",
        )(h, wp, lab_l)
    return lse_l[:, 0], tgt_l[:, 0]


def _fused_core_fwd(h, w, labels, block_n, block_v, interpret):
    lse, tgt = _fused_fwd_impl(h, w, labels, block_n, block_v, interpret)
    return (lse, tgt), (h, w, labels, lse)


def _fused_core_bwd(block_n, block_v, interpret, res, cots):
    h, w, labels, lse = res
    glse, gtgt = cots
    n, e = h.shape
    vocab = w.shape[0]
    v_pad = -vocab % block_v
    wp = jnp.pad(w, ((0, v_pad), (0, 0))) if v_pad else w
    v_blocks = (vocab + v_pad) // block_v
    n_blocks = n // block_n
    lab_l = _expand_lanes(labels.astype(jnp.int32))
    lse_l = _expand_lanes(lse)
    glse_l = _expand_lanes(glse.astype(jnp.float32))
    gtgt_l = _expand_lanes(gtgt.astype(jnp.float32))
    lane_spec = pl.BlockSpec((block_n, NUM_LANES), lambda i, j: (i, 0))

    with jax.named_scope("hetu.fused_ce"):
        dh = pl.pallas_call(
            functools.partial(_dh_kernel, block_n=block_n, block_v=block_v,
                              v_blocks=v_blocks, vocab=vocab),
            grid=(n_blocks, v_blocks),
            in_specs=[
                pl.BlockSpec((block_n, e), lambda i, j: (i, 0)),
                pl.BlockSpec((block_v, e), lambda i, j: (j, 0)),
                lane_spec, lane_spec, lane_spec, lane_spec,
            ],
            out_specs=pl.BlockSpec((block_n, e), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, e), h.dtype),
            scratch_shapes=[pltpu.VMEM((block_n, e), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="hetu_fused_ce_dh",
        )(h, wp, lab_l, lse_l, glse_l, gtgt_l)

    lane_spec_vn = pl.BlockSpec((block_n, NUM_LANES), lambda j, i: (i, 0))
    with jax.named_scope("hetu.fused_ce"):
        dwp = pl.pallas_call(
            functools.partial(_dw_kernel, block_n=block_n, block_v=block_v,
                              n_blocks=n_blocks, vocab=vocab),
            grid=(v_blocks, n_blocks),
            in_specs=[
                pl.BlockSpec((block_n, e), lambda j, i: (i, 0)),
                pl.BlockSpec((block_v, e), lambda j, i: (j, 0)),
                lane_spec_vn, lane_spec_vn, lane_spec_vn, lane_spec_vn,
            ],
            out_specs=pl.BlockSpec((block_v, e), lambda j, i: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((vocab + v_pad, e), w.dtype),
            scratch_shapes=[pltpu.VMEM((block_v, e), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="hetu_fused_ce_dw",
        )(h, wp, lab_l, lse_l, glse_l, gtgt_l)
    dw = dwp[:vocab] if v_pad else dwp
    return dh, dw, None


fused_lse_tgt.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_lm_ce(hidden, vocab_weight, labels, *,
                ignore_index: int = -100,
                block_n: int | None = None, block_v: int = 512,
                interpret: bool | None = None):
    """Mean LM CE over (B, S, E) hidden states without materializing
    logits. Differentiable wrt (hidden, vocab_weight).

    Numerics match ``chunked_lm_loss`` / ``cross_entropy_mean``: fp32
    logits tiles, fp32 online softmax, ignored positions excluded from
    the mean.
    """
    B, S, E = hidden.shape
    n = B * S
    h = hidden.reshape(n, E)
    labels = labels.reshape(n)
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0).astype(jnp.int32)
    interpret = _interpret_default() if interpret is None else interpret

    bn = block_n or _pick_block(n)
    pad = -n % bn
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        safe = jnp.pad(safe, (0, pad))
        valid = jnp.pad(valid, (0, pad))

    lse, tgt = fused_lse_tgt(h, vocab_weight, safe, bn, block_v, interpret)
    loss_tok = jnp.where(valid, lse - tgt, 0.0)
    return loss_tok.sum() / jnp.maximum(valid.sum(), 1)


def fused_vocab_parallel_ce(h, w_local, labels, *, axis_name: str,
                            vocab_start, ignore_index: int = -100,
                            block_n: int | None = None, block_v: int = 512,
                            interpret: bool | None = None):
    """Per-token CE with the vocab sharded over ``axis_name`` — the fused
    analogue of :func:`hetu_tpu.ops.losses.vocab_parallel_cross_entropy`.
    Must be called inside ``shard_map``. ``h``: (N, E) local tokens;
    ``w_local``: (V_local, E); ``labels``: (N,) GLOBAL vocab ids.

    Streams this shard's vocab through :func:`fused_lse_tgt`, then
    combines across shards with a psum logsumexp — AD through the
    combine delivers the correct per-shard (glse, gtgt) cotangents.
    Returns (per-token loss with ignored zeroed, valid mask).
    """
    n, _ = h.shape
    v_local = w_local.shape[0]
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    local_ids = safe - vocab_start
    in_shard = (local_ids >= 0) & (local_ids < v_local)
    # out-of-shard tokens carry an impossible id -> tgt contribution 0
    local_lab = jnp.where(in_shard, local_ids, -1).astype(jnp.int32)
    interpret = _interpret_default() if interpret is None else interpret

    bn = block_n or _pick_block(n)
    pad = -n % bn
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        local_lab = jnp.pad(local_lab, (0, pad), constant_values=-1)

    lse_loc, tgt_loc = fused_lse_tgt(h, w_local, local_lab, bn, block_v,
                                     interpret)
    if pad:
        lse_loc, tgt_loc = lse_loc[:n], tgt_loc[:n]

    # global logsumexp across shards (max-shift for stability; the shift
    # cancels in value and gradient, so stop_gradient keeps AD simple)
    gmax = jax.lax.pmax(jax.lax.stop_gradient(lse_loc), axis_name)
    lse = jnp.log(jax.lax.psum(jnp.exp(lse_loc - gmax), axis_name)) + gmax
    tgt = jax.lax.psum(tgt_loc, axis_name)
    return (lse - tgt) * valid, valid
