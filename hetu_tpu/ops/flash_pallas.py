"""Pallas TPU flash attention: fused fwd + bwd with custom_vjp.

TPU-native replacement for the reference's FlashAttention wrapper
(``hetu/impl/kernel/FlashAttention.cu:1-50``, which marshals into the vendored
``third_party/flash_attn`` CUDA kernels) and the cp=1 fast path of
``ParallelAttentionOp`` (``hetu/graph/ops/ParallelAttention.h:711``).

Design (TPU-first, not a translation):
- Online-softmax streaming over KV blocks; grid ``(batch, q_heads, q_blocks,
  kv_blocks)`` with the KV axis innermost ("arbitrary" semantics) so running
  max / denominator / accumulator live in VMEM scratch across KV iterations.
- GQA without materializing repeated KV: the K/V BlockSpec index_map divides
  the q-head program id by the group size.
- Packing / varlen is expressed with segment ids (TPU formulation of the
  reference's cu_seqlens varlen path): q ids broadcast to 128 lanes, kv ids
  to 8 sublanes, the same layout the proven TPU kernels use.
- Backward = two kernels: dq streams KV blocks per Q block; dK/dV stream Q
  blocks per KV block (dK/dV produced per q-head then group-summed for GQA).
- ``q_offset``/``kv_offset`` shift absolute positions for the causal mask so
  ring-attention CP (``hetu_tpu.parallel.ring_attention``) can reuse these
  kernels per hop and combine with the returned LSE.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.core.bits import fmix32

NEG_INF = -1e30
NUM_LANES = 128
NUM_SUBLANES = 8


def _pick_block(n: int, target: int = 512) -> int:
    for b in (target, 256, 128):
        if n % b == 0 and b <= n:
            return b
    return n


def _tuned_entries() -> tuple:
    """Block winners measured by ``workloads/flash_tune.py`` on this
    machine's chip; () when absent or when not running on TPU."""
    if jax.default_backend() != "tpu":
        return ()
    from hetu_tpu.core.measured import read_measured
    data = read_measured("flash_blocks.json")
    try:
        return tuple(tuple(sorted(e.items())) for e in data["entries"])
    except (KeyError, TypeError):
        return ()


def _default_blocks(sq: int, sk: int, kind: str) -> tuple:
    """Tuned (block_q, block_k) for this q/kv length if measured (exact
    q-seq match whose blocks divide both lengths), else the static
    heuristic. ``kind``: "fwd" | "bwd"."""
    for items in _tuned_entries():
        e = dict(items)
        if e.get("seq") == sq and kind in e:
            bq, bk = e[kind]
            if sq % bq == 0 and sk % bk == 0:
                return bq, bk
    return _pick_block(sq), _pick_block(sk)


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
    """(block_q, block_k) bool keep-mask from a counter-based RNG.

    Addressed by ABSOLUTE (q, k) position + (batch, head) + seed — not by
    block indices — so the forward and both backward kernels regenerate
    the IDENTICAL mask even when their tuned block sizes differ (the
    same property the reference gets from flash-attn's philox offsets,
    ``hetu/impl/kernel/FlashAttention.cu:1-50``). Pure uint32 jnp ops:
    one code path for Mosaic and interpret modes.
    """
    qpos = jnp.uint32(iq * block_q + q_offset) + jax.lax.broadcasted_iota(
        jnp.uint32, (block_q, block_k), 0)
    kpos = jnp.uint32(ik * block_k + kv_offset) + jax.lax.broadcasted_iota(
        jnp.uint32, (block_q, block_k), 1)
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


def _block_live(iq, ik, *, causal, block_q, block_k, q_offset, kv_offset):
    """Scalar predicate: does this (q_block, kv_block) cell have any live
    causal entry? Cells entirely above the diagonal are skipped with
    ``pl.when`` so the MXU never sees them (~2x FLOPs saved at long seq —
    the flash-attn tiling trick the reference gets from the CUDA kernels).
    Returns None when nothing can be skipped statically (non-causal)."""
    if not causal:
        return None
    last_q = iq * block_q + (block_q - 1) + q_offset
    first_k = ik * block_k + kv_offset
    return last_q >= first_k


def _mask_for_block(iq, ik, *, block_q, block_k, causal,
                    q_offset, kv_offset, q_ids, kv_ids):
    """Returns bool mask (block_q, block_k) or None if nothing masks."""
    mask = None
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + q_offset
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1) + kv_offset
        mask = qpos >= kpos
    if q_ids is not None:
        smask = q_ids == kv_ids  # (block_q,1) == (1,block_k)
        mask = smask if mask is None else mask & smask
    return mask


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, seed_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                causal, block_q, block_k, kv_blocks, q_offset, kv_offset,
                dropout_rate=0.0):
    ib = pl.program_id(0)
    ih = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0, 0]  # (block_q, d), scale already folded in
        k = k_ref[0, 0]  # (block_k, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        q_ids = qseg_ref[0][:, :1] if qseg_ref is not None else None
        kv_ids = kseg_ref[0][:1, :] if kseg_ref is not None else None
        mask = _mask_for_block(iq, ik, block_q=block_q, block_k=block_k,
                               causal=causal, q_offset=q_offset,
                               kv_offset=kv_offset, q_ids=q_ids,
                               kv_ids=kv_ids)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_next)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # exact zero for fully-masked rows
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        alpha = jnp.exp(m_prev - m_next)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(alpha * l_prev + l_cur, l_scr.shape)
        if dropout_rate > 0.0:
            # dropout on the (later-normalized) probs: mask only the
            # VALUE accumulation — the denominator l stays un-dropped,
            # so out = Σ mask∘softmax∘V / keep and LSE is unchanged
            keep = _dropout_keep(seed_ref[0], ib, ih, iq, ik,
                                 rate=dropout_rate, block_q=block_q,
                                 block_k=block_k, q_offset=q_offset,
                                 kv_offset=kv_offset)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    live = _block_live(iq, ik, causal=causal, block_q=block_q,
                       block_k=block_k, q_offset=q_offset,
                       kv_offset=kv_offset)
    if live is None:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(ik == kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd(q, k, v, q_seg, kv_seg, *, causal, scale,
               q_offset=0, kv_offset=0, interpret=None,
               block_q=None, block_k=None,
               dropout_rate=0.0, seed=None):
    """q (b,hq,sq,d); k/v (b,hkv,sk,d); seg ids (b,s) or None.

    Returns out (b,hq,sq,d) and lse (b,hq,sq) (natural-log-sum-exp of the
    scaled, masked logits — fp32).
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    if block_q is None and block_k is None:
        block_q, block_k = _default_blocks(sq, sk, "fwd")
    else:
        block_q = block_q or _pick_block(sq)
        block_k = block_k or _pick_block(sk)
    kv_blocks = sk // block_k
    interpret = _interpret_default() if interpret is None else interpret

    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    grid = (b, hq, sq // block_q, kv_blocks)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
    ]
    args = [qf, k, v]
    has_seg = q_seg is not None
    has_drop = dropout_rate > 0.0
    if has_seg:
        in_specs.append(pl.BlockSpec(
            (1, block_q, NUM_LANES), lambda ib, ih, iq, ik: (ib, iq, 0)))
        in_specs.append(pl.BlockSpec(
            (1, NUM_SUBLANES, block_k), lambda ib, ih, iq, ik: (ib, 0, ik)))
        args += [_expand_q_ids(q_seg), _expand_kv_ids(kv_seg)]
    if has_drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    kernel = functools.partial(_opt_refs_wrapper, _fwd_kernel, 3,
                               has_seg, has_drop)

    out_shape = [
        jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, hq, sq, NUM_LANES), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_q, NUM_LANES),
                     lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
    ]
    with jax.named_scope("hetu.flash_fwd"):
        out, lse_l = pl.pallas_call(
            functools.partial(kernel, causal=causal, block_q=block_q,
                              block_k=block_k, kv_blocks=kv_blocks,
                              q_offset=q_offset, kv_offset=kv_offset,
                              dropout_rate=dropout_rate),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                pltpu.VMEM((block_q, NUM_LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="hetu_flash_fwd",
        )(*args)
    return out, lse_l[..., 0]


def _opt_refs_wrapper(kernel, n_tensor, has_seg, has_seed, *refs, **kw):
    """Adapts a kernel expecting (tensor refs..., qseg, kseg, seed,
    outs/scratch...) to a call where the optional refs may be absent —
    pallas passes only the refs that were given specs, in order."""
    idx = n_tensor
    if has_seg:
        qseg, kseg = refs[idx], refs[idx + 1]
        idx += 2
    else:
        qseg = kseg = None
    if has_seed:
        seed = refs[idx]
        idx += 1
    else:
        seed = None
    kernel(*refs[:n_tensor], qseg, kseg, seed, *refs[idx:], **kw)


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   qseg_ref, kseg_ref, seed_ref, dq_ref, dq_scr, *,
                   causal, block_q, block_k, kv_blocks, q_offset,
                   kv_offset, dropout_rate=0.0):
    ib = pl.program_id(0)
    ih = pl.program_id(1)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute():
        q = q_ref[0, 0]          # (bq, d) pre-scaled
        k = k_ref[0, 0]          # (bk, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]        # (bq, d)
        lse = lse_ref[0, 0][:, :1]     # (bq, 1)
        delta = delta_ref[0, 0][:, :1]  # (bq, 1)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_ids = qseg_ref[0][:, :1] if qseg_ref is not None else None
        kv_ids = kseg_ref[0][:1, :] if kseg_ref is not None else None
        mask = _mask_for_block(iq, ik, block_q=block_q, block_k=block_k,
                               causal=causal, q_offset=q_offset,
                               kv_offset=kv_offset, q_ids=q_ids,
                               kv_ids=kv_ids)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)

        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # dA = mask ∘ (dO Vᵀ) / keep; delta = Σ dO∘O is invariant
            # under dropout (see _flash_bwd docnote), so ds keeps form
            keep = _dropout_keep(seed_ref[0], ib, ih, iq, ik,
                                 rate=dropout_rate, block_q=block_q,
                                 block_k=block_k, q_offset=q_offset,
                                 kv_offset=kv_offset)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta)    # (bq, bk), fp32
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _block_live(iq, ik, causal=causal, block_q=block_q,
                       block_k=block_k, q_offset=q_offset,
                       kv_offset=kv_offset)
    if live is None:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(ik == kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qseg_ref, kseg_ref, seed_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr, *,
                    causal, block_q, block_k, q_blocks, q_offset,
                    kv_offset, dropout_rate=0.0):
    ib = pl.program_id(0)
    ih = pl.program_id(1)
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_ids = qseg_ref[0][:, :1] if qseg_ref is not None else None
        kv_ids = kseg_ref[0][:1, :] if kseg_ref is not None else None
        mask = _mask_for_block(iq, ik, block_q=block_q, block_k=block_k,
                               causal=causal, q_offset=q_offset,
                               kv_offset=kv_offset, q_ids=q_ids,
                               kv_ids=kv_ids)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)

        keep = None
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref[0], ib, ih, iq, ik,
                                 rate=dropout_rate, block_q=block_q,
                                 block_k=block_k, q_offset=q_offset,
                                 kv_offset=kv_offset)
        # dV += Ad^T @ dO (Ad = dropped probs — what the forward output
        # actually mixed)
        p_v = p if keep is None else \
            jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dS = P * (mask∘(dO @ V^T)/keep - delta);  dK += dS^T @ Q
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _block_live(iq, ik, causal=causal, block_q=block_q,
                       block_k=block_k, q_offset=q_offset,
                       kv_offset=kv_offset)
    if live is None:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(iq == q_blocks - 1)
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
    if block_q is None and block_k is None:
        block_q, block_k = _default_blocks(sq, sk, "bwd")
    else:
        block_q = block_q or _pick_block(sq)
        block_k = block_k or _pick_block(sk)
    interpret = _interpret_default() if interpret is None else interpret

    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    if delta is None:
        delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)                                # (b,hq,sq)
    lse_l = jax.lax.broadcast_in_dim(lse, (*lse.shape, NUM_LANES), (0, 1, 2))
    delta_l = jax.lax.broadcast_in_dim(delta, (*delta.shape, NUM_LANES),
                                       (0, 1, 2))

    lane_spec_q = pl.BlockSpec((1, 1, block_q, NUM_LANES),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    args = [qf, k, v, do, lse_l, delta_l]
    has_seg = q_seg is not None
    has_drop = dropout_rate > 0.0
    seed_args, seed_specs = [], []
    if has_drop:
        seed_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        seed_args = [jnp.asarray(seed, jnp.int32).reshape(1)]
    seg_args, seg_specs_dq, seg_specs_dkv = [], [], []
    if q_seg is not None:
        seg_args = [_expand_q_ids(q_seg), _expand_kv_ids(kv_seg)]
        seg_specs_dq = [
            pl.BlockSpec((1, block_q, NUM_LANES),
                         lambda ib, ih, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((1, NUM_SUBLANES, block_k),
                         lambda ib, ih, iq, ik: (ib, 0, ik)),
        ]
        seg_specs_dkv = [
            pl.BlockSpec((1, block_q, NUM_LANES),
                         lambda ib, ih, ik, iq: (ib, iq, 0)),
            pl.BlockSpec((1, NUM_SUBLANES, block_k),
                         lambda ib, ih, ik, iq: (ib, 0, ik)),
        ]

    # ---- dQ: grid (b, hq, q_blocks, kv_blocks), accumulate over kv ----
    dq_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        lane_spec_q,
        lane_spec_q,
    ] + seg_specs_dq + seed_specs
    dq_kernel = functools.partial(_opt_refs_wrapper, _bwd_dq_kernel, 6,
                                  has_seg, has_drop)
    with jax.named_scope("hetu.flash_bwd"):
        dq = pl.pallas_call(
            functools.partial(dq_kernel, causal=causal, block_q=block_q,
                              block_k=block_k, kv_blocks=sk // block_k,
                              q_offset=q_offset, kv_offset=kv_offset,
                              dropout_rate=dropout_rate),
            grid=(b, hq, sq // block_q, sk // block_k),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="hetu_flash_bwd_dq",
        )(*args, *seg_args, *seed_args)
    dq = (dq * scale).astype(q.dtype)  # undo the q-scale folding

    # ---- dK/dV: grid (b, hq, kv_blocks, q_blocks), accumulate over q ----
    # dK/dV are produced per *q* head (GQA read via index_map), then
    # group-summed down to kv heads.
    dkv_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, ik, iq: (ib, ih // rep, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, ik, iq: (ib, ih // rep, ik, 0)),
        pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_q, NUM_LANES),
                     lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_q, NUM_LANES),
                     lambda ib, ih, ik, iq: (ib, ih, iq, 0)),
    ] + seg_specs_dkv + seed_specs
    dkv_kernel = functools.partial(_opt_refs_wrapper, _bwd_dkv_kernel, 6,
                                   has_seg, has_drop)
    kv_out_spec = pl.BlockSpec((1, 1, block_k, d),
                               lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    with jax.named_scope("hetu.flash_bwd"):
        dk, dv = pl.pallas_call(
            functools.partial(dkv_kernel, causal=causal, block_q=block_q,
                              block_k=block_k, q_blocks=sq // block_q,
                              q_offset=q_offset, kv_offset=kv_offset,
                              dropout_rate=dropout_rate),
            grid=(b, hq, sk // block_k, sq // block_q),
            in_specs=dkv_specs,
            out_specs=[kv_out_spec, kv_out_spec],
            out_shape=[jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
                       jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="hetu_flash_bwd_dkv",
        )(*args, *seg_args, *seed_args)
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
