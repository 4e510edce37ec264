"""Attention ops: reference implementation + dispatch to the Pallas flash
kernel on TPU.

Replaces the reference's FlashAttention wrapper
(``hetu/impl/kernel/FlashAttention.cu`` over vendored ``third_party/
flash_attn``) and the cp=1 path of ``ParallelAttentionOp``
(``hetu/graph/ops/ParallelAttention.h:711``). Packing/varlen is expressed via
``segment_ids`` (the TPU-native formulation) instead of cu_seqlens.

Layout convention everywhere: (batch, seq, num_heads, head_dim), GQA allowed
(kv heads divide q heads).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

# -- decode-kernel dispatch (ISSUE 14) ---------------------------------------
# The paged Pallas decode kernel and the XLA-gather reference path are
# selected per call site; a request for the kernel that cannot be
# honored (tp-sharded GSPMD context — Mosaic kernels cannot be
# auto-partitioned — or a non-causal attention module) degrades LOUDLY:
# warn once per site and count it, the same discipline as
# ``parallel.overlap.record_ring_fallback``.

_KERNEL_FALLBACKS: dict = {}
_WARNED_KERNEL_SITES: set = set()
_KERNEL_LOCK = threading.Lock()


def record_kernel_fallback(site: str, detail: str = "") -> None:
    """Count (and warn ONCE per site about) a decode-attention call that
    asked for the paged Pallas kernel but ran the XLA-gather reference
    path instead. Audited by ``attn_kernel_fallback_total``."""
    with _KERNEL_LOCK:
        _KERNEL_FALLBACKS[site] = _KERNEL_FALLBACKS.get(site, 0) + 1
        first = site not in _WARNED_KERNEL_SITES
        _WARNED_KERNEL_SITES.add(site)
    from hetu_tpu import telemetry
    if telemetry.enabled():
        telemetry.get_registry().counter(
            "attn_kernel_fallback_total",
            "paged-kernel requests that fell back to the XLA-gather "
            "reference path").inc(site=site)
    if first:
        import warnings
        warnings.warn(
            f"attn_kernel='paged' fell back to the XLA-gather reference "
            f"path at {site}: {detail} (warned once per site; counted "
            f"in attn_kernel_fallback_total)", stacklevel=3)


def kernel_fallbacks() -> dict:
    with _KERNEL_LOCK:
        return dict(_KERNEL_FALLBACKS)


def resolve_decode_kernel(requested: str, *, tp: int = 1,
                          site: str = "decode",
                          num_heads: Optional[int] = None,
                          num_kv_heads: Optional[int] = None) -> str:
    """Resolve an ``attn_kernel`` request to the path that will run.

    ``"auto"`` → the paged Pallas kernel on TPU, the XLA-gather
    reference elsewhere (interpret-mode Pallas loses to the XLA-fused
    gather on CPU — the same heuristic ``flash_attention`` uses).
    Under tp > 1 the paged call is wrapped in a shard_map over the
    plan's tp axis (``paged_pallas.paged_attention_auto``) — Mosaic
    kernels cannot be GSPMD-auto-partitioned, so each shard runs the
    kernel on its local head slice. That only works when BOTH head
    counts divide by tp; a non-divisible model (or unknown head
    counts) still degrades to the gather path, counted at the ``tp``
    site."""
    if requested not in ("auto", "paged", "reference"):
        raise ValueError(
            f"attn_kernel must be auto|paged|reference, got {requested!r}")
    resolved = requested
    if resolved == "auto":
        resolved = "paged" if jax.default_backend() == "tpu" \
            else "reference"
    # tp > 1: honor "paged" only when the shard_map wrapper can slice
    # the head axis evenly across the tp axis — a raw Mosaic call must
    # never be handed to GSPMD for auto-partitioning
    if resolved == "paged" and tp > 1:
        if num_heads is None or num_kv_heads is None:
            record_kernel_fallback(
                site, f"tp={tp} with unknown head counts — cannot "
                      f"prove the shard_map head slice is even")
            return "reference"
        if num_heads % tp or num_kv_heads % tp:
            record_kernel_fallback(
                site, f"tp={tp} does not divide heads "
                      f"(q={num_heads}, kv={num_kv_heads}) — the "
                      f"shard_map head slice would be ragged")
            return "reference"
    return resolved


def _expand_kv(k, num_q_heads):
    """Repeat kv heads to match q heads for GQA in the reference path."""
    kv_heads = k.shape[-2]
    if kv_heads == num_q_heads:
        return k
    rep = num_q_heads // kv_heads
    return jnp.repeat(k, rep, axis=-2)


def gather_block_rows(buf, block_tables):
    """Paged-KV gather: ``(n_blocks, block_size, ...)`` arena + ``(b, W)``
    block tables → the contiguous ``(b, W*block_size, ...)`` per-row view.

    Row ``r``'s position ``p`` lives at arena row
    ``block_tables[r, p // block_size] * block_size + p % block_size`` —
    the PagedAttention indirection (vLLM, SOSP'23) expressed as one XLA
    gather, so a paged cache reads like a dense one. Table entries are
    data, never shapes: any block remap (prefix sharing, CoW,
    reallocation) re-runs the same compiled program."""
    n_blocks, block_size = buf.shape[0], buf.shape[1]
    flat = buf.reshape((n_blocks * block_size,) + buf.shape[2:])
    rows = (block_tables[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :])
    rows = rows.reshape(block_tables.shape[0], -1)
    return jnp.take(flat, rows, axis=0)


def block_bound(pos, block: int):
    """The last key a query at ``pos`` sees under the block bound:
    ``pos | (block - 1)`` — the end of its block of ``block`` positions
    (a power of two); ``pos`` itself, untouched, at 1."""
    if block == 1:
        return pos
    if block < 1 or block & (block - 1):
        raise ValueError(f"a block bound is a power of two, got {block}")
    return pos | (block - 1)


def attention_reference(q, k, v, *, causal: bool = False,
                        segment_ids: Optional[jnp.ndarray] = None,
                        kv_segment_ids: Optional[jnp.ndarray] = None,
                        scale: Optional[float] = None,
                        return_lse: bool = False,
                        q_offset: int | jnp.ndarray = 0,
                        kv_offset: int | jnp.ndarray = 0,
                        dropout_rate: float = 0.0,
                        dropout_key: Optional[jax.Array] = None,
                        window=None, block: int = 1):
    """Pure-jnp attention oracle, fp32 softmax.

    ``block`` (causal only; a power of two, 1 = plain causal): the
    BLOCK bound of a block-diffusion model — a query at absolute
    position ``p`` sees every key ``j <= p | (block - 1)``, its own
    block whole and the blocks before it (:func:`block_bound`).

    ``window`` (causal only; an int, a traced int32 scalar or one per
    batch row, ``None`` = no window): a query at absolute position ``p``
    sees keys ``p - window < j <= p``.

    ``q_offset``/``kv_offset`` shift the absolute positions used by the causal
    mask — needed when q/kv are chunks of a longer sequence (ring attention).
    An ARRAY ``q_offset`` gives every batch row its own base position, and
    ``sq > 1`` then spans positions ``q_offset[r]..q_offset[r]+sq-1`` per
    row: this is the speculative-decoding verify lane (each serving slot
    checks its k draft tokens in one causal forward — row ``i`` attends
    exactly the prefix a sequential decode at position ``q_offset[r]+i``
    would have seen).

    ``dropout_rate``/``dropout_key``: inverted dropout on the softmax
    probabilities (the reference flash wrapper's p_dropout,
    ``hetu/impl/kernel/FlashAttention.cu:1-50``); a None key (eval) is
    the identity. The LSE is computed on the UN-dropped distribution —
    dropout perturbs the value mix, not the normalizer.
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    qf = q.astype(jnp.float32) * scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))

    mask = jnp.ones((b, 1, sq, sk), dtype=bool)
    if causal:
        qoff = jnp.asarray(q_offset)
        koff = jnp.asarray(kv_offset)
        per_row_window = window is not None and jnp.ndim(window) > 0
        if per_row_window:
            window = jnp.reshape(window, (-1, 1, 1))
        if qoff.ndim or koff.ndim or per_row_window:
            # per-batch-row offsets (serving: every KV-pool slot decodes
            # at its own absolute position) — (b,) or scalar, broadcast
            # to (b, sq, sk) then into the (b, 1, sq, sk) mask layout
            qpos = jnp.arange(sq)[None, :, None] + qoff.reshape(-1, 1, 1)
            kpos = jnp.arange(sk)[None, None, :] + koff.reshape(-1, 1, 1)
            seen = block_bound(qpos, block) >= kpos
            if window is not None:
                seen = seen & (kpos > qpos - window)
            mask = mask & seen[:, None]
        else:
            qpos = jnp.arange(sq)[:, None] + q_offset
            kpos = jnp.arange(sk)[None, :] + kv_offset
            seen = block_bound(qpos, block) >= kpos
            if window is not None:
                seen = seen & (kpos > qpos - window)
            mask = mask & seen[None, None]
    elif window is not None or block != 1:
        raise ValueError("a window or a block bound needs causal "
                         "attention")
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        mask = mask & (segment_ids[:, None, :, None] == kv_seg[:, None, None, :])
    logits = jnp.where(mask, logits, NEG_INF)

    lse = jax.nn.logsumexp(logits, axis=-1)  # (b, h, q)
    # rows that are fully masked (can happen in ring hops) produce 0 output
    probs = jnp.exp(logits - lse[..., None])
    probs = jnp.where(mask, probs, 0.0)
    if dropout_rate > 0.0 and dropout_key is not None:
        from hetu_tpu.ops.dropout import dropout
        probs = dropout(probs, dropout_rate, dropout_key)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    out = out.astype(q.dtype)
    if return_lse:
        return out, lse
    return out


def flash_attention(q, k, v, *, causal: bool = False,
                    segment_ids: Optional[jnp.ndarray] = None,
                    scale: Optional[float] = None,
                    impl: str = "auto",
                    dropout_rate: float = 0.0,
                    dropout_key: Optional[jax.Array] = None):
    """Dispatch: Pallas flash kernel on TPU, reference elsewhere.

    ``impl``: "auto" | "pallas" | "reference".

    Attention dropout (``dropout_rate`` > 0 with a live ``dropout_key``)
    is carried by BOTH paths (parity: the reference wrapper's p_dropout
    rides the flash kernel's RNG, ``hetu/impl/kernel/
    FlashAttention.cu:1-50``): the Pallas kernels regenerate a
    position-addressable counter-RNG mask in forward and backward
    (``flash_pallas._dropout_keep``), the reference path drops the
    softmax probs with ``jax.random``. The two paths draw DIFFERENT
    masks (their RNGs differ) — same distribution, not bit-identical.
    """
    if impl == "auto":
        # Pallas kernel on real TPU; on CPU the XLA-fused oracle is faster
        # than interpret-mode Pallas.
        impl = "pallas" if _on_tpu() and _pallas_supported(q, k) \
            else "reference"
    if impl == "pallas":
        out = _pallas_sharded_call(q, k, v, causal=causal,
                                   segment_ids=segment_ids, scale=scale,
                                   dropout_rate=dropout_rate,
                                   dropout_key=dropout_key)
        if out is not None:
            return out
        from hetu_tpu.ops.flash_pallas import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal,
                                      segment_ids=segment_ids, scale=scale,
                                      dropout_rate=dropout_rate,
                                      dropout_key=dropout_key)
    return attention_reference(q, k, v, causal=causal,
                               segment_ids=segment_ids, scale=scale,
                               dropout_rate=dropout_rate,
                               dropout_key=dropout_key)


def attention_with_lse(q, k, v, *, causal: bool = False,
                       segment_ids: Optional[jnp.ndarray] = None,
                       scale: Optional[float] = None,
                       impl: str = "reference",
                       interpret: Optional[bool] = None,
                       block: int = 1):
    """Attention that ALSO returns the log-sum-exp — ``(out, lse)`` with
    ``out`` (b, s, h, d) and ``lse`` (b, h, s) fp32.

    The packed-prefill flash lane needs both: each pack token's output
    is the LSE-combine of an intra-pack part (this function, segment
    isolation via ``segment_ids``) and an arena-history part (the paged
    kernel) — ``ops.paged_pallas.combine_attention_lse``. Inference-only
    (no vjp); ``impl="pallas"`` runs the flash forward kernel,
    ``"reference"`` the fp32 oracle. ``block``: the block bound
    (:func:`block_bound`) in place of the causal one."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if impl == "pallas":
        from hetu_tpu.ops.flash_pallas import _flash_fwd
        out, lse = _flash_fwd(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), segment_ids, segment_ids,
            causal=causal, scale=scale, interpret=interpret, block=block)
        return jnp.swapaxes(out, 1, 2), lse
    return attention_reference(q, k, v, causal=causal,
                               segment_ids=segment_ids, scale=scale,
                               return_lse=True, block=block)


def _pallas_sharded_call(q, k, v, *, causal, segment_ids, scale,
                         dropout_rate=0.0, dropout_key=None):
    """Run the Pallas kernel per-device under ``shard_map`` when the
    batch/head dims are mesh-sharded.

    XLA:TPU cannot auto-partition Mosaic kernels ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map"),
    so the plain GSPMD path — dp/tp sharding with cp=1, and the pipeline
    executor's partial-manual region whose dp/tp stay auto — MUST wrap
    the call; the CPU mesh never sees this because interpret-mode Pallas
    lowers to partitionable jax ops (caught by the offline AOT matrix,
    ``workloads/aot_check.py``). Returns None when no wrap is needed
    (no sharding context, single-device axes, or non-divisible dims —
    the plain call is then the status quo). The cp>1 seq-sharded cases
    never reach here (ring/ulysses own them and bind the mesh manual
    themselves)."""
    from hetu_tpu.parallel.sharding import (
        _axis_size, current_act_sharding, manual_unbound_axes,
    )

    b, _, hq, _ = q.shape
    hkv = k.shape[2]
    ctx = current_act_sharding()
    if ctx is not None:
        mesh = ctx.mesh
        batch_ax = ctx.batch
        head_ax = ctx.tp if isinstance(ctx.tp, str) else None
        # seq sharded → the ring/ulysses paths own the kernel call
        if isinstance(ctx.seq, str) and _axis_size(mesh, ctx.seq) > 1:
            return None
        # GSPMD with nothing to shard the call over: plain call is fine
        if _axis_size(mesh, batch_ax) * _axis_size(mesh, head_ax) == 1:
            return None
        # a dim whose size doesn't divide its mesh axes is carried
        # REPLICATED instead (shard_map gathers it; slower but correct —
        # the raw call would not compile at all)
        if _axis_size(mesh, batch_ax) > 1 and b % _axis_size(mesh,
                                                            batch_ax):
            batch_ax = None
        nh = _axis_size(mesh, head_ax)
        if nh > 1 and (hq % nh or hkv % nh):
            head_ax = None
        axis_names = set(mesh.shape)
    else:
        # partial-manual pipeline region: pp/cp/ep are bound, dp/tp are
        # auto — the call must be wrapped even when the auto axes are
        # all size 1 (a partial-manual region still counts as "auto" to
        # the partitioner, which rejects raw Mosaic calls in it)
        info = manual_unbound_axes(b, (hq, hkv))
        if info is None:
            return None
        mesh, axis_names, batch_ax, head_ax = info

    from jax import shard_map

    from hetu_tpu.ops.flash_pallas import flash_attention_pallas

    qkv_spec = P(batch_ax, None, head_ax, None)
    drop_active = dropout_rate > 0.0 and dropout_key is not None

    def local(q, k, v, *seg):
        key = dropout_key
        if drop_active:
            # decorrelate shards: without the fold-in, every shard's
            # local (batch, head) indices draw the same mask
            for ax in (batch_ax, head_ax):
                if ax is not None:
                    key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        return flash_attention_pallas(
            q, k, v, causal=causal, scale=scale,
            segment_ids=seg[0] if seg else None,
            dropout_rate=dropout_rate if drop_active else 0.0,
            dropout_key=key)

    if segment_ids is None:
        fn = shard_map(local, mesh=mesh, in_specs=(qkv_spec,) * 3,
                       out_specs=qkv_spec, axis_names=axis_names,
                       check_vma=False)
        return fn(q, k, v)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(qkv_spec,) * 3 + (P(batch_ax, None),),
                   out_specs=qkv_spec, axis_names=axis_names,
                   check_vma=False)
    return fn(q, k, v, segment_ids)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_supported(q, k) -> bool:
    d = q.shape[-1]
    return d in (64, 128, 256) and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
