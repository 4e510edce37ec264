"""Tensor-parallel layers and the stacked-block transformer core.

TPU-native equivalent of the reference's multi-ds parallel layers
(``python/hetu/nn/modules/parallel_multi_ds.py``: ``HtMultiColumnParallelLinear``
:328, ``HtMultiRowParallelLinear`` :411, ``HtMultiQKVColumnParallelLinear``
:504 (GQA-aware), ``HtMultiVocabParallelEmbedding`` :268). The reference
threads per-strategy ``DistributedStates`` unions through every layer and a
C++ pass inserts comm ops; here layers declare *logical* axes on their params
("mlp", "heads", "kv_heads", "vocab", "embed", "layers") and call
``act_constrain`` at the canonical activation cut points — GSPMD then inserts
the same collectives ``SubstituteCommOp`` would (allreduce after row-parallel,
allgather on resharding, …).

``StackedBlocks`` is the scan-over-layers representation: every block param
gains a leading ``layers`` dim so (a) compile time is O(1) in depth, (b) the
pipeline executor can shard the ``layers`` axis over ``pp``
(``hetu_tpu.parallel.pipeline``), and (c) remat policy is applied per block
exactly like the reference's per-block recompute config
(``hetu/graph/recompute/recompute.h:12``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from hetu_tpu.core.dtypes import autocast
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import (
    Module, ParamSpec, StackedLeaf, normal_init, zeros_init,
)
from hetu_tpu.ops import activations as act_ops
from hetu_tpu.ops import embedding as embed_ops
from hetu_tpu.ops.attention import attention_reference, flash_attention
from hetu_tpu.ops.rotary import rope_frequencies, apply_rotary
from hetu_tpu.parallel.sharding import (
    act_constrain, current_act_sharding, current_manual_axes,
)


def _ring_overlap_active(overlap: str) -> bool:
    """Resolve a layer's ``overlap`` mode against the ambient context:
    "ring" forces the decomposed collective matmul, "off" never uses it,
    "auto" (default) follows the Strategy's ``tp_overlap`` via the
    :class:`~hetu_tpu.parallel.sharding.ActivationSharding` context —
    so one Strategy flag flips every TP layer in the model."""
    if overlap == "off":
        return False
    ctx = current_act_sharding()
    if ctx is None:
        return False        # single device / manual pipeline region
    if overlap == "ring":
        return True
    return getattr(ctx, "tp_overlap", "off") == "ring"


class ColumnParallelLinear(Module):
    """Linear whose *output* features shard over tp (Y = XW, W: (in, out/tp)).

    Reference: ``HtMultiColumnParallelLinear`` (`parallel_multi_ds.py:328`).
    No gather is emitted here — the consumer is expected to be tp-local
    (attention heads, MLP hidden) until a RowParallelLinear reduces back.

    ``overlap="ring"`` (or "auto" + ``Strategy(tp_overlap="ring")``)
    decomposes the Megatron-SP all-gather→matmul pair into a ppermute
    ring of chunk matmuls (``parallel.overlap.ring_ag_matmul``) so each
    comm hop hides behind the previous chunk's compute. Without sp the
    column matmul has no gather to hide and the mode is a no-op.
    """

    def __init__(self, in_features: int, out_features: int, *,
                 bias: bool = True, init=None, axis: str = "mlp",
                 out_kind: str = "hidden", overlap: str = "auto"):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias
        self.out_kind = out_kind
        self.overlap = overlap
        self.param("weight", (in_features, out_features),
                   init or normal_init(0.02), axes=("embed", axis))
        if bias:
            self.param("bias", (out_features,), zeros_init(), axes=(axis,))

    def __call__(self, params, x):
        dt = self.compute_dtype()
        x = x.astype(dt)
        w = params["weight"].astype(dt)
        if _ring_overlap_active(self.overlap):
            from hetu_tpu.parallel.overlap import (
                maybe_record_column_fallback, ring_ag_matmul,
                ring_column_applicable,
            )
            ctx = current_act_sharding()
            if ring_column_applicable(ctx, x.shape, w.shape):
                b = params["bias"].astype(dt) if self.use_bias else None
                y = ring_ag_matmul(x, w, b, ctx=ctx,
                                   out_kind=self.out_kind)
                return act_constrain(y, self.out_kind)
            maybe_record_column_fallback(ctx, x.shape, w.shape)
        y = jnp.matmul(x, w)
        if self.use_bias:
            y = y + params["bias"].astype(dt)
        return act_constrain(y, self.out_kind)


class RowParallelLinear(Module):
    """Linear whose *input* features shard over tp (W: (in/tp, out)).

    The contraction over the sharded dim leaves a partial sum; constraining
    the output to a tp-replicated spec makes GSPMD emit the allreduce — the
    same comm the reference deduces for ds ``-2`` partial states
    (`parallel_multi_ds.py:411`, ``distributed_states.h:133``).
    """

    def __init__(self, in_features: int, out_features: int, *,
                 bias: bool = True, init=None, axis: str = "mlp",
                 overlap: str = "auto"):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias
        self.overlap = overlap
        self.param("weight", (in_features, out_features),
                   init or normal_init(0.02), axes=(axis, "embed"))
        if bias:
            self.param("bias", (out_features,), zeros_init(), axes=(None,))

    def __call__(self, params, x):
        dt = self.compute_dtype()
        x = x.astype(dt)
        w = params["weight"].astype(dt)
        if _ring_overlap_active(self.overlap):
            from hetu_tpu.parallel.overlap import (
                maybe_record_row_fallback, ring_matmul_rs,
                ring_row_applicable,
            )
            ctx = current_act_sharding()
            if ring_row_applicable(ctx, x.shape, w.shape):
                # the ring IS the reduce(-scatter): no act_constrain
                # needed to trigger the collective, the output already
                # carries the "tokens" layout
                y = ring_matmul_rs(x, w, ctx=ctx)
                if self.use_bias:
                    y = y + params["bias"].astype(dt)
                return y
            maybe_record_row_fallback(ctx, x.shape, w.shape)
        y = jnp.matmul(x, w)
        y = act_constrain(y, "tokens")
        if self.use_bias:
            y = y + params["bias"].astype(dt)
        return y


class VocabParallelEmbedding(Module):
    """Embedding with the vocabulary dim sharded over tp.

    Reference: ``HtMultiVocabParallelEmbedding`` (`parallel_multi_ds.py:268`)
    — masked local lookup + allreduce. When an ActivationSharding context with
    tp>1 is active the lookup runs under ``shard_map`` (local masked take +
    ``psum``), so no device materializes the full table; otherwise a plain
    take.
    """

    def __init__(self, num_embeddings: int, features: int, init=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.features = features
        self.param("weight", (num_embeddings, features),
                   init or normal_init(0.02), axes=("vocab", "embed"))

    def __call__(self, params, ids):
        w = params["weight"]
        ctx = current_act_sharding()
        if ctx is not None and isinstance(ctx.tp, str) \
                and ctx.mesh.shape[ctx.tp] > 1 \
                and self.num_embeddings % ctx.mesh.shape[ctx.tp] == 0:
            out = _vocab_parallel_lookup(w, ids, ctx)
        else:
            out = embed_ops.embedding_lookup(w, ids)
        return act_constrain(out.astype(self.compute_dtype()), "tokens")


def _vocab_parallel_lookup(weight, ids, ctx):
    tp = ctx.tp
    v_local = weight.shape[0] // ctx.mesh.shape[tp]
    # decide the table-grad formulation from the GLOBAL vocab (inside
    # shard_map w is the V/tp local shard, which would trip the measured
    # winner's vocab-distance guard at high tp even though per-shard
    # token count — the quantity the probe measured — is unchanged)
    bwd = embed_ops.preferred_embedding_bwd(weight.shape[0])

    @functools.partial(
        shard_map, mesh=ctx.mesh,
        in_specs=(P(tp, None), P(ctx.batch, ctx.seq)),
        out_specs=P(ctx.batch, ctx.seq, None), check_vma=False)
    def lookup(w, ids):
        start = jax.lax.axis_index(tp) * v_local
        local = ids - start
        ok = (local >= 0) & (local < v_local)
        # masked local take; the measured onehot-matmul formulation can
        # replace the scatter-add table grad on TPU
        emb = embed_ops.embedding_lookup(
            w, jnp.clip(local, 0, v_local - 1), bwd=bwd)
        emb = jnp.where(ok[..., None], emb, jnp.zeros([], emb.dtype))
        return jax.lax.psum(emb, tp)

    return lookup(weight, ids)


def lora_apply(lora, name, x, y):
    """Batched-gather LoRA (the Punica / S-LoRA "BGMV" shape): add the
    per-token adapter delta for projection ``name`` to its base output
    ``y``.

    ``lora`` is ``{"ids": (b, s) int32 arena pages, "pages": {name:
    {"A": (P, in, r), "B": (P, r, out)}}}`` — ONE layer's slice of the
    device-resident adapter arena (the stacked (L, P, ...) tree rides
    ``StackedBlocks.decode``'s scan as xs; the page ids close over the
    scan body).  Each token gathers its page's A/B slice and two
    batched einsums produce the delta; scaling is folded into B at
    registry load time so no per-adapter scalars ride the step.

    Page 0 is the base model's zero page: those tokens take ``y`` back
    through a masked select rather than ``y + 0.0``, so base-only
    tokens stay BITWISE identical to a build without the lane (``-0.0
    + 0.0`` would flip sign bits).  ``lora`` None/empty or a projection
    the arena does not carry returns ``y`` untouched — no extra ops.
    """
    if not lora or name not in lora["pages"]:
        return y
    ab = lora["pages"][name]
    ids = lora["ids"]                               # (b, s) pages
    a = ab["A"][ids]                                # (b, s, in, r)
    bm = ab["B"][ids]                               # (b, s, r, out)
    t = jnp.einsum("bsi,bsir->bsr", x.astype(a.dtype), a)
    d = jnp.einsum("bsr,bsro->bso", t, bm)
    return jnp.where((ids != 0)[..., None], y + d.astype(y.dtype), y)


class ParallelMLP(Module):
    """Transformer MLP: column-parallel up, row-parallel down.

    ``gated=True`` gives the Llama SwiGLU form (reference MLP
    `llama_model.py:292`, fused kernel ``impl/kernel/SwiGLU.cu``); otherwise
    GPT-2 GELU.
    """

    def __init__(self, features: int, hidden: int, *, bias: bool = True,
                 gated: bool = False, activation=None):
        super().__init__()
        self.gated = gated
        self.activation = activation or (act_ops.swiglu if gated
                                         else jax.nn.gelu)
        if gated:
            # separate gate/up projections: both column-sharded over tp, so
            # the elementwise gate never crosses a shard boundary (a fused
            # (E, 2H) kernel + split would force a per-layer reshard)
            self.gate_proj = ColumnParallelLinear(
                features, hidden, bias=bias, axis="mlp", out_kind="hidden")
            self.up_proj = ColumnParallelLinear(
                features, hidden, bias=bias, axis="mlp", out_kind="hidden")
        else:
            self.fc_in = ColumnParallelLinear(
                features, hidden, bias=bias, axis="mlp", out_kind="hidden")
        self.fc_out = RowParallelLinear(hidden, features, bias=bias,
                                        axis="mlp")

    def __call__(self, params, x, *, w8a8=None, w8a8_wq=None,
                 lora=None):
        """``w8a8`` (None | traced bool) selects the quantized-COMPUTE
        lane per call: activations quantize per token, weights per
        output channel, and both matmuls contract in int8 with one
        fused rescale (``ops.quantization.int8_w8a8_matmul``). A traced
        flag rides ``lax.cond`` so the serving engine can A/B the lane
        PER LAYER as data (``StackedBlocks.decode(w8a8_mask=)``);
        ``None`` (the default, and every training path) is exactly the
        historical fp lane — no cond, bit-for-bit unchanged.

        ``w8a8_wq`` (a :meth:`prequantize` tree for THIS layer) skips
        the per-call weight quantization: only the per-token activation
        quant remains on the hot path — the serving engine quantizes
        once at construction / weight swap.

        ``lora`` (a :func:`lora_apply` dict for THIS layer) adds the
        batched multi-adapter BGMV delta to every targeted projection;
        None is exactly the historical lane."""
        if w8a8 is None:
            return self._fp_lane(params, x, lora=lora)
        if w8a8_wq is None and lora is None:
            return jax.lax.cond(w8a8, self._w8a8_lane, self._fp_lane,
                                params, x)
        return jax.lax.cond(
            w8a8,
            lambda p, v: self._w8a8_lane(p, v, wq=w8a8_wq, lora=lora),
            lambda p, v: self._fp_lane(p, v, lora=lora),
            params, x)

    def prequantize(self, params, *, stacked: bool = False):
        """Quantize this MLP's weight matrices ONCE into the W8A8
        lane's ``{name: {"q": int8, "scale": fp32}}`` tree (per-output-
        channel scales over the contraction axis — ``axis=1`` for a
        ``StackedBlocks`` (L, in, out) param tree, ``axis=0`` for a
        single layer). Feed the result back via ``w8a8_wq=`` so the
        decode lane stops paying the per-step quantize of weights that
        never change between steps."""
        from hetu_tpu.ops.quantization import quantize_int8
        axis = 1 if stacked else 0
        names = (["gate_proj", "up_proj"] if self.gated
                 else ["fc_in"]) + ["fc_out"]
        return {
            name: dict(zip(("q", "scale"), quantize_int8(
                params[name]["weight"], axis=axis)))
            for name in names
        }

    def _fp_lane(self, params, x, lora=None):
        if self.gated:
            g = lora_apply(lora, "gate_proj", x,
                           self.gate_proj(params["gate_proj"], x))
            u = lora_apply(lora, "up_proj", x,
                           self.up_proj(params["up_proj"], x))
            h = self.activation(g, u)
        else:
            h = self.activation(lora_apply(
                lora, "fc_in", x, self.fc_in(params["fc_in"], x)))
        h = act_constrain(h, "hidden")
        return lora_apply(lora, "fc_out", h,
                          self.fc_out(params["fc_out"], h))

    def _w8a8_lane(self, params, x, wq=None, lora=None):
        """Both FFN matmuls in int8 (W8A8). Biases and the activation
        stay fp; the canonical activation cut points keep their
        ``act_constrain`` layouts so GSPMD shards the lane like the fp
        one. Weights quantize at trace time from the live fp params —
        or, when ``wq`` carries a :meth:`prequantize` tree, stream
        pre-quantized int8 weights straight into the contraction
        (halving the lane's weight reads: no fp load + int8 re-store
        per step)."""
        from hetu_tpu.ops.quantization import (
            int8_w8a8_matmul, int8_w8a8_matmul_prequant,
        )
        dt = self.compute_dtype()
        x = x.astype(dt)

        def mm(v, p, name):
            if wq is not None:
                return int8_w8a8_matmul_prequant(
                    v, wq[name]["q"], wq[name]["scale"], dtype=dt)
            return int8_w8a8_matmul(v, p["weight"].astype(dt), dtype=dt)

        def lin(mod, p, name):
            y = mm(x, p, name)
            if mod.use_bias:
                y = y + p["bias"].astype(dt)
            return act_constrain(lora_apply(lora, name, x, y), "hidden")

        if self.gated:
            h = self.activation(
                lin(self.gate_proj, params["gate_proj"], "gate_proj"),
                lin(self.up_proj, params["up_proj"], "up_proj"))
        else:
            h = self.activation(lin(self.fc_in, params["fc_in"], "fc_in"))
        h = act_constrain(h, "hidden")
        y = mm(h, params["fc_out"], "fc_out")
        y = act_constrain(y, "tokens")
        if self.fc_out.use_bias:
            y = y + params["fc_out"]["bias"].astype(dt)
        return lora_apply(lora, "fc_out", h, y)


def _pop_path(tree: dict, path: tuple):
    """``tree`` without the leaf at ``path`` (the dicts on the way
    copied, nothing else), and the leaf."""
    if len(path) == 1:
        rest = dict(tree)
        return rest, rest.pop(path[0])
    sub, leaf = _pop_path(tree[path[0]], path[1:])
    return {**tree, path[0]: sub}, leaf


def _set_path(tree: dict, path: tuple, leaf):
    if len(path) == 1:
        return {**tree, path[0]: leaf}
    return {**tree, path[0]: _set_path(tree.get(path[0], {}), path[1:],
                                       leaf)}


def _at_layer(buf, layer):
    """One layer of a stacked cache leaf, ``buf[layer]`` — a COPY of
    that layer where ``layer`` is traced: for the dense caches and the
    gather reference, never for the paged kernel's arena."""
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def _scatter_layer_rows(buf, layer, rows, new):
    """Write ``new``'s rows into the stacked paged leaf ``(layers,
    n_blocks, block_size, H)`` at ``[layer, rows]``, where a row is
    ``block * block_size + offset`` and one past the arena drops. The
    arena merges (hkv, d) into one minor dim (serving/kv_pool.py), so
    rows take the buffer's. One scatter into the donated buffer: the
    leaf is neither sliced nor rebuilt."""
    L, n_blk, blk = buf.shape[:3]
    flat = buf.reshape((L, n_blk * blk) + buf.shape[3:])
    flat = flat.at[layer, rows].set(
        new.reshape((-1,) + buf.shape[3:]).astype(buf.dtype), mode="drop")
    return flat.reshape(buf.shape)


def _scatter_layer_scales(buf, layer, rows, new):
    """:func:`_scatter_layer_rows` for the int8 arena's scale leaves
    ``(layers, n_blocks, block_size, hkv)``; returns the stacked leaf
    and this layer's ``(n_blocks, block_size, hkv)``, which is what the
    attention then reads. Their minor dim is under a lane tile, so the
    TPU stores the stack blocks-minor: the scatter and the paged kernel
    each want a re-tiled copy, and taking the layer out first re-tiles
    a layer (a tenth of a K leaf's layer) instead of the whole stack
    per layer. The K/V leaves never take this route."""
    one = _at_layer(buf, layer)
    one = _scatter_layer_rows(one[None], 0, rows, new)[0]
    return jax.lax.dynamic_update_index_in_dim(buf, one, layer, 0), one


class LayerKV(NamedTuple):
    """What the attention gets as ``kv_cache`` from the layer scan
    (:meth:`StackedBlocks.decode`): the STACKED cache leaves — every
    layer's, ``(layers, ...)`` — and which layer this call is. The
    attention writes its rows in place at ``[layer, ...]`` and hands
    the same stacked leaves back, so the scan carries ONE buffer per
    leaf and never slices a layer out or stacks one back in (for the
    paged arena each of those is a copy of a layer's whole leaf)."""
    leaves: tuple
    layer: jax.Array


def kv_leaves(attn, lead: tuple, dtype, *, paged: bool = False,
              sharding=None) -> tuple:
    """Zeros for the cache leaves ``attn`` declares
    (``kv_leaf_shapes()``: K and V rows per kv head, or ONE latent row)
    under the leading dims ``lead``: ``(layers, batch, max_len)`` of
    the dense cache, ``(layers, n_blocks, block_size)`` of the
    ``paged`` arena, whose trailing ``(hkv, d)`` are merged into ONE
    minor dim. The TPU tiles an array's last two dims (8, 128): a
    (12, 64) pair would either pad 2.7x or make XLA pick a
    blocks-minor layout the paged kernel cannot take a page from.
    Allocated in the stored shape and, where ``sharding`` is given, in
    place on it — a reshape or a ``device_put`` of finished zeros would
    hold the arena twice on the device.

    ``dtype=jnp.int8`` builds the QUANTIZED cache — (k int8, k scales,
    v int8, v scales) with per-(position, head) fp32 scales — the
    reference's inference-side weight/state compression applied to the
    decode bottleneck (the per-step cache read is pure HBM bandwidth;
    int8 halves it vs bf16 and quarters it vs fp32)."""
    shapes = [(tuple(t), dtype) for t in attn.kv_leaf_shapes()]
    if dtype == jnp.int8:
        if len(shapes) != 2:
            raise LatentKVNotSupported(
                "the int8 cache keeps one scale per (position, kv head) "
                "of a K and a V leaf; this model's attention caches "
                f"{len(shapes)} leaf of {shapes[0][0]} a token")
        shapes = [x for t, _ in shapes
                  for x in ((t, jnp.int8), (t[:-1] + (1,), jnp.float32))]
    return tuple(
        jnp.zeros(lead + ((math.prod(t),) if paged else t), dt,
                  device=sharding)
        for t, dt in shapes)


class ParallelAttention(Module):
    """Multi-head attention with GQA, RoPE and flash-kernel dispatch, heads
    sharded over tp.

    Reference: ``HtMultiQKVColumnParallelLinear`` (`parallel_multi_ds.py:504`)
    + ``ParallelAttentionOp`` cp=1 path (`hetu/graph/ops/ParallelAttention.h:711`).
    Ring-attention CP wraps this at the op level
    (``hetu_tpu.parallel.ring_attention``) — this module stays cp-agnostic
    and only sees its local sequence chunk (positions/segment_ids make the
    causal mask correct for chunks).
    """

    #: what the serving engine asks the attention that speaks for its
    #: arena: a cache of ONE latent row a token (not K and V per kv
    #: head)? is a pack's history read in tiles of a request's run (the
    #: engine builds the tile map; ``"every_run"``: of the runs without
    #: history too)?
    latent, history_tiles = False, True
    #: the leaves :meth:`init_leaves` builds (the int8 arena: twice)
    cache_leaves = 2

    def __init__(self, embed_dim: int, num_heads: int, *,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 bias: bool = True, causal: bool = True,
                 use_rope: bool = False, rope_theta: float = 10000.0,
                 rope_interleaved: bool = False,
                 min_window: Optional[int] = None,
                 max_positions: int = 4096, qk_norm: bool = False,
                 qk_gain: float = 1.0, norm_eps: float = 1e-6,
                 attn_block: int = 1, rotary_dim: Optional[int] = None,
                 out_gate: bool = False, zero_centered: bool = False,
                 init=None):
        super().__init__()
        # RMSNorm with a learned gain over each head's numbers, on q
        # and on k, before RoPE (the gains are the heads' own, drawn at
        # ``qk_gain``: 1 where a checkpoint's are learned).
        # ``zero_centered``: the parameter is the gain's distance from
        # one, the gain ``1 + w``
        self.qk_norm, self.norm_eps = bool(qk_norm), norm_eps
        self._gain_at = 1.0 if zero_centered else 0.0
        if qk_norm:
            from hetu_tpu.nn.module import constant_init
            hd = head_dim or embed_dim // num_heads
            self.param("q_gain", (hd,),
                       constant_init(qk_gain - self._gain_at))
            self.param("k_gain", (hd,),
                       constant_init(qk_gain - self._gain_at))
        #: a sigmoid gate on the attention's output, a number a head
        #: and channel, from the SAME projection as the queries:
        #: ``q_proj`` is twice as wide, a head's ``head_dim`` of q then
        #: its ``head_dim`` of gate
        self.out_gate = bool(out_gate)
        #: the BLOCK bound of a block-diffusion model (1: causal): a
        #: query sees its own block of ``attn_block`` positions whole
        #: and the blocks before it (``ops.attention.block_bound``)
        self.attn_block = int(attn_block)
        if self.attn_block != 1 and (min_window is not None or not causal):
            raise ValueError("attn_block goes with causal attention "
                             "and no window")
        self.rope_interleaved = rope_interleaved
        #: the smallest ``window=`` any layer is called with (static;
        #: the packed prefill lane checks its chunk against it)
        self.min_window = min_window
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        if num_heads % self.num_kv_heads != 0:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        self.head_dim = head_dim or embed_dim // num_heads
        self.causal = causal
        self.use_rope = use_rope
        init = init or normal_init(0.02)
        self.q_proj = ColumnParallelLinear(
            embed_dim, num_heads * self.head_dim * (2 if out_gate else 1),
            bias=bias, init=init, axis="heads", out_kind="hidden")
        self.k_proj = ColumnParallelLinear(
            embed_dim, self.num_kv_heads * self.head_dim, bias=bias,
            init=init, axis="kv_heads", out_kind="hidden")
        self.v_proj = ColumnParallelLinear(
            embed_dim, self.num_kv_heads * self.head_dim, bias=bias,
            init=init, axis="kv_heads", out_kind="hidden")
        self.out_proj = RowParallelLinear(
            num_heads * self.head_dim, embed_dim, bias=bias, init=init,
            axis="heads")
        #: numbers of a head that rotate (``None``: all): the FIRST
        #: ``rotary_dim``, as one head of that size; the rest pass
        self.rotary_dim = rotary_dim
        if rotary_dim is not None and not (
                use_rope and 0 < rotary_dim <= self.head_dim
                and rotary_dim % 2 == 0):
            raise ValueError(f"rotary_dim {rotary_dim} of a head of "
                             f"{self.head_dim} (use_rope={use_rope})")
        if use_rope:
            self._rope = rope_frequencies(rotary_dim or self.head_dim,
                                          max_positions, theta=rope_theta)
        else:
            self._rope = None

    def _heads_q(self, y, lead: tuple):
        """``q_proj``'s result -> ``(q lead + (heads, head_dim), gate
        lead + (heads * head_dim,) or None)``."""
        if not self.out_gate:
            return y.reshape(lead + (self.num_heads, self.head_dim)), None
        y = y.reshape(lead + (self.num_heads, 2, self.head_dim))
        return y[..., 0, :], y[..., 1, :].reshape(lead + (-1,))

    def _gated(self, out, gate):
        """The attention's output ``lead + (heads * head_dim,)`` under
        its gate (float32 inside), where the module has one."""
        if gate is None:
            return out
        return (out.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(out.dtype)

    @property
    def _bound(self) -> dict:
        """``block=`` for the attention calls (nothing at 1: the calls
        are the causal ones, operand for operand)."""
        return {} if self.attn_block == 1 else {"block": self.attn_block}

    def kv_leaf_shapes(self) -> tuple:
        """The cache spec: the trailing dims of each cache leaf, a
        token and layer — what ``generation.init_kv_caches`` builds the
        arena's leaves from (K and V rows per kv head here; an int8
        arena adds one float32 scale a head to each)."""
        return ((self.num_kv_heads, self.head_dim),) * 2

    def row_bytes(self, itemsize: int) -> dict:
        """Bytes a token holds in one layer, as ``stored`` and as
        ``needed`` (alike here: nothing is padded; the int8 arena,
        ``itemsize`` 1, keeps a float32 scale a kv head beside K and V
        each and needs all it stores)."""
        row = 2 * self.num_kv_heads * (
            self.head_dim * itemsize + (4 if itemsize == 1 else 0))
        return {"stored": row, "needed": row}

    def init_leaves(self, layers: int, n_blocks: int, block_size: int,
                    dtype, sharding=None) -> tuple:
        return kv_leaves(self, (layers, n_blocks, block_size), dtype,
                         paged=True, sharding=sharding)

    def _rotate(self, q, k, positions, rope_on, params=None):
        """RoPE on q and k where the module has it; ``rope_on`` (a
        traced bool from a layer scan whose layers differ by it, else
        ``None``) says whether THIS layer rotates. Where the module
        norms q and k (``qk_norm``; ``params`` hold the gains), before
        it does."""
        if self.qk_norm:
            q = _gain(params, "q_gain", q, self.norm_eps, q.dtype,
                      self._gain_at)
            k = _gain(params, "k_gain", k, self.norm_eps, k.dtype,
                      self._gain_at)
        if self._rope is None:
            return q, k
        cos, sin = self._rope
        rd = self.rotary_dim

        def rotate(x):
            if rd is None or rd == self.head_dim:
                return apply_rotary(x, cos, sin, positions=positions,
                                    interleaved=self.rope_interleaved)
            return jnp.concatenate([
                apply_rotary(x[..., :rd], cos, sin, positions=positions,
                             interleaved=self.rope_interleaved),
                x[..., rd:]], axis=-1)
        qr, kr = rotate(q), rotate(k)
        if rope_on is None:
            return qr, kr
        return jnp.where(rope_on, qr, q), jnp.where(rope_on, kr, k)

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, dropout_rate: float = 0.0, dropout_key=None,
                 return_kv: bool = False, lora=None, window=None,
                 rope_on=None):
        """``window`` / ``rope_on`` (``None`` on every model whose
        layers are alike): this layer's attention window (query ``p``
        sees keys ``p - window < j <= p``; an int32 scalar, traced in a
        layer scan) and whether it rotates q and k. The whole-sequence
        forward honours a window through the reference attention path
        (the flash kernel has none).

        ``return_kv=True`` (train path only) additionally returns the
        rotary-applied per-head ``(k, v)`` of this call — the exact
        values the decode path would have written to a KV cache — as
        ``(out, (k, v))``. The serving CP-prefill lane uses this to run
        a long prompt through the TRAINING forward (ring/ulysses over
        the cp axis) and scatter the resulting KV into the paged arena
        (``StackedBlocks.prefill``)."""
        if kv_cache is not None:
            if return_kv:
                raise ValueError(
                    "return_kv applies to the training forward only "
                    "(decode already threads its cache)")
            return self._decode(params, x, kv_cache, positions=positions,
                                slot_mask=slot_mask,
                                block_tables=block_tables,
                                row_mask=row_mask,
                                attn_kernel=attn_kernel, pack=pack,
                                lora=lora, window=window,
                                rope_on=rope_on)
        b, s, _ = x.shape
        q, gate = self._heads_q(self.q_proj(params["q_proj"], x), (b, s))
        k = self.k_proj(params["k_proj"], x).reshape(
            b, s, self.num_kv_heads, self.head_dim)
        v = self.v_proj(params["v_proj"], x).reshape(
            b, s, self.num_kv_heads, self.head_dim)
        q, k = self._rotate(q, k, positions, rope_on, params)
        q = act_constrain(q, "heads")
        k = act_constrain(k, "heads")
        v = act_constrain(v, "heads")
        ctx = current_act_sharding()
        mctx = current_manual_axes()
        manual_cp = (ctx is None and mctx is not None
                     and "cp" in mctx.axes and mctx.mesh.shape["cp"] > 1)
        gspmd_cp = (ctx is not None and isinstance(ctx.seq, str)
                    and ctx.mesh.shape[ctx.seq] > 1)
        if window is not None or self.attn_block != 1:
            if manual_cp or gspmd_cp:
                raise NotImplementedError(
                    "a windowed or block-causal layer under context "
                    "parallelism")
            out = attention_reference(
                q, k, v, causal=self.causal, segment_ids=segment_ids,
                window=window, dropout_rate=dropout_rate,
                dropout_key=dropout_key, block=self.attn_block)
        elif manual_cp:
            # inside a manual region (pipeline executor) with cp bound:
            # run the cp attention core directly on the bound axis —
            # x/q/k/v here are the per-device local seq chunks
            if mctx.cp_impl == "ulysses":
                from hetu_tpu.parallel.ulysses import \
                    ulysses_attention_manual
                out = ulysses_attention_manual(
                    q, k, v, axis_name="cp", cp=mctx.mesh.shape["cp"],
                    tp=mctx.mesh.shape.get("tp", 1), causal=self.causal,
                    segment_ids=segment_ids, impl=attn_impl,
                    dropout_rate=dropout_rate, dropout_key=dropout_key)
            else:
                from hetu_tpu.parallel.ring_attention import \
                    ring_attention_manual
                out = ring_attention_manual(
                    q, k, v, axis_name="cp", cp=mctx.mesh.shape["cp"],
                    causal=self.causal, segment_ids=segment_ids,
                    impl=attn_impl, layout=mctx.cp_layout,
                    dropout_rate=dropout_rate, dropout_key=dropout_key)
        elif gspmd_cp:
            # context parallelism: seq dim is sharded — KV ring
            # (reference: ParallelAttentionOp → AttnCommRing) or the
            # beyond-reference Ulysses all_to_all head scatter
            if getattr(ctx, "cp_impl", "ring") == "ulysses":
                from hetu_tpu.parallel.ulysses import ulysses_attention
                out = ulysses_attention(q, k, v, ctx=ctx,
                                        causal=self.causal,
                                        segment_ids=segment_ids,
                                        impl=attn_impl,
                                        dropout_rate=dropout_rate,
                                        dropout_key=dropout_key)
            else:
                from hetu_tpu.parallel.ring_attention import ring_attention
                out = ring_attention(q, k, v, ctx=ctx, causal=self.causal,
                                     segment_ids=segment_ids,
                                     impl=attn_impl,
                                     dropout_rate=dropout_rate,
                                     dropout_key=dropout_key)
        else:
            out = flash_attention(q, k, v, causal=self.causal,
                                  segment_ids=segment_ids, impl=attn_impl,
                                  dropout_rate=dropout_rate,
                                  dropout_key=dropout_key)
        out = act_constrain(out, "heads")
        out = self._gated(
            out.reshape(b, s, self.num_heads * self.head_dim), gate)
        out = self.out_proj(params["out_proj"], out)
        if return_kv:
            return out, (k, v)
        return out

    def _decode(self, params, x, kv_cache, *, positions=None,
                slot_mask=None, block_tables=None, row_mask=None,
                attn_kernel: str = "reference", pack=None, lora=None,
                window=None, rope_on=None):
        """Incremental decoding with a KV cache.

        ``kv_cache``: a :class:`LayerKV` — the STACKED leaves of every
        layer and this call's layer index. The leaves are (k_buf,
        v_buf) of shape (layers, b, max_len, hkv, d), or the QUANTIZED
        4-tuple (k int8, k scales, v int8, v scales) with (layers, b,
        max_len, hkv, 1) fp32 scales (``generation.init_kv_caches``
        with dtype=jnp.int8) — new rows quantize on write, the read
        dequant fuses into the attention einsum. This layer's rows are
        written at ``[layer]`` and the stacked leaves come back as the
        new cache. The write ``index`` arrives via ``positions[:,
        0]``-style absolute positions (all rows share the index —
        batched decode). Replaces the reference's dynamic-concat KV
        append op (inference path of ``graph/ops``: dynamic concat).

        ``slot_mask`` switches to PER-ROW decode (the serving engine's
        slot-pooled path): every batch row writes at its own
        ``positions[:, 0]`` index and the causal mask uses per-row
        offsets, so requests at different depths decode in one batched
        call. Rows with ``slot_mask=False`` (free / prefilling slots)
        leave their cache rows untouched (their compute is discarded by
        the caller; the paged kernel spends none on them and returns
        zeros).

        ``block_tables`` (b, W) switches the cache to the PAGED layout:
        leaves are ``(layers, n_blocks, block_size, hkv*d)`` arenas
        shared by every row, and row ``r``'s position ``p`` lives at
        arena row ``block_tables[r, p // bs] * bs + p % bs`` of this
        layer. Writes become scatters at ``[layer, row]`` into the
        stacked leaf, in place (rows with ``slot_mask=False`` scatter
        out of bounds and are dropped), reads go through the table at
        ``(layer, page)``. Requires ``slot_mask`` (per-row positions
        are the only meaningful paged mode).

        ``row_mask`` (b, s) bool refines ``slot_mask`` WITHIN a row's
        ``s`` positions: only masked-true cells write their KV (the
        rest scatter out of bounds and drop). The speculative-decoding
        verify lane needs this — a slot verifying fewer than the step's
        max draft depth must not write the unused trailing rows, whose
        positions could land beyond the blocks its table owns (a
        clamped scatter there would corrupt a live block). Paged mode
        only.

        ``attn_kernel`` ("reference" | "paged", paged mode only)
        selects HOW the attention reads the arena: "reference" is the
        XLA-gather path (materializes each row's full table view —
        :func:`~hetu_tpu.ops.attention.gather_block_rows`, the
        CPU path), "paged" streams KV tiles through the
        block tables inside the Pallas kernel
        (:func:`~hetu_tpu.ops.paged_pallas.paged_attention_pallas`,
        which takes the stacked leaf and the layer — no materialized
        gather, no slice of the arena, cost ∝ live context). Resolve requests
        with :func:`~hetu_tpu.ops.attention.resolve_decode_kernel`.

        ``pack`` switches to the PACKED-PREFILL flash mode
        (:meth:`_decode_packed`): ``x`` is one ``(1, C, embed)`` row of
        C pack tokens from many requests, with per-token
        ``block_tables`` (C, W) / ``positions`` (1, C) (the KV writes'
        and the gather path's) and pack dict keys ``segment_ids``
        (1, C), ``hist`` (C,), ``valid`` (C,), ``impl`` and, for
        ``attn_kernel="paged"``, ``tiles``: ``map``, the pack's tile
        map (``ops.paged_pallas.pack_history_tiles``), ``tables``
        (G, W), each tile's request's block table, and ``rows``, the
        static tile size."""
        if pack is not None:
            return self._decode_packed(params, x, kv_cache,
                                       positions=positions,
                                       block_tables=block_tables,
                                       pack=pack,
                                       attn_kernel=attn_kernel,
                                       lora=lora, window=window,
                                       rope_on=rope_on)
        leaves, layer = kv_cache
        quant = len(leaves) == 4
        b, s, _ = x.shape
        per_row = slot_mask is not None
        paged = block_tables is not None
        if paged and not per_row:
            raise ValueError("block_tables requires slot_mask "
                             "(per-row paged decode)")
        if row_mask is not None and not paged:
            raise ValueError("row_mask requires block_tables (the "
                             "dense cache writes contiguous rows)")
        if per_row:
            index = positions[:, 0]                     # (b,) per-slot
        else:
            index = positions[0, 0] if positions is not None else 0
        q, gate = self._heads_q(
            lora_apply(lora, "q_proj", x,
                       self.q_proj(params["q_proj"], x)), (b, s))
        k = lora_apply(lora, "k_proj", x,
                       self.k_proj(params["k_proj"], x)).reshape(
            b, s, self.num_kv_heads, self.head_dim)
        v = lora_apply(lora, "v_proj", x,
                       self.v_proj(params["v_proj"], x)).reshape(
            b, s, self.num_kv_heads, self.head_dim)
        if self._rope is not None or self.qk_norm:
            q, k = self._rotate(
                q, k, positions if positions is not None
                else jnp.arange(s)[None, :], rope_on, params)

        if paged:
            n_blk, blk = leaves[0].shape[1], leaves[0].shape[2]
            pos_rows = index[:, None] + jnp.arange(s)[None, :]  # (b, s)
            blk_ids = jnp.take_along_axis(block_tables,
                                          pos_rows // blk, axis=1)
            rows = blk_ids * blk + pos_rows % blk
            # masked-off rows scatter out of bounds → dropped (the
            # paged analogue of the jnp.where keep-mask below)
            keep = slot_mask[:, None]
            if row_mask is not None:
                keep = keep & row_mask
            rows = jnp.where(keep, rows, n_blk * blk).reshape(-1)

        def upd(buf, new):
            if paged:
                return _scatter_layer_rows(buf, layer, rows, new)
            new = new.astype(buf.dtype)
            if per_row:
                # per-slot scatter: row r writes its s new entries at
                # index[r]; inactive slots select their old rows back
                old = _at_layer(buf, layer)
                written = jax.vmap(
                    lambda bb, nn, ii: jax.lax.dynamic_update_slice_in_dim(
                        bb, nn, ii, axis=0))(old, new, index)
                keep = slot_mask.reshape((b,) + (1,) * (old.ndim - 1))
                return jax.lax.dynamic_update_index_in_dim(
                    buf, jnp.where(keep, written, old), layer, 0)
            at = [jnp.zeros((), jnp.int32)] * buf.ndim
            at[0], at[2] = layer, jnp.asarray(index, jnp.int32)
            return jax.lax.dynamic_update_slice(buf, new[None], at)

        if quant:
            # int8 KV cache: decode is HBM-bound on the cache read, so
            # 1 byte/elem halves the bandwidth vs bf16 (and 4x vs fp32);
            # XLA fuses the dequant into the attention einsum's operand
            # stream (compiler-verified: workloads/quant_bench.py --aot).
            # Per-(position, head) symmetric scales over head_dim; zero
            # scales on never-written slots dequantize to exact 0, like
            # the fp cache's zeros.
            from hetu_tpu.ops.quantization import (dequantize_int8,
                                                   quantize_int8)
            kq_b, ks_b, vq_b, vs_b = leaves
            with jax.named_scope("hetu.kv_arena"):
                knew_q, knew_s = quantize_int8(k, axis=-1)
                vnew_q, vnew_s = quantize_int8(v, axis=-1)
                kq_b, vq_b = upd(kq_b, knew_q), upd(vq_b, vnew_q)
                if paged:
                    ks_b, ks_l = _scatter_layer_scales(ks_b, layer, rows,
                                                       knew_s)
                    vs_b, vs_l = _scatter_layer_scales(vs_b, layer, rows,
                                                       vnew_s)
                else:
                    ks_b, vs_b = upd(ks_b, knew_s), upd(vs_b, vnew_s)
                    ks_l, vs_l = _at_layer(ks_b, layer), \
                        _at_layer(vs_b, layer)
            new_cache = (kq_b, ks_b, vq_b, vs_b)
            arena = dict(k_scale=ks_l, v_scale=vs_l)   # this layer's
            k_buf, v_buf = kq_b, vq_b
        else:
            k_buf, v_buf = leaves
            with jax.named_scope("hetu.kv_arena"):
                k_buf, v_buf = upd(k_buf, k), upd(v_buf, v)
            new_cache = (k_buf, v_buf)
            arena = {}

        if paged and attn_kernel == "paged" and self.causal:
            # the Pallas kernel streams arena tiles through the block
            # tables at (layer, page) of the stacked leaves — no
            # materialized gather, no slice of the arena, a grid of
            # the live (slot, chunk) pairs alone (rows of a slot that
            # slot_mask turns off are zeros: no step, no read), int8
            # pages dequantized per tile in VMEM; the
            # _auto wrapper shard_maps the call over a tp-sharded plan's
            # head axis (Mosaic kernels cannot be GSPMD-auto-partitioned)
            from hetu_tpu.ops.paged_pallas import paged_attention_auto
            out = paged_attention_auto(q, k_buf, v_buf, block_tables,
                                       index, layer=layer, window=window,
                                       live=slot_mask, **self._bound,
                                       **arena)
        elif paged:
            if attn_kernel == "paged":
                from hetu_tpu.ops.attention import record_kernel_fallback
                record_kernel_fallback(
                    "decode_non_causal",
                    "the paged kernel implements causal decode only")
            # the XLA-gather twin, on this layer's leaves (int8 arenas
            # gather quantized rows + scales — 1/4 the bytes — and
            # dequantize after); causal offsets mask both the future and
            # never-written slots
            from hetu_tpu.ops.paged_pallas import \
                paged_attention_reference
            out = paged_attention_reference(
                q, _at_layer(k_buf, layer), _at_layer(v_buf, layer),
                block_tables, index, causal=self.causal, window=window,
                **self._bound, **arena)
        else:
            k_buf, v_buf = _at_layer(k_buf, layer), _at_layer(v_buf, layer)
            if quant:
                k_buf = dequantize_int8(k_buf, ks_l, q.dtype)
                v_buf = dequantize_int8(v_buf, vs_l, q.dtype)
            out = attention_reference(
                q, k_buf, v_buf, causal=self.causal,
                q_offset=index, kv_offset=0, window=window,
                **self._bound)
        out = self._gated(
            out.reshape(b, s, self.num_heads * self.head_dim), gate)
        return lora_apply(lora, "out_proj", out,
                          self.out_proj(params["out_proj"], out)), \
            new_cache

    def _decode_packed(self, params, x, kv_cache, *, positions,
                       block_tables, pack, attn_kernel, lora=None,
                       window=None, rope_on=None):
        """Packed-prefill FLASH mode: the serving engine's prefill pack
        as ONE ``(1, C, embed)`` row instead of C one-token batch rows.

        The C tokens belong to many requests (``pack["segment_ids"]``,
        -1 on pad lanes); each token's attention decomposes into two
        DISJOINT parts that LSE-combine exactly
        (``ops.paged_pallas.combine_attention_lse``):

        - **intra-pack**: flash attention over the pack itself with
          segment isolation + causal masking — within one request's
          contiguous run positions ascend with pack index, so
          index-causality IS position-causality, and segment ids stop
          any cross-request (or cross-document) leakage;
        - **arena history**: each token attends its request's
          already-resident KV — earlier chunks of a multi-chunk
          prompt, prefix-cache hits — through its block table, masked
          to positions ``< pack["hist"][t]`` (the token's chunk-start
          offset, so the rows this very pack just scattered are
          excluded: the intra part owns them). On the kernel path the
          tokens of one request's run share ONE pass over its pages per
          tile of the chunk (``pack["tiles"]``:
          ``ops.paged_pallas.paged_history_attention``); the gather
          path keeps one row per token.

        KV writes stay per-token scatters through the tables (pads drop
        out of bounds) at ``[layer, row]`` of the stacked leaves
        (``kv_cache`` is a :class:`LayerKV`), bit-identical to the
        per-token reference lane — only the attention READ changes
        formulation.

        A ``window`` cuts only the history part: the chunk is no longer
        than the window (asserted), so no in-pack key of a token's own
        request lies below it."""
        if not self.causal:
            raise ValueError(
                "the packed-prefill flash lane requires causal "
                "attention: its intra-pack/arena-history split relies "
                "on the causal position mask to keep the two KV sets "
                "disjoint (use prefill_attn='reference')")
        leaves, layer = kv_cache
        quant = len(leaves) == 4
        b, C, _ = x.shape
        n_blk, blk = leaves[0].shape[1], leaves[0].shape[2]
        q, gate = self._heads_q(
            lora_apply(lora, "q_proj", x,
                       self.q_proj(params["q_proj"], x)), (b, C))
        k = lora_apply(lora, "k_proj", x,
                       self.k_proj(params["k_proj"], x)).reshape(
            b, C, self.num_kv_heads, self.head_dim)
        v = lora_apply(lora, "v_proj", x,
                       self.v_proj(params["v_proj"], x)).reshape(
            b, C, self.num_kv_heads, self.head_dim)
        q, k = self._rotate(q, k, positions, rope_on, params)
        pos = positions[0]                               # (C,)
        blk_ids = jnp.take_along_axis(block_tables,
                                      (pos // blk)[:, None], axis=1)[:, 0]
        rows = jnp.where(pack["valid"], blk_ids * blk + pos % blk,
                         n_blk * blk)                    # pad → dropped

        def upd(buf, new):
            return _scatter_layer_rows(buf, layer, rows, new[0])

        if quant:
            from hetu_tpu.ops.quantization import (dequantize_int8,
                                                   quantize_int8)
            kq_b, ks_b, vq_b, vs_b = leaves
            with jax.named_scope("hetu.kv_arena"):
                knew_q, knew_s = quantize_int8(k, axis=-1)
                vnew_q, vnew_s = quantize_int8(v, axis=-1)
                kq_b, vq_b = upd(kq_b, knew_q), upd(vq_b, vnew_q)
                ks_b, ks_l = _scatter_layer_scales(ks_b, layer, rows,
                                                   knew_s[0])
                vs_b, vs_l = _scatter_layer_scales(vs_b, layer, rows,
                                                   vnew_s[0])
            new_cache = (kq_b, ks_b, vq_b, vs_b)
            # the reference per-token lane attends the arena's
            # ROUND-TRIPPED int8 values for in-pack rows — match it
            k = dequantize_int8(knew_q, knew_s, q.dtype)
            v = dequantize_int8(vnew_q, vnew_s, q.dtype)
            arena = dict(k_scale=ks_l, v_scale=vs_l)   # this layer's
            ka, va = kq_b, vq_b
        else:
            k_b, v_b = leaves
            with jax.named_scope("hetu.kv_arena"):
                k_b, v_b = upd(k_b, k), upd(v_b, v)
            new_cache = (k_b, v_b)
            arena = {}
            ka, va = k_b, v_b

        from hetu_tpu.ops.attention import attention_with_lse
        from hetu_tpu.ops.paged_pallas import (
            combine_attention_lse, paged_attention_reference,
            paged_history_attention,
        )
        # under a block bound a request's run starts at a whole block
        # and holds whole blocks (the engine cuts its chunks so): a
        # token's block lies inside the pack, index for position, and
        # every history key (< the run's start) below every block of it
        intra, lse_i = attention_with_lse(
            q, k, v, causal=self.causal,
            segment_ids=pack["segment_ids"], impl=pack["impl"],
            **self._bound)

        if window is not None and self.min_window is not None \
                and C > self.min_window:
            raise ValueError(
                f"a prefill pack of {C} tokens is longer than the "
                f"model's window {self.min_window}: in-pack keys "
                f"would fall below it")
        if attn_kernel == "paged":
            # one pass over a request's pages per TILE of its chunk:
            # the tile's rows sit at their own positions under a key
            # cap of hist - 1, so the layer's window is the model's
            tiles = pack["tiles"]
            hist, lse_h = paged_history_attention(
                q[0], ka, va, tiles["tables"], pack["hist"],
                tiles["map"], tile_rows=tiles["rows"], layer=layer,
                window=window, **arena)
            hist, lse_h = hist[None], lse_h.T[None]  # (1,C,hq,d) (1,hq,C)
        else:
            # the per-token formulation (the CPU path and the parity
            # oracle): every token is a one-row slot at hist - 1
            hist_off = pack["hist"].astype(jnp.int32) - 1
            if window is not None:
                # the row sits at hist - 1 and the token at pos: keys
                # > pos - window are keys > (hist - 1) - (window - (pos
                # - hist + 1)) — a window shorter by the token's depth
                # in its chunk, one per token
                arena["window"] = window - (pos - hist_off)
            hist, lse_h = paged_attention_reference(
                q[0][:, None], _at_layer(ka, layer), _at_layer(va, layer),
                block_tables, hist_off, return_lse=True, **arena)
            hist = hist[:, 0][None]                  # (1, C, hq, d)
            lse_h = lse_h[:, :, 0].T[None]           # (C, hq, 1) → (1, hq, C)
        out = combine_attention_lse(intra, lse_i, hist, lse_h)
        out = self._gated(
            out.reshape(b, C, self.num_heads * self.head_dim), gate)
        return lora_apply(lora, "out_proj", out,
                          self.out_proj(params["out_proj"], out)), \
            new_cache


class LatentKVNotSupported(NotImplementedError):
    """A feature asked for a per-head (K, V) cache of a model whose
    attention caches ONE latent row a token (:class:`LatentAttention`):
    the int8 arena (its scales are per head), the dense ``generate``
    cache, the CP-prefill lane's ``return_kv``."""


class LatentAttention(Module):
    """Multi-head latent attention (MLA, no query compression): what is
    cached is ONE row a token and layer, ``[c ‖ k_r]`` — the RMS-normed
    ``kv_rank``-wide compression of the keys and values of all heads
    and the one RoPE key (``rope_dim`` wide) they share.

    ``q = u W_q`` per head is ``[q_nope (nope_dim) ‖ q_rope]``;
    ``[c ‖ k_r] = u W_dkv``, ``c <- RMSNorm(c)``, RoPE on ``k_r`` and
    ``q_rope`` in adjacent pairs; per head ``[k_nope ‖ v] = c W_ukv``;
    scores ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope_dim +
    rope_dim)``. The whole-sequence forward computes exactly that (the
    EXPANDED form, through the reference attention: the flash kernel
    has one head size). Every cached path computes the ABSORBED form,
    the same arithmetic reassociated: ``q~_h = [q_nope,h W_uk,h^T ‖
    q_rope,h]`` scores against the cached rows themselves, ``o~_h =
    sum p c`` and ``o_h = o~_h W_uv,h`` — one key head that all query
    heads share, whose first ``kv_rank`` columns are also the value
    (``ops.paged_pallas``: ``v_width``), so no K or V of a context is
    ever expanded.

    ``stored_row`` (default ``kv_rank + rope_dim``) is the arena row's
    width; columns beyond ``kv_rank + rope_dim`` are zeros in the rows
    and in ``q~`` alike. To the engine this module is an attention of
    ``num_heads`` query heads over ONE kv head of ``head_dim =
    stored_row``; :meth:`kv_leaf_shapes` says what its arena holds.
    """

    latent, history_tiles, cache_leaves = True, True, 1

    def __init__(self, embed_dim: int, num_heads: int, *, kv_rank: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 stored_row: Optional[int] = None,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-6,
                 max_positions: int = 4096, qk_norm: bool = False,
                 qk_gain: float = 1.0, init=None):
        super().__init__()
        from hetu_tpu.nn.layers import RMSNorm
        self.num_heads, self.num_kv_heads = num_heads, 1
        # RMSNorm with a learned gain on each head's query (its nope
        # and rope parts together) and on the one RoPE key, both before
        # the rotation: the forms the absorbed path can hold (a norm of
        # each head's expanded key would need the key expanded). The
        # gains are drawn at ``qk_gain`` (1: a checkpoint's are learned)
        self.qk_norm, self.norm_eps = bool(qk_norm), norm_eps
        if qk_norm:
            from hetu_tpu.nn.module import constant_init
            self.param("q_gain", (nope_dim + rope_dim,),
                       constant_init(qk_gain))
            self.param("kr_gain", (rope_dim,), constant_init(qk_gain))
        self.kv_rank, self.nope_dim = kv_rank, nope_dim
        self.rope_dim, self.v_dim = rope_dim, v_dim
        self.row = kv_rank + rope_dim
        self.head_dim = stored_row or self.row
        if self.head_dim < self.row:
            raise ValueError(f"stored_row {stored_row} is narrower than "
                             f"the latent row {self.row}")
        self.min_window = None
        self.scale = 1.0 / (nope_dim + rope_dim) ** 0.5
        init = init or normal_init(0.02)
        self.q_proj = ColumnParallelLinear(
            embed_dim, num_heads * (nope_dim + rope_dim), bias=False,
            init=init, axis="heads", out_kind="hidden")
        self.kv_down = ColumnParallelLinear(
            embed_dim, self.row, bias=False, init=init, axis=None,
            out_kind="hidden")
        self.kv_norm = RMSNorm(kv_rank, eps=norm_eps)
        self.kv_up = ColumnParallelLinear(
            kv_rank, num_heads * (nope_dim + v_dim), bias=False,
            init=init, axis="heads", out_kind="hidden")
        self.out_proj = RowParallelLinear(
            num_heads * v_dim, embed_dim, bias=False, init=init,
            axis="heads")
        self._rope = rope_frequencies(rope_dim, max_positions,
                                      theta=rope_theta)

    def kv_leaf_shapes(self) -> tuple:
        """The trailing dims of each cache leaf, a token and layer."""
        return ((1, self.head_dim),)

    def row_bytes(self, itemsize: int) -> dict:
        """Bytes a token holds in one layer: the row as ``stored``
        (padded to ``stored_row``) and the ``needed`` latent row."""
        return {"stored": self.head_dim * itemsize,
                "needed": self.row * itemsize}

    def init_leaves(self, layers: int, n_blocks: int, block_size: int,
                    dtype, sharding=None) -> tuple:
        return kv_leaves(self, (layers, n_blocks, block_size), dtype,
                         paged=True, sharding=sharding)

    def _rotate(self, x, positions):
        cos, sin = self._rope
        return apply_rotary(x, cos, sin, positions=positions,
                            interleaved=True)

    def _latent_rows(self, params, x, positions):
        """``[RMSNorm(c) ‖ RoPE(k_r) ‖ zeros]`` of every token: ``(b, s,
        stored_row)`` in the compute dtype — what the cache stores."""
        with jax.named_scope("hetu.mla_down"):
            ckr = self.kv_down(params["kv_down"], x)
            c = self.kv_norm(params["kv_norm"], ckr[..., :self.kv_rank])
            kr = ckr[..., None, self.kv_rank:]
            if self.qk_norm:
                kr = _gain(params, "kr_gain", kr, self.norm_eps, kr.dtype)
            kr = self._rotate(kr, positions)[..., 0, :]
            return self._pad(
                jnp.concatenate([c, kr.astype(c.dtype)], axis=-1))

    def _queries(self, params, x, positions):
        """``(q_nope (b, s, H, nope), RoPE(q_rope) (b, s, H, rope))``."""
        b, s, _ = x.shape
        q = self.q_proj(params["q_proj"], x).reshape(
            b, s, self.num_heads, self.nope_dim + self.rope_dim)
        if self.qk_norm:
            q = _gain(params, "q_gain", q, self.norm_eps, q.dtype)
        return q[..., :self.nope_dim], \
            self._rotate(q[..., self.nope_dim:], positions)

    def _up(self, params, dt):
        """``(W_uk (rank, H, nope), W_uv (rank, H, v))``."""
        w = params["kv_up"]["weight"].astype(dt).reshape(
            self.kv_rank, self.num_heads, self.nope_dim + self.v_dim)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def _pad(self, x):
        """Zeros up to the stored row's width."""
        pad = self.head_dim - self.row
        return x if not pad else jnp.pad(
            x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])

    def _absorbed_queries(self, params, x, positions):
        """``q~ (b, s, H, stored_row)``."""
        q_nope, q_rope = self._queries(params, x, positions)
        with jax.named_scope("hetu.mla_absorb"):
            w_uk, _ = self._up(params, q_nope.dtype)
            q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk,
                               preferred_element_type=jnp.float32)
            return self._pad(jnp.concatenate(
                [q_lat.astype(q_nope.dtype), q_rope], axis=-1))

    def _output(self, params, o_lat):
        """``o~ (b, s, H, rank)`` -> the attention's result."""
        b, s = o_lat.shape[:2]
        dt = self.compute_dtype()
        with jax.named_scope("hetu.mla_absorb"):
            _, w_uv = self._up(params, dt)
            o = jnp.einsum("bshc,chv->bshv", o_lat.astype(dt), w_uv,
                           preferred_element_type=jnp.float32).astype(dt)
        return self.out_proj(params["out_proj"],
                             o.reshape(b, s, self.num_heads * self.v_dim))

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, return_kv: bool = False):
        del attn_impl          # one head size in the flash kernel
        if kv_cache is not None:
            if pack is not None:
                return self._decode_packed(
                    params, x, kv_cache, positions=positions,
                    block_tables=block_tables, pack=pack,
                    attn_kernel=attn_kernel)
            return self._decode(params, x, kv_cache, positions=positions,
                                slot_mask=slot_mask,
                                block_tables=block_tables,
                                row_mask=row_mask, attn_kernel=attn_kernel)
        if return_kv:
            raise LatentKVNotSupported(
                "return_kv (the CP-prefill lane) wants per-head (k, v); "
                "a latent attention caches one row a token")
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        q_nope, q_rope = self._queries(params, x, positions)
        rows = self._latent_rows(params, x, positions)
        with jax.named_scope("hetu.mla_expand"):
            kv = self.kv_up(params["kv_up"], rows[..., :self.kv_rank]) \
                .reshape(b, s, self.num_heads, self.nope_dim + self.v_dim)
            k_rope = jnp.broadcast_to(
                rows[..., None, self.kv_rank:self.row],
                (b, s, self.num_heads, self.rope_dim))
            k = jnp.concatenate([kv[..., :self.nope_dim], k_rope], -1)
            v = kv[..., self.nope_dim:]
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = attention_reference(q, k, v, causal=True,
                                  segment_ids=segment_ids,
                                  scale=self.scale)
        return self.out_proj(
            params["out_proj"],
            out.reshape(b, s, self.num_heads * self.v_dim))

    def _write(self, buf, layer, rows, new):
        with jax.named_scope("hetu.kv_arena"):
            return _scatter_layer_rows(buf, layer, rows, new)

    def _decode(self, params, x, kv_cache, *, positions, slot_mask,
                block_tables, row_mask, attn_kernel):
        """The decode and verify rows (``ParallelAttention._decode``'s
        per-row paged mode): every slot writes its rows' latent rows at
        ``[layer, page]`` of the ONE stacked leaf and attends, absorbed,
        the rows its table names — through the paged kernel in place
        (``attn_kernel="paged"``) or the gather reference."""
        (buf,), layer = kv_cache
        if block_tables is None or slot_mask is None:
            raise LatentKVNotSupported(
                "latent attention decodes from the paged arena, per "
                "slot (block_tables= and slot_mask=); it has no dense "
                "cache")
        b, s, _ = x.shape
        n_blk, blk = buf.shape[1], buf.shape[2]
        index = positions[:, 0]
        pos_rows = index[:, None] + jnp.arange(s)[None, :]
        blk_ids = jnp.take_along_axis(block_tables, pos_rows // blk, axis=1)
        keep = slot_mask[:, None]
        if row_mask is not None:
            keep = keep & row_mask
        at = jnp.where(keep, blk_ids * blk + pos_rows % blk,
                       n_blk * blk).reshape(-1)
        buf = self._write(buf, layer, at,
                          self._latent_rows(params, x, positions))
        q = self._absorbed_queries(params, x, positions)
        if attn_kernel == "paged":
            from hetu_tpu.ops.paged_pallas import paged_attention_auto
            o = paged_attention_auto(
                q, buf, None, block_tables, index, layer=layer,
                live=slot_mask, scale=self.scale, v_width=self.kv_rank)
        else:
            from hetu_tpu.ops.paged_pallas import \
                paged_attention_reference
            o = paged_attention_reference(
                q, _at_layer(buf, layer), None, block_tables, index,
                scale=self.scale, v_width=self.kv_rank)
        return self._output(params, o), (buf,)

    def _chunk_attention(self, q, rows, seg, block: int = 256):
        """The in-pack part of the packed prefill lane, absorbed, in
        XLA (the flash kernel takes one head size): ``q`` ``(C, H,
        stored_row)`` against the pack's own latent ``rows`` ``(C,
        stored_row)``, causal by pack index within a segment. Blocks of
        queries bound the ``(H, block, C)`` scores. Returns ``(C, H,
        kv_rank)`` and the LSE ``(H, C)``."""
        C, H, _ = q.shape
        block = min(block, C)
        pad = -C % block
        idx = jnp.arange(C + pad)
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, block, H, q.shape[-1])
        segq = jnp.pad(seg, (0, pad), constant_values=-1)
        val = rows[:, :self.kv_rank]

        def one(args):
            qi, ii, si = args
            s = jnp.einsum("qhd,kd->hqk", qi, rows,
                           preferred_element_type=jnp.float32) * self.scale
            seen = (idx[None, :C] <= ii[:, None]) \
                & (seg[None, :] == si[:, None])
            s = jnp.where(seen[None], s, -1e30)
            m = s.max(-1, keepdims=True)
            p = jnp.where(seen[None], jnp.exp(s - m), 0.0)
            l = p.sum(-1, keepdims=True)
            o = jnp.einsum("hqk,kc->qhc", (p / jnp.where(l == 0, 1.0, l))
                           .astype(val.dtype), val,
                           preferred_element_type=jnp.float32)
            lse = jnp.where(l == 0, -1e30, m + jnp.log(
                jnp.where(l == 0, 1.0, l)))[..., 0]
            return o.astype(q.dtype), lse

        o, lse = jax.lax.map(one, (qb, idx.reshape(-1, block),
                                   segq.reshape(-1, block)))
        return o.reshape(-1, H, self.kv_rank)[:C], \
            jnp.moveaxis(lse, 1, 0).reshape(H, -1)[:, :C]

    def _decode_packed(self, params, x, kv_cache, *, positions,
                       block_tables, pack, attn_kernel):
        """The packed prefill lane (``ParallelAttention._decode_packed``:
        an in-pack part and each token's resident history, LSE-combined)
        in the absorbed form: the pack's rows are written per token, the
        in-pack part runs in XLA over those rows, and the history is
        read in place in TILES by the same latent paged call."""
        (buf,), layer = kv_cache
        b, C, _ = x.shape
        n_blk, blk = buf.shape[1], buf.shape[2]
        pos = positions[0]
        blk_ids = jnp.take_along_axis(block_tables,
                                      (pos // blk)[:, None], axis=1)[:, 0]
        at = jnp.where(pack["valid"], blk_ids * blk + pos % blk,
                       n_blk * blk)
        rows = self._latent_rows(params, x, positions)
        buf = self._write(buf, layer, at, rows[0])
        q = self._absorbed_queries(params, x, positions)
        from hetu_tpu.ops.paged_pallas import (
            combine_attention_lse, paged_attention_reference,
            paged_history_attention,
        )
        intra, lse_i = self._chunk_attention(
            q[0], rows[0].astype(q.dtype), pack["segment_ids"][0])
        if attn_kernel == "paged":
            tiles = pack["tiles"]
            hist, lse_h = paged_history_attention(
                q[0], buf, None, tiles["tables"], pack["hist"],
                tiles["map"], tile_rows=tiles["rows"], layer=layer,
                scale=self.scale, v_width=self.kv_rank)
            lse_h = lse_h.T
        else:
            hist, lse_h = paged_attention_reference(
                q[0][:, None], _at_layer(buf, layer), None, block_tables,
                pack["hist"].astype(jnp.int32) - 1, scale=self.scale,
                v_width=self.kv_rank, return_lse=True)
            hist, lse_h = hist[:, 0], lse_h[:, :, 0].T
        o = combine_attention_lse(intra[None], lse_i[None], hist[None],
                                  lse_h[None])
        return self._output(params, o), (buf,)


class SlotStateNotSupported(NotImplementedError):
    """A feature asked for a cache made of token rows in pages alone of
    a model that also keeps a RECURRENT STATE per slot
    (:class:`LightningAttention`) or picks its pages from a
    compressed-key leaf (:class:`BlockSparseAttention`): the prefix
    cache, preemption and spill, the fleet's KV export and replication,
    the verify lane (a rejected draft would need the state rolled
    back), the int8 arena, ``long_max_len``, the dense ``generate``
    cache."""


def _gain(params, name, x, eps, dtype, at: float = 0.0):
    """RMSNorm over the last dim with the learned gain ``name`` (``at``
    1: the parameter is the gain's distance from one)."""
    from hetu_tpu.ops.normalization import rms_norm
    gain = params[name]
    if at:
        gain = at + gain.astype(jnp.float32)
    return rms_norm(x.astype(jnp.float32), gain, eps).astype(dtype)


def _cached_rows(x, positions, slot_mask, block_tables, row_mask, pack,
                 paged: bool = True):
    """The rows of either cached lane, flat: ``(u (N, E), pos (N,),
    valid (N,), tables (N, W), slot (N,) or None)`` — the decode rows
    (row ``r`` is slot ``r``: ``slot`` is ``None``) or a prefill pack
    (``pack["slot"]`` names each token's slot). ``paged=False``: a
    mixer of a stack that keeps no token rows at all is handed no
    tables."""
    if pack is not None:
        if "slot" not in pack:
            raise SlotStateNotSupported(
                "a prefill pack without its tokens' slots "
                "(pack['slot'], pack['slot_tables']): the engine hands "
                "them to a model whose blocks keep a slot_state")
        return x[0], positions[0], pack["valid"], block_tables, \
            pack["slot"]
    if x.shape[1] != 1:
        raise SlotStateNotSupported(
            "the verify lane (spec_depth > 0): a slot's rows beyond its "
            "first would advance a state that a rejected draft cannot "
            "roll back")
    if (paged and block_tables is None) or slot_mask is None:
        raise SlotStateNotSupported(
            "this attention decodes from the paged arena and the slot "
            "states, per slot (block_tables= and slot_mask=); it has no "
            "dense cache")
    valid = slot_mask if row_mask is None else slot_mask & row_mask[:, 0]
    return x[:, 0], positions[:, 0], valid, block_tables, None


def count_sparse_pages(values, tokens=None) -> None:
    """:class:`BlockSparseAttention`'s ``layer_stats`` on the host:
    ``values (sparse layers, 5)`` — the pages the rows of a lane chose
    and could see, summed over its live rows and kv heads, as
    ``[chosen, visible]`` of the decode rows then of the prefill pack,
    then how many of the pack's chosen pages were FORCED and read a
    tile of the pack at a time (``state="band"``; none where the pack
    read a token at a time) — into ``serving_sparse_pages_total{state,
    lane}``."""
    import numpy as np
    from hetu_tpu import telemetry
    v = np.asarray(values, np.int64).sum(axis=0)
    c = telemetry.get_registry().counter(
        "serving_sparse_pages_total",
        "pages the block-sparse attention's rows chose / could see")
    for i, lane in enumerate(("decode", "prefill")):
        if v[2 * i + 1]:
            c.inc(int(v[2 * i]), state="chosen", lane=lane)
            c.inc(int(v[2 * i + 1]), state="visible", lane=lane)
    if v[4]:
        c.inc(int(v[4]), state="band", lane="prefill")


# The two halves of :meth:`BlockSparseAttention._read_split` that are new
# to a pack are functions of their own under ``jax.jit``: the sparse
# layers of a stack are traced one after another at the same shapes, and
# a trace — the kernel's body, its nineteen index maps, the tables'
# compares: some 500 small traces a layer, 7 s of the chip's host over
# four layers — is made once and found again by the others.

@functools.partial(jax.jit, static_argnames=(
    "hkv", "tile_rows", "block_size", "init_blocks", "window_blocks"))
def _split_tables(ids, n, tables, pos, valid, tile_map, slot_tables, *,
                  hkv, tile_rows, **forced):
    """A pack's chosen blocks as the split read's operands: the free
    lanes' virtual tables, offsets and live rows, the tiles' band
    tables and map (``ops.sparse_select``), and how many of the live
    rows' chosen blocks the tiles read."""
    from hetu_tpu.ops import sparse_select as ss
    free = ss.free_tables(ids, n, tables, pos, valid, **forced)
    band = ss.band_tables(tile_map, slot_tables, hkv=hkv,
                          cells=-(-pos.shape[0] // tile_rows),
                          tile_rows=tile_rows, **forced)
    return free + band + (jnp.sum(jnp.where(
        valid, ss.forced_blocks(pos, **forced), 0)),)


@functools.partial(jax.jit, static_argnames=(
    "hkv", "tile_rows", "band", "scale"))
def _band_read(q, k, v, tables, valid, tiles, layer, *, hkv, tile_rows,
               band, scale):
    """The forced blocks' attention of a pack ``q (N, hq, d)`` over
    pages of ONE kv head, a TILE of a run at a time: the pack laid out
    a head at a time, each head's rows in whole cells, through the
    banded tiled call. Returns ``(N, hq, d)`` and the LSE ``(N, hq)``."""
    from hetu_tpu.ops.paged_pallas import paged_history_attention
    N, hq, d = q.shape
    G, pad = hq // hkv, -N % tile_rows
    qh = jnp.pad(q.reshape(N, hkv, G, d).transpose(1, 0, 2, 3),
                 ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = paged_history_attention(
        qh.reshape(-1, G, d), k, v, tables,
        jnp.broadcast_to(jnp.pad(valid, (0, pad))[None],
                         (hkv, N + pad)).reshape(-1), tiles,
        tile_rows=tile_rows, band=band, layer=layer, scale=scale)

    def rows(x):              # (hkv * cells * rows, G, ..) -> (N, hq, ..)
        x = x.reshape((hkv, N + pad) + x.shape[1:])[:, :N]
        return jnp.moveaxis(x, 0, 1).reshape((N, hq) + x.shape[3:])

    return tuple(rows(x) for x in out)


class BlockSparseAttention(Module):
    """Block-sparse GQA over a compressed-key cache, NoPE, with RMSNorm
    on every q and k head and an output gate (``mixer: minicpm4``).

    Cached a token: ``k``, ``v`` after the norm; every ``kernel_stride``
    tokens and kv head the mean of those tokens' keys (a *stride mean*).
    A query at ``t`` attends, per kv group, the keys ``<= t`` of its
    ``topk`` best blocks of ``block_size`` tokens (one block = one
    page), chosen from the compressed keys (``ops.sparse_select`` has
    the rule); then ``o <- o ⊙ sigmoid(u W_g)`` and ``W_o``.

    Every cached path — the decode rows and a prefill pack alike — is
    rows ``(slot table, position)``: a row's K, V land in the arena, a
    row that completes a stride writes its mean, and every row chooses
    its pages (``hetu.sparse_select``) and reads them through the paged
    attention call (``hetu.sparse_attn``; a pack is no longer than the
    forced window, so a token's in-pack keys are among its chosen
    pages, written before they are read). A DECODE row reads its
    choice as a ``topk``-lane table of its own. A prefill PACK on the
    kernel path reads it in two parts joined by their LSE
    (:meth:`_read_split`): the FORCED blocks — the first, the window
    before the token's own, its own: the same pages for the tokens of
    a block and all but a few for a tile of the pack — once a TILE of
    a run, through the tiled call under its banded causal mask; and
    the token's free choices, at most ``topk`` less the forced blocks,
    as a table of its own (without the engine's tile map, or on the
    gather path, the whole choice as the decode rows do). The
    whole-sequence forward is the same rule with a dense mask.

    The arena's leaves (:meth:`init_leaves`): K and V ``(layers,
    n_blocks, hkv * block_size, d)`` — a page holds ONE kv head,
    head-minor within its block, so the read streams the chosen head's
    bytes alone — and the stride means ``(layers, n_blocks, block_size
    // stride * hkv * d)``.
    """

    #: sizes of the cached read: pages a grid step of the paged call
    #: joins, virtual slots a call (its tables ride SMEM), and pack
    #: tokens a block of the selection's scores
    PAGES_PER_STEP, ROWS_PER_CALL, SELECT_ROWS = 8, 512, 256
    #: rows of one kv head in a tile of the pack's band read (tokens x
    #: the group: 16 tokens here). The call is 2.6 ms a layer at 1,024
    #: rows and 3.9 at 256, of a read of ~20 — but the step's compile
    #: is 7.6 s longer than without the band at 1,024 and 2.9 s at 256
    #: (the chip's host, PERF.md section 6, PR 52), and the benchmark's
    #: cold run has seconds to spare
    BAND_ROWS = 256
    #: a pack's forced blocks are read in tiles of a run, its own keys
    #: among them: the engine's tile map cuts EVERY run of the pack,
    #: with or without history
    latent, history_tiles, cache_leaves = False, "every_run", 3
    #: a cached call's third result (:func:`count_sparse_pages`)
    layer_stats = {"sparse_pages": ((5,), jnp.int32, count_sparse_pages)}

    def __init__(self, embed_dim: int, num_heads: int, *,
                 num_kv_heads: int, head_dim: int, block_size: int = 64,
                 kernel_size: int = 32, kernel_stride: int = 16,
                 topk: int = 64, init_blocks: int = 1,
                 window_size: int = 2048, norm_eps: float = 1e-6,
                 qk_gain: float = 1.0, init=None):
        super().__init__()
        from hetu_tpu.nn.module import constant_init
        if kernel_size % kernel_stride or block_size % kernel_stride \
                or window_size % block_size:
            raise ValueError(
                f"kernel_size {kernel_size} and block_size {block_size} "
                f"must be multiples of kernel_stride {kernel_stride}, "
                f"window_size {window_size} of block_size")
        self.window_blocks = window_size // block_size
        if init_blocks + self.window_blocks + 1 > topk:
            raise ValueError(
                f"topk {topk} is under the forced blocks: {init_blocks} "
                f"first + {self.window_blocks} before the query's + its "
                f"own")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.block_size = head_dim, block_size
        self.kernel_size, self.kernel_stride = kernel_size, kernel_stride
        self.topk, self.init_blocks = topk, init_blocks
        self.window_size = window_size
        self.norm_eps = norm_eps
        self.min_window = None
        self.scale = 1.0 / head_dim ** 0.5
        init = init or normal_init(0.02)
        self.q_proj = ColumnParallelLinear(
            embed_dim, num_heads * head_dim, bias=False, init=init,
            axis="heads", out_kind="hidden")
        self.k_proj = ColumnParallelLinear(
            embed_dim, num_kv_heads * head_dim, bias=False, init=init,
            axis="kv_heads", out_kind="hidden")
        self.v_proj = ColumnParallelLinear(
            embed_dim, num_kv_heads * head_dim, bias=False, init=init,
            axis="kv_heads", out_kind="hidden")
        self.gate_proj = ColumnParallelLinear(
            embed_dim, num_heads * head_dim, bias=False, init=init,
            axis="heads", out_kind="hidden")
        self.out_proj = RowParallelLinear(
            num_heads * head_dim, embed_dim, bias=False, init=init,
            axis="heads")
        self.param("q_gain", (head_dim,), constant_init(qk_gain))
        self.param("k_gain", (head_dim,), constant_init(qk_gain))

    # -- the cache spec ----------------------------------------------------
    @property
    def per_block(self) -> int:
        return self.block_size // self.kernel_stride

    def kv_leaf_shapes(self) -> tuple:
        """K and V rows per kv head, a token and layer (the stride
        means are a leaf of their own, a row a stride:
        :meth:`init_leaves`)."""
        return ((self.num_kv_heads, self.head_dim),) * 2

    def row_bytes(self, itemsize: int) -> dict:
        """Bytes a token holds in one layer, by leaf."""
        row = self.num_kv_heads * self.head_dim * itemsize
        return {"k": row, "v": row,
                "compressed_k": row // self.kernel_stride}

    def init_leaves(self, layers: int, n_blocks: int, block_size: int,
                    dtype, sharding=None) -> tuple:
        if block_size != self.block_size:
            raise ValueError(
                f"one page is one selection block: the arena's "
                f"block_size {block_size} must be the model's "
                f"{self.block_size}")
        if dtype == jnp.int8:
            raise SlotStateNotSupported(
                "the int8 arena: the compressed keys are means of the "
                "stored keys and a page holds one kv head")
        hkv, d = self.num_kv_heads, self.head_dim
        page = (layers, n_blocks, hkv * block_size, d)
        return (jnp.zeros(page, dtype, device=sharding),
                jnp.zeros(page, dtype, device=sharding),
                jnp.zeros((layers, n_blocks, self.per_block * hkv * d),
                          dtype, device=sharding))

    # -- projections ---------------------------------------------------------
    def _qkv(self, params, u):
        dt = self.compute_dtype()
        lead = u.shape[:-1]
        q = self.q_proj(params["q_proj"], u).reshape(
            lead + (self.num_heads, self.head_dim))
        k = self.k_proj(params["k_proj"], u).reshape(
            lead + (self.num_kv_heads, self.head_dim))
        v = self.v_proj(params["v_proj"], u).reshape(
            lead + (self.num_kv_heads, self.head_dim))
        return (_gain(params, "q_gain", q, self.norm_eps, dt),
                _gain(params, "k_gain", k, self.norm_eps, dt), v)

    def _output(self, params, o, u):
        gate = jax.nn.sigmoid(self.gate_proj(params["gate_proj"], u)
                              .astype(jnp.float32))
        o = (o.astype(jnp.float32) * gate).astype(self.compute_dtype())
        return self.out_proj(params["out_proj"], o)

    def _scores(self, q, kbar, pos):
        """Window scores ``(N, hkv, J)`` of rows that read ``kbar``."""
        from hetu_tpu.ops.sparse_select import window_scores
        return window_scores(
            q.reshape(q.shape[0], self.num_kv_heads, -1, self.head_dim),
            kbar, pos, stride=self.kernel_stride, kernel=self.kernel_size,
            scale=self.scale)

    def _choose(self, s, pos):
        from hetu_tpu.ops.sparse_select import choose_blocks
        return choose_blocks(
            s, pos, block_size=self.block_size, stride=self.kernel_stride,
            kernel=self.kernel_size, topk=self.topk,
            init_blocks=self.init_blocks,
            window_blocks=self.window_blocks)

    # -- the whole-sequence forward -------------------------------------------
    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, return_kv: bool = False):
        del attn_impl                    # the rule has no flash form
        if kv_cache is not None:
            return self._cached(params, x, kv_cache, positions=positions,
                                slot_mask=slot_mask,
                                block_tables=block_tables,
                                row_mask=row_mask, attn_kernel=attn_kernel,
                                pack=pack)
        if return_kv or segment_ids is not None:
            raise SlotStateNotSupported(
                "return_kv (the CP-prefill lane) and packed documents: "
                "the whole-sequence forward of the block-sparse "
                "attention is one document a row, from position 0")
        from hetu_tpu.ops.sparse_select import compressed_keys
        q, k, v = self._qkv(params, x)
        T = x.shape[1]
        bs, st = self.block_size, self.kernel_stride
        Tp = -(-T // bs) * bs
        pos = jnp.arange(T, dtype=jnp.int32)

        def one(q, k, v):
            kp = jnp.pad(k.astype(jnp.float32),
                         ((0, Tp - T), (0, 0), (0, 0)))
            cmean = kp.reshape((Tp // st, st) + kp.shape[1:]).mean(1)
            kbar = compressed_keys(cmean.astype(k.dtype),
                                   self.kernel_size // st)
            ids, _ = self._choose(self._scores(q, kbar, pos), pos)
            chosen = jnp.any(ids[..., None] == jnp.arange(Tp // bs),
                             axis=2)                       # (T, hkv, W)
            seen = jnp.repeat(chosen, bs, axis=-1)[..., :T] \
                & (pos[None, :] <= pos[:, None])[:, None, :]
            qg = q.reshape(T, self.num_kv_heads, -1, self.head_dim)
            sc = jnp.einsum("tkgd,jkd->tkgj", qg, k,
                            preferred_element_type=jnp.float32) * self.scale
            p = jax.nn.softmax(
                jnp.where(seen[:, :, None, :], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("tkgj,jkd->tkgd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
            return o.reshape(T, -1)

        return self._output(params, jax.vmap(one)(q, k, v), x)

    # -- the cached lanes ----------------------------------------------------
    def _cached(self, params, x, kv_cache, *, positions, slot_mask,
                block_tables, row_mask, attn_kernel, pack):
        """Either cached lane (the class docstring). Returns ``(out,
        (k, v, means), {"sparse_pages": (5,)})``: the ``[chosen,
        visible]`` pages summed over the live rows and kv heads, of the
        decode rows then of the pack — a lane fills its own pair —
        and how many of the pack's chosen pages its tiles read
        (:func:`count_sparse_pages`)."""
        from hetu_tpu.ops import sparse_select as ss
        (k_buf, v_buf, c_buf), layer = kv_cache
        layer = jnp.asarray(layer, jnp.int32)
        u, pos, valid, tables, slot = _cached_rows(
            x, positions, slot_mask, block_tables, row_mask, pack)
        N = u.shape[0]
        hkv, d, bs = self.num_kv_heads, self.head_dim, self.block_size
        st, per = self.kernel_stride, self.per_block
        L, n_blk = k_buf.shape[:2]
        q, k, v = self._qkv(params, u)

        blk = jnp.take_along_axis(tables, (pos // bs)[:, None],
                                  axis=1)[:, 0]
        heads = jnp.arange(hkv, dtype=jnp.int32)[None, :] * bs
        base = (blk * (hkv * bs) + pos % bs)[:, None] + heads   # (N, hkv)
        gone = n_blk * hkv * bs
        with jax.named_scope("hetu.kv_arena"):
            rows = jnp.where(valid[:, None], base, gone).reshape(-1)
            k_buf = _scatter_layer_rows(k_buf, layer, rows, k)
            v_buf = _scatter_layer_rows(v_buf, layer, rows, v)
            # a row that completes a stride writes the stride's mean,
            # from the arena's own rows (the stride may have begun in
            # an earlier pack, or a token at a time)
            back = jnp.arange(st - 1, -1, -1, dtype=jnp.int32)
            flat_k = k_buf.reshape(L, gone, d)
            seg = flat_k[layer, jnp.clip(
                base[:, :, None] - back[None, None, :], 0, gone - 1)]
            mean = jnp.mean(seg.astype(jnp.float32), axis=2)    # (N,hkv,d)
            done = valid & (pos % st == st - 1)
            c_rows = jnp.where(done, blk * per + (pos % bs) // st,
                               n_blk * per)
            c_buf = _scatter_layer_rows(
                c_buf.reshape(L, n_blk, per, hkv * d), layer, c_rows,
                mean.reshape(N, hkv * d)).reshape(c_buf.shape)

        with jax.named_scope("hetu.sparse_select"):
            ratio = self.kernel_size // st
            W = tables.shape[1]

            def means_of(tbl):            # (..., W) -> (..., J, hkv, d)
                got = c_buf[layer, tbl]
                return got.reshape(tbl.shape[:-1] + (W * per, hkv, d))

            if slot is None:
                s = self._scores(
                    q, ss.compressed_keys(means_of(tables), ratio), pos)
            else:
                s = self._pack_scores(
                    q, ss.compressed_keys(means_of(pack["slot_tables"]),
                                          ratio), slot, pos, valid)
            ids, n = self._choose(s, pos)
            # a pack on the kernel path reads a token's forced blocks a
            # TILE at a time and only its free choices through a table
            # of its own; every other row its whole choice through one
            tiles = pack.get("tiles") if pack is not None \
                and attn_kernel == "paged" else None
            if tiles is None:
                vt, voff = ss.virtual_tables(ids, n, tables, pos,
                                             block_size=bs)
                live = jnp.repeat(valid, hkv)
                band = 0
            else:
                vt, voff, live, bt, bmap, band = _split_tables(
                    ids, n, tables, pos, valid, tiles["map"],
                    pack["slot_tables"], hkv=hkv, tile_rows=tiles["rows"],
                    block_size=bs, init_blocks=self.init_blocks,
                    window_blocks=self.window_blocks)
            own = pos // bs + 1
            stats = jnp.stack([
                jnp.sum(jnp.where(valid, n, 0)),
                jnp.sum(jnp.where(valid, own, 0))]).astype(jnp.int32) * hkv

        with jax.named_scope("hetu.sparse_attn"):
            if tiles is None:
                o, k_buf, v_buf = self._read(
                    q, k_buf, v_buf, layer, vt, voff, live, attn_kernel)
            else:
                o, k_buf, v_buf = self._read_split(
                    q, k_buf, v_buf, layer, vt, voff, live, bt, bmap,
                    valid, tiles["rows"])
        out = self._output(params, o.reshape(N, -1), u)
        out = out[None] if pack is not None else out[:, None]
        zeros = jnp.zeros_like(stats)
        lanes = [zeros, stats] if pack is not None else [stats, zeros]
        return out, (k_buf, v_buf, c_buf), {"sparse_pages": jnp.concatenate(
            lanes + [jnp.asarray(band * hkv, jnp.int32)[None]])}

    def _pack_scores(self, q, kbar, slot, pos, valid):
        """Window scores of a pack's tokens, each against ITS slot's
        compressed keys ``kbar (S, J, hkv, d)``: blocks of
        ``SELECT_ROWS`` tokens, and within a block one pass a slot that
        has a token in it (a pack's runs are contiguous: one, seldom
        two)."""
        C = q.shape[0]
        B = min(self.SELECT_ROWS, C)
        pad = -C % B
        if pad:
            q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
            slot, pos = jnp.pad(slot, (0, pad)), jnp.pad(pos, (0, pad))
            valid = jnp.pad(valid, (0, pad))
        S, J = kbar.shape[:2]

        def block(args):
            qb, sb, pb, vb = args

            def per_slot(i, acc):
                mine = vb & (sb == i)
                return jax.lax.cond(
                    jnp.any(mine),
                    lambda a: jnp.where(
                        mine[:, None, None],
                        self._scores(qb, jax.lax.dynamic_index_in_dim(
                            kbar, i, 0, keepdims=False), pb), a),
                    lambda a: a, acc)

            return jax.lax.fori_loop(
                0, S, per_slot,
                jnp.zeros((B, self.num_kv_heads, J), jnp.float32))

        s = jax.lax.map(block, tuple(
            a.reshape((-1, B) + a.shape[1:])
            for a in (q, slot, pos, valid)))
        return s.reshape((-1,) + s.shape[2:])[:C]

    def _read_split(self, q, k_buf, v_buf, layer, vt, voff, live, bt,
                    bmap, valid, tile_rows):
        """A pack's read on the kernel path, in two parts joined by
        their LSE: the forced blocks a TILE of the pack at a time —
        one virtual tile a (kv head, tile) of ``tile_rows x G`` rows
        over the head's own pages, under the banded causal mask
        (:func:`_band_read` on ``sparse_select.band_tables``) — and a
        token's free choices through its own table (:meth:`_read` on
        ``sparse_select.free_tables``; a token without one gets the
        empty part)."""
        from hetu_tpu.ops.paged_pallas import combine_attention_lse
        hkv, d, bs = self.num_kv_heads, self.head_dim, self.block_size
        L, n_blk = k_buf.shape[:2]
        N = q.shape[0]
        pages = (L, n_blk * hkv, bs, d)
        free = None                  # (topk may be the forced blocks)
        if vt.shape[1]:
            # first: the leaves come out of its loop as they went in,
            # and the tiles read what the loop hands on (reading them
            # beside the loop, XLA copies each leaf for it)
            free, k_buf, v_buf = self._read(
                q, k_buf, v_buf, layer, vt, voff, live, "paged",
                return_lse=True)
        ob, lb = _band_read(
            q, k_buf.reshape(pages), v_buf.reshape(pages), bt, valid,
            bmap, layer, hkv=hkv, tile_rows=tile_rows,
            band=(self.window_blocks, self.init_blocks), scale=self.scale)
        if free is None:
            return ob, k_buf, v_buf
        of, lf = free
        o = combine_attention_lse(
            ob[None], lb.T[None], of.reshape(N, -1, d)[None],
            lf.reshape(N, -1).T[None])
        return o[0], k_buf, v_buf

    def _read(self, q, k_buf, v_buf, layer, vt, voff, live, attn_kernel,
              return_lse: bool = False):
        """The chosen pages' attention: one virtual slot a (row, kv
        head), ``G`` query heads over the head's own pages — the paged
        call, ``ROWS_PER_CALL`` virtual slots at a time (their tables
        ride SMEM). Returns ``(o, k_buf, v_buf)``: the leaves are the
        CARRY of the loop over the calls and come back as they went in
        — closed over, the loop would read a copy of each.
        ``return_lse``: ``o`` is ``(o, its LSE (M, G, 1))``, one part
        of a joined read."""
        from hetu_tpu.ops.paged_pallas import (
            paged_attention_auto, paged_attention_reference,
        )
        hkv, d, bs = self.num_kv_heads, self.head_dim, self.block_size
        L, n_blk = k_buf.shape[:2]
        M = vt.shape[0]
        qv = q.reshape(M, 1, self.num_heads // hkv, d)
        pages = (L, n_blk * hkv, bs, d)

        def call(kp, vp, qg, tg, og, lg):
            if attn_kernel == "paged":
                return paged_attention_auto(
                    qg, kp, vp, tg, og, layer=layer, live=lg,
                    scale=self.scale, pages_per_step=step,
                    return_lse=return_lse)
            return paged_attention_reference(
                qg, _at_layer(kp, layer), _at_layer(vp, layer), tg, og,
                scale=self.scale, return_lse=return_lse)

        # one more chunk of lanes than any row can fill: the paged call's
        # work list is then never FULL. The kernel's pipeline looks one
        # pair ahead; past a full list that is past the list itself, a
        # page of nowhere, and the chip halts (PERF.md section 6, PR 39:
        # every row at 64 chosen pages fills it; no other cell's slots
        # all stand in their last table chunk at once)
        step = min(self.PAGES_PER_STEP, vt.shape[1])
        vt = jnp.pad(vt, ((0, 0), (0, step)))
        R = self.ROWS_PER_CALL
        if M <= R:
            return call(k_buf.reshape(pages), v_buf.reshape(pages), qv,
                        vt, voff, live), k_buf, v_buf
        pad = -M % R
        if pad:
            qv = jnp.pad(qv, ((0, pad),) + ((0, 0),) * 3)
            vt = jnp.pad(vt, ((0, pad), (0, 0)))
            voff, live = jnp.pad(voff, (0, pad)), jnp.pad(live, (0, pad))

        def body(kv, xs):
            # the barrier keeps the leaves IN the loop's state: taken
            # out as invariants they are read by the loop and written
            # in place by the next layer, and XLA copies each first
            o = call(*kv, *xs)
            return jax.lax.optimization_barrier(kv), o

        (kp, vp), o = jax.lax.scan(
            body, (k_buf.reshape(pages), v_buf.reshape(pages)), tuple(
                a.reshape((-1, R) + a.shape[1:])
                for a in (qv, vt, voff, live)))
        o = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:])[:M], o)
        return o, kp.reshape(k_buf.shape), vp.reshape(v_buf.shape)


class LightningAttention(Module):
    """Linear attention with a per-head decay over a per-slot recurrent
    state (``mixer: lightning-attn``): ``q = RoPE(RMSNorm(u W_q))``,
    ``k`` likewise (split-half pairs over the whole head), ``v = u
    W_v``; per head a float32 state ``S_t = e^{-s_h} S_{t-1} + k_t
    v_t^T`` and ``o_t = S_t^T q_t * scale``; then RMSNorm over the
    joined heads, ``⊙ sigmoid(u W_g)`` and ``W_o``
    (``ops.linear_attention``).

    What is cached is a SLOT's, not a token's: ONE leaf ``(layers,
    slots, H, dk, dv)`` float32 (:meth:`init_leaves`), whatever the
    context. The decode rows advance their slot's state by a token
    (``hetu.linear_update``); a prefill pack's tokens advance theirs in
    blocks (``hetu.linear_scan``), a slot whose run starts at position
    0 from zeros. No page is ever read or written."""

    #: pack tokens a block of the chunk scan
    SCAN_BLOCK = 256
    cache_leaves = 1

    def __init__(self, embed_dim: int, num_heads: int, *, head_dim: int,
                 rope_theta: float = 10000.0, max_positions: int = 4096,
                 norm_eps: float = 1e-6, qk_gain: float = 1.0,
                 init=None):
        super().__init__()
        from hetu_tpu.nn.module import constant_init, ones_init
        self.num_heads = self.num_kv_heads = num_heads
        self.head_dim = head_dim
        self.norm_eps = norm_eps
        self.min_window = None
        self.scale = 1.0 / head_dim ** 0.5
        init = init or normal_init(0.02)
        inner = num_heads * head_dim
        for name in ("q_proj", "k_proj", "v_proj", "gate_proj"):
            setattr(self, name, ColumnParallelLinear(
                embed_dim, inner, bias=False, init=init, axis="heads",
                out_kind="hidden"))
        self.out_proj = RowParallelLinear(inner, embed_dim, bias=False,
                                          init=init, axis="heads")
        self.param("q_gain", (head_dim,), constant_init(qk_gain))
        self.param("k_gain", (head_dim,), constant_init(qk_gain))
        self.param("o_gain", (inner,), ones_init())
        self._rope = rope_frequencies(head_dim, max_positions,
                                      theta=rope_theta)

    def kv_leaf_shapes(self) -> tuple:
        """No leaf a token: the state is a slot's."""
        return ()

    def state_bytes(self) -> int:
        """Bytes a slot's state holds in one layer."""
        return self.num_heads * self.head_dim * self.head_dim * 4

    def init_leaves(self, layers: int, slots: int, sharding=None) -> tuple:
        return (jnp.zeros((layers, slots, self.num_heads, self.head_dim,
                           self.head_dim), jnp.float32, device=sharding),)

    def _slopes(self):
        from hetu_tpu.ops.linear_attention import decay_slopes
        return decay_slopes(self.num_heads)

    def _qkv(self, params, u, positions):
        """``u (b, s, E)``, ``positions (b, s)``."""
        dt = self.compute_dtype()
        shape = u.shape[:-1] + (self.num_heads, self.head_dim)
        cos, sin = self._rope
        q = _gain(params, "q_gain", self.q_proj(params["q_proj"], u)
                  .reshape(shape), self.norm_eps, dt)
        k = _gain(params, "k_gain", self.k_proj(params["k_proj"], u)
                  .reshape(shape), self.norm_eps, dt)
        v = self.v_proj(params["v_proj"], u).reshape(shape)
        return (apply_rotary(q, cos, sin, positions=positions),
                apply_rotary(k, cos, sin, positions=positions), v)

    def _output(self, params, o, u):
        """``o (..., H, dv)`` float32."""
        o = _gain(params, "o_gain", o.reshape(o.shape[:-2] + (-1,)),
                  self.norm_eps, jnp.float32)
        gate = jax.nn.sigmoid(self.gate_proj(params["gate_proj"], u)
                              .astype(jnp.float32))
        return self.out_proj(params["out_proj"],
                             (o * gate).astype(self.compute_dtype()))

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, return_kv: bool = False):
        del attn_impl, attn_kernel       # no attention kernel here
        from hetu_tpu.ops import linear_attention as la
        b, s, _ = x.shape
        if kv_cache is None:
            if return_kv or segment_ids is not None:
                raise SlotStateNotSupported(
                    "return_kv (the CP-prefill lane) and packed "
                    "documents: a linear attention has no (k, v) to "
                    "hand out, and its whole-sequence forward is one "
                    "document a row")
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q, k, v = self._qkv(params, x, positions)
            o = jax.vmap(lambda q, k, v: la.linear_recurrence(
                q, k, v, self._slopes(), scale=self.scale)[0])(q, k, v)
            return self._output(params, o, x)
        (buf,), layer = kv_cache
        _, pos, valid, _, slot = _cached_rows(
            x, positions, slot_mask, block_tables, row_mask, pack)
        q, k, v = self._qkv(params, x, positions)
        # (the state's read out of its leaf and its write back are the
        # scope's: they are most of what the update moves. A gather and
        # a scatter of this layer's slots, in place in the stacked leaf:
        # a dynamic slice of the layer and its update made the compiler
        # copy the WHOLE leaf once a layer and lane, PERF.md section 7)
        every = jnp.arange(buf.shape[1])
        if slot is None:
            with jax.named_scope("hetu.linear_update"):
                o, state = la.linear_update(
                    q[:, 0], k[:, 0], v[:, 0], buf.at[layer, every].get(),
                    valid, self._slopes(), scale=self.scale)
                buf = buf.at[layer, every].set(state)
            o = o[:, None]
        else:
            with jax.named_scope("hetu.linear_scan"):
                o, state = la.linear_scan(
                    q[0], k[0], v[0], buf.at[layer, every].get(), slot,
                    pos, valid, self._slopes(), scale=self.scale,
                    block=self.SCAN_BLOCK)
                buf = buf.at[layer, every].set(state)
            o = o[None]
        return self._output(params, o, x), (buf,)


def count_kda_steps(values, tokens=None, *, mixer: str = "kda") -> None:
    """A delta-rule mixer's ``layer_stats`` on the host
    (:class:`KimiDeltaAttention`; ``mixer="gdn"``:
    :class:`GatedDeltaNet`): ``values (its layers, 4)`` — of each
    layer's call ``[live, computed, advanced, stepped]``: a prefill
    pack's scan reports the grid steps that held a valid row and the
    grid steps run (``ops.kda_pallas.hetu_kda_scan(return_steps=True)``)
    and zeros behind them, the decode rows' update zeros and then the
    live slots it advanced and the slot steps of its grid
    (``hetu_kda_update(return_steps=True)``) — into
    ``<mixer>_scan_steps_total{kind}`` and
    ``<mixer>_update_slots_total{kind}``: neither lane adds to the
    other's counter."""
    import numpy as np
    from hetu_tpu import telemetry
    live, computed, advanced, stepped = np.asarray(
        values, np.int64).sum(axis=0).tolist()
    reg = telemetry.get_registry()
    if computed:
        c = reg.counter(
            f"{mixer}_scan_steps_total",
            "grid steps of the delta-rule scan kernel: live = (piece, "
            "head block) steps that held a valid row, computed = steps "
            "run (a chunk without a valid row costs one that writes "
            "zeros), summed over layer calls")
        c.inc(float(live), kind="live")
        c.inc(float(computed), kind="computed")
    if stepped:
        c = reg.counter(
            f"{mixer}_update_slots_total",
            "slots of the delta-rule decode rows' update kernel: live = "
            "slots whose state it advanced by a token (read once, "
            "written once), stepped = slot steps of its grid (a step "
            "behind the live ones moves nothing), summed over layer "
            "calls")
        c.inc(float(advanced), kind="live")
        c.inc(float(stepped), kind="stepped")


class DeltaRuleMixer(Module):
    """What the gated-delta-rule mixers share (``ops.kda``): a short
    causal convolution over the mixer's q, k and v channels, then the
    rule over a per-slot recurrent state, a VALUE head at a time::

        S_t = (I - beta k k^T) Diag(e^g) S_{t-1} + beta k v^T,  o = S_t^T q

    A subclass gives the projections (:meth:`_inputs`: the
    convolution's input, the log-decay ``g``, ``beta`` and what its
    output gate takes), the activation and norms behind the convolution
    (:meth:`_qkv`) and the output (:meth:`_output`), and names its
    sizes — ``num_heads`` value heads of ``head_dim`` (``dk = dv``),
    ``conv_channels`` — and its ``mixer`` (the device scopes
    ``hetu.<mixer>_conv`` / ``_scan`` / ``_update``, the stat
    ``<mixer>_steps``).

    What is cached is a SLOT's, in TWO leaves (:meth:`init_leaves`):
    the float32 state ``(layers, slots, H, dk, dv)`` and the
    convolution's TAIL ``(layers, slots, taps - 1, conv_channels)`` —
    the last input rows of q, k and v before the activation. A run that
    starts at position 0 starts from a zero state AND a zero tail,
    whatever its slot held. The decode rows advance their slot by a
    token (``_update``), a prefill pack's tokens theirs in chunks
    (``_scan``), both behind ``_conv``; each addresses the live slots of
    its own layer in the stacked leaves in place. Both are ONE Pallas
    call a layer call on the state leaf where it lies
    (``ops.kda_pallas``, interpreted on the CPU): ``hetu_kda_update``
    walks the live slots, each state read once and written once;
    ``hetu_kda_scan`` walks the pack's pieces, a run's state in VMEM
    across its chunks. ``ops.kda.kda_update`` and ``ops.kda.kda_scan``
    are their oracles and never run here. No page is ever read or
    written."""

    cache_leaves = 2
    mixer = "kda"
    #: (the subclass's ``__init__`` sets them)
    num_heads: int
    head_dim: int
    conv_size: int
    conv_channels: int

    @property
    def layer_stats(self) -> dict:
        """A cached call's third result (:func:`count_kda_steps`)."""
        return {f"{self.mixer}_steps": ((4,), jnp.int32, functools.partial(
            count_kda_steps, mixer=self.mixer))}

    def kv_leaf_shapes(self) -> tuple:
        """No leaf a token: the state and the tail are a slot's."""
        return ()

    def state_bytes(self) -> int:
        """Bytes a slot's state and tail hold in one layer."""
        return 4 * (self.num_heads * self.head_dim * self.head_dim
                    + (self.conv_size - 1) * self.conv_channels)

    def init_leaves(self, layers: int, slots: int, sharding=None) -> tuple:
        return (jnp.zeros((layers, slots, self.num_heads, self.head_dim,
                           self.head_dim), jnp.float32, device=sharding),
                jnp.zeros((layers, slots, self.conv_size - 1,
                           self.conv_channels), jnp.float32,
                          device=sharding))

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, return_kv: bool = False):
        del attn_impl, attn_kernel       # no attention kernel here
        from hetu_tpu.ops import kda
        b, s, E = x.shape
        taps = params["conv"]
        if kv_cache is None:
            if return_kv or segment_ids is not None:
                raise SlotStateNotSupported(
                    "return_kv (the CP-prefill lane) and packed "
                    "documents: a delta-rule attention has no (k, v) to "
                    "hand out, and its whole-sequence forward is one "
                    "document a row")
            a, g, beta, gate = self._inputs(params, x.reshape(-1, E))
            y = jax.vmap(lambda a: kda.conv_sequence(a, taps))(
                a.reshape(b, s, -1))
            q, k, v = self._qkv(y.reshape(b * s, -1))

            def rows(t):
                return t.reshape((b, s) + t.shape[1:])
            o = jax.vmap(lambda *t: kda.kda_recurrence(*t)[0])(
                rows(q), rows(k), rows(v), rows(g), rows(beta))
            return self._output(params, o.reshape((b * s,) + o.shape[2:]),
                                gate).reshape(b, s, E)
        (state, tail), layer = kv_cache
        u, pos, valid, _, slot = _cached_rows(
            x, positions, slot_mask, block_tables, row_mask, pack)
        a, g, beta, gate = self._inputs(params, u)
        # (the reads of a slot's state and tail out of their leaves and
        # the writes back are the scopes': most of what a row moves)
        with jax.named_scope(f"hetu.{self.mixer}_conv"):
            if slot is None:
                y, tail = kda.conv_rows(a, taps, tail, valid, layer=layer,
                                        fresh=pos == 0)
            else:
                y, tail = kda.conv_pack(a, taps, tail, slot, pos, valid,
                                        layer=layer)
        q, k, v = self._qkv(y)
        from hetu_tpu.ops.kda_pallas import hetu_kda_scan, hetu_kda_update
        none = jnp.zeros((2,), jnp.int32)
        if slot is None:
            with jax.named_scope(f"hetu.{self.mixer}_update"):
                o, state, steps = hetu_kda_update(
                    q, k, v, g, beta, state, valid, layer=layer,
                    fresh=pos == 0, return_steps=True)
            steps = jnp.concatenate([none, steps])
        else:
            with jax.named_scope(f"hetu.{self.mixer}_scan"):
                o, state, steps = hetu_kda_scan(
                    q, k, v, g, beta, state, slot, pos, valid,
                    layer=layer, return_steps=True)
            steps = jnp.concatenate([steps, none])
        return self._output(params, o, gate).reshape(x.shape), \
            (state, tail), {f"{self.mixer}_steps": steps}


def _unit(x):
    """``x / |x|`` over the last dim (float32)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)


class KimiDeltaAttention(DeltaRuleMixer):
    """Kimi Delta Attention (``ops.kda``): a gated delta rule with a
    decay per channel over a per-slot recurrent state, behind a short
    causal convolution (:class:`DeltaRuleMixer` has the rule, the
    caches and the lanes). With ``u`` the block's normed input::

        [q | k | v] = SiLU(conv(u [W_q | W_k | W_v]))    a channel at a time
        q <- q / |q| * dk^-1/2,  k <- k / |k|            a head
        g = lower_bound * sigmoid(exp(A_log_h) (u W_f + dt_bias))
        beta = sigmoid(u W_b)                             one a head
        out = W_o (RMSNorm_head(o) * sigmoid(u W_g))      one gate a head

    ``A_log``, ``dt_bias`` and the taps are drawn, not constants (a
    program that leaves one out must differ)."""

    def __init__(self, embed_dim: int, num_heads: int, *, head_dim: int,
                 conv_size: int = 4, lower_bound: float = -5.0,
                 norm_eps: float = 1e-6, init=None):
        super().__init__()
        from hetu_tpu.nn.module import ones_init
        self.num_heads = self.num_kv_heads = num_heads
        self.head_dim, self.conv_size = head_dim, conv_size
        self.lower_bound, self.norm_eps = float(lower_bound), norm_eps
        self.min_window = None
        init = init or normal_init(0.02)
        inner = num_heads * head_dim
        self.conv_channels = 3 * inner
        self.qkv_proj = ColumnParallelLinear(
            embed_dim, 3 * inner, bias=False, init=init, axis="heads",
            out_kind="hidden")
        self.decay_proj = ColumnParallelLinear(
            embed_dim, inner, bias=False, init=init, axis="heads",
            out_kind="hidden")
        # [beta | gate]: one of each a head
        self.head_proj = ColumnParallelLinear(
            embed_dim, 2 * num_heads, bias=False, init=init, axis=None,
            out_kind="hidden")
        self.out_proj = RowParallelLinear(inner, embed_dim, bias=False,
                                          init=init, axis="heads")
        # (float32 whatever the weights are served in: they shape the
        # decay and the window, a few thousand numbers)
        self.param("conv", (conv_size, 3 * inner),
                   normal_init(conv_size ** -0.5), dtype=jnp.float32)
        self.param("A_log", (num_heads,), normal_init(0.5),
                   dtype=jnp.float32)

        def dt_bias(key, shape, dtype):
            return (jax.random.normal(key, shape, jnp.float32)
                    - 2.0).astype(dtype)
        self.param("dt_bias", (inner,), dt_bias, dtype=jnp.float32)
        self.param("o_gain", (head_dim,), ones_init())

    def _inputs(self, params, u):
        """``u (N, E)`` -> ``(a (N, 3 H d) float32 — q, k, v before the
        convolution —, g (N, H, d), beta (N, H), gate (N, H))``."""
        H, d = self.num_heads, self.head_dim
        a = self.qkv_proj(params["qkv_proj"], u).astype(jnp.float32)
        f = self.decay_proj(params["decay_proj"], u).astype(jnp.float32)
        f = (f + params["dt_bias"].astype(jnp.float32)).reshape(-1, H, d)
        g = self.lower_bound * jax.nn.sigmoid(
            jnp.exp(params["A_log"].astype(jnp.float32))[None, :, None] * f)
        bg = jax.nn.sigmoid(self.head_proj(params["head_proj"], u)
                            .astype(jnp.float32))
        return a, g, bg[:, :H], bg[:, H:]

    def _qkv(self, y):
        """The convolution's result ``(N, 3 H d)`` -> ``q``, ``k``, ``v``
        ``(N, H, d)`` float32, activated and normalised."""
        H, d = self.num_heads, self.head_dim
        q, k, v = (jax.nn.silu(y).reshape(-1, 3, H, d)[:, i]
                   for i in range(3))
        return _unit(q) * d ** -0.5, _unit(k), v

    def _output(self, params, o, gate):
        """``o (N, H, dv)`` float32, ``gate (N, H)``."""
        o = _gain(params, "o_gain", o, self.norm_eps, jnp.float32)
        o = (o * gate[..., None]).reshape(o.shape[0], -1)
        return self.out_proj(params["out_proj"],
                             o.astype(self.compute_dtype()))


class GatedDeltaNet(DeltaRuleMixer):
    """Gated DeltaNet (arXiv:2412.06464, as ``qwen3_next`` builds it):
    the delta rule with ONE decay a head and token and ``num_key_heads``
    key heads under ``num_heads`` value heads (value head ``j`` reads
    key head ``j // (num_heads / num_key_heads)``; the state is a value
    head's). With ``u`` the block's normed input, no bias anywhere::

        [q | k | v | z] = u W_qkvz                      Hk dk, Hk dk, H dv, H dv
        [b | a] = u W_ba                                H and H
        [q | k | v] <- SiLU(conv([q | k | v]))          a channel at a time
        q <- q / |q| * dk^-1/2,  k <- k / |k|           a key head
        beta = sigmoid(b),  g = -exp(A_log_h) softplus(a + dt_bias_h)
        out = W_o (RMSNorm_dv(o) * w_o * silu(z))       w_o (dv,): the heads'

    ``W_qkvz`` stands in the order written (a checkpoint groups it by
    key head; ``models/converter.py`` loads none). ``A_log``,
    ``dt_bias`` and the taps are drawn: ``A`` uniform in ``a_range`` and
    the step ``softplus(dt_bias)`` log-uniform in ``dt_range``, so a
    layer's heads keep their past for a few tokens to thousands and a
    program that drops the decay, or a state between chunks, must
    differ. The kernels take this form by broadcasting it onto KDA's
    (``ops.kda.widen``)."""

    mixer = "gdn"

    def __init__(self, embed_dim: int, num_heads: int, *,
                 num_key_heads: int, head_dim: int, conv_size: int = 4,
                 norm_eps: float = 1e-6, a_range: tuple = (0.0, 16.0),
                 dt_range: tuple = (1e-3, 1e-1), init=None):
        super().__init__()
        from hetu_tpu.nn.module import ones_init
        if num_heads % num_key_heads:
            raise ValueError(f"{num_key_heads} key heads under "
                             f"{num_heads} value heads")
        self.num_heads, self.num_kv_heads = num_heads, num_heads
        self.num_key_heads = num_key_heads
        self.head_dim, self.conv_size = head_dim, conv_size
        self.norm_eps, self.min_window = norm_eps, None
        init = init or normal_init(0.02)
        self._key, self._value = num_key_heads * head_dim, \
            num_heads * head_dim
        self.conv_channels = 2 * self._key + self._value
        # [q | k | v | z]
        self.qkvz_proj = ColumnParallelLinear(
            embed_dim, self.conv_channels + self._value, bias=False,
            init=init, axis="heads", out_kind="hidden")
        # [b | a]: one of each a value head
        self.ba_proj = ColumnParallelLinear(
            embed_dim, 2 * num_heads, bias=False, init=init, axis=None,
            out_kind="hidden")
        self.out_proj = RowParallelLinear(self._value, embed_dim,
                                          bias=False, init=init,
                                          axis="heads")
        self.param("conv", (conv_size, self.conv_channels),
                   normal_init(conv_size ** -0.5), dtype=jnp.float32)
        lo, hi = a_range

        def a_log(key, shape, dtype):
            return jnp.log(jax.random.uniform(
                key, shape, jnp.float32, max(lo, 1e-3), hi)).astype(dtype)

        def dt_bias(key, shape, dtype):
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(dt_range[0]),
                math.log(dt_range[1])))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        self.param("A_log", (num_heads,), a_log, dtype=jnp.float32)
        self.param("dt_bias", (num_heads,), dt_bias, dtype=jnp.float32)
        self.param("o_gain", (head_dim,), ones_init())

    def _inputs(self, params, u):
        """``u (N, E)`` -> ``(a (N, 2 Hk d + H d) float32 — q, k, v
        before the convolution —, g (N, H), beta (N, H), z (N, H,
        d))``."""
        H, d = self.num_heads, self.head_dim
        y = self.qkvz_proj(params["qkvz_proj"], u).astype(jnp.float32)
        ba = self.ba_proj(params["ba_proj"], u).astype(jnp.float32)
        g = -jnp.exp(params["A_log"].astype(jnp.float32)) \
            * jax.nn.softplus(ba[:, H:]
                              + params["dt_bias"].astype(jnp.float32))
        return y[:, :self.conv_channels], g, jax.nn.sigmoid(ba[:, :H]), \
            y[:, self.conv_channels:].reshape(-1, H, d)

    def _qkv(self, y):
        """The convolution's result -> ``q``, ``k`` ``(N, Hk, d)``,
        ``v`` ``(N, H, d)`` float32, activated and normalised."""
        d, key = self.head_dim, self._key
        y = jax.nn.silu(y)
        q = y[:, :key].reshape(-1, self.num_key_heads, d)
        k = y[:, key:2 * key].reshape(-1, self.num_key_heads, d)
        return _unit(q) * d ** -0.5, _unit(k), \
            y[:, 2 * key:].reshape(-1, self.num_heads, d)

    def _output(self, params, o, z):
        """``o``, ``z`` ``(N, H, dv)`` float32."""
        o = _gain(params, "o_gain", o, self.norm_eps, jnp.float32)
        o = (o * jax.nn.silu(z)).reshape(o.shape[0], -1)
        return self.out_proj(params["out_proj"],
                             o.astype(self.compute_dtype()))


def count_retention(values, tokens=None) -> None:
    """:class:`PowerRetention`'s ``layer_stats`` on the host: ``values
    (layers, 3)`` — of each layer's call ``[decode rows, prefill rows,
    runs]`` (the live rows its update advanced; the valid tokens and the
    runs its scan held: a state is read and written once a RUN) — into
    ``retention_rows_total{lane}`` and ``retention_runs_total``, summed
    over layer calls."""
    import numpy as np
    from hetu_tpu import telemetry
    dec, pre, runs = np.asarray(values, np.int64).sum(axis=0).tolist()
    reg = telemetry.get_registry()
    rows = reg.counter(
        "retention_rows_total",
        "token rows the power-retention layers advanced a state by, "
        "summed over layer calls")
    if dec:
        rows.inc(float(dec), lane="decode")
    if pre:
        rows.inc(float(pre), lane="prefill")
        reg.counter(
            "retention_runs_total",
            "runs (a slot's tokens in one pack) the retention scan "
            "held: a state tile set read and written once each, summed "
            "over layer calls").inc(float(runs))


class PowerRetention(Module):
    """Power retention of degree 2 (``ops.retention``): with ``u`` the
    block's normed input, ``q = RoPE(RMSNorm_head(u W_q))`` over ``H``
    heads, ``k`` likewise and ``v = u W_v`` over ``Hkv`` (a kv head
    serves ``H / Hkv`` query heads), a gate a kv head and token ``log g
    = logsigmoid(u W_g + b_g)``; per kv head a float32 state ``S_t = g_t
    S_{t-1} + phi(k_t / d^{1/4}) [v_t, 1]^T`` of the keys' symmetric
    second tensor power against the values and a normaliser, ``y_t =
    n_t / (z_t + eps)``, ``[n_t, z_t] = phi(q_t / d^{1/4})^T S_t``; then
    ``W_o``. No softmax, no token row: what is cached is a SLOT's, ONE
    leaf ``(layers, slots, Hkv, d / 2 + 1, R, d)`` float32
    (:meth:`init_leaves`; the tiles of ``ops.retention.phi_tiles`` —
    36.2 MB a layer and slot at 8 kv heads of 128, the 8,256 features
    of the model in 8,320 places and 129 value rows in 136), whatever
    the context.

    The decode rows advance their slot's state by a token IN PLACE
    (``hetu.retention_update``: ``ops.retention_pallas.
    hetu_retention_update``), a prefill pack's tokens theirs in chunks
    with a run's state in VMEM (``hetu.retention_scan``:
    ``hetu_retention_scan``), a slot whose run starts at position 0
    from zeros; both kernels are interpreted on the CPU and neither has
    a ``jax.numpy`` form behind it. ``b_g`` is drawn so that the mean
    gate runs from ``gate_means[0]`` on a layer's first kv head to
    ``gate_means[1]`` on its last (evenly in the logit): horizons from
    a hundred tokens to the whole context in one layer, so a program
    that drops the gate or loses a state must differ."""

    cache_leaves = 1
    latent = False
    #: a cached call's third result (:func:`count_retention`)
    layer_stats = {"retention": ((3,), jnp.int32, count_retention)}

    def __init__(self, embed_dim: int, num_heads: int, *,
                 num_kv_heads: int, head_dim: int,
                 rope_theta: float = 1e6, max_positions: int = 4096,
                 norm_eps: float = 1e-6, qk_gain: float = 1.0,
                 eps: float = 1e-6, gate_means=(0.99, 0.99999),
                 init=None):
        super().__init__()
        from hetu_tpu.nn.module import constant_init
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads over "
                             f"{num_kv_heads} kv heads")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.norm_eps, self.eps = head_dim, norm_eps, eps
        self.min_window = None
        init = init or normal_init(0.02)
        self.q_proj = ColumnParallelLinear(
            embed_dim, num_heads * head_dim, bias=False, init=init,
            axis="heads", out_kind="hidden")
        for name in ("k_proj", "v_proj"):
            setattr(self, name, ColumnParallelLinear(
                embed_dim, num_kv_heads * head_dim, bias=False, init=init,
                axis="heads", out_kind="hidden"))
        self.gate_proj = ColumnParallelLinear(
            embed_dim, num_kv_heads, bias=False, init=init, axis=None,
            out_kind="hidden")
        self.out_proj = RowParallelLinear(
            num_heads * head_dim, embed_dim, bias=False, init=init,
            axis="heads")
        self.param("q_gain", (head_dim,), constant_init(qk_gain))
        self.param("k_gain", (head_dim,), constant_init(qk_gain))
        lo, hi = (math.log(g / (1.0 - g)) for g in gate_means)

        def gate_bias(key, shape, dtype):
            del key
            return jnp.linspace(lo, hi, shape[0]).astype(dtype)
        # (float32 whatever the weights are served in: it sets horizons)
        self.param("gate_bias", (num_kv_heads,), gate_bias,
                   dtype=jnp.float32)
        self._rope = rope_frequencies(head_dim, max_positions,
                                      theta=rope_theta)

    def kv_leaf_shapes(self) -> tuple:
        """No leaf a token: the state is a slot's."""
        return ()

    def _tiles(self) -> tuple:
        from hetu_tpu.ops.retention import feature_rows, value_rows
        return (self.num_kv_heads, feature_rows(self.head_dim),
                value_rows(self.head_dim), self.head_dim)

    def state_bytes(self) -> int:
        """Bytes a slot's state holds in one layer."""
        return 4 * math.prod(self._tiles())

    def init_leaves(self, layers: int, slots: int, sharding=None) -> tuple:
        return (jnp.zeros((layers, slots) + self._tiles(), jnp.float32,
                          device=sharding),)

    def _inputs(self, params, u, positions):
        """``u (b, s, E)``, ``positions (b, s)`` -> ``q (b, s, H, d)``,
        ``k``, ``v (b, s, Hkv, d)`` and ``log g (b, s, Hkv)`` float32."""
        dt = self.compute_dtype()
        cos, sin = self._rope

        def heads(proj, n):
            return getattr(self, proj)(params[proj], u).reshape(
                u.shape[:-1] + (n, self.head_dim))
        q = _gain(params, "q_gain", heads("q_proj", self.num_heads),
                  self.norm_eps, dt)
        k = _gain(params, "k_gain", heads("k_proj", self.num_kv_heads),
                  self.norm_eps, dt)
        gate = self.gate_proj(params["gate_proj"], u).astype(jnp.float32)
        return (apply_rotary(q, cos, sin, positions=positions),
                apply_rotary(k, cos, sin, positions=positions),
                heads("v_proj", self.num_kv_heads),
                jax.nn.log_sigmoid(gate + params["gate_bias"]))

    def _output(self, params, y):
        """``y (..., H, d)`` float32."""
        return self.out_proj(
            params["out_proj"], y.reshape(y.shape[:-2] + (-1,))
            .astype(self.compute_dtype()))

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, return_kv: bool = False):
        del attn_impl, attn_kernel       # no attention kernel here
        b, s, _ = x.shape
        if kv_cache is None:
            if return_kv or segment_ids is not None:
                raise SlotStateNotSupported(
                    "return_kv (the CP-prefill lane) and packed "
                    "documents: power retention has no (k, v) to hand "
                    "out, and its whole-sequence forward is one document "
                    "a row")
            from hetu_tpu.ops.retention import retention_recurrence
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            y = jax.vmap(lambda *t: retention_recurrence(
                *t, eps=self.eps)[0])(*self._inputs(params, x, positions))
            return self._output(params, y)
        from hetu_tpu.ops.retention_pallas import (
            hetu_retention_scan, hetu_retention_update,
        )
        (buf,), layer = kv_cache
        _, pos, valid, _, slot = _cached_rows(
            x, positions, slot_mask, block_tables, row_mask, pack,
            paged=False)
        q, k, v, log_g = self._inputs(params, x, positions)
        zero = jnp.zeros((), jnp.int32)
        if slot is None:
            with jax.named_scope("hetu.retention_update"):
                y, buf = hetu_retention_update(
                    q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], buf, valid,
                    eps=self.eps, layer=layer)
            y = y[:, None]
            stats = jnp.stack([jnp.sum(valid, dtype=jnp.int32), zero, zero])
        else:
            with jax.named_scope("hetu.retention_scan"):
                y, buf, runs = hetu_retention_scan(
                    q[0], k[0], v[0], log_g[0], buf, slot, pos, valid,
                    eps=self.eps, layer=layer, return_runs=True)
            y = y[None]
            stats = jnp.stack([zero, jnp.sum(valid, dtype=jnp.int32), runs])
        return self._output(params, y), (buf,), {"retention": stats}


def count_ssm_steps(values, tokens=None) -> None:
    """:class:`MambaMixer`'s ``layer_stats`` on the host: ``values
    (Mamba layers, 4)`` — of each layer's call ``[live, computed,
    advanced, stepped]``, as :func:`count_kda_steps` reads the delta
    rule's: a prefill pack's scan reports the grid steps that held a
    valid row and the grid steps run
    (``ops.selective_scan_pallas.hetu_selective_scan(return_steps=
    True)``) and zeros behind them, the decode rows' update zeros and
    then the live slots it advanced and the slot steps of its grid —
    into ``ssm_scan_steps_total{kind}`` and
    ``ssm_update_slots_total{kind}``."""
    import numpy as np
    from hetu_tpu import telemetry
    live, computed, advanced, stepped = np.asarray(
        values, np.int64).sum(axis=0).tolist()
    reg = telemetry.get_registry()
    if computed:
        c = reg.counter(
            "ssm_scan_steps_total",
            "grid steps of the selective-scan kernel: live = (piece, "
            "channel block) steps that held a valid row, computed = "
            "steps run (a chunk without a valid row costs one that "
            "writes zeros), summed over layer calls")
        c.inc(float(live), kind="live")
        c.inc(float(computed), kind="computed")
    if stepped:
        c = reg.counter(
            "ssm_update_slots_total",
            "slots of the selective scan's decode-row update kernel: "
            "live = slots whose state it advanced by a token (read "
            "once, written once), stepped = slot steps of its grid (a "
            "step behind the live ones moves nothing), summed over "
            "layer calls")
        c.inc(float(advanced), kind="live")
        c.inc(float(stepped), kind="stepped")


class MambaMixer(Module):
    """The Mamba-1 mixer of the Jamba family (``ops.selective_scan``;
    Gu & Dao, arXiv:2312.00752; Jamba, arXiv:2403.19887, whose one
    addition is the three inner norms): a selective scan over a
    DIAGONAL per-slot state behind a short causal convolution. With
    ``u`` the block's normed input, ``D`` inner channels, ``N``
    states::

        [x | z] = u W_in
        x <- SiLU(conv(x) + b_conv)                 a channel at a time
        [r | B | C] = x W_x;  r, B, C <- RMSNorm    each its own gain
        dt = softplus(r W_dt + b_dt),  A = -exp(A_log)
        h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
        y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]
        out = (y * SiLU(z)) W_out

    What is cached is a SLOT's, in TWO leaves (:meth:`init_leaves`):
    the float32 state ``(layers, slots, N, R, 128)`` — a state ``n``'s
    channels in ``R = D / 128`` rows of 128 lanes
    (``ops.selective_scan_pallas.state_tiles``: 16 x 40 x 128 at 5120
    channels, 327,680 B, nothing padded) — and the convolution's TAIL
    ``(layers, slots, tail_rows, D)``, the last inputs of the
    convolution: ``taps - 1`` of them in whole groups of four (3 -> 4;
    the window runs over ``tail_rows + 1`` taps whose first are zero —
    a leaf of 3 rows the TPU compiler re-lays whole at the step's entry
    and exit, two copies of 28.7 MB a step at the served size, a leaf
    of 4 rows it leaves where it lies). A run that starts at position 0 starts from a zero
    state AND a zero tail, whatever its slot held. The decode rows
    advance their slot by a token (``hetu.ssm_update``), a prefill
    pack's tokens theirs one after another (``hetu.ssm_scan``), both
    behind ``hetu.ssm_conv`` (``ops.kda``'s three convolution
    functions, the bias added behind them); each is ONE Pallas call a
    layer call on the state leaf where it lies
    (``ops.selective_scan_pallas``, interpreted on the CPU), and
    ``ops.selective_scan``'s forms are their oracles and never run
    here. No page is ever read or written.

    ``A_log``, ``b_dt``, the taps and ``D`` are DRAWN as the family
    initialises them: ``A_log[n, c] = log(n + 1)``, ``b_dt`` the inverse
    softplus of a step drawn log-uniform in ``dt_range`` — horizons from
    under a token to a thousand tokens across channels and states, so a
    program that loses the state between chunks, drops the tail or
    leaves the inner norms out must differ. The state, ``dt``, ``A``,
    the decays and the sum over ``n`` are float32; the projections take
    the compute dtype's operands and accumulate in float32 (``x_proj``
    and ``dt_proj`` keep their float32 results)."""

    cache_leaves = 2
    #: a cached call's third result (:func:`count_ssm_steps`)
    layer_stats = {"ssm_steps": ((4,), jnp.int32, count_ssm_steps)}

    def __init__(self, embed_dim: int, *, d_state: int = 16,
                 d_conv: int = 4, expand: int = 2,
                 dt_rank: Optional[int] = None, conv_bias: bool = True,
                 norm_eps: float = 1e-6, dt_range=(1e-3, 1e-1),
                 init=None):
        super().__init__()
        from hetu_tpu.nn.module import ones_init
        from hetu_tpu.ops.selective_scan_pallas import state_tiles
        self.d_inner, self.d_state = expand * embed_dim, d_state
        self.d_conv, self.norm_eps = d_conv, norm_eps
        self.dt_rank = dt_rank or -(-embed_dim // 16)
        self.conv_bias = conv_bias
        self._tiles = state_tiles(self.d_inner)
        #: rows of a slot's tail: the window's history in fours
        self.tail_rows = -(-(d_conv - 1) // 4) * 4
        init = init or normal_init(0.02)
        D, N, r = self.d_inner, d_state, self.dt_rank
        self.in_proj = ColumnParallelLinear(
            embed_dim, 2 * D, bias=False, init=init, axis="heads",
            out_kind="hidden")
        self.x_proj = ColumnParallelLinear(
            D, r + 2 * N, bias=False, init=init, axis=None,
            out_kind="hidden")
        self.dt_proj = ColumnParallelLinear(
            r, D, bias=False, init=init, axis=None, out_kind="hidden")
        self.out_proj = RowParallelLinear(D, embed_dim, bias=False,
                                          init=init, axis="heads")
        # (float32 whatever the weights are served in: they shape the
        # window, the step and the decays)
        self.param("conv", (d_conv, D), normal_init(d_conv ** -0.5),
                   dtype=jnp.float32)
        if conv_bias:
            self.param("conv_bias", (D,), normal_init(0.1),
                       dtype=jnp.float32)

        def a_log(key, shape, dtype):
            del key
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None],
                shape).astype(dtype)
        self.param("A_log", (N, D), a_log, dtype=jnp.float32)
        lo, hi = (math.log(v) for v in dt_range)

        def dt_bias(key, shape, dtype):
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        self.param("dt_bias", (D,), dt_bias, dtype=jnp.float32)
        self.param("D", (D,), ones_init(), dtype=jnp.float32)
        for name, n in (("dt_gain", r), ("b_gain", N), ("c_gain", N)):
            self.param(name, (n,), ones_init())

    def kv_leaf_shapes(self) -> tuple:
        """No leaf a token: the state and the tail are a slot's."""
        return ()

    def state_bytes(self) -> int:
        """Bytes a slot's state and tail hold in one layer."""
        return 4 * self.d_inner * (self.d_state + self.tail_rows)

    def init_leaves(self, layers: int, slots: int, sharding=None) -> tuple:
        return (jnp.zeros((layers, slots, self.d_state) + self._tiles,
                          jnp.float32, device=sharding),
                jnp.zeros((layers, slots, self.tail_rows, self.d_inner),
                          jnp.float32, device=sharding))

    def _accumulated(self, name, params, x):
        """``x W`` of a small projection, its float32 accumulation kept
        (the step and ``B``, ``C`` are not rounded to the operands')."""
        dt = self.compute_dtype()
        return jnp.matmul(x.astype(dt), params[name]["weight"].astype(dt),
                          preferred_element_type=jnp.float32)

    def _activate(self, params, y):
        """The convolution's result ``(N, D)`` -> ``(x, dt, B, C)``
        float32: the activated input and what it selects."""
        r, N = self.dt_rank, self.d_state
        if self.conv_bias:
            y = y + params["conv_bias"]
        x = jax.nn.silu(y)
        sel = self._accumulated("x_proj", params, x)
        parts = (sel[:, :r], sel[:, r:r + N], sel[:, r + N:])
        rr, B, C = (_gain(params, g, p, self.norm_eps, jnp.float32)
                    for g, p in zip(("dt_gain", "b_gain", "c_gain"), parts))
        dt = jax.nn.softplus(self._accumulated("dt_proj", params, rr)
                             + params["dt_bias"])
        return x, dt, B, C

    def _output(self, params, y, x, z):
        y = (y + params["D"] * x) * jax.nn.silu(z.astype(jnp.float32))
        return self.out_proj(params["out_proj"],
                             y.astype(self.compute_dtype()))

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl: str = "auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None, attn_kernel="reference",
                 pack=None, return_kv: bool = False):
        del attn_impl, attn_kernel       # no attention kernel here
        from hetu_tpu.ops import kda
        b, s, E = x.shape
        D, taps = self.d_inner, params["conv"]
        A = -jnp.exp(params["A_log"])
        if kv_cache is None:
            if return_kv or segment_ids is not None:
                raise SlotStateNotSupported(
                    "return_kv (the CP-prefill lane) and packed "
                    "documents: a selective scan has no (k, v) to hand "
                    "out, and its whole-sequence forward is one document "
                    "a row")
            from hetu_tpu.ops.selective_scan import selective_recurrence
            xz = self.in_proj(params["in_proj"], x.reshape(-1, E))
            y = jax.vmap(lambda a: kda.conv_sequence(a, taps))(
                xz[:, :D].reshape(b, s, D))
            xs, dt, B, C = self._activate(params, y.reshape(b * s, D))

            def rows(t):
                return t.reshape((b, s) + t.shape[1:])
            y = jax.vmap(lambda *t: selective_recurrence(
                t[0], t[1], A, t[2], t[3])[0])(
                    rows(xs), rows(dt), rows(B), rows(C))
            return self._output(params, y.reshape(b * s, D), xs,
                                xz[:, D:]).reshape(b, s, E)
        from hetu_tpu.ops.selective_scan_pallas import (
            hetu_selective_scan, hetu_selective_update,
        )
        (state, tail), layer = kv_cache
        u, pos, valid, _, slot = _cached_rows(
            x, positions, slot_mask, block_tables, row_mask, pack,
            paged=False)
        xz = self.in_proj(params["in_proj"], u)
        # (the reads of a slot's state and tail out of their leaves and
        # the writes back are the scopes': most of what a row moves)
        with jax.named_scope("hetu.ssm_conv"):
            # the window over the tail's rows: zero taps before the
            # model's own
            taps = jnp.pad(taps, ((self.tail_rows + 1 - self.d_conv, 0),
                                  (0, 0)))
            if slot is None:
                y, tail = kda.conv_rows(xz[:, :D], taps, tail, valid,
                                        layer=layer, fresh=pos == 0)
            else:
                y, tail = kda.conv_pack(xz[:, :D], taps, tail, slot, pos,
                                        valid, layer=layer)
        xs, dt, B, C = self._activate(params, y)
        none = jnp.zeros((2,), jnp.int32)
        if slot is None:
            with jax.named_scope("hetu.ssm_update"):
                y, state, steps = hetu_selective_update(
                    xs, dt, A, B, C, state, valid, layer=layer,
                    fresh=pos == 0, return_steps=True)
            steps = jnp.concatenate([none, steps])
        else:
            with jax.named_scope("hetu.ssm_scan"):
                y, state, steps = hetu_selective_scan(
                    xs, dt, A, B, C, state, slot, pos, valid,
                    layer=layer, return_steps=True)
            steps = jnp.concatenate([steps, none])
        return self._output(params, y, xs, xz[:, D:]).reshape(x.shape), \
            (state, tail), {"ssm_steps": steps}


def remat_policy(name: str):
    """Map a Strategy remat/offload name to a ``jax.checkpoint`` policy.

    Reference equivalents: recompute pass (``recompute/recompute.h:12``) and
    activation CPU offload pass (``offload/activation_cpu_offload.h:11``).
    """
    if name == "none":
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "selective":
        # dots + the flash-attention kernel residuals (tagged in
        # ``ops.flash_pallas._flash_core_fwd``): saving out/lse means the
        # backward runs only the flash bwd kernels, not fwd again
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"))
    if name == "offload":
        make = getattr(jax.checkpoint_policies,
                       "offload_dot_with_no_batch_dims", None)
        # host offload needs the TPU runtime's annotate_device_placement;
        # the CPU backend has no implementation (and GSPMD on CPU chokes
        # on the unsharded side-effect custom call) — degrade to full
        # remat there so offload strategies stay runnable in simulation
        if make is None or jax.default_backend() != "tpu":
            return jax.checkpoint_policies.nothing_saveable
        return make("device", "pinned_host")
    raise ValueError(
        f"remat must be none|full|selective|offload, got {name!r}")


class StackedBlocks(Module):
    """N identical blocks as one scan, params stacked on a leading ``layers``
    dim.

    The reference represents depth as N distinct subgraphs with per-block
    recompute/offload flags (`llama_model.py:342`); on TPU the idiomatic form
    is a single block traced once and scanned, with the stacked ``layers``
    axis available to the pipeline executor (axis rule ``"layers" → "pp"``)
    and ``jax.checkpoint`` applied per block for recompute parity.

    ``first_layer`` (0 on every model whose layers are all this block):
    the cache layer :meth:`decode` gives its first layer.

    ``layer_data`` (``{name: one value per layer}``, ``None`` on every
    model whose layers are alike) is how layers of ONE scanned block
    differ: each array rides the scan as xs and layer ``l``'s block is
    called with ``name=value[l]`` (a traced scalar) — a window, a flag
    — so a model that mixes layer kinds keeps one block, one scan and
    one stacked cache.
    """

    def __init__(self, make_block: Callable[[], Module], num_layers: int,
                 layer_data: Optional[dict] = None, first_layer: int = 0):
        super().__init__()
        self.num_layers = num_layers
        #: where this stack's layer 0 sits in the caches it decodes
        #: into (a model whose leading layers are another block keeps
        #: them outside the scan, in the same stacked arena)
        self.first_layer = int(first_layer)
        self._block = make_block()  # underscore: excluded from children()
        if layer_data is not None:
            layer_data = {k: jnp.asarray(v) for k, v in layer_data.items()}
            if any(v.shape[0] != num_layers for v in layer_data.values()):
                raise ValueError("layer_data needs one value per layer")
        self.layer_data = layer_data

    @property
    def block(self) -> Module:
        return self._block

    def children(self):
        # expose the template so module-tree walks (named_modules, LoRA
        # injection) reach the per-layer submodules; abstract_specs is
        # overridden so this never double-counts params
        return {"block": self._block}

    def abstract_specs(self) -> dict:
        inner = self._block.abstract_specs()
        L = self.num_layers

        def wrap(spec: ParamSpec) -> ParamSpec:
            def init(key, shape, dtype, _orig=spec):
                keys = jax.random.split(key, shape[0])
                return jax.vmap(
                    lambda k: _orig.init(k, _orig.shape, dtype))(keys)
            axes = spec.axes if spec.axes is not None \
                else (None,) * len(spec.shape)
            return ParamSpec((L,) + spec.shape, init, spec.dtype,
                             ("layers",) + axes)

        return jax.tree.map(wrap, inner,
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    @property
    def returns_aux(self):
        return self._block.returns_aux

    def __call__(self, params, x, *, remat: str = "none",
                 remat_mask: Optional[Sequence[bool]] = None,
                 unroll: bool = False, **kwargs):
        """``remat_mask``: per-layer recompute flags (the reference's
        per-block recompute config, ``recompute.h:12`` via ds-config
        ``recompute_config``; emitted by ``search_layerwise``). Layers are
        grouped into consecutive runs, one scan per run, remat applied to
        the True runs (policy = ``remat`` or "full" when remat is none).

        ``unroll`` unrolls the layer scan into straight-line code: XLA
        then schedules across layer boundaries and drops the per-layer
        dynamic-update-slice residual stacking (measurably faster on a
        single chip; costs compile time ∝ layers)."""
        # layer count comes from the params actually passed — pipeline /
        # hetero executors call this with a per-stage CHUNK whose leading
        # axis is shorter than the full model's num_layers
        n_layers = jax.tree.leaves(params)[0].shape[0]
        unroll_n = n_layers if unroll else 1
        # per-layer dropout keys ride the scan as xs (None = deterministic)
        dropout_key = kwargs.pop("dropout_key", None)
        layer_keys = None if dropout_key is None \
            else jax.random.split(dropout_key, n_layers)
        layer_data = self.layer_data
        if layer_data is not None and n_layers != self.num_layers:
            raise NotImplementedError(
                "a chunk of the layers of a model whose layers differ "
                "by layer_data")

        def call_block(layer_params, h, xs_key, ld=None):
            kw = kwargs if ld is None else {**kwargs, **ld}
            if xs_key is not None:
                return self._block(layer_params, h, dropout_key=xs_key,
                                   **kw)
            return self._block(layer_params, h, **kw)

        # per-layer ZeRO-3 gather ring (Strategy(fsdp_overlap="ring")):
        # block params arrive dp-sharded on inner dims and each layer is
        # gathered explicitly — block k+1's gather prefetched under
        # block k's compute — instead of GSPMD's monolithic all-gather
        ctx = current_act_sharding()
        if (ctx is not None
                and getattr(ctx, "fsdp_overlap", "off") == "ring"
                and getattr(ctx, "fsdp_specs", None) is not None
                and ctx.mesh.shape.get("dp", 1) > 1):
            if layer_data is not None:
                raise NotImplementedError(
                    "the ZeRO-3 gather ring with layer_data")
            return self._fsdp_ring_scan(
                params, x, ctx, remat=remat, remat_mask=remat_mask,
                unroll=unroll, n_layers=n_layers, layer_keys=layer_keys,
                call_block=call_block)

        if self._block.returns_aux:
            def body(carry, xs):
                layer_params, xs_key, ld = xs
                h, aux = carry
                h, a = call_block(layer_params, h, xs_key, ld)
                return (h, aux + a), None
        else:
            def body(carry, xs):
                layer_params, xs_key, ld = xs
                return call_block(layer_params, carry, xs_key, ld), None

        def rematted(b, policy_name):
            return jax.checkpoint(b, policy=remat_policy(policy_name),
                                  prevent_cse=False)

        aux0 = jnp.zeros([], jnp.float32)
        carry0 = (x, aux0) if self._block.returns_aux else x

        if remat_mask is not None:
            if len(remat_mask) != n_layers:
                raise ValueError(
                    f"remat_mask has {len(remat_mask)} entries for "
                    f"{n_layers} layers")
            policy_name = remat if remat != "none" else "full"
            runs = []  # (start, stop, flag) consecutive same-flag runs
            start = 0
            for i in range(1, n_layers + 1):
                if i == n_layers \
                        or bool(remat_mask[i]) != bool(remat_mask[start]):
                    runs.append((start, i, bool(remat_mask[start])))
                    start = i
            carry = carry0
            for lo, hi, flag in runs:
                seg = jax.tree.map(lambda p: p[lo:hi], params)
                seg_keys = None if layer_keys is None else layer_keys[lo:hi]
                seg_ld = None if layer_data is None else \
                    {k: v[lo:hi] for k, v in layer_data.items()}
                b = rematted(body, policy_name) if flag else body
                carry, _ = jax.lax.scan(b, carry, (seg, seg_keys, seg_ld),
                                        unroll=hi - lo if unroll else 1)
            if self._block.returns_aux:
                return carry
            return carry

        if remat != "none":
            body = rematted(body, remat)
        if self._block.returns_aux:
            (x, aux), _ = jax.lax.scan(
                body, carry0, (params, layer_keys, layer_data),
                unroll=unroll_n)
            return x, aux
        x, _ = jax.lax.scan(body, x, (params, layer_keys, layer_data),
                            unroll=unroll_n)
        return x

    def _fsdp_ring_scan(self, params, x, ctx, *, remat, remat_mask,
                        unroll, n_layers, layer_keys, call_block):
        """ZeRO-3 per-block execution: every layer's dp-sharded params
        ring-gather (``parallel.overlap.ring_gather_block_params``)
        instead of riding one monolithic GSPMD all-gather.

        Two scan shapes, chosen per remat mode:

        - no remat → **prefetch-by-one**: the gathered params of layer
          *k* ride the scan carry while layer *k+1*'s gather is issued at
          the top of the body — the ring hops share no data with the
          block matmuls, so the scheduler overlaps them (ZeRO SC'20 §5.3
          prefetch);
        - remat → **gather inside the checkpointed region**: the saved
          residuals are the 1/ndp local shards, so the backward
          REGATHERS each block instead of pinning full replicated layer
          params (prefetch-by-one would make the gathered carry a saved
          checkpoint input, defeating ZeRO-3's memory point).
        """
        from hetu_tpu.parallel.overlap import (
            record_fsdp_gather_bytes, ring_gather_block_params,
        )
        mesh, specs = ctx.mesh, ctx.fsdp_specs
        ndp = mesh.shape["dp"]
        # analytic trace-time accounting: stacked leaf sizes already
        # cover every layer, and the ring is an overlapping path.
        # Rematted layers gather TWICE per step (the backward regathers
        # inside the checkpointed region) — scale their share.
        if remat_mask is not None:
            n_regather = sum(bool(f) for f in remat_mask)
        elif remat != "none":
            n_regather = n_layers
        else:
            n_regather = 0
        record_fsdp_gather_bytes(
            params, specs, ndp,
            n_layers=(n_layers + n_regather) / n_layers, overlapped=True)

        def gather(layer_params):
            return ring_gather_block_params(layer_params, specs,
                                            mesh=mesh)

        aux_mode = self._block.returns_aux

        def compute(g_params, carry, xs_key):
            if aux_mode:
                h, aux = carry
                h2, a = call_block(g_params, h, xs_key)
                return (h2, aux + a)
            return call_block(g_params, carry, xs_key)

        def seg_prefetch(carry, lo, hi):
            g0 = gather(jax.tree.map(lambda p: p[lo], params))
            idxs = jnp.arange(lo, hi)
            keys = None if layer_keys is None else layer_keys[lo:hi]

            def body(c, xs):
                i, xs_key = xs
                inner, g_cur = c
                # issue layer i+1's gather BEFORE layer i's compute —
                # the two share no data, XLA overlaps them (the last
                # iteration regathers hi-1; its result is discarded)
                nxt = jnp.minimum(i + 1, hi - 1)
                p_next = jax.tree.map(
                    lambda p: jax.lax.dynamic_index_in_dim(
                        p, nxt, 0, keepdims=False), params)
                g_next = gather(p_next)
                return (compute(g_cur, inner, xs_key), g_next), None

            (carry, _), _ = jax.lax.scan(
                body, (carry, g0), (idxs, keys),
                unroll=(hi - lo) if unroll else 1)
            return carry

        def seg_remat(carry, lo, hi, policy_name):
            seg = jax.tree.map(lambda p: p[lo:hi], params)
            keys = None if layer_keys is None else layer_keys[lo:hi]

            def body(c, xs):
                lp, xs_key = xs
                return compute(gather(lp), c, xs_key), None

            b = jax.checkpoint(body, policy=remat_policy(policy_name),
                               prevent_cse=False)
            carry, _ = jax.lax.scan(
                b, carry, (seg, keys),
                unroll=(hi - lo) if unroll else 1)
            return carry

        carry = (x, jnp.zeros([], jnp.float32)) if aux_mode else x
        if remat_mask is not None:
            if len(remat_mask) != n_layers:
                raise ValueError(
                    f"remat_mask has {len(remat_mask)} entries for "
                    f"{n_layers} layers")
            policy_name = remat if remat != "none" else "full"
            runs = []
            start = 0
            for i in range(1, n_layers + 1):
                if i == n_layers \
                        or bool(remat_mask[i]) != bool(remat_mask[start]):
                    runs.append((start, i, bool(remat_mask[start])))
                    start = i
            for lo, hi, flag in runs:
                carry = seg_remat(carry, lo, hi, policy_name) if flag \
                    else seg_prefetch(carry, lo, hi)
        elif remat != "none":
            carry = seg_remat(carry, 0, n_layers, remat)
        else:
            carry = seg_prefetch(carry, 0, n_layers)
        return carry

    def decode(self, params, x, caches, *, w8a8_mask=None,
               w8a8_wq=None, lora=None, with_stats=False, **kwargs):
        """Incremental decoding: scan layers CARRYING the stacked KV
        caches (leaves shaped (layers, b, max_len, hkv, d), or the
        paged arena's (layers, n_blocks, block_size, hkv*d)). The
        caches are part of the scan's carry beside the activations, and
        layer ``l``'s attention gets all of them with its index
        (:class:`LayerKV`), writes its rows at ``[l]`` and reads at
        ``[l]`` — as xs and ys the scan would slice a layer's leaf out
        and stack it back per layer, two copies of a leaf as large as
        the arena is, and hold a second arena for the stacked output.

        ``w8a8_mask`` ((layers,) bool, optional) rides the scan as xs:
        layer ``l``'s decode FFN takes the W8A8 int8 lane iff
        ``w8a8_mask[l]`` (``ParallelMLP.__call__(w8a8=...)``) — the
        per-layer A/B knob for quantized decode compute. ``None`` (the
        default) never touches the flag and stays bit-identical to the
        historical path. ``w8a8_wq`` (optional, a stacked
        ``prequantize`` tree with (layers, ...) leaves) also rides the
        scan as xs so each layer streams its pre-quantized int8
        weights instead of re-quantizing per step.

        ``lora`` (optional) is the multi-tenant adapter arena:
        ``{"ids": (b, s) int32 pages, "pages": {proj: {"A": (L, P, in,
        r), "B": (L, P, r, out)}}}``. The stacked pages ride the scan
        as xs (each layer sees its (P, ...) slice) while the per-token
        page ids close over the body; each layer's targeted
        projections add the :func:`lora_apply` BGMV delta.

        A block whose layers differ by ``layer_data`` gets each value
        as a keyword (they ride the scan as xs). A block's ``unsliced``
        parameter paths pass the scan whole and reach it as
        ``StackedLeaf(all layers, this layer)``: an operand a kernel
        cannot read through a dynamic slice would be copied per layer.
        A block that declares ``layer_stats`` (``{name: (shape, dtype,
        emit)}``) returns ``{name: value}`` as a third result of its
        decode call; ``with_stats=True`` returns them stacked over the
        layers as a third result here (``{}`` from any other block) —
        the serving step hands them out and the engine gives each
        executed lane's to ``emit(values, tokens=the token rows a call
        of that lane's layers takes)`` on the host."""
        xs = {"p": params,
              "layer": jnp.arange(self.num_layers, dtype=jnp.int32)}
        if self.first_layer:
            # the caches' layer; ``unsliced`` leaves are this stack's
            xs["cache_layer"] = xs["layer"] + self.first_layer
        lora_ids = None
        if w8a8_mask is not None:
            xs["w8a8"] = jnp.asarray(w8a8_mask, bool)
            if w8a8_wq is not None:
                xs["wq"] = w8a8_wq
        if lora:
            xs["lora"] = lora["pages"]
            lora_ids = lora["ids"]
        if self.layer_data is not None:
            xs["ld"] = self.layer_data
        whole = {}
        for path in getattr(self._block, "unsliced", ()):
            xs["p"], whole[path] = _pop_path(xs["p"], path)

        def body(carry, inputs):
            h, caches = carry
            kw = dict(kwargs, **inputs.get("ld", {}))
            if "w8a8" in inputs:
                kw["w8a8"] = inputs["w8a8"]
            if "wq" in inputs:
                kw["w8a8_wq"] = inputs["wq"]
            if "lora" in inputs:
                kw["lora"] = {"ids": lora_ids, "pages": inputs["lora"]}
            layer_params = inputs["p"]
            for path, stack in whole.items():
                layer_params = _set_path(
                    layer_params, path, StackedLeaf(stack, inputs["layer"]))
            h, caches, *stats = self._block(
                layer_params, h, kv_cache=LayerKV(
                    caches, inputs.get("cache_layer", inputs["layer"])),
                **kw)
            return (h, caches), (stats[0] if stats else None)

        (x, caches), stats = jax.lax.scan(body, (x, tuple(caches)), xs)
        return (x, caches, stats or {}) if with_stats else (x, caches)

    @property
    def layer_stats(self) -> dict:
        return self._block.layer_stats

    def layer_stats_zeros(self) -> dict:
        """Zeros with the shape of :meth:`decode`'s third result (``{}``
        for a block that reports nothing): what a lane that did not run
        returns in their place."""
        return {name: jnp.zeros((self.num_layers,) + tuple(shape), dtype)
                for name, (shape, dtype, _) in self.layer_stats.items()}

    # -- what the serving engine asks a model's ``blocks`` about their
    # caches (:class:`LayerStack` answers the same for layers of
    # several kinds)
    #: a recurrent state per SLOT beside the pages?
    slot_state = False
    #: token rows in pages? (a :class:`LayerStack` may have none)
    paged = True

    def init_paged_caches(self, n_blocks: int, block_size: int, dtype,
                          slots: int = 0, sharding=None) -> tuple:
        """The block-paged arena, ``(layers, n_blocks, block_size,
        hkv*d)`` leaves (:func:`kv_leaves`); no leaf over ``slots``."""
        return self.block.attn.init_leaves(
            self.num_layers, n_blocks, block_size, dtype, sharding)

    def cache_bytes(self, itemsize: int) -> dict:
        """``kv_row_bytes{kind}``: a token's bytes in ONE layer."""
        return {"row": self.block.attn.row_bytes(itemsize), "state": {}}

    def refuse_serving(self, **asked) -> None:
        """A cache of token rows in pages alone refuses nothing."""

    def prefill(self, params, x, *, positions=None, segment_ids=None,
                attn_impl: str = "auto"):
        """Training-mode forward that ALSO returns every layer's
        rotary-applied ``(k, v)``: ``(h, (k, v))`` with k/v shaped
        ``(layers, b, s, hkv, d)``.

        The serving CP-prefill lane's core: a long prompt runs through
        the SAME attention path training uses — under a cp-sharded
        activation context that means ring/ulysses attention over the
        mesh's cp axis — and the stacked KV is what the caller scatters
        into the paged serving arena. Inference-only by construction
        (no dropout, no remat; MoE aux losses are discarded)."""
        def body(h, xs):
            layer_params, ld = xs
            out = self._block(layer_params, h, positions=positions,
                              segment_ids=segment_ids,
                              attn_impl=attn_impl, return_kv=True,
                              **(ld or {}))
            out, kv = out
            if self._block.returns_aux:
                out, _ = out
            return out, kv

        x, kvs = jax.lax.scan(body, x, (params, self.layer_data))
        return x, kvs


class PreNormBlock(Module):
    """One sequential pre-norm layer of a drawn decoder (``n`` =
    RMSNorm, no biases): ``h = x + a Mixer(n1(x))``, ``y = h + a
    FFN(n2(h))``. It is handed its ``mixer`` (an attention of this
    file), its FFN — a dense ``mlp``, or the ``shared`` experts (ONE
    gated MLP; ``None`` where the model has none) summed with a routed
    ``moe``
    (:class:`~hetu_tpu.nn.moe.ExpertShareMoE`) —, the residual scale
    ``a`` and the operands' ``compute_dtype`` ("bfloat16": bf16
    operands, float32 accumulation; the residual stream, the norms and
    the router stay float32). ``zero_centered`` (a standard deviation):
    both norms' gains are ``1 + w``, ``w`` drawn at it. ``shared_gate``:
    the shared expert is weighted ``sigmoid(u w_sg)``, one number a
    token (``shared_gate (features, 1)``, float32 inside).
    ``mixer_scope`` names a device scope around the mixer's call. A
    cached call returns ``(y, cache)``
    and, where the mixer or the experts declare ``layer_stats``, what
    they report as a third result (the experts' under ``moe_<name>``).
    """
    returns_aux = False

    def __init__(self, features: int, mixer: Module, *, eps: float,
                 mlp: Optional[Module] = None,
                 shared: Optional[Module] = None,
                 moe: Optional[Module] = None,
                 residual_scale: float = 1.0,
                 compute_dtype: str = "float32", model: str = "the model",
                 zero_centered: float = 0.0, shared_gate: bool = False,
                 mixer_scope: Optional[str] = None):
        super().__init__()
        self.norm1 = RMSNorm(features, eps=eps, zero_centered=zero_centered)
        self.norm2 = RMSNorm(features, eps=eps, zero_centered=zero_centered)
        self._mixer_scope = mixer_scope
        self._shared_gate = bool(shared_gate) and shared is not None
        if self._shared_gate:
            self.param("shared_gate", (features, 1), normal_init(0.02))
        self.attn = mixer
        self.layer_stats = dict(mixer.layer_stats)
        self._dense = moe is None
        if self._dense:
            self.mlp = mlp
        else:
            if shared is not None:      # (a model may have none)
                self.shared = shared
            self._shared = shared is not None
            self.moe = moe
            #: the grouped expert matmul cannot read through the layer
            #: scan's slice (``StackedBlocks.decode``)
            self.unsliced = (("moe", "wg"), ("moe", "wi"), ("moe", "wo"))
            self.layer_stats.update(
                {"moe_" + k: v for k, v in moe.layer_stats.items()})
        self._alpha = residual_scale
        self._policy = {"float32": "fp32",
                        "bfloat16": "bf16"}[compute_dtype]
        self._model = model

    def _add(self, x, branch):
        branch = branch.astype(x.dtype)
        return x + (branch if self._alpha == 1.0
                    else self._alpha * branch)

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl="auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None,
                 attn_kernel="reference", pack=None, w8a8=None,
                 w8a8_wq=None, lora=None, dropout_key=None,
                 return_kv=False):
        if w8a8 is not None or lora or dropout_key is not None:
            raise NotImplementedError(
                f"{self._model} has no W8A8, LoRA or dropout lane")
        new_cache, stats = None, {}
        u = self.norm1(params["norm1"], x)              # float32
        with autocast(self._policy), (
                jax.named_scope(self._mixer_scope) if self._mixer_scope
                else contextlib.nullcontext()):
            if kv_cache is not None:
                a, new_cache, *st = self.attn(
                    params["attn"], u, positions=positions,
                    kv_cache=kv_cache, slot_mask=slot_mask,
                    block_tables=block_tables, row_mask=row_mask,
                    attn_kernel=attn_kernel, pack=pack)
                stats.update(*st)
            else:
                a = self.attn(params["attn"], u, positions=positions,
                              segment_ids=segment_ids, attn_impl=attn_impl,
                              return_kv=return_kv)
        h = self._add(x, a)
        u = self.norm2(params["norm2"], h)              # float32
        with autocast(self._policy):
            if self._dense:
                f = self.mlp(params["mlp"], u)
            else:
                if self._shared:
                    with jax.named_scope("hetu.moe_shared"):
                        shared = self.shared(params["shared"], u)
                        if self._shared_gate:
                            shared = shared.astype(jnp.float32) \
                                * jax.nn.sigmoid(jnp.matmul(
                                    u.astype(jnp.float32),
                                    params["shared_gate"].astype(
                                        jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST))
                routed, st = self.moe(params["moe"], u, return_stats=True)
                f = routed.astype(jnp.float32)
                if self._shared:
                    f = shared.astype(jnp.float32) + f
                stats.update({"moe_" + k: v for k, v in st.items()})
        y = self._add(h, f)
        if kv_cache is None:
            return act_constrain(y, "tokens")
        return (y, new_cache, stats) if stats else (y, new_cache)


class LayerStack(Module):
    """The layers of a decoder whose layers are NOT all one block, from
    the list of their mixer ``kinds`` and ``make_block(kind, dense)``:
    the first ``n_dense`` layers (``dense=True``: another FFN) run one
    by one, every run of consecutive like layers behind them is ONE
    :class:`StackedBlocks` scan. To the serving engine it is what
    ``StackedBlocks`` is.

    Each kind counts ITS OWN layers in its cache leaves (a run's
    ``first_layer`` is that kind's layers before it) — no page for a
    layer that has no keys. The caches are every kind's leaves, as its
    mixer builds them (``init_leaves``): first the kinds that keep
    token rows in pages (``kv_leaf_shapes()`` not empty), then those
    that keep a state per SLOT, each group in the order the kinds first
    appear. ``block`` is the first paged kind's first scanned block:
    its attention speaks for the arena (heads, row width, page size).
    Where NO kind is paged (``paged`` is False: every layer keeps a
    state a slot) the caches are the slot leaves alone and ``block`` is
    the first kind's first scanned block.

    The parameters are ``dense.<i>`` and ``runs.<i>``; ``lone_run``
    names the ONE run of a stack that stores it under that name
    instead."""

    #: no layer differs by data (``StackedBlocks.layer_data``)
    layer_data = None

    def __init__(self, kinds: Sequence[str],
                 make_block: Callable[[str, bool], Module], *,
                 n_dense: int = 0, lone_run: Optional[str] = None,
                 model: str = "the model"):
        super().__init__()
        kinds = tuple(kinds)
        self.num_layers = len(kinds)
        #: kind -> its layers (while building: those so far)
        self.layers_of = count = dict.fromkeys(kinds, 0)
        mixers = {}                                  # kind -> one of it
        self.dense, self._dense_at = [], []
        for kind in kinds[:n_dense]:
            self.dense.append(make_block(kind, True))
            mixers.setdefault(kind, self.dense[-1].attn)
            self._dense_at.append((kind, count[kind]))
            count[kind] += 1
        self._runs, self.run_kinds = [], []
        i = n_dense
        while i < len(kinds):
            kind, n = kinds[i], 1
            while i + n < len(kinds) and kinds[i + n] == kind:
                n += 1
            self._runs.append(StackedBlocks(
                lambda kind=kind: make_block(kind, False), n,
                first_layer=count[kind]))
            mixers.setdefault(kind, self._runs[-1].block.attn)
            self.run_kinds.append(kind)
            count[kind] += n
            i += n
        if lone_run is None:
            self.runs = self._runs
        else:
            (run,) = self._runs
            setattr(self, lone_run, run)
        self._lone_run, self._model = lone_run, model
        # the caches' order: the paged kinds, then the per-slot ones
        paged = [k for k, m in mixers.items() if m.kv_leaf_shapes()]
        self._mixers = {k: mixers[k] for k in
                        paged + [k for k in mixers if k not in paged]}
        self.slot_state = len(paged) < len(mixers)
        #: does any layer keep token rows in pages? A stack whose every
        #: kind keeps a state a slot has no arena at all
        self.paged = bool(paged)
        head = paged[0] if paged else next(iter(self._mixers))
        self._block = next(
            (r.block for r, k in zip(self._runs, self.run_kinds)
             if k == head), None)
        if self._block is None:
            raise ValueError(
                f"{kinds} behind {n_dense} unscanned layers: at least "
                f"one scanned layer of the kind that speaks for the "
                f"caches ({head!r}: the first that keeps token rows in "
                f"pages, or the first kind where none does)")
        #: every block's ``layer_stats`` (like names are like stats)
        self.layer_stats = {}
        for blk, _ in self._reporting():
            self.layer_stats.update(blk.layer_stats)

    @property
    def block(self) -> Module:
        return self._block

    def _parts(self, params) -> list:
        """``(block or run, its parameters, its kind, an unscanned
        block's layer in its kind's leaves — ``None`` for a run)`` of
        every unscanned layer and every run, in layer order."""
        runs = [params[self._lone_run]] if self._lone_run else \
            [params["runs"][str(i)] for i in range(len(self._runs))]
        return [(b, params["dense"][str(i)], *self._dense_at[i])
                for i, b in enumerate(self.dense)] \
            + [(r, p, kind, None)
               for r, p, kind in zip(self._runs, runs, self.run_kinds)]

    def __call__(self, params, x, **kwargs):
        for part, p, _, _ in self._parts(params):
            x = part(p, x, **kwargs)
        return x

    # -- the caches ----------------------------------------------------------
    def init_paged_caches(self, n_blocks: int, block_size: int, dtype,
                          slots: int, sharding=None) -> tuple:
        """Every kind's leaves over ITS layers: the paged kinds' over
        the pages, then the per-slot kinds' over the ``slots``."""
        leaves = ()
        for kind, mixer in self._mixers.items():
            n = self.layers_of[kind]
            leaves += mixer.init_leaves(
                n, n_blocks, block_size, dtype, sharding) \
                if mixer.kv_leaf_shapes() \
                else mixer.init_leaves(n, slots, sharding)
        return leaves

    def cache_bytes(self, itemsize: int) -> dict:
        """``kv_row_bytes{kind}`` / ``kv_state_bytes{kind}``: a token's
        bytes by leaf and a slot's state, each over ALL the layers that
        keep it — where every layer keeps the same, a token's bytes in
        ONE layer, as :meth:`StackedBlocks.cache_bytes` gives them."""
        rows, state = {}, {}
        for kind, mixer in self._mixers.items():
            n = self.layers_of[kind] if len(self._mixers) > 1 else 1
            if mixer.kv_leaf_shapes():
                for leaf, b in mixer.row_bytes(itemsize).items():
                    rows[leaf] = rows.get(leaf, 0) + b * n
            else:
                # (a slot's state is what admission prices where no
                # layer has rows: over all its layers, always)
                state["slot"] = state.get("slot", 0) \
                    + mixer.state_bytes() * self.layers_of[kind]
        return {"row": rows, "state": state}

    def refuse_serving(self, **asked) -> None:
        """An honest refusal, by name, of what assumes a cache of token
        rows in pages alone (``asked``: feature -> whether it is on),
        where some layers keep a state per slot."""
        for what, on in asked.items():
            if on and self.slot_state:
                raise SlotStateNotSupported(
                    f"{what} is not available over a per-slot recurrent "
                    f"state: it would need the state snapshotted (or "
                    f"rolled back) with the pages")

    def decode(self, params, x, caches, *, with_stats=False,
               w8a8_mask=None, w8a8_wq=None, lora=None, **kwargs):
        """:meth:`StackedBlocks.decode` over every layer: a layer or a
        run gets its kind's leaves and hands them back; what the layers
        report is concatenated over the layers that report it."""
        if w8a8_mask is not None or w8a8_wq is not None or lora:
            raise NotImplementedError(
                f"{self._model} has no W8A8 or LoRA lane")
        caches, leaves = tuple(caches), {}
        for kind, mixer in self._mixers.items():
            n = mixer.cache_leaves
            leaves[kind], caches = caches[:n], caches[n:]
        if caches:
            raise NotImplementedError(
                "leaves beyond the mixers' own (the int8 arena's "
                "scales) under layers of several kinds")
        stats = {}
        for part, p, kind, layer in self._parts(params):
            if layer is None:
                x, leaves[kind], st = part.decode(
                    p, x, leaves[kind], with_stats=True, **kwargs)
            else:
                x, leaves[kind], *st = part(
                    p, x, kv_cache=LayerKV(
                        leaves[kind], jnp.asarray(layer, jnp.int32)),
                    **kwargs)
                st = {k: v[None] for k, v in st[0].items()} if st else {}
            for name, v in st.items():
                stats.setdefault(name, []).append(v)
        caches = sum((tuple(leaves[k]) for k in self._mixers), ())
        if not with_stats:
            return x, caches
        return x, caches, {name: jnp.concatenate(v)
                           for name, v in stats.items()}

    def _reporting(self) -> list:
        """``(block, its layers)`` of every unscanned layer and run."""
        return [(b, 1) for b in self.dense] \
            + [(r.block, r.num_layers) for r in self._runs]

    def layer_stats_zeros(self) -> dict:
        layers = {}
        for blk, n in self._reporting():
            for name in blk.layer_stats:
                layers[name] = layers.get(name, 0) + n
        return {name: jnp.zeros((layers[name],) + tuple(shape), dtype)
                for name, (shape, dtype, _) in self.layer_stats.items()}

    def prefill(self, *args, **kwargs):
        raise (SlotStateNotSupported if self.slot_state
               else LatentKVNotSupported if self._block.attn.latent
               else NotImplementedError)(
            "StackedBlocks.prefill (the CP-prefill lane) returns "
            "per-head (k, v) of every layer: layers of several kinds "
            "have them in no one shape, a latent layer caches one row a "
            "token and a per-slot state has none")
