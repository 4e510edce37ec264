"""Latent-attention (MLA) decoders with bias-corrected routed experts
behind leading dense layers (``model_type: deepseek_v3`` and its kin).

The config dataclass keeps the published key names. Per layer (``x`` is
``(T, hidden)``, no biases, ``n`` = RMSNorm):
``h = x + Attn(n1(x))``, ``y = h + FFN(n2(h))``.

- attention: :class:`~hetu_tpu.nn.parallel.LatentAttention` — queries of
  ``[qk_nope_head_dim ‖ qk_rope_head_dim]`` a head (``q_lora_rank``
  null), keys and values compressed to ONE ``kv_lora_rank + rope`` row a
  token, which is what the cache holds; every cached path attends in
  that latent space (the absorbed form), the whole-sequence forward in
  the expanded form;
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``. They sit OUTSIDE the layer scan and write the
  same stacked arena at their own layer index;
- FFN of every other layer (``moe_layer_freq`` 1): a sigmoid router over
  ``n_routed_experts`` with a per-expert selection bias
  (``topk_method: noaux_tc``; ``n_group`` = ``topk_group`` = 1, so no
  group limit): the ``num_experts_per_tok`` largest ``s + b`` are
  chosen, weighted ``routed_scaling_factor * s_e / sum_chosen s``
  (:class:`~hetu_tpu.nn.moe.ExpertShareMoE`, ``select_bias`` and
  ``scale``; no token is dropped), beside ``n_shared_experts`` shared
  SwiGLU experts that are SUMMED: one gated MLP ``n_shared_experts x
  moe_intermediate_size`` wide.

A final RMSNorm, then an UNTIED head. Operands: ``compute_dtype``
("bfloat16" to serve: bf16 operands, float32 accumulation; "float32" in
the CPU tests) is what the attention, shared and expert matmuls take;
the residual stream, the norms and the router stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.dtypes import autocast
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import Module, normal_init
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import (
    LatentAttention, LatentKVNotSupported, LayerKV, ParallelMLP,
    StackedBlocks, VocabParallelEmbedding,
)
from hetu_tpu.parallel.sharding import act_constrain


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264           # the dense layers' FFN
    moe_intermediate_size: int = 1408        # ONE routed or shared expert
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.446
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 800000.0
    max_position_embeddings: int = 131072
    #: the arena row's width (None: ``kv_lora_rank + qk_rope_head_dim``)
    stored_row: Optional[int] = None
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError("query compression (q_lora_rank)")
        if not 1 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers: at least one dense "
                f"and one expert layer")

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @classmethod
    def tiny(cls, **kw):
        """Test size: 1 dense + 3 expert layers, 4 heads of nope 16 /
        rope 8 / v 16, latent 32, 8 experts top-3, 2 shared."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
            n_shared_experts=2, num_experts_per_tok=3,
            max_position_embeddings=128), **kw})


class MLABlock(Module):
    """One layer: latent attention, then a dense SwiGLU (``dense``) or
    the routed experts beside the shared ones."""
    returns_aux = False

    def __init__(self, cfg: MLAMoEConfig, *, dense: bool):
        super().__init__()
        init = normal_init(cfg.init_std)
        self.norm1 = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.norm2 = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.attn = LatentAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            kv_rank=cfg.kv_lora_rank, nope_dim=cfg.qk_nope_head_dim,
            rope_dim=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
            stored_row=cfg.stored_row, rope_theta=cfg.rope_theta,
            norm_eps=cfg.rms_norm_eps, max_positions=cfg.max_positions,
            init=init)
        self._dense = dense
        if dense:
            self.mlp = ParallelMLP(cfg.hidden_size, cfg.intermediate_size,
                                   bias=False, gated=True)
        else:
            self.shared = ParallelMLP(
                cfg.hidden_size,
                cfg.n_shared_experts * cfg.moe_intermediate_size,
                bias=False, gated=True)
            self.moe = ExpertShareMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, k=cfg.num_experts_per_tok,
                select_bias=True, scale=cfg.routed_scaling_factor,
                init=init)
            #: the grouped expert matmul cannot read through the layer
            #: scan's slice (``StackedBlocks.decode``)
            self.unsliced = (("moe", "wg"), ("moe", "wi"), ("moe", "wo"))
            self.layer_stats = {"moe_local_sizes": (
                (cfg.n_routed_experts,), jnp.int32, self.moe.count_share)}
        self._policy = {"float32": "fp32",
                        "bfloat16": "bf16"}[cfg.compute_dtype]

    def _ffn(self, params, u):
        """``(FFN(u), the routed experts' group sizes or None)``."""
        if self._dense:
            return self.mlp(params["mlp"], u), None
        with jax.named_scope("hetu.moe_shared"):
            shared = self.shared(params["shared"], u)
        routed, sizes = self.moe(params["moe"], u, return_sizes=True)
        return shared.astype(jnp.float32) + routed.astype(jnp.float32), \
            sizes

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl="auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None,
                 attn_kernel="reference", pack=None, w8a8=None,
                 w8a8_wq=None, lora=None, dropout_key=None,
                 return_kv=False):
        if w8a8 is not None or lora or dropout_key is not None:
            raise NotImplementedError(
                "mla_moe has no W8A8, LoRA or dropout lane")
        new_cache = None
        u = self.norm1(params["norm1"], x)              # float32
        with autocast(self._policy):
            if kv_cache is not None:
                a, new_cache = self.attn(
                    params["attn"], u, positions=positions,
                    kv_cache=kv_cache, slot_mask=slot_mask,
                    block_tables=block_tables, row_mask=row_mask,
                    attn_kernel=attn_kernel, pack=pack)
            else:
                a = self.attn(params["attn"], u, positions=positions,
                              segment_ids=segment_ids, attn_impl=attn_impl,
                              return_kv=return_kv)
        h = x + a.astype(x.dtype)
        u = self.norm2(params["norm2"], h)              # float32
        with autocast(self._policy):
            f, sizes = self._ffn(params, u)
        y = h + f.astype(x.dtype)
        if kv_cache is not None:
            if self._dense:
                return y, new_cache
            return y, new_cache, {"moe_local_sizes": sizes}
        return act_constrain(y, "tokens")


class LeadingDenseBlocks(Module):
    """The layers of a model whose first ``n_dense`` layers are another
    block than the scanned ones: the dense layers run one by one outside
    the scan and the expert layers as ONE :class:`StackedBlocks` behind
    them (``first_layer = n_dense``) — all of them write the same
    stacked cache leaves, each at its own layer. The interface is
    ``StackedBlocks``'s as the serving engine uses it; ``block`` is the
    scanned (expert) block, whose attention speaks for every layer's."""

    def __init__(self, cfg: MLAMoEConfig):
        super().__init__()
        k = cfg.first_k_dense_replace
        self.num_layers = cfg.num_hidden_layers
        self.dense = [MLABlock(cfg, dense=True) for _ in range(k)]
        self.experts = StackedBlocks(
            lambda: MLABlock(cfg, dense=False), cfg.num_hidden_layers - k,
            first_layer=k)

    @property
    def block(self) -> Module:
        return self.experts.block

    def __call__(self, params, x, **kwargs):
        for i, blk in enumerate(self.dense):
            x = blk(params["dense"][str(i)], x, **kwargs)
        return self.experts(params["experts"], x, **kwargs)

    def decode(self, params, x, caches, *, with_stats=False,
               w8a8_mask=None, w8a8_wq=None, lora=None, **kwargs):
        if w8a8_mask is not None or w8a8_wq is not None or lora:
            raise NotImplementedError(
                "mla_moe has no W8A8 or LoRA lane")
        caches = tuple(caches)
        for i, blk in enumerate(self.dense):
            x, caches = blk(
                params["dense"][str(i)], x,
                kv_cache=LayerKV(caches, jnp.asarray(i, jnp.int32)),
                **kwargs)
        return self.experts.decode(params["experts"], x, caches,
                                   with_stats=with_stats, **kwargs)

    def layer_stats_zeros(self) -> dict:
        return self.experts.layer_stats_zeros()

    def prefill(self, *args, **kwargs):
        raise LatentKVNotSupported(
            "StackedBlocks.prefill (the CP-prefill lane) returns per-head "
            "(k, v); a latent attention caches one row a token")


class MLAMoEForCausalLM(Module):
    def __init__(self, cfg: MLAMoEConfig):
        super().__init__()
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                          init=init)
        self.blocks = LeadingDenseBlocks(cfg)
        self.final_norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        # untied: the same (V, E) layout the tied models' head has
        self.lm_head = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, init=init)

    def _head_weight(self, params):
        return params["lm_head"]["weight"]

    def embed(self, params, input_ids, *, positions=None):
        del positions          # rotary positions are applied per layer
        return act_constrain(self.wte(params["wte"], input_ids), "tokens")

    def hidden_norm(self, params, h):
        return self.final_norm(params["final_norm"], h)

    def hidden_states(self, params, input_ids, *, positions=None,
                      segment_ids=None, attn_impl="auto"):
        h = self.embed(params, input_ids)
        h = self.blocks(params["blocks"], h, positions=positions,
                        segment_ids=segment_ids, attn_impl=attn_impl)
        return self.hidden_norm(params, h)

    def __call__(self, params, input_ids, **kwargs):
        h = self.hidden_states(params, input_ids, **kwargs)
        logits = jnp.einsum(
            "bse,ve->bsv", h.astype(jnp.float32),
            self._head_weight(params).astype(jnp.float32))
        return act_constrain(logits, "logits")
