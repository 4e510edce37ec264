"""Command A+ (``model_type: cohere2_moe``): a parallel block of GQA
attention and a routed-expert FFN, window (RoPE) and full (NoPE) layers
mixed.

Source: ``CohereLabs/command-a-plus-05-2026`` ``config.json``; the
config dataclass keeps the published key names. Per layer (``x`` is
``(T, hidden)``, no biases):

- ONE Cohere LayerNorm (weight only) feeds both branches:
  ``y = x + Attn_l(n(x)) + FFN(n(x))`` (``use_parallel_block``);
- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``. A
  ``sliding_attention`` layer rotates q and k (RoPE, interleaved pairs:
  ``rope_gptj``) and query ``p`` sees keys ``p - sliding_window < j <=
  p``; a ``full_attention`` layer applies no positional embedding and
  sees every ``j <= p``. The layers of the ONE scanned block differ by
  data: ``StackedBlocks(layer_data={"window", "rope_on"})``;
- FFN: ``sigmoid`` router over ``num_experts``, the
  ``num_experts_per_tok`` largest scores renormalised
  (:class:`~hetu_tpu.nn.moe.ExpertShareMoE`, which holds
  ``local_experts`` of them — one chip's share of an expert-parallel
  deployment), beside ``num_shared_experts`` shared SwiGLU experts whose
  outputs are AVERAGED: one gated MLP ``num_shared_experts x
  intermediate_size`` wide times ``1 / num_shared_experts`` is the same
  arithmetic.

Logits are ``n_f(h) W_emb^T logit_scale`` (tied embeddings).

Operands: ``compute_dtype`` ("bfloat16" to serve: bf16 operands,
float32 accumulation; "float32" in the CPU tests) is what the
attention, shared and expert matmuls take; the residual stream, the
norms and the router stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.dtypes import autocast
from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import LayerNorm
from hetu_tpu.nn.module import Module, normal_init
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import (
    ParallelAttention, ParallelMLP, StackedBlocks,
)
from hetu_tpu.parallel.sharding import act_constrain

#: the ``window`` of a full-attention layer: no key is ever below it
NO_WINDOW = 2 ** 30
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Cohere2MoEConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096        # the width of ONE expert
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    sliding_window: int = 4096
    #: one of SLIDING / FULL per layer; ``None`` = three sliding layers
    #: then a full one, repeated (``layer_switch`` 4, local first)
    layer_types: Optional[tuple] = None
    rope_theta: float = 50000.0
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    max_position_embeddings: int = 200000
    #: (first, count) of the routed experts held here; None = all
    local_experts: Optional[tuple] = None
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            types = tuple(FULL if i % 4 == 3 else SLIDING
                          for i in range(self.num_hidden_layers))
        types = tuple(types)
        if len(types) != self.num_hidden_layers \
                or set(types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {types!r} for "
                             f"{self.num_hidden_layers} layers")
        object.__setattr__(self, "layer_types", types)

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @classmethod
    def tiny(cls, **kw):
        """Test size: two periods, window 8, 16 experts top-4, 2 shared,
        GQA 8:2."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=32,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            num_experts=16, num_experts_per_tok=4, num_shared_experts=2,
            max_position_embeddings=128), **kw})


class Cohere2MoEBlock(Module):
    returns_aux = False
    #: the grouped expert matmul cannot read through the layer scan's
    #: slice: the decode scan hands these whole (``StackedLeaf``)
    unsliced = (("moe", "wg"), ("moe", "wi"), ("moe", "wo"))

    def __init__(self, cfg: Cohere2MoEConfig):
        super().__init__()
        init = normal_init(cfg.init_std)
        self.norm = LayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                              use_bias=False)
        self.attn = ParallelAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            bias=False, causal=True, use_rope=True,
            rope_theta=cfg.rope_theta, rope_interleaved=True,
            min_window=cfg.sliding_window,
            max_positions=cfg.max_positions, init=init)
        self.shared = ParallelMLP(
            cfg.hidden_size,
            cfg.num_shared_experts * cfg.intermediate_size, bias=False,
            gated=True)
        self.moe = ExpertShareMoE(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_experts,
            k=cfg.num_experts_per_tok, local_experts=cfg.local_experts,
            init=init)
        #: what a decode call reports beside its result
        #: (``StackedBlocks.decode(with_stats=True)``): the expert
        #: layer's group sizes, counted on the host
        self.layer_stats = {"moe_" + k: v
                            for k, v in self.moe.layer_stats.items()}
        self._shared_mean = 1.0 / cfg.num_shared_experts
        self._policy = {"float32": "fp32",
                        "bfloat16": "bf16"}[cfg.compute_dtype]

    def __call__(self, params, x, *, window, rope_on, positions=None,
                 segment_ids=None, attn_impl="auto", kv_cache=None,
                 slot_mask=None, block_tables=None, row_mask=None,
                 attn_kernel="reference", pack=None, w8a8=None,
                 w8a8_wq=None, lora=None, dropout_key=None,
                 return_kv=False):
        if w8a8 is not None or lora or dropout_key is not None:
            raise NotImplementedError(
                "cohere2_moe has no W8A8, LoRA or dropout lane")
        h = self.norm(params["norm"], x)            # float32
        new_cache = kv = None
        with autocast(self._policy):
            if kv_cache is not None:
                a, new_cache = self.attn(
                    params["attn"], h, positions=positions,
                    kv_cache=kv_cache, slot_mask=slot_mask,
                    block_tables=block_tables, row_mask=row_mask,
                    attn_kernel=attn_kernel, pack=pack, window=window,
                    rope_on=rope_on)
            else:
                a = self.attn(params["attn"], h, positions=positions,
                              segment_ids=segment_ids,
                              attn_impl=attn_impl, return_kv=return_kv,
                              window=window, rope_on=rope_on)
                if return_kv:
                    a, kv = a
            with jax.named_scope("hetu.moe_shared"):
                shared = self.shared(params["shared"], h)
            routed, st = self.moe(params["moe"], h, return_stats=True)
        y = x + a.astype(x.dtype) \
            + shared.astype(x.dtype) * self._shared_mean \
            + routed.astype(x.dtype)
        if kv_cache is not None:
            return y, new_cache, {"moe_" + k: v for k, v in st.items()}
        y = act_constrain(y, "tokens")
        return (y, kv) if return_kv else y


class Cohere2MoEForCausalLM(DecoderLM):
    """Tied embeddings; ``logit_scale`` rides the final norm."""

    def __init__(self, cfg: Cohere2MoEConfig):
        sliding = [t == SLIDING for t in cfg.layer_types]
        super().__init__(
            cfg, StackedBlocks(
                lambda: Cohere2MoEBlock(cfg), cfg.num_hidden_layers,
                layer_data={
                    "window": jnp.asarray(
                        [cfg.sliding_window if s else NO_WINDOW
                         for s in sliding], jnp.int32),
                    "rope_on": jnp.asarray(sliding, bool)}),
            LayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                      use_bias=False),
            tied=True, norm_scale=cfg.logit_scale)
