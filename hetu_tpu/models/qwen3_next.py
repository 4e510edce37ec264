"""Qwen3-Next (``model_type: qwen3_next``): a decoder of Gated DeltaNet
layers with, every ``full_attention_interval`` layers, one GATED softmax
attention layer of few wide kv heads, every layer over a wide softmax
router of small experts beside one gated shared expert.

Source: ``Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json``; the config
dataclass keeps the published key names. ``n(x) = x / sqrt(mean x^2 +
eps) * (1 + w)`` — the ZERO-CENTRED gain — everywhere but the delta
rule's output norm. Per layer (``x`` is ``(T, hidden)``, no bias
anywhere): ``h = x + Mixer(n1(x))``, ``y = h + MoE(n2(h))``.

- mixer of layer ``i``: gated attention where ``(i + 1) %
  full_attention_interval == 0``
  (:class:`~hetu_tpu.nn.parallel.ParallelAttention` with ``out_gate``:
  ``[q | gate] = u W_q`` a head, q and k through an RMSNorm with a
  ``1 + w`` gain a head, RoPE (split halves) on the first ``head_dim x
  partial_rotary_factor`` numbers of a head only, a causal softmax at
  ``head_dim^-1/2``, ``W_o (attn * sigmoid(gate))``: a token's cache is
  one k and one v row a kv head, in pages), else Gated DeltaNet
  (:class:`~hetu_tpu.nn.parallel.GatedDeltaNet`: ONE decay a head,
  ``linear_num_key_heads`` key heads under ``linear_num_value_heads``
  value heads; a slot's cache is a float32 state a value head and the
  convolution's tail, whatever the context);
- experts: ``softmax`` router ``num_experts`` wide in float32, the
  ``num_experts_per_tok`` largest renormalised (``norm_topk_prob``;
  :class:`~hetu_tpu.nn.moe.ExpertShareMoE` ``score="softmax"``, which
  holds ``local_experts`` of them), SwiGLU experts ``hidden ->
  moe_intermediate_size -> hidden``, beside ONE shared SwiGLU expert of
  ``shared_expert_intermediate_size`` under ``sigmoid(u w_sg)``.

The two mixers differ in parameter shapes, so RUNS of like layers are
scanned and a :class:`~hetu_tpu.nn.parallel.LayerStack` strings the
runs (``blocks.runs.<i>``); each kind counts ITS OWN layers in its
cache leaves. The caches are ``(k, v, states, tails)``: two paged
leaves over the attention layers and two slot leaves over the delta-rule
layers.

A final norm (zero-centred too), then an UNTIED head. Operands:
``compute_dtype`` ("bfloat16" to serve: bf16 operands, float32
accumulation) is what the projections, attention, the shared and expert
matmuls take; the residual stream, the norms, the router, the shared
expert's gate, the softmax statistics, the logits and, in the delta
rule, the convolution and its tail, the state, ``g``, ``beta``, the
triangular solve and the L2 norms stay float32. The multi-token
prediction module the model card describes has no key in the
``config.json`` and is not built.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import (
    GatedDeltaNet, LayerStack, ParallelAttention, ParallelMLP, PreNormBlock,
)

GDN, ATTENTION = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    #: the router's width (the experts of the whole deployment)
    num_experts: int = 512
    #: ``(first, count)`` of them held here (None: all)
    local_experts: Optional[tuple] = None
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: the gain the q and k norms are drawn at (``1 + w`` with ``w`` this
    #: less one; 1: a checkpoint's are learned)
    qk_norm_gain: float = 1.0
    #: the standard deviation the other norms' ``w`` is drawn at
    norm_w_std: float = 0.02
    #: ``A`` uniform in it a head, and the step ``softplus(dt_bias)``
    #: log-uniform in it
    a_range: tuple = (0.0, 16.0)
    dt_range: tuple = (1e-3, 1e-1)
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        for name in ("local_experts", "a_range", "dt_range"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.linear_key_head_dim != self.linear_value_head_dim:
            raise NotImplementedError(
                "a delta rule whose key and value heads differ in size")
        if self.tie_word_embeddings or not self.norm_topk_prob:
            raise NotImplementedError(
                "a tied head, or chosen weights that are not renormalised")
        kinds = self.mixer_types
        if GDN not in kinds or ATTENTION not in kinds:
            raise ValueError(
                f"{self.num_hidden_layers} layers with attention every "
                f"{self.full_attention_interval}: at least one attention "
                f"layer (it speaks for the arena) and one delta-rule "
                f"layer")

    @property
    def mixer_types(self) -> tuple:
        return tuple(
            ATTENTION if (i + 1) % self.full_attention_interval == 0
            else GDN for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @classmethod
    def tiny(cls, **kw):
        """Test size: 8 layers (two periods of 3 : 1), 4 key heads under
        8 value heads of 16, 4 query heads over 2 kv heads of 32 with 8
        rotated, a router 16 wide top-3, all held."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            linear_num_key_heads=4, linear_num_value_heads=8,
            linear_key_head_dim=16, linear_value_head_dim=16,
            num_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            max_position_embeddings=256, qk_norm_gain=2.0,
            dt_range=(1e-2, 1.0)), **kw})


def make_block(cfg: Qwen3NextConfig, kind: str) -> PreNormBlock:
    init = normal_init(cfg.init_std)
    if kind == ATTENTION:
        mixer = ParallelAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            bias=False, causal=True, use_rope=True,
            rope_theta=cfg.rope_theta, max_positions=cfg.max_positions,
            rotary_dim=cfg.rotary_dim, qk_norm=True,
            qk_gain=cfg.qk_norm_gain, zero_centered=True, out_gate=True,
            norm_eps=cfg.rms_norm_eps, init=init)
    else:
        mixer = GatedDeltaNet(
            cfg.hidden_size, cfg.linear_num_value_heads,
            num_key_heads=cfg.linear_num_key_heads,
            head_dim=cfg.linear_key_head_dim,
            conv_size=cfg.linear_conv_kernel_dim,
            norm_eps=cfg.rms_norm_eps, a_range=cfg.a_range,
            dt_range=cfg.dt_range, init=init)
    return PreNormBlock(
        cfg.hidden_size, mixer, eps=cfg.rms_norm_eps,
        shared=ParallelMLP(cfg.hidden_size,
                           cfg.shared_expert_intermediate_size,
                           bias=False, gated=True),
        moe=ExpertShareMoE(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            k=cfg.num_experts_per_tok, local_experts=cfg.local_experts,
            score="softmax", init=init),
        compute_dtype=cfg.compute_dtype, model="qwen3_next",
        zero_centered=cfg.norm_w_std, shared_gate=True,
        mixer_scope="hetu.gated_attn" if kind == ATTENTION else None)


class Qwen3NextForCausalLM(DecoderLM):
    """Untied head; runs of three Gated DeltaNet layers between the
    gated attention layers."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__(
            cfg, LayerStack(
                cfg.mixer_types, lambda kind, dense: make_block(cfg, kind),
                model="qwen3_next"),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps,
                    zero_centered=cfg.norm_w_std), tied=False,
            embed_scale=1.0)           # the stream in float32, unscaled
