"""Jamba (``model_type: jamba``; AI21, arXiv:2403.19887): a decoder of
Mamba-1 selective-scan layers with, every ``attn_layer_period`` layers,
one softmax attention layer of few kv heads and NO position encoding
anywhere in the model (the scan's order is the position).

The config dataclass keeps the published key names. Per layer (``x`` is
``(T, hidden)``, no biases but the convolution's and the step's, ``n`` =
RMSNorm):

    h = x + Mixer(n1(x)),    y = h + W_down(silu(W_gate u) * W_up u),
    u = n2(h)

- mixer of layer ``i``: attention where ``i % attn_layer_period ==
  attn_layer_offset`` (:class:`~hetu_tpu.nn.parallel.ParallelAttention`
  with ``use_rope=False``: a token's cache is one k and one v row a kv
  head, in pages), else the Mamba mixer
  (:class:`~hetu_tpu.nn.parallel.MambaMixer`: a slot's cache is a
  float32 state ``d_state x expand hidden`` and the convolution's tail,
  whatever the context);
- the MLP is a SwiGLU in every layer: ``num_experts`` 1 (the family's
  larger members route experts every ``expert_layer_period`` layers;
  they are another configuration and are refused by name).

The two mixers differ in parameter shapes, so RUNS of like layers are
scanned and a :class:`~hetu_tpu.nn.parallel.LayerStack` strings the
runs (``blocks.runs.<i>``); each kind counts ITS OWN layers in its
cache leaves. The caches are ``(k, v, states, tails)``: two paged
leaves over the attention layers and two slot leaves over the Mamba
layers.

A final RMSNorm; the logits go through the embedding's transpose
(``tie_word_embeddings``). Operands: ``compute_dtype`` ("bfloat16" to
serve: bf16 operands, float32 accumulation) is what the projections,
attention and the MLP take; the residual stream, the norms, the
convolution, the step, ``A``, the state with its decays, the sum over
the states and the logits stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.parallel import (
    LayerStack, MambaMixer, ParallelAttention, ParallelMLP, PreNormBlock,
)

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_experts: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    #: the step the bias of ``dt_proj`` is drawn for, log-uniform
    dt_range: tuple = (1e-3, 1e-1)
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "dt_range", tuple(self.dt_range))
        if self.num_experts > 1:
            raise NotImplementedError(
                f"num_experts={self.num_experts}: routed experts every "
                f"expert_layer_period layers (the family's larger "
                f"members) are another configuration")
        if self.mamba_proj_bias or not self.tie_word_embeddings:
            raise NotImplementedError(
                "mamba_proj_bias, or a head of its own")
        kinds = self.mixer_types
        if MAMBA not in kinds or ATTENTION not in kinds:
            raise ValueError(
                f"{self.num_hidden_layers} layers with attention every "
                f"{self.attn_layer_period} from {self.attn_layer_offset}: "
                f"at least one attention layer (it speaks for the arena) "
                f"and one Mamba layer")

    @property
    def mixer_types(self) -> tuple:
        return tuple(
            ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.num_hidden_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @classmethod
    def tiny(cls, **kw):
        """Test size: ONE whole period of 14 layers (7 Mamba, 1
        attention, 6 Mamba), 4 query heads of 16 over one kv head, 128
        inner channels of 4 states, step rank 8."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_hidden_layers=14, num_attention_heads=4,
            num_key_value_heads=1, mamba_d_state=4, mamba_dt_rank=8,
            max_position_embeddings=256, dt_range=(1e-2, 1.0)), **kw})


def make_block(cfg: JambaConfig, kind: str) -> PreNormBlock:
    init = normal_init(cfg.init_std)
    if kind == ATTENTION:
        mixer = ParallelAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            bias=False, use_rope=False, init=init)
    else:
        mixer = MambaMixer(
            cfg.hidden_size, d_state=cfg.mamba_d_state,
            d_conv=cfg.mamba_d_conv, expand=cfg.mamba_expand,
            dt_rank=cfg.mamba_dt_rank, conv_bias=cfg.mamba_conv_bias,
            norm_eps=cfg.rms_norm_eps, dt_range=cfg.dt_range, init=init)
    return PreNormBlock(
        cfg.hidden_size, mixer, eps=cfg.rms_norm_eps,
        mlp=ParallelMLP(cfg.hidden_size, cfg.intermediate_size,
                        bias=False, gated=True),
        compute_dtype=cfg.compute_dtype, model="jamba")


class JambaForCausalLM(DecoderLM):
    """Tied head; runs of Mamba layers between the attention layers."""

    def __init__(self, cfg: JambaConfig):
        super().__init__(
            cfg, LayerStack(
                cfg.mixer_types, lambda kind, dense: make_block(cfg, kind),
                model="jamba"),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps), tied=True,
            embed_scale=1.0)           # the stream in float32, unscaled
