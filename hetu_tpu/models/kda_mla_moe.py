"""Decoders that mix Kimi-Delta-Attention layers with latent-attention
(MLA) layers over group-limited routed experts (the Ling-3.0 / Kimi
Linear family).

The config dataclass keeps the published key names. Per layer (``x`` is
``(T, hidden)``, no biases, ``n`` = RMSNorm):
``h = x + Mixer(n1(x))``, ``y = h + FFN(n2(h))``.

- mixer of layer ``i``: latent attention where ``(i + 1) %
  layer_group_size == 0``
  (:class:`~hetu_tpu.nn.parallel.LatentAttention`, RMSNorm on q and the
  RoPE key where ``use_qk_norm``: a token's cache is ONE latent row),
  else Kimi Delta Attention
  (:class:`~hetu_tpu.nn.parallel.KimiDeltaAttention`: a slot's cache is
  a float32 state and the convolution's tail, whatever the context);
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``; they run one by one OUTSIDE the layer scans;
- FFN of every other layer: a sigmoid router ``n_routed_experts`` wide
  with a selection bias, GROUP-LIMITED (``n_group`` groups of
  consecutive experts, the ``topk_group`` best stay), top
  ``num_experts_per_tok``, weights ``routed_scaling_factor * s_e / sum
  s`` (:class:`~hetu_tpu.nn.moe.ExpertShareMoE`; ``local_experts`` is
  the share held here — one group a chip in the deployment), beside
  ONE shared SwiGLU expert.

The two mixers differ in parameter shapes, so RUNS of like layers are
scanned and a :class:`~hetu_tpu.nn.parallel.LayerStack` strings the
leading dense layers and the runs (``blocks.dense.<i>``,
``blocks.runs.<i>``); each kind counts ITS OWN layers in its cache
leaves. The caches are ``(latent rows, states, tails)``: one paged leaf
over the MLA layers and two slot leaves over the KDA layers.

A final RMSNorm, then an UNTIED head. Operands: ``compute_dtype``
("bfloat16" to serve: bf16 operands, float32 accumulation) is what the
projections, the latent products, the shared and expert matmuls take;
the residual stream, the norms, the router, the convolution, the state
with its decays, ``beta``, the triangular solve and the L2 norms, and
the logits stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import (
    KimiDeltaAttention, LatentAttention, LayerStack, ParallelMLP,
    PreNormBlock,
)

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KDAMLAMoEConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144            # the dense layers' FFN
    moe_intermediate_size: int = 768         # ONE routed expert
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    num_attention_heads: int = 32
    head_dim: int = 128                      # the KDA heads' dk = dv
    layer_group_size: int = 6
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    use_qk_norm: bool = True
    #: the router's width (the experts of the whole deployment)
    n_routed_experts: int = 512
    #: ``(first, count)`` of them held here (None: all)
    local_experts: Optional[tuple] = None
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 2
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    max_position_embeddings: int = 131072
    #: the gain the MLA q and RoPE-key norms are drawn at (1: a
    #: checkpoint's would be learned)
    qk_norm_gain: float = 1.0
    #: the arena row's width (None: ``kv_lora_rank + qk_rope_head_dim``)
    stored_row: Optional[int] = None
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError("query compression (q_lora_rank)")
        if self.local_experts is not None:
            object.__setattr__(self, "local_experts",
                               tuple(self.local_experts))
        kinds = self.mixer_types
        if MLA not in kinds[self.first_k_dense_replace:] \
                or KDA not in kinds:
            raise ValueError(
                f"{self.num_hidden_layers} layers in groups of "
                f"{self.layer_group_size} behind "
                f"{self.first_k_dense_replace} dense ones: at least one "
                f"scanned latent layer (its attention speaks for the "
                f"arena) and one delta-rule layer")

    @property
    def mixer_types(self) -> tuple:
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in range(self.num_hidden_layers))

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @classmethod
    def tiny(cls, **kw):
        """Test size: 7 layers in groups of 3 (KDA, KDA, MLA, ...), the
        first a dense one; 4 heads of 16; latent 32, nope 16 / rope 8 /
        v 16; a router 16 wide in 4 groups of which 2 stay, top-3,
        all held."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_hidden_layers=7,
            num_attention_heads=4, head_dim=16, layer_group_size=3,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=3,
            n_group=4, topk_group=2, first_k_dense_replace=1,
            max_position_embeddings=256, qk_norm_gain=2.0), **kw})


def make_block(cfg: KDAMLAMoEConfig, kind: str, dense: bool):
    """One layer (:class:`~hetu_tpu.nn.parallel.PreNormBlock`): a mixer
    of ``kind``, then a dense SwiGLU (``dense``) or the routed experts
    beside the shared one."""
    init = normal_init(cfg.init_std)
    if kind == MLA:
        attn = LatentAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            kv_rank=cfg.kv_lora_rank, nope_dim=cfg.qk_nope_head_dim,
            rope_dim=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
            stored_row=cfg.stored_row, rope_theta=cfg.rope_theta,
            norm_eps=cfg.rms_norm_eps, max_positions=cfg.max_positions,
            qk_norm=cfg.use_qk_norm, qk_gain=cfg.qk_norm_gain, init=init)
    else:
        attn = KimiDeltaAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            head_dim=cfg.head_dim, conv_size=cfg.short_conv_kernel_size,
            lower_bound=cfg.kda_lower_bound, norm_eps=cfg.rms_norm_eps,
            init=init)
    if dense:
        ffn = dict(mlp=ParallelMLP(
            cfg.hidden_size, cfg.intermediate_size, bias=False,
            gated=True))
    else:
        ffn = dict(
            shared=ParallelMLP(
                cfg.hidden_size, cfg.moe_shared_expert_intermediate_size,
                bias=False, gated=True),
            moe=ExpertShareMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, k=cfg.num_experts_per_tok,
                local_experts=cfg.local_experts, select_bias=True,
                scale=cfg.routed_scaling_factor, n_group=cfg.n_group,
                topk_group=cfg.topk_group, init=init))
    return PreNormBlock(cfg.hidden_size, attn, eps=cfg.rms_norm_eps,
                        compute_dtype=cfg.compute_dtype,
                        model="kda_mla_moe", **ffn)


class KDAMLAMoEForCausalLM(DecoderLM):
    def __init__(self, cfg: KDAMLAMoEConfig):
        super().__init__(
            cfg, LayerStack(
                cfg.mixer_types,
                lambda kind, dense: make_block(cfg, kind, dense),
                n_dense=cfg.first_k_dense_replace, model="kda_mla_moe"),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps), tied=False,
            embed_scale=1.0)           # the stream in float32, unscaled
