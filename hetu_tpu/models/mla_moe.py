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
  same stacked arena at their own layer index
  (:class:`~hetu_tpu.nn.parallel.LayerStack`; the ONE run of expert
  layers behind them is stored as ``blocks.experts``);
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

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import (
    LatentAttention, LayerStack, ParallelMLP, PreNormBlock,
)

MLA = "mla"


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


def make_block(cfg: MLAMoEConfig, kind: str = MLA, dense: bool = False):
    """One layer (:class:`~hetu_tpu.nn.parallel.PreNormBlock`): latent
    attention, then a dense SwiGLU (``dense``) or the routed experts
    beside the shared ones."""
    init = normal_init(cfg.init_std)
    attn = LatentAttention(
        cfg.hidden_size, cfg.num_attention_heads,
        kv_rank=cfg.kv_lora_rank, nope_dim=cfg.qk_nope_head_dim,
        rope_dim=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
        stored_row=cfg.stored_row, rope_theta=cfg.rope_theta,
        norm_eps=cfg.rms_norm_eps, max_positions=cfg.max_positions,
        init=init)
    if dense:
        ffn = dict(mlp=ParallelMLP(
            cfg.hidden_size, cfg.intermediate_size, bias=False,
            gated=True))
    else:
        ffn = dict(
            shared=ParallelMLP(
                cfg.hidden_size,
                cfg.n_shared_experts * cfg.moe_intermediate_size,
                bias=False, gated=True),
            moe=ExpertShareMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, k=cfg.num_experts_per_tok,
                select_bias=True, scale=cfg.routed_scaling_factor,
                init=init))
    return PreNormBlock(cfg.hidden_size, attn, eps=cfg.rms_norm_eps,
                        compute_dtype=cfg.compute_dtype, model="mla_moe",
                        **ffn)


class MLAMoEForCausalLM(DecoderLM):
    def __init__(self, cfg: MLAMoEConfig):
        super().__init__(
            cfg, LayerStack(
                (MLA,) * cfg.num_hidden_layers,
                lambda kind, dense: make_block(cfg, kind, dense),
                n_dense=cfg.first_k_dense_replace, lone_run="experts",
                model="mla_moe"),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps), tied=False)
