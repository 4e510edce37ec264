"""SDAR-MoE (``model_type: sdar_moe``): a GQA + routed-expert decoder
that GENERATES by diffusion over blocks.

Source: ``JetLM/SDAR-30B-A3B-Chat`` ``config.json``; the config
dataclass keeps the published key names. Per layer (``x`` is ``(T,
hidden)``, no biases, ``n`` = RMSNorm with a gain):
``h = x + Attn(n1(x))``, ``y = h + MoE(n2(h))``
(:class:`~hetu_tpu.nn.parallel.PreNormBlock` without shared experts);

- attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim``; an RMSNorm
  over each head's numbers on q and on k (gains ``(head_dim,)``), then
  RoPE on the whole head (split halves), scores ``q k / sqrt(head_dim)``
  — and a query at position ``p`` sees key ``j`` iff ``j // B <= p //
  B``, ``B = block_length``: BLOCK-causal
  (``ParallelAttention(attn_block=B)``);
- experts: ``softmax`` router over ``num_experts``, the
  ``num_experts_per_tok`` largest renormalised
  (:class:`~hetu_tpu.nn.moe.ExpertShareMoE` ``score="softmax"``, which
  holds ``local_experts`` of them), experts ``hidden ->
  moe_intermediate_size -> hidden``; no shared expert.

Final RMSNorm, a head of its own (``tie_word_embeddings`` false).

What makes the family is not in a layer: a block of ``block_length``
``[MASK]`` tokens is denoised over several forward passes against the
committed cache and then committed whole. The model STATES it
(:attr:`SDARMoEForCausalLM.generation`, a :class:`BlockDiffusion`) and
the serving engine takes its decode lane's shape from that
(``docs/SERVING.md``, "The block lane"): position ``i``'s logits
predict position ``i`` (no shift).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import (
    ParallelAttention, PreNormBlock, StackedBlocks,
)

REMASKING = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """How a model generates, where it is not a token a step: blocks of
    ``block_length`` positions (a power of two), each begun as
    ``mask_token_id`` and unmasked over at most ``denoising_steps``
    passes — the defaults a request that names none gets."""
    block_length: int
    mask_token_id: int
    denoising_steps: int
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        B = self.block_length
        if B < 2 or B & (B - 1) or not 1 <= self.denoising_steps <= B \
                or self.remasking not in REMASKING:
            raise ValueError(f"{self!r}: a block of a power of two, "
                             f"1..block steps, remasking of {REMASKING}")


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    num_experts: int = 128
    num_experts_per_tok: int = 8
    max_position_embeddings: int = 32768
    # -- generation (inference settings: not in config.json) --
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    #: None: the last id of the vocabulary held
    mask_token_id: Optional[int] = None
    #: the gain the q and k norms are drawn at (1: a checkpoint's are
    #: learned)
    qk_norm_gain: float = 1.0
    #: (first, count) of the routed experts held here; None = all
    local_experts: Optional[tuple] = None
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @property
    def generation(self) -> BlockDiffusion:
        mask = self.vocab_size - 1 if self.mask_token_id is None \
            else self.mask_token_id
        return BlockDiffusion(self.block_length, mask,
                              self.denoising_steps, self.remasking,
                              self.confidence_threshold)

    @classmethod
    def tiny(cls, **kw):
        """Test size: 3 layers, GQA 4:2 of 16, 8 experts top-2."""
        return cls(**{**dict(
            vocab_size=96, hidden_size=32, moe_intermediate_size=16,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=8,
            num_experts_per_tok=2, max_position_embeddings=128,
            qk_norm_gain=2.0), **kw})


def make_block(cfg: SDARMoEConfig) -> PreNormBlock:
    init = normal_init(cfg.init_std)
    attn = ParallelAttention(
        cfg.hidden_size, cfg.num_attention_heads,
        num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        bias=False, causal=True, use_rope=True,
        rope_theta=cfg.rope_theta, max_positions=cfg.max_positions,
        qk_norm=True, qk_gain=cfg.qk_norm_gain,
        norm_eps=cfg.rms_norm_eps, attn_block=cfg.block_length,
        init=init)
    moe = ExpertShareMoE(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
        k=cfg.num_experts_per_tok, local_experts=cfg.local_experts,
        score="softmax", init=init)
    return PreNormBlock(cfg.hidden_size, attn, eps=cfg.rms_norm_eps,
                        moe=moe, compute_dtype=cfg.compute_dtype,
                        model="sdar_moe")


class SDARMoEForCausalLM(DecoderLM):
    """Untied head; ``generation`` states the block diffusion."""

    def __init__(self, cfg: SDARMoEConfig):
        super().__init__(
            cfg, StackedBlocks(lambda: make_block(cfg),
                               cfg.num_hidden_layers),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps), tied=False,
            embed_scale=1.0)
        self.generation = cfg.generation
