"""MiniCPM-SALA (``model_type: minicpm_sala``): a dense decoder whose
layers mix two kinds of sequence mixer, ``mixer_types[l]``:

- ``"minicpm4"`` — block-sparse attention, NoPE, 32 query heads over 2
  kv heads, ``qk_norm``, an output gate
  (:class:`~hetu_tpu.nn.parallel.BlockSparseAttention`): a token's cache
  is K, V and a share of a compressed key; a query reads the ``topk``
  pages it chooses;
- ``"lightning-attn"`` — linear attention with a per-head decay, RoPE,
  an output norm and gate
  (:class:`~hetu_tpu.nn.parallel.LightningAttention`): the cache is a
  float32 state per SLOT, whatever the context.

Frame (MiniCPM's μP scalings; ``u`` the RMS-normed input, no biases):
``x0 = scale_emb * E[ids]``; ``h = x + a * Mixer(n1(x))``, ``y = h + a *
MLP(n2(h))`` with ``a = scale_depth / sqrt(published_depth)`` — the
PUBLISHED depth, a constant of the model whatever depth is held — and a
SwiGLU MLP; a final RMSNorm; logits ``(h / (hidden_size /
dim_model_base)) W_head^T``, untied.

The two kinds differ in parameter shapes, so RUNS of like layers are
scanned and a :class:`~hetu_tpu.nn.parallel.LayerStack` strings the
runs (``blocks.runs.<i>``); each kind counts ITS OWN layers in its
cache leaves — no page for a layer that has no keys. The caches are
``(K, V, stride means, states)``: three paged leaves over the sparse
layers and one slot leaf over the lightning layers.

Operands: ``compute_dtype`` ("bfloat16" to serve: bf16 operands,
float32 accumulation) is what the projections, scores, values and the
MLP take; the residual stream, the norms, the selection, the lightning
state and its decay, and the logits stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.parallel import (
    BlockSparseAttention, LayerStack, LightningAttention, ParallelMLP,
    PreNormBlock,
)

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    #: the mixers of the layers HELD, in order
    mixer_types: tuple = (SPARSE,) + (LINEAR,) * 3
    #: the depth ``scale_depth`` is divided by the root of: the
    #: published model's, whatever is held
    published_depth: int = 32
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 524288
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # the sparse layers' sizes (the family's ``sparse_config``)
    block_size: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    #: the gain the q and k norms are drawn at (1: a checkpoint's
    #: would be learned)
    qk_norm_gain: float = 1.0
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {SPARSE, LINEAR}
        if bad or SPARSE not in self.mixer_types:
            raise ValueError(
                f"mixer_types holds {sorted(bad) or 'no sparse layer'}: "
                f"each is {SPARSE!r} or {LINEAR!r}, at least one the "
                f"former (its attention speaks for the arena)")

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    num_hidden_layers = num_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.published_depth ** 0.5

    @classmethod
    def tiny(cls, **kw):
        """Test size: 2 sparse + 3 lightning layers, 4 query heads over
        2 kv heads of 16, 4 lightning heads of 16, pages of 4 tokens,
        top-4 (first block, the query's own and the one before forced:
        one place competed for), compressed keys of 2 tokens every 1."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            lightning_nh=4, lightning_head_dim=16,
            mixer_types=(SPARSE, LINEAR, LINEAR, SPARSE, LINEAR),
            published_depth=8, max_position_embeddings=256,
            block_size=4, kernel_size=2, kernel_stride=1, topk=4,
            init_blocks=1, window_size=4, qk_norm_gain=2.0), **kw})


def make_block(cfg: MiniCPMSALAConfig, kind: str, dense: bool = False):
    """One layer (:class:`~hetu_tpu.nn.parallel.PreNormBlock`): a mixer
    of ``kind``, then the SwiGLU MLP, each behind its RMSNorm and
    weighted ``residual_scale`` into the stream."""
    init = normal_init(cfg.init_std)
    if kind == SPARSE:
        attn = BlockSparseAttention(
            cfg.hidden_size, cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, block_size=cfg.block_size,
            kernel_size=cfg.kernel_size,
            kernel_stride=cfg.kernel_stride, topk=cfg.topk,
            init_blocks=cfg.init_blocks, window_size=cfg.window_size,
            norm_eps=cfg.rms_norm_eps, qk_gain=cfg.qk_norm_gain,
            init=init)
    else:
        attn = LightningAttention(
            cfg.hidden_size, cfg.lightning_nh,
            head_dim=cfg.lightning_head_dim, rope_theta=cfg.rope_theta,
            max_positions=cfg.max_positions, norm_eps=cfg.rms_norm_eps,
            qk_gain=cfg.qk_norm_gain, init=init)
    return PreNormBlock(
        cfg.hidden_size, attn, eps=cfg.rms_norm_eps,
        mlp=ParallelMLP(cfg.hidden_size, cfg.intermediate_size,
                        bias=False, gated=True),
        residual_scale=cfg.residual_scale,
        compute_dtype=cfg.compute_dtype, model="minicpm_sala")


class MiniCPMSALAForCausalLM(DecoderLM):
    """``scale_emb`` on the embedding; the head's ``1 / (hidden_size /
    dim_model_base)`` folded into the final norm's output."""

    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__(
            cfg, LayerStack(
                cfg.mixer_types,
                lambda kind, dense: make_block(cfg, kind, dense),
                model="minicpm_sala"),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps), tied=False,
            embed_scale=cfg.scale_emb,
            norm_scale=cfg.dim_model_base / cfg.hidden_size)
