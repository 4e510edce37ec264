"""Brumby (``model_type: brumby``): a Qwen3-shaped dense decoder — every
key of its ``config.json`` is Qwen3's — whose softmax attention is
replaced in EVERY layer by power retention
(:class:`~hetu_tpu.nn.parallel.PowerRetention`; Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239):

    h = x + W_o Retention(n1(x)),    y = h + W_down(silu(W_gate u) * W_up u),
    u = n2(h)

(``n`` = RMSNorm, no biases), a final RMSNorm and an untied head. Per
layer ``q = RoPE(RMSNorm_head(W_q u))`` over 40 heads of 128, ``k``
likewise and ``v`` over 8; a gate a kv head and token, ``log g =
logsigmoid(W_g u + b_g)``; a float32 state a kv head and SLOT of the
keys' symmetric second tensor power against the values and a
normaliser. No layer keeps a token row: the stack's caches are ONE
per-slot leaf and the serving engine holds no arena
(``docs/SERVING.md``, "A model without an arena").

What ``config.json`` has no key for — the degree (2), the gate's
projection, bias and log-sigmoid, the normaliser and ``eps``, ``1 /
sqrt(head_dim)`` inside the power — is stated in
``benchmark/configs/brumby-14b-pp4.json`` under ``assumed``.
``max_window_layers``, ``use_sliding_window`` and ``sliding_window``
are Qwen3's keys and select nothing.

Operands: ``compute_dtype`` ("bfloat16" to serve: bf16 operands,
float32 accumulation) is what the projections, the retention kernels'
MXU products and the MLP take; the residual stream, the norms, the
state, the gate's sums, the normaliser and the logits stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from hetu_tpu.models.decoder import DecoderLM
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import normal_init
from hetu_tpu.nn.parallel import (
    LayerStack, ParallelMLP, PowerRetention, PreNormBlock,
)

RETENTION = "power-retention"


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    #: the normaliser's ``eps``: ``y = n / (z + eps)``
    retention_eps: float = 1e-6
    #: the mean gate on a layer's first and last kv head (the bias is
    #: drawn evenly in the logit between them)
    gate_means: tuple = (0.99, 0.99999)
    #: the gain the q and k norms are drawn at (1: a checkpoint's are
    #: learned; the normalised power does not see it)
    qk_norm_gain: float = 1.0
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "gate_means", tuple(self.gate_means))

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @classmethod
    def tiny(cls, **kw):
        """Test size: 3 layers, 4 query heads over 2 kv heads of 16."""
        return cls(**{**dict(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, gate_means=(0.8, 0.999)), **kw})


def make_block(cfg: BrumbyConfig) -> PreNormBlock:
    init = normal_init(cfg.init_std)
    mixer = PowerRetention(
        cfg.hidden_size, cfg.num_attention_heads,
        num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, max_positions=cfg.max_positions,
        norm_eps=cfg.rms_norm_eps, qk_gain=cfg.qk_norm_gain,
        eps=cfg.retention_eps, gate_means=cfg.gate_means, init=init)
    return PreNormBlock(
        cfg.hidden_size, mixer, eps=cfg.rms_norm_eps,
        mlp=ParallelMLP(cfg.hidden_size, cfg.intermediate_size,
                        bias=False, gated=True),
        compute_dtype=cfg.compute_dtype, model="brumby")


class BrumbyForCausalLM(DecoderLM):
    """Untied head; the layers are one kind, one scan, and keep a state
    a slot (``blocks.paged`` is False)."""

    def __init__(self, cfg: BrumbyConfig):
        super().__init__(
            cfg, LayerStack(
                (RETENTION,) * cfg.num_hidden_layers,
                lambda kind, dense: make_block(cfg), lone_run="layers",
                model="brumby"),
            RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps), tied=False,
            embed_scale=1.0)
