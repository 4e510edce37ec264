"""The shell of the drawn decoders (``cohere2_moe``, ``mla_moe``,
``minicpm_sala``, ``kda_mla_moe``): an embedding, the model's stack of
blocks, a final norm and a head on the embedding's ``(V, E)`` layout.
The parameters are ``wte, blocks, final_norm[, lm_head]``.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from hetu_tpu.nn.module import Module, normal_init
from hetu_tpu.nn.parallel import VocabParallelEmbedding
from hetu_tpu.parallel.sharding import act_constrain


class DecoderLM(Module):
    """``logits = norm_scale * n_f(Blocks(embed_scale * E[ids])) W^T``,
    ``W`` the embedding itself (``tied``) or a head of its own.

    ``embed_scale`` (None: the rows as looked up) puts the residual
    stream in float32 and multiplies it; ``norm_scale`` (None: none)
    rides the final norm's output — the head is linear, so the scale of
    the logits can ride its input."""

    def __init__(self, cfg, blocks: Module, final_norm: Module, *,
                 tied: bool, embed_scale: Optional[float] = None,
                 norm_scale: Optional[float] = None):
        super().__init__()
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                          init=init)
        self.blocks = blocks
        self.final_norm = final_norm
        if not tied:
            self.lm_head = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size, init=init)
        self._tied = tied
        self._embed_scale, self._norm_scale = embed_scale, norm_scale

    def _head_weight(self, params):
        return params["wte" if self._tied else "lm_head"]["weight"]

    def embed(self, params, input_ids, *, positions=None):
        del positions          # rotary positions are applied per layer
        h = self.wte(params["wte"], input_ids)
        if self._embed_scale is not None:
            h = h.astype(jnp.float32)
            if self._embed_scale != 1.0:
                h = h * self._embed_scale
        return act_constrain(h, "tokens")

    def hidden_norm(self, params, h):
        """The final norm, scaled: what the head multiplies."""
        h = self.final_norm(params["final_norm"], h)
        return h if self._norm_scale is None else h * self._norm_scale

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
