"""GPT-2's forward pass and loss in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks. It follows the published model (Radford et al. 2019;
``openai-community/gpt2`` ``modeling_gpt2.py``): learned position
embeddings, pre-LayerNorm blocks, causal softmax attention, tanh-GELU
MLP of 4 x hidden, tied output head.

Independent of ``hetu_tpu.models``: it only READS the same parameter
tree (``wte``, ``wpe``, ``blocks.{ln_1,attn.{q,k,v,out}_proj,ln_2,
mlp.{fc_in,fc_out}}`` stacked over layers on axis 0, ``ln_f``; weights
stored (in, out)). Packed rows carry ``positions`` and ``segment_ids``:
a token attends only inside its own document.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _linear(p, x):
    return x @ p["weight"] + p["bias"]


def hidden_states(params, input_ids, *, n_head: int, eps: float = 1e-5,
                  positions=None, segment_ids=None):
    """Final-norm hidden states ``(rows, seq, hidden)`` in float32."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        ids = jnp.asarray(input_ids, jnp.int32)
        rows, seq = ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(seq), (rows, seq))
        x = p["wte"]["weight"][ids] + p["wpe"]["weight"][positions]
        mask = jnp.tril(jnp.ones((seq, seq), bool))[None]
        if segment_ids is not None:
            seg = jnp.asarray(segment_ids)
            mask = mask & (seg[:, :, None] == seg[:, None, :])
        d = x.shape[-1] // n_head

        def heads(t):
            return t.reshape(rows, seq, n_head, d).transpose(0, 2, 1, 3)

        def block(x, blk):
            h = _layer_norm(blk["ln_1"], x, eps)
            q = heads(_linear(blk["attn"]["q_proj"], h))
            k = heads(_linear(blk["attn"]["k_proj"], h))
            v = heads(_linear(blk["attn"]["v_proj"], h))
            s = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(d))
            s = jnp.where(mask[:, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1) @ v
            a = a.transpose(0, 2, 1, 3).reshape(rows, seq, n_head * d)
            x = x + _linear(blk["attn"]["out_proj"], a)
            h = _layer_norm(blk["ln_2"], x, eps)
            h = jax.nn.gelu(_linear(blk["mlp"]["fc_in"], h),
                            approximate=True)
            return x + _linear(blk["mlp"]["fc_out"], h), None

        # the layers' parameters are stacked on axis 0: one block after
        # another, written as a scan so that depth costs no compile time
        x, _ = jax.lax.scan(block, x, p["blocks"])
        return _layer_norm(p["ln_f"], x, eps)


def logits(params, input_ids, **kw):
    """Next-token logits ``(rows, seq, vocab)`` in float32."""
    h = hidden_states(params, input_ids, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ jnp.asarray(params["wte"]["weight"], jnp.float32).T


def loss_terms(params, input_ids, labels, *, ignore_index: int = -100,
               **kw):
    """(sum of the token losses, number of positions that carry a
    label): a batch too large for one pass is summed over chunks."""
    lg = logits(params, input_ids, **kw)
    labels = jnp.asarray(labels)
    keep = labels != ignore_index
    logp = jax.nn.log_softmax(lg, axis=-1)
    tok = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[..., None], axis=-1)[..., 0]
    return -(tok * keep).sum(), keep.sum()


def loss(params, input_ids, labels, **kw):
    """Mean cross-entropy over the positions that carry a label."""
    total, n = loss_terms(params, input_ids, labels, **kw)
    return total / jnp.maximum(n, 1)
