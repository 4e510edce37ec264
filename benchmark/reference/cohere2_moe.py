"""Command A+ (``cohere2_moe``) in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks. It follows the published ``config.json`` of
``CohereLabs/command-a-plus-05-2026`` (``config`` below is that file's
keys) and shares no code with ``hetu_tpu``: it only READS the same
parameter tree — ``wte.weight`` ``(V, E)``; ``blocks`` stacked over
layers on axis 0: ``norm.scale``, ``attn.{q,k,v,out}_proj.weight``
``(in, out)``, ``shared.{gate_proj,up_proj,fc_out}.weight``,
``moe.router`` ``(E, experts)``, ``moe.{wg,wi}`` ``(held, E, width)``,
``moe.wo`` ``(held, width, E)``; ``final_norm.scale``.

Per layer, with ``n`` the Cohere LayerNorm (weight only) and ONE norm
feeding both branches: ``y = x + Attn_l(n(x)) + FFN(n(x))``.

* Attention: GQA, scale ``1/sqrt(head_dim)``, causal. A
  ``sliding_attention`` layer rotates q and k with RoPE on interleaved
  pairs ``(2i, 2i+1)`` (``rope_gptj``), all dims, and query ``p`` sees
  keys ``p - sliding_window < j <= p``; a ``full_attention`` layer has
  no positional embedding and sees every ``j <= p``.
* FFN: ``s = sigmoid(n(x) W_r)``, the ``num_experts_per_tok`` largest
  chosen, ``w_e = s_e / sum_chosen s``; expert ``E_e(u) = (silu(u W_g)
  * (u W_u)) W_d``; ``FFN(u) = sum_chosen w_e E_e(u) + mean_s E_s(u)``
  over the shared experts.
* Logits: ``n_f(h) W_emb^T logit_scale``.

Departures from the published description, each noted in the
configuration file's ``assumed``: the expert width is
``intermediate_size``; the shared experts are stored as ONE gated MLP
``num_shared_experts x intermediate_size`` wide whose output is divided
by ``num_shared_experts`` (the mean of the shared outputs, the same
arithmetic); the full layers are NoPE; no vision tower.

``local_experts = (first, count)`` is one chip's share of an
expert-parallel deployment: the parameter tree then holds only those
experts (``moe.wg[i]`` is expert ``first + i``), routing is still over
all ``num_experts`` with the weights normalised over all chosen, and
only the held experts' terms are summed — what the absent ones would
have added is left out, as in the program.

To fit an 8k row beside the served weights on one chip, attention is
computed in blocks of queries and keys with a running softmax, the
experts one after another, and every matrix is upcast from its stored
(bf16) values where it is used.

``operands`` (default ``None``: everything float32) rounds both
operands of the attention, shared and expert matmuls — the ones whose
operand type a configuration's ``serve`` block states — and the K and V
rows as a cache would store them to that type before each product, with
float32 accumulation. It exists for ONE reading: what a computation in
a precision below the stated one does to the logits, which the
benchmark's tolerance has to refuse (PERF.md section 6, PR 26).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30
SLIDING = "sliding_attention"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(a, w, operands=None):
    """``a @ w`` in float32, from operands rounded to ``operands``."""
    if operands is None:
        return a @ _f32(w)
    return _f32(a.astype(operands)) @ _f32(jnp.asarray(w, operands))


def _norm(scale, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(scale)


def rope_interleaved(x, positions, theta: float):
    """``x`` ``(seq, heads, d)``: pair ``i`` is dims ``(2i, 2i+1)``,
    rotated by ``positions * theta ** (-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, positions, window, *, block: int):
    """Causal (and windowed) GQA attention of one row, in blocks:
    ``q`` ``(S, hq, d)``, ``k``/``v`` ``(S, hkv, d)``; ``window`` None
    or a number (traced is fine): query ``p`` sees ``p - window < j <=
    p``."""
    S, hq, d = q.shape
    hkv = k.shape[1]
    block = min(block, S)
    if S % block:
        raise ValueError(f"the row {S} is not a multiple of {block}")
    nb = S // block
    qb = q.reshape(nb, block, hkv, hq // hkv, d) / jnp.sqrt(float(d))
    kb = k.reshape(nb, block, hkv, d)
    vb = v.reshape(nb, block, hkv, d)
    pb = positions.reshape(nb, block)

    def one_query_block(args):
        qi, pq = args

        def key_block(carry, kvp):
            m, l, acc = carry
            kj, vj, pk = kvp
            s = jnp.einsum("qhgd,khd->hgqk", qi, kj)
            seen = pk[None, :] <= pq[:, None]
            if window is not None:
                seen = seen & (pk[None, :] > pq[:, None] - window)
            s = jnp.where(seen, s, NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            return (m_new, alpha * l + p.sum(-1),
                    alpha[..., None] * acc
                    + jnp.einsum("hgqk,khd->hgqd", p, vj)), None

        g = hq // hkv
        init = (jnp.full((hkv, g, block), NEG), jnp.zeros((hkv, g, block)),
                jnp.zeros((hkv, g, block, d)))
        (_, l, acc), _ = jax.lax.scan(key_block, init, (kb, vb, pb))
        out = acc / l[..., None]                     # (hkv, g, q, d)
        return out.transpose(2, 0, 1, 3).reshape(block, hq * d)

    return jax.lax.map(one_query_block, (qb, pb)).reshape(S, hq * d)


def route(router, u, config, local_experts=None):
    """``(experts (S, k), weights (S, k), margin (S,))``: the chosen
    experts and their weights, and — for the caller that wants to know
    where rounding could change WHICH held experts are chosen — how far
    the nearest held expert's router logit lies from the cut (midway
    between the last chosen and the first not chosen logit), as a share
    of the token's spread of router logits (their standard deviation
    over the experts: rounding moves a logit in proportion to it). The
    set of chosen held experts can only change if one of them crosses
    the cut."""
    k = config["num_experts_per_tok"]
    z = u @ _f32(router)
    first, count = local_experts or (0, z.shape[-1])
    top, idx = jax.lax.top_k(z, k + 1)
    s = jax.nn.sigmoid(top[:, :k])
    cut = (top[:, k - 1] + top[:, k]) / 2
    margin = jnp.abs(z[:, first:first + count] - cut[:, None]).min(-1)
    return idx[:, :k], s / s.sum(-1, keepdims=True), margin / z.std(-1)


def ffn(blk, u, config, local_experts=None, operands=None):
    """One layer's FFN on ``u = n(x)`` ``(S, E)``: the held experts'
    part of the routed sum plus the mean of the shared experts. Also
    returns the routing margin per token (:func:`route`)."""
    first, count = local_experts or (0, config["num_experts"])
    idx, w, margin = route(blk["moe"]["router"], u, config, local_experts)

    def expert(e, acc):
        h = jax.nn.silu(_mm(u, blk["moe"]["wg"][e], operands)) \
            * _mm(u, blk["moe"]["wi"][e], operands)
        w_e = jnp.where(idx == first + e, w, 0.0).sum(-1)
        return acc + w_e[:, None] * _mm(h, blk["moe"]["wo"][e], operands)

    routed = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(u))
    sh = blk["shared"]
    h = jax.nn.silu(_mm(u, sh["gate_proj"]["weight"], operands)) \
        * _mm(u, sh["up_proj"]["weight"], operands)
    shared = _mm(h, sh["fc_out"]["weight"], operands) \
        / config["num_shared_experts"]
    return routed + shared, margin


def hidden_states(params, input_ids, config, *, local_experts=None,
                  attn_block: int = 1024, with_margins: bool = False,
                  operands=None):
    """Final-norm hidden states times ``logit_scale``, ``(rows, seq,
    hidden)`` float32; positions are ``0..seq-1`` in every row. With
    ``with_margins`` also ``(rows, seq)``: the smallest routing margin
    (:func:`route`) over the layers."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["layer_norm_eps"]
    theta = config["rope_theta"]
    types = config["layer_types"][:config["num_hidden_layers"]]
    sliding = jnp.asarray([t == SLIDING for t in types])
    far = 2 ** 30               # a window no key is ever below

    def row(ids):
        S = ids.shape[0]
        pos = jnp.arange(S)
        x = _f32(params["wte"]["weight"][ids])

        def layer(carry, xs):
            x, low = carry
            blk, is_sliding = xs
            u = _norm(blk["norm"]["scale"], x, eps)
            a = blk["attn"]
            q = _mm(u, a["q_proj"]["weight"], operands).reshape(S, hq, d)
            k = _mm(u, a["k_proj"]["weight"], operands).reshape(S, hkv, d)
            v = _mm(u, a["v_proj"]["weight"], operands).reshape(S, hkv, d)
            q = jnp.where(is_sliding, rope_interleaved(q, pos, theta), q)
            k = jnp.where(is_sliding, rope_interleaved(k, pos, theta), k)
            if operands is not None:        # as a cache would store them
                q, k, v = (_f32(t.astype(operands)) for t in (q, k, v))
            att = attention(
                q, k, v, pos,
                jnp.where(is_sliding, config["sliding_window"], far),
                block=attn_block)
            f, margin = ffn(blk, u, config, local_experts, operands)
            low = jnp.minimum(low, margin)
            return (x + _mm(att, a["out_proj"]["weight"], operands) + f,
                    low), None

        (x, low), _ = jax.lax.scan(
            layer, (x, jnp.full((S,), jnp.inf)),
            (params["blocks"], sliding))
        h = _norm(params["final_norm"]["scale"], x, eps) \
            * config["logit_scale"]
        return h, low

    with jax.default_matmul_precision("highest"):
        h, low = jax.lax.map(row, jnp.asarray(input_ids, jnp.int32))
    return (h, low) if with_margins else h


def logits(params, input_ids, config, **kw):
    """Next-token logits ``(rows, seq, vocab)`` in float32."""
    h = hidden_states(params, input_ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["wte"]["weight"]).T
