"""A latent-attention (MLA) decoder with bias-corrected routed experts
behind a leading dense layer, in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks. It follows the language model's keys of
``moonshotai/Kimi-VL-A3B-Instruct`` ``config.json`` (``config`` below is
that file's keys) and shares no code with ``hetu_tpu``: it only READS
the same parameter tree — ``wte.weight``, ``lm_head.weight`` ``(V, E)``;
``blocks.dense.<i>`` one tree a dense layer and ``blocks.experts``
stacked over the expert layers on axis 0, each with ``norm1.scale``,
``norm2.scale``, ``attn.{q_proj,kv_down,kv_up,out_proj}.weight``
``(in, out)``, ``attn.kv_norm.scale``; a dense layer's
``mlp.{gate_proj,up_proj,fc_out}.weight``; an expert layer's
``shared.{gate_proj,up_proj,fc_out}.weight``, ``moe.router`` ``(E,
experts)``, ``moe.select_bias`` ``(experts,)``, ``moe.{wg,wi}``
``(experts, E, width)``, ``moe.wo`` ``(experts, width, E)``;
``final_norm.scale``.

Per layer, ``n`` the RMSNorm: ``h = x + Attn(n1(x))``, ``y = h +
FFN(n2(h))``.

* Attention, the EXPANDED form: ``q = u W_q``, per head ``[q_nope ‖
  q_rope]``; ``[c ‖ k_r] = u W_dkv``, ``c <- RMSNorm(c)``; RoPE on
  ``k_r`` (one a token, shared by the heads) and ``q_rope``, adjacent
  pairs ``(2i, 2i+1)``; per head ``[k_nope ‖ v] = c W_ukv``; scores
  ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``, causal.
* FFN of the first ``first_k_dense_replace`` layers: ``(silu(u W_g) *
  (u W_u)) W_d``. Of the others: ``s = sigmoid(u W_r)``, the
  ``num_experts_per_tok`` largest ``s + b`` chosen, ``w_e =
  routed_scaling_factor * s_e / sum_chosen s`` — from ``s``, not ``s +
  b``; ``FFN(u) = sum_chosen w_e E_e(u) + S(u)`` with the shared
  experts ONE gated MLP ``n_shared_experts x moe_intermediate_size``
  wide (their sum: the same arithmetic).
* Logits: ``n_f(h) W_head^T``, an untied head.

To fit a 16k row beside the served weights on one chip, attention is
computed in blocks of queries and keys with a running softmax, the
experts one after another, the dense layer's FFN in blocks of rows, and
every matrix is upcast from its stored (bf16) values where it is used.

``operands`` (default ``None``: everything float32) rounds both
operands of the attention, dense, shared and expert matmuls — the ones
whose operand type the configuration's ``serve`` block states — and the
latent rows as a cache would store them to that type before each
product, with float32 accumulation. ``ignore_bias`` routes by ``s``
alone. Both exist for ONE reading each: what a computation below the
stated precision, or one that leaves the selection bias out, does to
the logits, which the benchmark's tolerance has to refuse (PERF.md
section 6, PR 30).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _mm(a, w, operands=None):
    """``a @ w`` in float32, from operands rounded to ``operands``."""
    if operands is None:
        return a @ _f32(w)
    return _f32(a.astype(operands)) @ _f32(jnp.asarray(w, operands))


def _norm(scale, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def rope_pairs(x, positions, theta: float):
    """``x`` ``(seq, heads, d)``: pair ``i`` is dims ``(2i, 2i+1)``,
    rotated by ``positions * theta ** (-2i / d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, scale: float, *, block: int):
    """Causal attention of one row, in blocks: ``q``, ``k`` ``(S, H,
    dqk)``, ``v`` ``(S, H, dv)``, positions ``0..S-1``."""
    S, H, _ = q.shape
    block = min(block, S)
    if S % block:
        raise ValueError(f"the row {S} is not a multiple of {block}")
    nb = S // block
    qb = q.reshape(nb, block, H, -1) * scale
    kb = k.reshape(nb, block, H, -1)
    vb = v.reshape(nb, block, H, -1)
    pb = jnp.arange(S).reshape(nb, block)

    def one_query_block(args):
        qi, pq = args

        def key_block(carry, kvp):
            m, l, acc = carry
            kj, vj, pk = kvp
            s = jnp.einsum("qhd,khd->hqk", qi, kj)
            seen = pk[None, :] <= pq[:, None]
            s = jnp.where(seen, s, NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            return (m_new, alpha * l + p.sum(-1),
                    alpha[..., None] * acc
                    + jnp.einsum("hqk,khd->hqd", p, vj)), None

        init = (jnp.full((H, block), NEG), jnp.zeros((H, block)),
                jnp.zeros((H, block, v.shape[-1])))
        (_, l, acc), _ = jax.lax.scan(key_block, init, (kb, vb, pb))
        return (acc / l[..., None]).transpose(1, 0, 2).reshape(block, -1)

    return jax.lax.map(one_query_block, (qb, pb)).reshape(S, -1)


def mla(a, u, config, operands=None, *, block: int):
    """One layer's attention on ``u = n1(x)`` ``(S, E)``, expanded."""
    S = u.shape[0]
    H = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, dc = config["v_head_dim"], config["kv_lora_rank"]
    theta, pos = config["rope_theta"], jnp.arange(S)
    q = _mm(u, a["q_proj"]["weight"], operands).reshape(S, H, dn + dr)
    ckr = _mm(u, a["kv_down"]["weight"], operands)
    c = _norm(a["kv_norm"]["scale"], ckr[:, :dc], config["rms_norm_eps"])
    k_r = rope_pairs(ckr[:, None, dc:], pos, theta)
    q_r = rope_pairs(q[..., dn:], pos, theta)
    if operands is not None:            # as a cache would store them
        c, k_r = (_f32(t.astype(operands)) for t in (c, k_r))
    kv = _mm(c, a["kv_up"]["weight"], operands).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], q_r], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (S, H, dr))], -1)
    v = kv[..., dn:]
    if operands is not None:
        q, k, v = (_f32(t.astype(operands)) for t in (q, k, v))
    att = attention(q, k, v, (dn + dr) ** -0.5, block=block)
    return _mm(att, a["out_proj"]["weight"], operands)


def gated(p, u, operands=None, *, rows: int = 2048):
    """``(silu(u W_g) * (u W_u)) W_d``, in blocks of rows."""
    S = u.shape[0]
    rows = min(rows, S)
    if S % rows:
        raise ValueError(f"the row {S} is not a multiple of {rows}")

    def one(ub):
        h = jax.nn.silu(_mm(ub, p["gate_proj"]["weight"], operands)) \
            * _mm(ub, p["up_proj"]["weight"], operands)
        return _mm(h, p["fc_out"]["weight"], operands)

    return jax.lax.map(one, u.reshape(S // rows, rows, -1)).reshape(u.shape)


def route(moe, u, config, ignore_bias: bool = False):
    """``(experts (S, k), weights (S, k), margin (S,))``: the chosen
    experts and their weights, and — for the caller that wants to know
    where rounding could change WHICH experts are chosen — how far the
    nearest expert's selection score ``s + b`` lies from the cut (midway
    between the last chosen and the first not chosen score), as a share
    of the token's spread of selection scores (their standard deviation
    over the experts: rounding moves a score in proportion to it). The
    chosen set can only change if an expert crosses the cut."""
    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(u @ _f32(moe["router"]))
    sel = s if ignore_bias else s + _f32(moe["select_bias"])
    top, idx = jax.lax.top_k(sel, k + 1)
    cut = (top[:, k - 1] + top[:, k]) / 2
    margin = jnp.abs(sel - cut[:, None]).min(-1) / sel.std(-1)
    chosen = jnp.take_along_axis(s, idx[:, :k], axis=-1)
    w = config["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)
    return idx[:, :k], w, margin


def expert_ffn(blk, u, config, operands=None, ignore_bias=False):
    """An expert layer's FFN on ``u = n2(h)`` ``(S, E)`` and the routing
    margin per token (:func:`route`)."""
    idx, w, margin = route(blk["moe"], u, config, ignore_bias)

    def expert(e, acc):
        h = jax.nn.silu(_mm(u, blk["moe"]["wg"][e], operands)) \
            * _mm(u, blk["moe"]["wi"][e], operands)
        w_e = jnp.where(idx == e, w, 0.0).sum(-1)
        return acc + w_e[:, None] * _mm(h, blk["moe"]["wo"][e], operands)

    routed = jax.lax.fori_loop(0, config["n_routed_experts"], expert,
                               jnp.zeros_like(u))
    return routed + gated(blk["shared"], u, operands), margin


def hidden_states(params, input_ids, config, *, attn_block: int = 1024,
                  with_margins: bool = False, operands=None,
                  ignore_bias: bool = False):
    """Final-norm hidden states ``(rows, seq, hidden)`` float32;
    positions are ``0..seq-1`` in every row. With ``with_margins`` also
    ``(rows, seq)``: the smallest routing margin (:func:`route`) over
    the expert layers."""
    eps = config["rms_norm_eps"]
    blocks = params["blocks"]

    def attend(blk, x):
        return x + mla(blk["attn"], _norm(blk["norm1"]["scale"], x, eps),
                       config, operands, block=attn_block)

    def row(ids):
        x = _f32(params["wte"]["weight"][ids])
        for i in range(config["first_k_dense_replace"]):
            blk = blocks["dense"][str(i)]
            h = attend(blk, x)
            x = h + gated(blk["mlp"],
                          _norm(blk["norm2"]["scale"], h, eps), operands)

        def layer(carry, blk):
            x, low = carry
            h = attend(blk, x)
            f, margin = expert_ffn(
                blk, _norm(blk["norm2"]["scale"], h, eps), config,
                operands, ignore_bias)
            return (h + f, jnp.minimum(low, margin)), None

        (x, low), _ = jax.lax.scan(
            layer, (x, jnp.full((ids.shape[0],), jnp.inf)),
            blocks["experts"])
        return _norm(params["final_norm"]["scale"], x, eps), low

    with jax.default_matmul_precision("highest"):
        h, low = jax.lax.map(row, jnp.asarray(input_ids, jnp.int32))
    return (h, low) if with_margins else h


def logits(params, input_ids, config, **kw):
    """Next-token logits ``(rows, seq, vocab)`` in float32."""
    h = hidden_states(params, input_ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["lm_head"]["weight"]).T
