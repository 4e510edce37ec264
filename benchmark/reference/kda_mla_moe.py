"""A decoder of Kimi-Delta-Attention layers beside latent-attention
(MLA) layers over group-limited routed experts, in plain ``jax.numpy``:
float32, ``jax.default_matmul_precision("highest")``, no kernels, no
cache, no chunk form. It follows the keys of
``inclusionAI/Ling-3.0-flash-VL`` ``config.json`` (``config`` below is
the configuration file: those keys, the published values under
``published``, the deployment under ``deployment``, what the keys leave
open under ``assumed``) and shares no code with ``hetu_tpu``: it only
READS the same parameter tree — ``wte.weight``, ``lm_head.weight``
``(V, E)``; ``blocks.dense.<i>`` one tree a leading dense layer and
``blocks.runs.<i>`` one tree a RUN of like expert layers, stacked over
the run's layers on axis 0; each layer ``norm1.scale``, ``norm2.scale``
and

* a KDA layer's ``attn``: ``qkv_proj.weight`` ``(E, 3 H d)`` (q, k, v
  side by side), ``decay_proj.weight`` ``(E, H d)``,
  ``head_proj.weight`` ``(E, 2 H)`` (beta, then the output gate),
  ``out_proj.weight``, ``conv`` ``(taps, 3 H d)``, ``A_log`` ``(H,)``,
  ``dt_bias`` ``(H d,)``, ``o_gain`` ``(d,)``;
* an MLA layer's ``attn``: ``{q_proj,kv_down,kv_up,out_proj}.weight``,
  ``kv_norm.scale``, ``q_gain`` ``(nope + rope,)``, ``kr_gain``
  ``(rope,)``;
* a dense layer's ``mlp.{gate_proj,up_proj,fc_out}.weight``; an expert
  layer's ``shared.{...}.weight``, ``moe.router`` ``(E, 512)``,
  ``moe.select_bias`` ``(512,)``, ``moe.{wg,wi}`` ``(held, E, width)``,
  ``moe.wo``.

Per layer, ``n`` the RMSNorm: ``h = x + Mixer(n1(x))``, ``y = h +
FFN(n2(h))``; the mixer of layer ``i`` is MLA where ``(i + 1) %
layer_group_size == 0``, else KDA.

* KDA, the TOKEN RECURRENCE (``u = n1(x)``): ``[q | k | v] = SiLU(conv(u
  [W_q | W_k | W_v]))``, ``y_t = sum_j c_j a_{t-3+j}`` a channel at a
  time, zeros before the row; per head ``q <- q / |q| / sqrt(d)``, ``k
  <- k / |k|``; ``g = kda_lower_bound * sigmoid(exp(A_log_h) (u W_f +
  dt_bias))`` a channel, ``beta = sigmoid(u W_b)`` a head; ``S_t = (I -
  beta k k^T) Diag(e^g) S_{t-1} + beta k v^T``, ``o_t = S_t^T q_t``;
  out ``W_o (RMSNorm_head(o) * sigmoid(u W_gate))``, one gate a head.
  ASSUMED (the keys leave them open): no RoPE in KDA; the L2 norms;
  ``group_norm_size`` 1 read as the per-head output norm;
  ``num_kv_heads_for_linear_attn`` 0 read as "as many as query heads".
* MLA, the expanded form of ``reference/mla_moe.py`` with, ASSUMED for
  ``use_qk_norm``, an RMSNorm with a gain on each head's query (nope
  and rope parts together) and on the one RoPE key, both before the
  rotation; the latent's own norm is the published ``kv_norm``.
* Experts: ``s = sigmoid(u W_r)`` (512), ``s' = s + b``; a group's
  score is the sum of its two largest ``s'`` (``n_group`` groups of
  consecutive experts); the ``topk_group`` best groups stay; the
  ``num_experts_per_tok`` largest ``s'`` inside them are chosen,
  weighted ``routed_scaling_factor * s_e / sum_chosen s``; a PER-TOKEN
  LOOP over the chosen experts adds those held here
  (``deployment.expert_group``: experts ``64 g .. 64 g + 63``; what the
  others would add is the other chips'), beside ONE shared SwiGLU.
* Logits: ``n_f(h) W_head^T``, an untied head.

To fit an 8k row beside the served weights the layers' matrices are
upcast where they are used, attention runs in blocks and the experts
one after another (a loop over the held experts, each applied to the
tokens that chose it: the same sum as the per-token loop).

``CONTROL`` plants ONE departure for a reading of what the comparison
must refuse (PERF.md section 6, PR 41): ``operands`` (a dtype: both
operands of the projections, attention, shared and expert matmuls
rounded to it), ``no_erase`` (``S_t = Diag(e^g) S_{t-1} + beta k v^T``:
a gated linear attention), ``no_conv`` (``y_t = a_t``), ``head_decay``
(a head's decay is the mean of its channels' ``g``), ``no_group_limit``
(top-8 of all 512), ``ignore_bias`` (selection by ``s``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.mla_moe import (
    _f32, _mm, _norm, attention, gated, rope_pairs,
)

#: the departure planted for a negative control (see the module
#: docstring); ``{}`` is the reference
CONTROL: dict = {}


def kda(a, u, config, operands=None, *, no_erase=False, no_conv=False,
        head_decay=False):
    """One KDA layer's mixer on ``u = n1(x)`` ``(S, E)``."""
    S = u.shape[0]
    H, d = config["num_attention_heads"], config["head_dim"]
    pre = _mm(u, a["qkv_proj"]["weight"], operands)           # (S, 3Hd)
    if not no_conv:
        taps = _f32(a["conv"])
        K = taps.shape[0]
        padded = jnp.pad(pre, ((K - 1, 0), (0, 0)))
        pre = sum(taps[j] * padded[j:j + S] for j in range(K))
    q, k, v = (jax.nn.silu(pre).reshape(S, 3, H, d)[:, i] for i in range(3))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-12) / d ** 0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-12)
    f = (_mm(u, a["decay_proj"]["weight"], operands)
         + _f32(a["dt_bias"])).reshape(S, H, d)
    g = config["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_f32(a["A_log"]))[None, :, None] * f)
    if head_decay:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    bg = jax.nn.sigmoid(_mm(u, a["head_proj"]["weight"], operands))
    beta, gate = bg[:, :H], bg[:, H:]

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, :, None] * state
        seen = vt if no_erase else vt - jnp.einsum("hk,hkv->hv", kt, state)
        state = state + (bt[:, None] * kt)[:, :, None] * seen[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = _norm(a["o_gain"], o, config["rms_norm_eps"]) * gate[:, :, None]
    return _mm(o.reshape(S, H * d), a["out_proj"]["weight"], operands)


def mla(a, u, config, operands=None, *, block: int):
    """One MLA layer's attention on ``u = n1(x)`` ``(S, E)``, expanded,
    with the q and RoPE-key norms."""
    S = u.shape[0]
    H = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, dc = config["v_head_dim"], config["kv_lora_rank"]
    eps, theta, pos = config["rms_norm_eps"], config["rope_theta"], \
        jnp.arange(S)
    q = _mm(u, a["q_proj"]["weight"], operands).reshape(S, H, dn + dr)
    ckr = _mm(u, a["kv_down"]["weight"], operands)
    c = _norm(a["kv_norm"]["scale"], ckr[:, :dc], eps)
    k_r = ckr[:, None, dc:]
    if config["use_qk_norm"]:
        q = _norm(a["q_gain"], q, eps)
        k_r = _norm(a["kr_gain"], k_r, eps)
    k_r = rope_pairs(k_r, pos, theta)
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


def _divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` not above ``cap`` (the blocks a row
    is worked in: memory only)."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def held_experts(config) -> tuple:
    """``(first, count)`` of the router's experts held on this chip."""
    n = config["num_experts"]
    return config["deployment"]["expert_group"] * n, n


def route(moe, u, config, *, ignore_bias=False, no_group_limit=False):
    """``(experts (S, k), weights (S, k), margin (S,))``: the chosen
    experts of the whole router and their weights, and how close the
    choice of an expert HELD HERE is to flipping — the distance of the
    nearest held expert's selection score from the cut between chosen
    and not chosen (inside the kept groups), or of the held group's
    score from the cut between kept and dropped groups, whichever is
    smaller, as a share of the token's spread of selection scores."""
    k, n_group = config["num_experts_per_tok"], config["n_group"]
    first, count = held_experts(config)
    s = jax.nn.sigmoid(u @ _f32(moe["router"]))
    sel = s if ignore_bias else s + _f32(moe["select_bias"])
    spread = sel.std(-1)
    held = (jnp.arange(sel.shape[-1]) >= first) \
        & (jnp.arange(sel.shape[-1]) < first + count)
    masked, margin = sel, jnp.full(sel.shape[:1], jnp.inf)
    if not no_group_limit:
        per = sel.reshape(sel.shape[0], n_group, -1)
        score = jax.lax.top_k(per, 2)[0].sum(-1)               # (S, G)
        top, _ = jax.lax.top_k(score, config["topk_group"] + 1)
        cut = (top[:, -2] + top[:, -1]) / 2
        kept = score > cut[:, None]
        g = jnp.arange(n_group) * per.shape[-1]
        mine = (g < first + count) & (g + per.shape[-1] > first)
        margin = jnp.where(mine[None], jnp.abs(score - cut[:, None]),
                           jnp.inf).min(-1) / spread
        masked = jnp.where(jnp.repeat(kept, per.shape[-1], axis=1), sel,
                           -jnp.inf)
    top, idx = jax.lax.top_k(masked, k + 1)
    cut = (top[:, k - 1] + top[:, k]) / 2
    near = jnp.where(held[None] & jnp.isfinite(masked),
                     jnp.abs(masked - cut[:, None]), jnp.inf).min(-1)
    margin = jnp.minimum(margin, near / spread)
    chosen = jnp.take_along_axis(s, idx[:, :k], axis=-1)
    w = config["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)
    return idx[:, :k], w, margin


def expert_ffn(blk, u, config, operands=None, **control):
    """An expert layer's FFN on ``u = n2(h)`` ``(S, E)`` — this chip's
    share of the routed sum beside the shared expert — and the routing
    margin per token (:func:`route`)."""
    idx, w, margin = route(blk["moe"], u, config, **control)
    first, count = held_experts(config)

    def expert(e, acc):
        h = jax.nn.silu(_mm(u, blk["moe"]["wg"][e], operands)) \
            * _mm(u, blk["moe"]["wi"][e], operands)
        w_e = jnp.where(idx == first + e, w, 0.0).sum(-1)
        return acc + w_e[:, None] * _mm(h, blk["moe"]["wo"][e], operands)

    routed = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(u))
    return routed + gated(blk["shared"], u, operands,
                          rows=_divisor(u.shape[0], 2048)), margin


def mixer_kinds(config) -> list:
    return ["mla" if (i + 1) % config["layer_group_size"] == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def hidden_states(params, ids, config, *, attn_block: int = 1024,
                  with_margins: bool = False, operands=None,
                  no_erase=False, no_conv=False, head_decay=False,
                  no_group_limit=False, ignore_bias=False):
    """Final-norm hidden states ``(seq, hidden)`` float32 of ONE row
    ``ids (seq,)`` at positions ``0..seq-1``. With ``with_margins`` also
    ``(seq,)``: the smallest routing margin (:func:`route`) over the
    expert layers."""
    eps = config["rms_norm_eps"]
    blocks, kinds = params["blocks"], mixer_kinds(config)
    n_dense = config["first_k_dense_replace"]

    def mix(kind, blk, x):
        u = _norm(blk["norm1"]["scale"], x, eps)
        if kind == "mla":
            return x + mla(blk["attn"], u, config, operands,
                           block=_divisor(x.shape[0], attn_block))
        return x + kda(blk["attn"], u, config, operands,
                       no_erase=no_erase, no_conv=no_conv,
                       head_decay=head_decay)

    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["weight"][jnp.asarray(ids, jnp.int32)])
        low = jnp.full((x.shape[0],), jnp.inf)
        for i in range(n_dense):
            blk = blocks["dense"][str(i)]
            h = mix(kinds[i], blk, x)
            x = h + gated(blk["mlp"],
                          _norm(blk["norm2"]["scale"], h, eps), operands,
                          rows=_divisor(h.shape[0], 2048))
        # the runs of like expert layers, each a scan over its stack
        i, r = n_dense, 0
        while i < len(kinds):
            kind = kinds[i]
            n = 1
            while i + n < len(kinds) and kinds[i + n] == kind:
                n += 1

            def layer(carry, blk, kind=kind):
                x, low = carry
                h = mix(kind, blk, x)
                f, margin = expert_ffn(
                    blk, _norm(blk["norm2"]["scale"], h, eps), config,
                    operands, ignore_bias=ignore_bias,
                    no_group_limit=no_group_limit)
                return (h + f, jnp.minimum(low, margin)), None

            (x, low), _ = jax.lax.scan(layer, (x, low),
                                       blocks["runs"][str(r)])
            i, r = i + n, r + 1
        h = _norm(params["final_norm"]["scale"], x, eps)
    return (h, low) if with_margins else h


def logits(params, ids, config, **kw):
    """Next-token logits ``(seq, vocab)`` of one row, in float32."""
    h = hidden_states(params, ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["lm_head"]["weight"]).T
