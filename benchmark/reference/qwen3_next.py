"""Qwen3-Next's decoder in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, the gated delta rule as the
PUBLISHED recurrence — one token after another over the whole row
(``lax.scan`` over the sequence) —, no chunk form, no kernels, no cache.
It follows the keys of ``Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json`` (``config`` below is the benchmark's configuration
file: those keys, the published values under ``published``, the
deployment under ``deployment``, what the keys leave open under
``assumed``) and shares no code with ``hetu_tpu`` (its rounding and
row-block helpers are ``reference/brumby.py``'s, the state's rounding
``reference/jamba.py``'s): it only READS the
same parameter tree — ``wte.weight``, ``lm_head.weight`` ``(V, E)``,
``final_norm.scale``; ``blocks.runs.<i>`` one tree a RUN of like
layers, stacked over the run's layers on axis 0; each layer
``norm1.scale``, ``norm2.scale``,
``shared.{gate_proj,up_proj,fc_out}.weight`` ``(in, out)``,
``shared_gate`` ``(E, 1)``, ``moe.router`` ``(E, 512)``,
``moe.{wg,wi}`` ``(held, E, width)``, ``moe.wo`` and

* a Gated DeltaNet layer's ``attn``: ``qkvz_proj.weight`` ``(E, 2 Hk d
  + 2 H d)`` (q, k, v, z side by side), ``ba_proj.weight`` ``(E, 2 H)``
  (b, then a), ``conv`` ``(taps, 2 Hk d + H d)``, ``A_log``, ``dt_bias``
  ``(H,)``, ``o_gain`` ``(d,)``, ``out_proj.weight``;
* a gated attention layer's ``attn``: ``q_proj.weight`` ``(E, Hq x 2
  d)`` (a head's ``d`` of q, then its ``d`` of gate),
  ``{k_proj,v_proj,out_proj}.weight``, ``q_gain``, ``k_gain`` ``(d,)``.

ONE row of token ids ``(T,)``; ``n(x) = x / sqrt(mean x^2 + eps) * (1 +
w)`` with ``w`` the stored ``scale`` (the ZERO-CENTRED gain) everywhere
but the delta rule's output norm; layer ``i`` is gated attention where
``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet; ``h =
x + Mixer(n1(x))``, ``y = h + MoE(n2(h))``; the logits ``n_f(x)
W_head^T``.

* Gated DeltaNet (``u = n1(x)``; ``Hk`` key heads under ``H`` value
  heads of ``d``): ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``;
  ``[q | k | v] <- silu(sum_j c_j [q | k | v]_{t-3+j})`` a channel at a
  time, zeros before the row; a key head ``q <- q / |q| / sqrt(d)``,
  ``k <- k / |k|``; value head ``j`` reads key head ``j // (H / Hk)``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log_j) softplus(a + dt_bias_j)``
  ONE number a value head and token; per token ``S <- e^g S``, ``r = v
  - S^T k``, ``S <- S + beta k r^T``, ``o = S^T q``; out ``W_o
  (RMSNorm_d(o) * w_o * silu(z))``, ``w_o`` the heads' one gain (NOT
  zero-centred).
* Gated attention (``Hq`` query heads over ``Hkv`` kv heads of ``d``):
  ``[q | gate] = u W_q`` a head, ``k``, ``v``; ``q <- n_q(q)``, ``k <-
  n_k(k)`` a head (gain ``1 + w``); RoPE in split halves at
  ``rope_theta`` on the FIRST ``d x partial_rotary_factor`` numbers of
  a head, the rest pass; a causal softmax at ``1 / sqrt(d)``; out ``W_o
  (attn * sigmoid(gate))``.
* Experts: ``s = softmax(u W_r)`` over the published 512, the
  ``num_experts_per_tok`` largest chosen, ``w_e = s_e / sum_chosen s``;
  the held experts (``deployment.expert_share``: experts ``64 g .. 64 g
  + 63``) one after another, each applied to the tokens that chose it;
  beside ``sigmoid(u w_sg) * SwiGLU_shared(u)``.

To fit a 33k row beside the served weights on one chip every matrix is
upcast from its stored (bf16) values where it is used, the attention
runs in blocks of QUERIES (each against all the row's keys at once: one
masked softmax) and the wide products in blocks of rows.

``CONTROL`` (empty: the reference) plants ONE departure for a reading
that the benchmark's limits have to refuse (PERF.md section 6, PR 59):
``operands`` (a dtype: both operands of the projections, attention,
shared and expert matmuls rounded to it), ``no_erase`` (``S <- e^g S +
beta k v^T``: a gated linear attention), ``no_conv`` (the convolution
left out), ``tile_key_heads`` (value head ``j`` reads key head ``j %
Hk``), ``full_rotary`` (all ``d`` numbers of a head rotated),
``no_out_gate``, ``no_shared_gate``, ``plain_gain`` (``w`` for ``1 +
w``), ``sigmoid_router`` (``s = sigmoid(u W_r)``), ``state_dtype`` (the
delta rule's state kept in that type: rounded after every token by
``lax.reduce_precision`` — a convert there and back the TPU compiler
takes out of a float32 program, PERF.md section 6, PR 55).

``stops`` (positions) makes :func:`hidden_states` hand back, beside the
hidden states, every Gated DeltaNet layer's STATE as it stands after
each of those positions, ``(GDN layers, len(stops), H, d, d)``: what a
slot's state leaf has to hold there
(``benchmark/archs/qwen3_next.py::state_gap``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.brumby import _f32, _mm, _r, _rows
from benchmark.reference.jamba import _keep

#: see the module docstring; set by a builder's script, never by a run
CONTROL: dict = {}
#: rows a block of the wide products, queries a block of the attention
#: (memory only)
ROWS, Q_BLOCK = 2048, 128


def _unit_norm(x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def norm(w, x, eps, plain_gain=False):
    """The zero-centred RMSNorm: gain ``1 + w``."""
    return _unit_norm(x, eps) * (_f32(w) if plain_gain else 1.0 + _f32(w))


def layer_kinds(config) -> list:
    return ["attention" if (i + 1) % config["full_attention_interval"] == 0
            else "gdn" for i in range(config["num_hidden_layers"])]


def layer_runs(config) -> list:
    """The kind of every run of consecutive like layers, in order: the
    parameter tree's ``blocks.runs.<i>``."""
    kinds = layer_kinds(config)
    return [k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]


def gated_delta_net(a, u, config, *, stops, operands=None, no_erase=False,
                    no_conv=False, tile_key_heads=False, state_dtype=None):
    """``a``: one Gated DeltaNet layer's ``attn`` tree; ``u (T, E)`` the
    normed input -> ``(T, E)``, and the state after each position of
    ``stops (S,)``, ``(S, H, d, d)``."""
    T = u.shape[0]
    Hk, H = config["linear_num_key_heads"], config["linear_num_value_heads"]
    d, eps = config["linear_key_head_dim"], config["rms_norm_eps"]
    rep, key = H // Hk, Hk * d
    y = _rows(lambda ub: _mm(ub, a["qkvz_proj"]["weight"], operands), u,
              ROWS)
    pre, z = y[:, :2 * key + H * d], y[:, 2 * key + H * d:]
    if not no_conv:
        taps = _f32(a["conv"])
        K = taps.shape[0]
        padded = jnp.pad(pre, ((K - 1, 0), (0, 0)))
        pre = sum(taps[j] * padded[j:j + T] for j in range(K))
    pre = jax.nn.silu(pre)
    q = pre[:, :key].reshape(T, Hk, d)
    k = pre[:, key:2 * key].reshape(T, Hk, d)
    v = pre[:, 2 * key:].reshape(T, H, d)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-12) / d ** 0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-12)
    ba = _mm(u, a["ba_proj"]["weight"], operands)
    beta = jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(_f32(a["A_log"])) * jax.nn.softplus(
        ba[:, H:] + _f32(a["dt_bias"]))

    def of_key_head(x):
        """``(Hk, d)`` -> ``(H, d)``: the key head a value head reads."""
        if tile_key_heads:
            return jnp.tile(x, (rep, 1))
        return jnp.broadcast_to(x[:, None], (Hk, rep, d)).reshape(H, d)

    def token(carry, x):
        state, kept = carry
        qt, kt, vt, gt, bt, p = x
        qt, kt = of_key_head(qt), of_key_head(kt)
        state = jnp.exp(gt)[:, None, None] * state
        seen = vt if no_erase else vt - jnp.einsum("hk,hkv->hv", kt, state)
        state = state + (bt[:, None] * kt)[:, :, None] * seen[:, None, :]
        if state_dtype is not None:
            state = _keep(state, state_dtype)
        kept = jnp.where((stops == p)[:, None, None, None], state, kept)
        return (state, kept), jnp.einsum("hkv,hk->hv", state, qt)

    (_, kept), o = jax.lax.scan(
        token, (jnp.zeros((H, d, d), jnp.float32),
                jnp.zeros((len(stops), H, d, d), jnp.float32)),
        (q, k, v, g, beta, jnp.arange(T)))
    o = _unit_norm(o, eps) * _f32(a["o_gain"]) \
        * jax.nn.silu(z.reshape(T, H, d))
    return _rows(lambda ob: _mm(ob, a["out_proj"]["weight"], operands),
                 o.reshape(T, H * d), ROWS), kept


def rope_first(x, n: int, theta: float):
    """``x (T, heads, d)`` at positions ``0..T-1``: the first ``n``
    numbers of a head rotated as one head of ``n`` in split halves
    (pair ``i`` is dims ``(i, i + n/2)``, by ``t * theta ** (-2i /
    n)``), the other ``d - n`` as they are."""
    T = x.shape[0]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    lo, hi = x[..., :n // 2], x[..., n // 2:n]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang),
                            x[..., n:]], -1)


def gated_attention(a, u, config, *, operands=None, full_rotary=False,
                    no_out_gate=False, plain_gain=False,
                    q_block: int = Q_BLOCK):
    """``a``: one gated attention layer's ``attn`` tree; ``u (T, E)`` ->
    ``(T, E)``: one causal softmax a block of queries."""
    T = u.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    n_rot = d if full_rotary else int(d * config["partial_rotary_factor"])
    qg = _rows(lambda ub: _mm(ub, a["q_proj"]["weight"], operands), u,
               ROWS).reshape(T, H, 2, d)
    q, gate = qg[:, :, 0], qg[:, :, 1].reshape(T, H * d)
    k = _mm(u, a["k_proj"]["weight"], operands).reshape(T, Hkv, d)
    v = _mm(u, a["v_proj"]["weight"], operands).reshape(T, Hkv, d)
    q = rope_first(norm(a["q_gain"], q, eps, plain_gain), n_rot,
                   config["rope_theta"]).reshape(T, Hkv, H // Hkv, d)
    k = rope_first(norm(a["k_gain"], k, eps, plain_gain), n_rot,
                   config["rope_theta"])
    pos = jnp.arange(T)
    kr, vr = _r(k, operands), _r(v, operands)  # as a cache would store them

    def queries(args):
        qb, tb = args                       # (B, Hkv, g, d), (B,)
        s = jnp.einsum("qhgd,khd->hgqk", _r(qb, operands), kr) / d ** 0.5
        s = jnp.where((pos[None, :] <= tb[:, None])[None, None], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", _r(w, operands), vr)

    pad = -T % q_block
    nb = (T + pad) // q_block

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, q_block) + x.shape[1:])
    o = jax.lax.map(queries, (blocks(q), blocks(pos)))
    o = o.reshape(nb * q_block, H * d)[:T]
    if not no_out_gate:
        o = o * jax.nn.sigmoid(gate)
    return _rows(lambda ob: _mm(ob, a["out_proj"]["weight"], operands), o,
                 ROWS)


def held_experts(config) -> tuple:
    """``(first, count)`` of the router's experts held on this chip."""
    n = config["num_experts"]
    return config["deployment"]["expert_share"] * n, n


def route(router, u, config, *, sigmoid_router=False):
    """``(experts (S, k), weights (S, k), margin (S,))``: the chosen
    experts of the whole router, their weights, and how far the nearest
    HELD expert's router logit lies from the cut between chosen and not
    chosen (midway between the k-th and the k+1-th logit), as a share of
    the token's spread of router logits: the set of chosen held experts
    changes on rounding only if one of them crosses the cut."""
    k = config["num_experts_per_tok"]
    first, count = held_experts(config)
    z = u @ _f32(router)
    s = jax.nn.sigmoid(z) if sigmoid_router else jax.nn.softmax(z, axis=-1)
    top, idx = jax.lax.top_k(s, k)
    zs = jax.lax.top_k(z, k + 1)[0]
    cut = (zs[:, k - 1] + zs[:, k]) / 2
    margin = jnp.abs(z[:, first:first + count] - cut[:, None]).min(-1)
    return idx, top / top.sum(-1, keepdims=True), margin / z.std(-1)


def swiglu(p, u, operands=None):
    def block(ub):
        h = jax.nn.silu(_mm(ub, p["gate_proj"]["weight"], operands)) \
            * _mm(ub, p["up_proj"]["weight"], operands)
        return _mm(h, p["fc_out"]["weight"], operands)
    return _rows(block, u, ROWS)


def expert_ffn(blk, u, config, operands=None, *, sigmoid_router=False,
               no_shared_gate=False):
    """A layer's FFN on ``u = n2(h)`` ``(S, E)`` — this chip's share of
    the routed sum beside the gated shared expert — and the routing
    margin a token (:func:`route`)."""
    idx, w, margin = route(blk["moe"]["router"], u, config,
                           sigmoid_router=sigmoid_router)
    first, count = held_experts(config)

    def expert(e, acc):
        h = jax.nn.silu(_mm(u, blk["moe"]["wg"][e], operands)) \
            * _mm(u, blk["moe"]["wi"][e], operands)
        w_e = jnp.where(idx == first + e, w, 0.0).sum(-1)
        return acc + w_e[:, None] * _mm(h, blk["moe"]["wo"][e], operands)

    routed = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(u))
    shared = swiglu(blk["shared"], u, operands)
    if not no_shared_gate:
        shared = shared * jax.nn.sigmoid(u @ _f32(blk["shared_gate"]))
    return routed + shared, margin


def horizons(params, config):
    """Tokens a value head of every Gated DeltaNet layer keeps its past
    for, ``(GDN layers, H)``: ``1 / (A dt0)`` at the step the layer's
    bias alone gives, ``dt0 = softplus(dt_bias)``."""
    def leaf(name):
        return jnp.concatenate([
            _f32(params["blocks"]["runs"][str(j)]["attn"][name])
            for j, kind in enumerate(layer_runs(config)) if kind == "gdn"])
    return 1.0 / (jax.nn.softplus(leaf("dt_bias")) * jnp.exp(leaf("A_log")))


def hidden_states(params, ids, config, *, operands=None, stops=None,
                  with_margins: bool = False, no_erase=False,
                  no_conv=False, tile_key_heads=False, full_rotary=False,
                  no_out_gate=False, no_shared_gate=False,
                  plain_gain=False, sigmoid_router=False, state_dtype=None):
    """``ids (T,)`` -> the final-normed hidden states ``(T, E)``; with
    ``with_margins`` also ``(T,)``, the smallest routing margin
    (:func:`route`) over the layers; with ``stops`` (positions) also the
    Gated DeltaNet layers' states after each."""
    at = jnp.zeros((0,), jnp.int32) if stops is None \
        else jnp.asarray(stops, jnp.int32)
    eps = config["rms_norm_eps"]
    states = []
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["wte"]["weight"], ids, axis=0))
        low = jnp.full((x.shape[0],), jnp.inf)
        for j, kind in enumerate(layer_runs(config)):
            def layer(carry, p, kind=kind):
                x, low = carry
                # (the layer's matrices are upcast where they are used,
                # not beside the whole stack)
                p = jax.lax.optimization_barrier(p)
                u = norm(p["norm1"]["scale"], x, eps, plain_gain)
                if kind == "attention":
                    mixed, kept = gated_attention(
                        p["attn"], u, config, operands=operands,
                        full_rotary=full_rotary, no_out_gate=no_out_gate,
                        plain_gain=plain_gain), None
                else:
                    mixed, kept = gated_delta_net(
                        p["attn"], u, config, stops=at, operands=operands,
                        no_erase=no_erase, no_conv=no_conv,
                        tile_key_heads=tile_key_heads,
                        state_dtype=state_dtype)
                h = x + mixed
                f, margin = expert_ffn(
                    p, norm(p["norm2"]["scale"], h, eps, plain_gain),
                    config, operands, sigmoid_router=sigmoid_router,
                    no_shared_gate=no_shared_gate)
                return (h + f, jnp.minimum(low, margin)), kept
            (x, low), kept = jax.lax.scan(
                layer, (x, low), params["blocks"]["runs"][str(j)])
            if kind == "gdn":
                states.append(kept)
        h = norm(params["final_norm"]["scale"], x, eps, plain_gain)
    out = (h,) + ((low,) if with_margins else ()) \
        + ((jnp.concatenate(states),) if stops is not None else ())
    return out[0] if len(out) == 1 else out


def logits(params, ids, config, **kw):
    """Next-token logits ``(T, vocab)`` of one row, in float32."""
    h = hidden_states(params, ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["lm_head"]["weight"]).T
