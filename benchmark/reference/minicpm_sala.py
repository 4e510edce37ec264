"""MiniCPM-SALA's decoder in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
chunking of the mathematics. It follows the keys of
``openbmb/MiniCPM-SALA`` ``config.json`` (``config`` below is the
benchmark's configuration file: those keys, the layers held in
``mixer_types``, the sparse sizes under ``assumed``) and shares no code
with ``hetu_tpu``: it only READS the same parameter tree —
``wte.weight``, ``lm_head.weight`` ``(V, E)``; ``blocks.runs.<i>`` one
tree a RUN of like layers, stacked over the run's layers on axis 0, each
with ``norm1.scale``, ``norm2.scale``,
``attn.{q_proj,k_proj,v_proj,gate_proj,out_proj}.weight`` ``(in,
out)``, ``attn.{q_gain,k_gain}`` (and ``attn.o_gain`` in a lightning
layer), ``mlp.{gate_proj,up_proj,fc_out}.weight``; ``final_norm.scale``.

ONE row of token ids ``(T,)``; ``n`` the RMSNorm (eps 1e-6), ``a =
scale_depth / sqrt(32)`` (the PUBLISHED depth):

* ``x0 = scale_emb * E[ids]``; a layer: ``h = x + a * Mixer(n1(x))``,
  ``y = h + a * (silu(u W_g) * (u W_u)) W_d`` with ``u = n2(h)``; the
  logits ``(n_f(x) / (hidden_size / dim_model_base)) W_head^T``.
* ``minicpm4`` — block-sparse attention, NoPE: ``q``, ``k`` RMS-normed
  per head with a gain, ``v``; the compressed keys ``kbar_j = mean(k[16
  j : 16 j + 32])``. For the query at ``t`` and kv group ``g``: ``p_h =
  softmax_j(q_h . kbar_j / sqrt(128))`` over the windows complete at
  ``t`` (``16 j + 32 <= t + 1``), ``s_j = sum_{h in g} p_h[j]``; a
  64-token block's score is the max of ``s_j`` over the windows that
  overlap it; block 0, the query's own block and the 32 before it score
  ``+inf``; the 64 highest blocks at or below the query's own are
  chosen, lowest index first among equals (fewer than 64: all). Then
  per head a softmax over the keys ``j <= t`` of the chosen blocks,
  ``o <- o * sigmoid(u W_g)`` and ``W_o``. Departures from the family's
  code (the configuration's ``assumed`` says why): no ``dense_len``
  switch, no log-sum-exp approximation in the selection.
* ``lightning-attn`` — ``q = RoPE(n(u W_q))``, ``k`` likewise (theta
  10000, split-half pairs ``(i, i + 64)``), ``v = u W_v``; per head ``S_t
  = e^{-s_h} S_{t-1} + k_t v_t^T`` with ``s_h = 2^(-8 (h + 1) / 32)``,
  ``o_t = S_t^T q_t / sqrt(128)`` — the token recurrence, a token a
  step; ``o <- n_4096(o) * sigmoid(u W_g)`` and ``W_o``.

To fit a 32k row beside the served weights on one chip the sparse layer
is computed in blocks of QUERIES (every query of a block against all
its keys at once), the MLP in blocks of rows, and every matrix is upcast
from its stored (bf16) values where it is used.

Beside the logits it returns the SELECTION MARGIN of every position:
the gap between the 64th and the 65th block score as a share of the
spread of the competing blocks' scores, the smallest over the sparse
layers and kv groups (``inf`` where 64 blocks or fewer are visible) —
where it is small, rounding picks another block, a different and
equally valid computation.

``CONTROL`` (empty: the reference) is for THREE readings that the
benchmark's limits have to refuse (PERF.md section 6, PR 39):
``operands`` rounds both operands of every projection, score, value
and MLP product, and the keys, values and compressed keys as a cache
would store them, to that type; ``forced_only`` attends the forced
blocks alone (no top-k); ``no_decay`` leaves the decay out of the
lightning state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: see the module docstring; set by a builder's script, never by a run
CONTROL: dict = {}
SPARSE, LINEAR = "minicpm4", "lightning-attn"


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _r(x, operands):
    """``x`` rounded to ``operands`` and back (``None``: as it is)."""
    return x if operands is None else _f32(_f32(x).astype(operands))


def _mm(a, w, operands=None):
    return _r(a, operands) @ _r(_f32(w), operands)


def _norm(scale, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _rows(fn, x, rows: int):
    """``fn`` over blocks of ``rows`` rows of ``x`` (memory only)."""
    T = x.shape[0]
    if T <= rows:
        return fn(x)
    pad = -T % rows
    xb = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) \
        .reshape((-1, rows) + x.shape[1:])
    out = jax.lax.map(fn, xb)
    return out.reshape((-1,) + out.shape[2:])[:T]


def rope_half(x, theta: float):
    """``x`` ``(T, H, d)`` at positions ``0..T-1``: pair ``i`` is dims
    ``(i, i + d/2)``, rotated by ``t * theta ** (-2i / d)``."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def sparse_sizes(config: dict) -> dict:
    a = config["assumed"]
    return {k: int(a[k]) for k in ("kernel_size", "kernel_stride",
                                   "block_size", "topk", "init_blocks",
                                   "window_size")}


def sparse_attention(a, u, config, *, operands=None, forced_only=False,
                     q_block: int = 128):
    """One ``minicpm4`` mixer on ``u (T, E)``: ``(out (T, E), margin
    (T,))``."""
    sz = sparse_sizes(config)
    ks, st, B, topk = (sz["kernel_size"], sz["kernel_stride"],
                       sz["block_size"], sz["topk"])
    T = u.shape[0]
    H, Hkv, d = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    G = H // Hkv
    eps = config["rms_norm_eps"]
    q = _norm(a["q_gain"], _mm(u, a["q_proj"]["weight"], operands)
              .reshape(T, Hkv, G, d), eps)
    k = _norm(a["k_gain"], _mm(u, a["k_proj"]["weight"], operands)
              .reshape(T, Hkv, d), eps)
    v = _mm(u, a["v_proj"]["weight"], operands).reshape(T, Hkv, d)
    q, k, v = (_r(t, operands) for t in (q, k, v))
    # the compressed keys: window j is tokens [st j, st j + ks)
    J = max((T - ks) // st + 1, 0)
    win = np.arange(J)[:, None] * st + np.arange(ks)[None, :]
    kbar = _r(k[win].mean(axis=1), operands) if J else \
        jnp.zeros((0, Hkv, d), jnp.float32)               # (J, Hkv, d)
    nb = -(-T // B)
    # the windows that overlap block b: st j < B (b + 1), st j + ks > B b
    jj, bb = np.arange(J)[None, :], np.arange(nb)[:, None]
    ov = (st * jj < B * (bb + 1)) & (st * jj + ks > B * bb)   # (nb, J)
    width = max(1, int(ov.sum(1).max())) if J else 1
    over_idx = np.argsort(~ov, axis=1, kind="stable")[:, :width] \
        if J else np.zeros((nb, 1), np.int64)
    over_ok = np.take_along_axis(ov, over_idx, axis=1) if J else \
        np.zeros((nb, 1), bool)
    blocks = np.arange(nb)
    key_block = np.arange(T) // B
    scale = 1.0 / math.sqrt(d)

    def queries(args):
        qb, tb = args                         # (Bq, Hkv, G, d), (Bq,)
        if J:
            c = jnp.einsum("qkgd,jkd->qkgj", qb, kbar) * scale
            vis = (np.arange(J)[None, :] * st + ks) <= (tb[:, None] + 1)
            vis = vis[:, None, None, :]
            c = jnp.where(vis, c, -jnp.inf)
            m = jnp.max(c, -1, keepdims=True)
            e = jnp.where(vis, jnp.exp(c - jnp.where(
                jnp.isfinite(m), m, 0.0)), 0.0)
            den = e.sum(-1, keepdims=True)
            s = (e / jnp.where(den == 0, 1.0, den)).sum(2)   # (Bq,Hkv,J)
            blk = jnp.max(jnp.where(over_ok[None, None],
                                    s[:, :, over_idx], -jnp.inf), -1)
        else:
            blk = jnp.full((qb.shape[0], Hkv, nb), -jnp.inf)
        own = (tb // B)[:, None, None]
        b = blocks[None, None, :]
        forced = (b < sz["init_blocks"]) | (
            (b >= own - sz["window_size"] // B) & (b <= own))
        visible = b <= own
        score = jnp.where(visible, jnp.where(forced, jnp.inf, blk),
                          -jnp.inf)
        order = jnp.argsort(-score, axis=-1, stable=True)
        ranked = jnp.take_along_axis(score, order, axis=-1)
        pick = order[..., :topk]
        ok = ranked[..., :topk] > -jnp.inf
        if forced_only:
            ok &= jnp.isposinf(ranked[..., :topk])
        chosen = jnp.zeros(score.shape, jnp.int32).at[
            jnp.arange(score.shape[0])[:, None, None],
            jnp.arange(Hkv)[None, :, None], pick].add(
                ok.astype(jnp.int32)) > 0
        # the margin: 64th against 65th, over the competing blocks'
        # spread (the blocks that are visible and not forced)
        if nb > topk:
            comp = visible & ~forced
            hi = jnp.max(jnp.where(comp, blk, -jnp.inf), -1)
            lo = jnp.min(jnp.where(comp, blk, jnp.inf), -1)
            gap = ranked[..., topk - 1] - ranked[..., topk]
            margin = jnp.where(
                jnp.isfinite(ranked[..., topk]) & (hi > lo),
                gap / jnp.where(hi > lo, hi - lo, 1.0), jnp.inf)
            margin = jnp.min(margin, axis=-1)
        else:
            margin = jnp.full((qb.shape[0],), jnp.inf)
        seen = chosen[:, :, key_block] \
            & (np.arange(T)[None, :] <= tb[:, None])[:, None, :]
        sc = jnp.einsum("qkgd,jkd->qkgj", qb, k) * scale
        p = jax.nn.softmax(jnp.where(seen[:, :, None, :], sc, -jnp.inf),
                           axis=-1)
        o = jnp.einsum("qkgj,jkd->qkgd", _r(p, operands), v)
        return o.reshape(qb.shape[0], H * d), margin

    Bq = min(q_block, T)
    pad = -T % Bq
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))) \
        .reshape(-1, Bq, Hkv, G, d)
    tp = jnp.minimum(jnp.arange(T + pad), T - 1).reshape(-1, Bq)
    o, margin = jax.lax.map(queries, (qp, tp))
    o, margin = o.reshape(-1, H * d)[:T], margin.reshape(-1)[:T]
    gate = jax.nn.sigmoid(_mm(u, a["gate_proj"]["weight"], operands))
    return _mm(o * gate, a["out_proj"]["weight"], operands), margin


def lightning_attention(a, u, config, *, operands=None, no_decay=False,
                        head_block: int = 8):
    """One ``lightning-attn`` mixer on ``u (T, E)``: the recurrence, a
    token a step. The heads do not see one another, so they are taken
    ``head_block`` at a time (memory only)."""
    T = u.shape[0]
    H, d = config["lightning_nh"], config["lightning_head_dim"]
    eps = config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    hb = min(head_block, H)
    if H % hb:
        raise ValueError(f"{H} heads in blocks of {hb}")
    slope = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    wq, wk, wv = (_r(_f32(a[n]["weight"]), operands).reshape(-1, H // hb,
                                                             hb * d)
                  for n in ("q_proj", "k_proj", "v_proj"))
    ur = _r(u, operands)

    def heads(args):
        wq_g, wk_g, wv_g, slope_g = args
        q = rope_half(_norm(a["q_gain"], (ur @ wq_g).reshape(T, hb, d),
                            eps), theta)
        k = rope_half(_norm(a["k_gain"], (ur @ wk_g).reshape(T, hb, d),
                            eps), theta)
        v = (ur @ wv_g).reshape(T, hb, d)
        q, k, v = (_r(t, operands) for t in (q, k, v))
        lam = jnp.ones((hb, 1, 1)) if no_decay else \
            jnp.exp(-slope_g)[:, None, None]

        def token(S, x):
            qt, kt, vt = x
            S = lam * S + kt[:, :, None] * vt[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, qt) / math.sqrt(d)

        _, o = jax.lax.scan(token, jnp.zeros((hb, d, d), jnp.float32),
                            (q, k, v))
        return o                                      # (T, hb, d)

    o = jax.lax.map(heads, (jnp.moveaxis(wq, 1, 0), jnp.moveaxis(wk, 1, 0),
                            jnp.moveaxis(wv, 1, 0),
                            slope.reshape(H // hb, hb)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * d)
    o = _norm(a["o_gain"], o, eps)
    gate = jax.nn.sigmoid(_mm(u, a["gate_proj"]["weight"], operands))
    return _mm(o * gate, a["out_proj"]["weight"], operands)


def mlp(p, u, operands=None, *, rows: int = 2048):
    wg, wu, wd = (_r(_f32(p[n]["weight"]), operands)
                  for n in ("gate_proj", "up_proj", "fc_out"))

    def block(ub):
        ub = _r(ub, operands)
        return _r(jax.nn.silu(ub @ wg) * (ub @ wu), operands) @ wd

    return _rows(block, u, rows)


def layers_of(config):
    """``(kind, run, layer within the run)`` of the layers held, in
    order: a run of like layers is one stacked tree."""
    run, at, prev = -1, 0, None
    for kind in config["mixer_types"]:
        if kind != prev:
            run, at, prev = run + 1, 0, kind
        yield kind, str(run), at
        at += 1


def hidden_states(params, ids, config, *, q_block: int = 128,
                  with_margins: bool = False, operands=None,
                  forced_only: bool = False, no_decay: bool = False):
    """``ids (T,)`` -> the normed, head-scaled hidden state ``(T, E)``
    (and the selection margin ``(T,)``)."""
    eps = config["rms_norm_eps"]
    alpha = config["scale_depth"] / math.sqrt(
        config["published"]["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        x = config["scale_emb"] * _f32(params["wte"]["weight"][ids])
        margin = jnp.full(ids.shape, jnp.inf)
        for kind, run, at in layers_of(config):
            # the barrier ties a layer's matrices to its input: they are
            # taken out of their run and upcast when the layer runs, not
            # all of them ahead of it (a chip's memory beside the
            # served weights)
            tree, x = jax.lax.optimization_barrier(
                (params["blocks"]["runs"][run], x))
            blk = jax.tree.map(lambda w: w[at], tree)
            u = _norm(blk["norm1"]["scale"], x, eps)
            if kind == SPARSE:
                att, m = sparse_attention(
                    blk["attn"], u, config, operands=operands,
                    forced_only=forced_only, q_block=q_block)
                margin = jnp.minimum(margin, m)
            else:
                att = lightning_attention(blk["attn"], u, config,
                                          operands=operands,
                                          no_decay=no_decay)
            x = x + alpha * att
            x = x + alpha * mlp(
                blk["mlp"], _norm(blk["norm2"]["scale"], x, eps), operands)
        h = _norm(params["final_norm"]["scale"], x, eps) \
            * (config["dim_model_base"] / config["hidden_size"])
    return (h, margin) if with_margins else h


def logits(params, ids, config, **kw):
    h = hidden_states(params, ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["lm_head"]["weight"]).T
