"""Brumby's decoder in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, the QUADRATIC form of power
retention — every weight ``a_ts`` over all ``s <= t`` formed and summed
—, no state, no chunks, no kernel. It follows the keys of
``manifestai/Brumby-14B-Base`` ``config.json`` (``config`` below is the
benchmark's configuration file: those keys, and what the ``config.json``
has no key for under ``assumed``) and shares no code with ``hetu_tpu``:
it only READS the same parameter tree — ``wte.weight``,
``lm_head.weight`` ``(V, E)``; ``blocks.layers`` ONE tree stacked over
the layers on axis 0, with ``norm1.scale``, ``norm2.scale``,
``attn.{q_proj,k_proj,v_proj,gate_proj,out_proj}.weight`` ``(in, out)``,
``attn.{q_gain,k_gain}`` ``(128,)``, ``attn.gate_bias`` ``(8,)``,
``mlp.{gate_proj,up_proj,fc_out}.weight``; ``final_norm.scale``.

ONE row of token ids ``(T,)``; ``n`` the RMSNorm (eps 1e-6), no biases:

* ``x0 = E[ids]``; a layer: ``h = x + Retention(n1(x)) W_o``, ``y = h +
  (silu(u W_g) * (u W_u)) W_d`` with ``u = n2(h)``; the logits ``n_f(x)
  W_head^T``.
* Retention, with ``u = n1(x)``: ``q = RoPE(n_head(u W_q))`` (40 heads
  of 128), ``k = RoPE(n_head(u W_k))``, ``v = u W_v`` (8 kv heads; a kv
  head serves its 5 query heads); RoPE theta 1e6 over all 128 dims,
  split-half pairs ``(i, i + 64)``; the gate a kv head and token ``log
  g_t = logsigmoid(u_t W_gate + b)``, ``G_t = sum_{r <= t} log g_r``;
  ``a_ts = exp(G_t - G_s) (q_t . k_s / sqrt(128))^2`` for ``s <= t`` and
  ``y_t = sum_s a_ts v_s / (sum_s a_ts + eps)``.

To fit a 16.5k row beside the served weights on one chip the weights
``a_ts`` are formed in blocks of QUERIES (every query of a block against
all the row's keys at once), the MLP in blocks of rows, and every matrix
is upcast from its stored (bf16) values where it is used.

``CONTROL`` (empty: the reference) is for FOUR readings that the
benchmark's limits have to refuse (PERF.md section 6, PR 51):
``operands`` rounds both operands of every projection, score, value and
MLP product to that type; ``no_gate`` leaves the gate out (``log g =
0``); ``reset_every`` forgets everything before the last multiple of
that many positions (a cache that loses its state between chunks);
``diag_only`` leaves the off-diagonal terms of the second tensor power
out (``sum_a q_a^2 k_a^2`` in place of ``(q . k)^2``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: see the module docstring; set by a builder's script, never by a run
CONTROL: dict = {}


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


def retention(a, u, config, *, operands=None, no_gate=False,
              reset_every=None, diag_only=False, q_block: int = 64):
    """``a``: one layer's ``attn`` tree; ``u (T, E)`` the normed input
    -> ``(T, E)``."""
    T = u.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    ret_eps = config["assumed"]["retention_eps"]
    theta = float(config["rope_theta"])

    def heads(name, n):
        return _mm(u, a[name]["weight"], operands).reshape(T, n, d)
    q = rope_half(_norm(a["q_gain"], heads("q_proj", H), eps), theta)
    k = rope_half(_norm(a["k_gain"], heads("k_proj", Hkv), eps), theta)
    v = heads("v_proj", Hkv)
    log_g = jax.nn.log_sigmoid(
        u @ _f32(a["gate_proj"]["weight"]) + _f32(a["gate_bias"]))
    if no_gate:
        log_g = jnp.zeros_like(log_g)
    G = jnp.cumsum(log_g, axis=0)                           # (T, Hkv)
    q = q.reshape(T, Hkv, H // Hkv, d)
    pos = jnp.arange(T)

    def queries(args):
        qb, Gb, tb = args                   # (B, Hkv, g, d), (B, Hkv), (B,)
        if diag_only:
            s = jnp.einsum("qhgd,khd->hgqk", _r(qb * qb, operands),
                           _r(k * k, operands)) / d
        else:
            s = jnp.einsum("qhgd,khd->hgqk", _r(qb, operands),
                           _r(k, operands)) / d ** 0.5
            s = s * s
        see = pos[None, :] <= tb[:, None]                   # (B, T)
        if reset_every:
            see &= pos[None, :] // reset_every == tb[:, None] // reset_every
        decay = jnp.exp(jnp.minimum(
            Gb.T[:, :, None] - G.T[:, None, :], 0.0))       # (Hkv, B, T)
        w = jnp.where(see[None, None], s * decay[:, None], 0.0)
        n = jnp.einsum("hgqk,khd->qhgd", _r(w, operands), _r(v, operands))
        z = jnp.moveaxis(w.sum(-1), 2, 0)                   # (B, Hkv, g)
        return n / (z[..., None] + ret_eps)

    pad = -T % q_block
    nb = (T + pad) // q_block

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, q_block) + x.shape[1:])
    y = jax.lax.map(queries, (blocks(q), blocks(G), blocks(pos)))
    y = y.reshape(nb * q_block, H * d)[:T]
    return _mm(y, a["out_proj"]["weight"], operands)


def mlp(p, u, operands=None, *, rows: int = 2048):
    def block(ub):
        g = jax.nn.silu(_mm(ub, p["gate_proj"]["weight"], operands))
        return _mm(g * _mm(ub, p["up_proj"]["weight"], operands),
                   p["fc_out"]["weight"], operands)
    return _rows(block, u, rows)


def hidden_states(params, ids, config, *, q_block: int = 64,
                  operands=None, no_gate=False, reset_every=None,
                  diag_only=False):
    """``ids (T,)`` -> the final-normed hidden states ``(T, E)``."""
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["wte"]["weight"], ids, axis=0))

        def layer(x, p):
            # (the layer's matrices are upcast where they are used, not
            # beside the whole stack)
            p = jax.lax.optimization_barrier(p)
            h = x + retention(
                p["attn"], _norm(p["norm1"]["scale"], x, eps), config,
                operands=operands, no_gate=no_gate,
                reset_every=reset_every, diag_only=diag_only,
                q_block=q_block)
            y = h + mlp(p["mlp"], _norm(p["norm2"]["scale"], h, eps),
                        operands)
            return y, None

        x, _ = jax.lax.scan(layer, x, params["blocks"]["layers"])
        return _norm(params["final_norm"]["scale"], x, eps)


def logits(params, ids, config, **kw):
    h = hidden_states(params, ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["lm_head"]["weight"]).T
