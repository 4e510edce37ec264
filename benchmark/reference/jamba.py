"""Jamba's decoder in plain ``jax.numpy``: float32,
``jax.default_matmul_precision("highest")``, the selective scan as the
PUBLISHED recurrence — one token after another over the whole row
(``lax.scan`` over the sequence) —, no chunks, no kernels, no cache. It
follows the keys of ``ai21labs/AI21-Jamba2-3B`` ``config.json``
(``config`` below is the benchmark's configuration file: those keys,
and what the ``config.json`` has no key for under ``assumed``) and
shares no code with ``hetu_tpu`` (its rounding, norm, row-block and
SwiGLU helpers are ``reference/brumby.py``'s): it only READS the same
parameter tree — ``wte.weight`` ``(V, E)`` (also the head: the embedding is tied);
``blocks.runs.<i>`` one tree a RUN of like layers, stacked over the
run's layers on axis 0; each layer ``norm1.scale``, ``norm2.scale``,
``mlp.{gate_proj,up_proj,fc_out}.weight`` ``(in, out)`` and

* a Mamba layer's ``attn``: ``in_proj.weight`` ``(E, 2 D)`` (x, then
  z), ``conv`` ``(4, D)`` and ``conv_bias`` ``(D,)``,
  ``x_proj.weight`` ``(D, r + 2 N)`` (the step's low-rank input, then
  B, then C), ``dt_gain`` ``(r,)``, ``b_gain``, ``c_gain`` ``(N,)``,
  ``dt_proj.weight`` ``(r, D)`` and ``dt_bias`` ``(D,)``, ``A_log``
  ``(N, D)``, ``D`` ``(D,)``, ``out_proj.weight`` ``(D, E)``;
* an attention layer's ``attn``: ``{q_proj,k_proj,v_proj,out_proj}
  .weight``;

``final_norm.scale``.

ONE row of token ids ``(T,)``; ``n`` the RMSNorm (eps 1e-6); layer
``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``, else Mamba:

* ``x0 = E[ids]``; a layer: ``h = x + Mixer(n1(x))``, ``y = h +
  (silu(u W_g) * (u W_u)) W_d`` with ``u = n2(h)``; the logits
  ``n_f(x) E^T``.
* Mamba, with ``u = n1(x)``: ``[x | z] = u W_in``; ``x <- silu(sum_j
  c_j x_{t-3+j} + b_conv)`` a channel at a time (a sum of four shifted
  products, zeros before the row); ``[r | B | C] = x W_x``, each through
  an RMSNorm with its own gain; ``dt = softplus(r W_dt + b_dt)``; ``A =
  -exp(A_log)``; per token ``h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n,
  c] + dt_t[c] x_t[c] B_t[n]``, ``y_t[c] = sum_n h_t[n, c] C_t[n] + D[c]
  x_t[c]``; out ``(y * silu(z)) W_out``.
* Attention: ``q = u W_q`` (20 heads of 128), ``k``, ``v`` ONE head of
  128, a causal softmax at ``1 / sqrt(128)``, NO position encoding;
  ``W_o``.

To fit a 33k row beside the served weights on one chip every matrix is
upcast from its stored (bf16) values where it is used, the attention
runs in blocks of QUERIES (each against all the row's keys at once:
one masked softmax) and the wide products in blocks of rows.

``CONTROL`` (empty: the reference) plants ONE departure for a reading
that the benchmark's limit has to refuse (PERF.md section 6, PR 55):
``reset_every`` forgets the STATE at every multiple of that many
positions (a cache that loses its state between chunks);
``drop_tail_every`` lets the convolution see zeros before every such
multiple (a cache that drops its tail there); ``no_inner_norms`` leaves
the RMSNorms of ``r``, ``B`` and ``C`` out; ``no_skip`` leaves ``D x``
out; ``operands`` rounds both operands of every projection, attention
and MLP product to that type; ``state_dtype`` keeps the state in that
type (rounded after every token, by ``lax.reduce_precision``: a
convert there and back the TPU compiler takes out of a float32
program, and the control then reads as the reference itself — PERF.md
section 6, PR 55, call 8).

``stops`` (positions) makes :func:`hidden_states` hand back, beside the
hidden states, every Mamba layer's STATE as it stands after each of
those positions, ``(Mamba layers, len(stops), N, D)``: what a slot's
state leaf has to hold there (``benchmark/archs/jamba.py::state_gap``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.brumby import _f32, _mm, _norm, _r, _rows, mlp

#: see the module docstring; set by a builder's script, never by a run
CONTROL: dict = {}
#: rows a block of the wide products, queries a block of the attention
#: (memory only)
ROWS, Q_BLOCK = 2048, 128


def _keep(x, dtype):
    """``x`` (float32) with the exponent and mantissa bits of ``dtype``
    and no more, still float32."""
    f = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


def layer_kinds(config) -> list:
    return ["attention" if i % config["attn_layer_period"]
            == config["attn_layer_offset"] else "mamba"
            for i in range(config["num_hidden_layers"])]


def mamba(a, u, config, *, stops, operands=None, reset_every=None,
          drop_tail_every=None, no_inner_norms=False, no_skip=False,
          state_dtype=None):
    """``a``: one Mamba layer's ``attn`` tree; ``u (T, E)`` the normed
    input -> ``(T, E)``, and the state after each position of ``stops
    (S,)``, ``(S, N, D)``."""
    T = u.shape[0]
    N, r = config["mamba_d_state"], config["mamba_dt_rank"]
    eps = config["rms_norm_eps"]
    xz = _rows(lambda ub: _mm(ub, a["in_proj"]["weight"], operands), u,
               ROWS)
    D = xz.shape[1] // 2
    x, z = xz[:, :D], xz[:, D:]
    taps = _f32(a["conv"])
    K = taps.shape[0]
    pos = jnp.arange(T)
    conv = _f32(a["conv_bias"]) if "conv_bias" in a else 0.0
    for j in range(K):
        back = K - 1 - j                    # tap j reads x_{t - back}
        seen = pos >= back
        if drop_tail_every:
            seen &= pos % drop_tail_every >= back
        conv = conv + taps[j] * jnp.where(
            seen[:, None], jnp.roll(x, back, axis=0), 0.0)
    x = jax.nn.silu(conv)
    sel = _mm(x, a["x_proj"]["weight"], operands)
    rr, B, C = sel[:, :r], sel[:, r:r + N], sel[:, r + N:]
    if not no_inner_norms:
        rr = _norm(a["dt_gain"], rr, eps)
        B = _norm(a["b_gain"], B, eps)
        C = _norm(a["c_gain"], C, eps)
    dt = jax.nn.softplus(_mm(rr, a["dt_proj"]["weight"], operands)
                         + _f32(a["dt_bias"]))
    A = -jnp.exp(_f32(a["A_log"]))                          # (N, D)

    def token(carry, t):
        h, kept = carry
        xt, dtt, Bt, Ct, p = t
        if reset_every:
            h = jnp.where(p % reset_every == 0, 0.0, h)
        h = jnp.exp(dtt[None, :] * A) * h \
            + (dtt * xt)[None, :] * Bt[:, None]
        if state_dtype is not None:
            h = _keep(h, state_dtype)
        kept = jnp.where((stops == p)[:, None, None], h, kept)
        return (h, kept), (h * Ct[:, None]).sum(0)

    (_, kept), y = jax.lax.scan(
        token, (jnp.zeros((N, D), jnp.float32),
                jnp.zeros((len(stops), N, D), jnp.float32)),
        (x, dt, B, C, pos), unroll=8)
    if not no_skip:
        y = y + _f32(a["D"]) * x
    y = y * jax.nn.silu(z)
    return _rows(lambda yb: _mm(yb, a["out_proj"]["weight"], operands), y,
                 ROWS), kept


def attention(a, u, config, *, operands=None, q_block: int = Q_BLOCK):
    """``a``: one attention layer's ``attn`` tree; ``u (T, E)`` ->
    ``(T, E)``: one causal softmax a block of queries, no positions."""
    T = u.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // H

    def heads(name, n):
        return _mm(u, a[name]["weight"], operands).reshape(T, n, d)
    q = heads("q_proj", H).reshape(T, Hkv, H // Hkv, d)
    k, v = heads("k_proj", Hkv), heads("v_proj", Hkv)
    pos = jnp.arange(T)

    def queries(args):
        qb, tb = args                       # (B, Hkv, g, d), (B,)
        s = jnp.einsum("qhgd,khd->hgqk", _r(qb, operands),
                       _r(k, operands)) / d ** 0.5
        s = jnp.where((pos[None, :] <= tb[:, None])[None, None], s,
                      -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", _r(w, operands),
                          _r(v, operands))

    pad = -T % q_block
    nb = (T + pad) // q_block

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((nb, q_block) + x.shape[1:])
    o = jax.lax.map(queries, (blocks(q), blocks(pos)))
    o = o.reshape(nb * q_block, H * d)[:T]
    return _mm(o, a["out_proj"]["weight"], operands)


def layer_runs(config) -> list:
    """The kind of every run of consecutive like layers, in order: the
    parameter tree's ``blocks.runs.<i>``."""
    kinds = layer_kinds(config)
    return [k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]


def horizons(params, config):
    """Tokens a ``(state, channel)`` pair of every Mamba layer keeps its
    past for, ``(Mamba layers, N, D)``: ``1 / (dt0 |A|)`` at the step
    the layer's bias alone gives, ``dt0 = softplus(b_dt)``."""
    def leaf(name):
        return jnp.concatenate([
            _f32(params["blocks"]["runs"][str(j)]["attn"][name])
            for j, kind in enumerate(layer_runs(config))
            if kind == "mamba"])
    return 1.0 / (jax.nn.softplus(leaf("dt_bias"))[:, None, :]
                  * jnp.exp(leaf("A_log")))


def hidden_states(params, ids, config, *, operands=None, stops=None,
                  **control):
    """``ids (T,)`` -> the final-normed hidden states ``(T, E)``; with
    ``stops`` (positions) also the Mamba layers' states after each."""
    at = jnp.zeros((0,), jnp.int32) if stops is None \
        else jnp.asarray(stops, jnp.int32)
    states = []
    eps = config["rms_norm_eps"]
    runs = layer_runs(config)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["wte"]["weight"], ids, axis=0))
        for j, kind in enumerate(runs):
            def layer(x, p, kind=kind):
                # (the layer's matrices are upcast where they are used,
                # not beside the whole stack)
                p = jax.lax.optimization_barrier(p)
                u = _norm(p["norm1"]["scale"], x, eps)
                if kind == "attention":
                    mixed, kept = attention(p["attn"], u, config,
                                            operands=operands), None
                else:
                    mixed, kept = mamba(p["attn"], u, config, stops=at,
                                        operands=operands, **control)
                h = x + mixed
                y = h + mlp(p["mlp"], _norm(p["norm2"]["scale"], h, eps),
                            operands)
                return y, kept
            x, kept = jax.lax.scan(layer, x,
                                   params["blocks"]["runs"][str(j)])
            if kind == "mamba":
                states.append(kept)
        h = _norm(params["final_norm"]["scale"], x, eps)
        return h if stops is None else (h, jnp.concatenate(states))


def logits(params, ids, config, **kw):
    h = hidden_states(params, ids, config, **kw)
    with jax.default_matmul_precision("highest"):
        return h @ _f32(params["wte"]["weight"]).T
