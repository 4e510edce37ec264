"""The selective scan of a Mamba-1 mixer (Gu & Dao, arXiv:2312.00752):
a DIAGONAL state-space recurrence whose step size, input matrix and
output matrix are the token's own.

Per channel ``c`` of ``D`` and state ``n`` of ``N``, with a float32
state ``h (N, D)``, ``A (N, D)`` (``< 0``), a step ``dt_t (D,)`` (``>
0``), the token's ``B_t``, ``C_t`` ``(N,)`` and its input ``x_t
(D,)``::

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n]

The decay differs in every (channel, state) pair and every token, so
no chunk of tokens is a matrix product (what ``ops.linear_attention``,
``ops.kda`` and ``ops.retention`` all are): every form here and both
kernels walk the tokens one after another, and only decays ``exp(dt A)
<= 1`` are ever formed — no ``exp`` of a negated running sum. ``D x``
and the ``SiLU(z)`` gate are the mixer's, beside these.

* :func:`selective_recurrence` — that, over ONE sequence (the oracle);
* :func:`selective_update` — one token a slot (the decode rows);
* :func:`selective_scan` — a PACK of tokens of several slots' runs.

The layer runs the arithmetic of the last two as one Pallas call each
on the state leaf in place (``ops.selective_scan_pallas``; interpreted
on the CPU); these forms are those kernels' oracles, and only tests
and ``workloads/selective_scan_bench.py`` reach them. The states of
ALL slots ride the calls, as in ``ops.kda``: ``state`` is ``(S, N, D)``
or, with ``layer=``, the stacked ``(layers, S, N, D)``, and only
``[layer, slot]`` of a live slot or run is touched. Everything is
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(*xs):
    return tuple(jnp.asarray(x, jnp.float32) for x in xs)


def _step(h, x, dt, A, B, C):
    """One token on states ``h (..., N, D)``: ``x``, ``dt`` ``(...,
    D)``, ``B``, ``C`` ``(..., N)``, ``A (N, D)`` -> ``(h_t, y_t)``."""
    h = jnp.exp(dt[..., None, :] * A) * h \
        + (dt * x)[..., None, :] * B[..., :, None]
    return h, jnp.sum(h * C[..., :, None], axis=-2)


def selective_recurrence(x, dt, A, B, C, state=None):
    """The token recurrence over ONE sequence: ``x``, ``dt`` ``(T,
    D)``, ``B``, ``C`` ``(T, N)``, ``A (N, D)`` -> ``(y (T, D) float32,
    state (N, D))``."""
    x, dt, A, B, C = _f32(x, dt, A, B, C)
    if state is None:
        state = jnp.zeros(A.shape, jnp.float32)

    def step(h, t):
        return _step(h, t[0], t[1], A, t[2], t[3])

    state, y = jax.lax.scan(step, state, (x, dt, B, C))
    return y, state


def _stacked(state, layer):
    if layer is None:
        return state[None], jnp.zeros((), jnp.int32)
    return state, jnp.asarray(layer, jnp.int32)


def selective_update(x, dt, A, B, C, state, live, *, layer=None,
                     fresh=None):
    """One token a slot: ``x``, ``dt`` ``(S, D)``, ``B``, ``C`` ``(S,
    N)``; ``state`` ``(S, N, D)`` float32, or the stacked leaf with
    ``layer=``. Only the ``live`` slots' states are read and written; a
    slot that is not live keeps its state and its row of ``y`` is
    zeros. ``fresh`` ``(S,)`` bool: slots that start from a zero state.
    Returns ``(y (S, D) float32, new state)``."""
    x, dt, A, B, C = _f32(x, dt, A, B, C)
    buf, layer = _stacked(state, layer)
    S = live.shape[0]
    at = jnp.where(live, jnp.arange(S), buf.shape[1])
    old = buf.at[layer, at].get(mode="fill", fill_value=0.0)
    if fresh is not None:
        old = jnp.where(fresh[:, None, None], 0.0, old)
    new, y = _step(old, x, dt, A, B, C)
    buf = buf.at[layer, at].set(new, mode="drop")
    y = jnp.where(live[:, None], y, 0.0)
    return y, (buf if state.ndim == 4 else buf[0])


def selective_scan(x, dt, A, B, C, state, slot, pos, valid, *, layer=None):
    """A pack of ``P`` tokens: ``x``, ``dt`` ``(P, D)``, ``B``, ``C``
    ``(P, N)``; ``slot``, ``pos`` ``(P,)`` int32 and ``valid`` ``(P,)``
    bool — the tokens of one slot are contiguous with ascending
    positions; ``state`` ``(S, N, D)`` float32 (or the stacked leaf with
    ``layer=``), each slot's state after the position before its first
    token here. A run whose first token stands at position 0 starts
    from zeros, whatever its slot's state held (a slot taken again by
    another request). Only the states of the slots with a valid token
    here are read and written; a row that is not valid gives zeros.

    Returns ``(y (P, D) float32, new state)``."""
    x, dt, A, B, C = _f32(x, dt, A, B, C)
    buf, layer = _stacked(state, layer)
    N, D = A.shape

    def token(buf, t):
        xt, dtt, Bt, Ct, s, p, ok = t
        h = jax.lax.dynamic_slice(buf, (layer, s, 0, 0), (1, 1, N, D))[0, 0]
        new, y = _step(jnp.where(p == 0, 0.0, h), xt, dtt, A, Bt, Ct)
        buf = jax.lax.dynamic_update_slice(
            buf, jnp.where(ok, new, h)[None, None], (layer, s, 0, 0))
        return buf, jnp.where(ok, y, 0.0)

    buf, y = jax.lax.scan(token, buf, (
        x, dt, B, C, slot.astype(jnp.int32), pos.astype(jnp.int32), valid))
    return y, (buf if state.ndim == 4 else buf[0])
