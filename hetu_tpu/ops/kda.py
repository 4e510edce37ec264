"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): a gated
delta rule over a per-slot recurrent state, with a decay per CHANNEL
and token, behind a short causal convolution.

Per head, with a float32 state ``S`` ``(dk, dv)``, a log-decay ``g_t``
``(dk,)`` (``<= 0``; ``alpha_t = exp(g_t)``) and a write strength
``beta_t`` in ``(0, 1)``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

— the state is decayed a channel at a time, then ERASED along ``k_t``
before ``v_t`` is written there (``ops.linear_attention`` only decays
and adds).

* :func:`kda_recurrence` — that, a token at a time (the oracle);
* :func:`kda_update` — one token a slot (the decode rows): a gather of
  the live slots' states, the formula, a scatter;
* :func:`kda_scan` — a PACK of tokens of several slots' runs, in chunks
  of ``CHUNK`` pack rows: the ``jax.numpy`` chunk form.
  The layer runs the SAME arithmetic of both as one Pallas call each
  on the state leaf in place (``ops.kda_pallas.hetu_kda_update``,
  ``hetu_kda_scan``; interpreted on the CPU); these two forms are
  those kernels' oracles beside the recurrence, and only tests and
  ``workloads/kda_bench.py`` reach them;
* :func:`conv_pack`, :func:`conv_rows` — the causal depthwise
  convolution over the tokens of one request, carried across packs by
  a slot's TAIL (the last ``taps - 1`` input rows).

The chunk form (``G`` the running sum of ``g`` inside a chunk, from the
first row of a token's own run there)::

    A = strict_lower(Diag(beta) (K e^G) (K e^-G)^T)
    (I + A) [W | U'] = Diag(beta) [K e^G | V]          U = U' - W S_0
    O = (Q e^G) S_0 + tril((Q e^G) (K e^-G)^T) U
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

``e^{-G}`` alone overflows float32 after 18 tokens at ``g = -5``: every
product of a row's and a column's factor is formed against a reference
row BETWEEN the two (the first row of the query's sub-chunk of
``SUB`` rows, or of the run where it starts later), so that only the
columns of the query's own sub-chunk take a positive exponent, at most
``(SUB - 1) x 5 = 75 < 88``. ``I + A`` is solved by forward substitution
over the sub-chunks (a diagonal block's inverse is the finite Neumann
product of its nilpotent part). What depends on ``S_0`` is a loop over
PIECES — a chunk's rows of one run — that reads and writes ONE slot's
state of ONE layer in the (stacked) state leaf in place; everything
else is computed for all chunks at once.

**Gated DeltaNet's form** (arXiv:2412.06464) is the same rule with ONE
decay a head and token and FEWER key heads than value heads: every
entry point here and in ``ops.kda_pallas`` takes ``g`` ``(T, H)``
beside ``(T, H, dk)`` and ``q``, ``k`` of ``Hk`` heads where ``Hk``
divides the ``H`` of ``v`` — value head ``j`` reads key head ``j // (H
/ Hk)``; the state is a VALUE head's. :func:`widen` brings that form
onto the per-channel one's SHAPES (a broadcast; no operation on operands
that have them already). One thing of it is NOT the per-channel
arithmetic: with one decay a head a pair's factor ``e^{G_i - G_j}`` is a
number a pair and head, formed directly with no exponent above 0, so
the chunk forms (here and in the kernel) take ``g`` of any size — Gated
DeltaNet's ``g = -A softplus(.)`` has no lower bound, and the reference
row's bound (``(SUB - 1) x |g| < 88``) would not hold.

The states of ALL slots ride the calls, as in ``linear_scan``; with
``layer=`` the state is the STACKED leaf ``(layers, slots, H, dk,
dv)`` and only ``[layer, slot]`` of a live slot or run is touched — no
layer's slab of every slot is sliced out or put back (the compiler
copies the leaf for that). Everything here is float32 at the highest
matmul precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: pack rows a chunk, and rows a sub-chunk (the exponent's bound)
CHUNK, SUB = 64, 16
_HI = jax.lax.Precision.HIGHEST
#: exponents of masked pairs are cut here (a kept pair never gets near)
_EXP_MAX = 80.0


def _f32(*xs):
    return tuple(jnp.asarray(x, jnp.float32) for x in xs)


def widen(q, k, v, g):
    """Gated DeltaNet's operands in Kimi Delta Attention's shapes: ``g``
    ``(.., H)`` — one decay a head — over the ``dk`` channels, and
    ``q``, ``k`` ``(.., Hk, dk)`` of ``Hk`` key heads under the ``H``
    value heads of ``v`` ``(.., H, dv)``, value head ``j`` reading key
    head ``j // (H / Hk)``. Broadcasts and reshapes (no ``repeat``, no
    gather); operands already in those shapes come back as they are."""
    H, Hk, dk = v.shape[-2], q.shape[-2], q.shape[-1]
    if g.ndim == v.ndim - 1:
        g = jnp.broadcast_to(g[..., None], g.shape + (dk,))
    if Hk != H:
        if H % Hk or k.shape[-2] != Hk:
            raise ValueError(f"{Hk} key heads under {H} value heads")

        def heads(x):
            return jnp.broadcast_to(
                x[..., :, None, :], x.shape[:-2] + (Hk, H // Hk, dk)
            ).reshape(x.shape[:-2] + (H, dk))
        q, k = heads(q), heads(k)
    return q, k, g


def _step(S, q, k, v, g, beta):
    """One token on states ``S (..., dk, dv)``: ``(S_t, o_t)``."""
    S = jnp.exp(g)[..., :, None] * S
    r = v - jnp.einsum("...k,...kv->...v", k, S, precision=_HI)
    S = S + (beta[..., None] * k)[..., :, None] * r[..., None, :]
    return S, jnp.einsum("...kv,...k->...v", S, q, precision=_HI)


def kda_recurrence(q, k, v, g, beta, state=None):
    """The token recurrence over ONE sequence: ``q``, ``k``, ``g`` ``(T,
    H, dk)``, ``v`` ``(T, H, dv)``, ``beta`` ``(T, H)`` -> ``(o (T, H,
    dv) float32, state (H, dk, dv))``; ``g (T, H)`` and ``q``, ``k`` of
    fewer heads: :func:`widen`."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    q, k, g = widen(q, k, v, g)
    if state is None:
        state = jnp.zeros(q.shape[1:] + (v.shape[-1],), jnp.float32)

    def step(S, x):
        return _step(S, *x)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _stacked(state, layer):
    if layer is None:
        return state[None], jnp.zeros((), jnp.int32)
    return state, jnp.asarray(layer, jnp.int32)


def _live_rows(buf, layer, live, fresh):
    """The ``live`` slots' rows of ``buf[layer]`` (zeros for a slot that
    is ``fresh`` or not live) and the index that scatters them back
    (out of range, so dropped, for a slot that is not live)."""
    at = jnp.where(live, jnp.arange(live.shape[0]), buf.shape[1])
    old = buf.at[layer, at].get(mode="fill", fill_value=0.0)
    if fresh is not None:
        old = jnp.where(fresh.reshape((-1,) + (1,) * (old.ndim - 1)),
                        0.0, old)
    return old, at


def kda_update(q, k, v, g, beta, state, live, *, layer=None, fresh=None):
    """One token a slot: ``q``, ``k``, ``g`` ``(S, H, dk)``, ``v`` ``(S,
    H, dv)``, ``beta`` ``(S, H)``; ``state`` ``(S, H, dk, dv)`` float32,
    or the stacked leaf with ``layer=``. Only the ``live`` slots' states
    are read and written (a gather and a scatter of their rows of that
    layer); a slot that is not live keeps its state and its row of ``o``
    is zeros' result. ``fresh`` ``(S,)`` bool: slots that start from a
    zero state. Returns ``(o (S, H, dv) float32, new state)``.

    The oracle of ``ops.kda_pallas.hetu_kda_update``, which the layer
    runs (the TPU compiler lowers this gather and scatter to a loop over
    the slots, 2 MB a step): tests and ``workloads/kda_bench.py
    --update`` alone reach it."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    q, k, g = widen(q, k, v, g)
    buf, layer = _stacked(state, layer)
    old, at = _live_rows(buf, layer, live, fresh)
    new, o = _step(old, q, k, v, g, beta)
    buf = buf.at[layer, at].set(new, mode="drop")
    return o, (buf if state.ndim == 5 else buf[0])


# -- the chunk form ------------------------------------------------------------
def _segments(slot, pos, valid):
    """Of a pack's rows: ``start`` — the row opens a run (its slot's
    first row here) — and ``first`` — the index, inside the row's chunk,
    of the first row of its PIECE (its run's rows in this chunk)."""
    C = slot.shape[0]
    idx = jnp.arange(C)
    prev_slot = jnp.concatenate([slot[:1] - 1, slot[:-1]])
    prev_pos = jnp.concatenate([pos[:1], pos[:-1]])
    prev_valid = jnp.concatenate([jnp.zeros((1,), bool), valid[:-1]])
    start = valid & (~prev_valid | (slot != prev_slot)
                     | (pos != prev_pos + 1))
    opens = start | (idx % CHUNK == 0)
    first = jax.lax.cummax(jnp.where(opens, idx, 0)) % CHUNK
    return start, opens & valid, first


def _neumann_inverse(n):
    """``(I + n)^-1`` of strictly lower ``n (..., SUB, SUB)``: ``(I -
    n)(I + n^2)(I + n^4)...`` (``n^SUB = 0``)."""
    eye = jnp.eye(SUB, dtype=n.dtype)
    inv, p, reach = eye - n, n, 2
    while reach < SUB:
        p = jnp.matmul(p, p, precision=_HI)
        inv = jnp.matmul(inv, eye + p, precision=_HI)
        reach *= 2
    return inv


def _chunk_parts(q, k, v, g, beta, first, same, scalar=False):
    """What of a chunk does not depend on its entering state, all chunks
    at once: operands ``(N, H, c, d)``, ``beta (N, H, c)``, ``first (N,
    c)``, ``same (N, c, c)`` (row and column in one piece). Returns
    ``(Gs, W, U', Q e^G, tril((Q e^G)(K e^-G)^T))``. ``scalar``: ``g``
    is ONE decay a head (its channels alike), and a pair's factor is
    ``e^{G_i - G_j}`` itself, a number a pair and head with no
    exponent above 0 — no reference row and no bound on ``g``."""
    c = q.shape[2]
    G = jnp.cumsum(g, axis=2)
    at = first[:, None, :, None]
    Gs = G - jnp.take_along_axis(G - g, at, axis=2)
    i = jnp.arange(c)
    if scalar:
        G1 = Gs[..., 0]
        dec = jnp.exp(jnp.minimum(G1[..., :, None] - G1[..., None, :], 0.0))
        rows_q = [jnp.einsum("nhid,nhjd->nhij", q, k, precision=_HI) * dec]
        rows_k = [jnp.einsum("nhid,nhjd->nhij", k, k, precision=_HI) * dec]
    else:
        rows_q, rows_k = [], []
        for b in range(c // SUB):
            # the reference row of each column's piece for this row block
            ref = jnp.take_along_axis(
                Gs, jnp.maximum(b * SUB, first)[:, None, :, None], axis=2)
            kc = k * jnp.exp(jnp.minimum(ref - Gs, _EXP_MAX))
            sl = slice(b * SUB, (b + 1) * SUB)
            rf = jnp.exp(jnp.minimum(Gs[:, :, sl] - ref[:, :, sl],
                                     _EXP_MAX))
            rows_q.append(jnp.einsum("nhid,nhjd->nhij", q[:, :, sl] * rf,
                                     kc, precision=_HI))
            rows_k.append(jnp.einsum("nhid,nhjd->nhij", k[:, :, sl] * rf,
                                     kc, precision=_HI))
    lower = same & (i[None, :] <= i[:, None])[None]
    strict = same & (i[None, :] < i[:, None])[None]
    Pq = jnp.where(lower[:, None], jnp.concatenate(rows_q, axis=2), 0.0)
    A = jnp.where(strict[:, None], jnp.concatenate(rows_k, axis=2), 0.0) \
        * beta[..., None]
    eG = jnp.exp(Gs)
    B = beta[..., None] * jnp.concatenate([k * eG, v], axis=-1)
    xs = []
    for b in range(c // SUB):
        sl = slice(b * SUB, (b + 1) * SUB)
        rhs = B[:, :, sl]
        if b:
            rhs = rhs - jnp.matmul(A[:, :, sl, :b * SUB],
                                   jnp.concatenate(xs, axis=2),
                                   precision=_HI)
        xs.append(jnp.matmul(_neumann_inverse(A[:, :, sl, sl]), rhs,
                             precision=_HI))
    X = jnp.concatenate(xs, axis=2)
    dk = k.shape[-1]
    return Gs, X[..., :dk], X[..., dk:], q * eG, Pq


def kda_scan(q, k, v, g, beta, state, slot, pos, valid, *, layer=None):
    """A pack of ``C`` tokens: ``q``, ``k``, ``g`` ``(C, H, dk)``, ``v``
    ``(C, H, dv)``, ``beta`` ``(C, H)``; ``slot``, ``pos`` ``(C,)`` int32
    and ``valid`` ``(C,)`` bool — the tokens of one slot are contiguous
    with ascending positions; ``state`` ``(S, H, dk, dv)`` float32 (or
    the stacked leaf with ``layer=``), each slot's state after the
    position before its first token here. A run whose first token
    stands at position 0 starts from zeros, whatever its slot's state
    held (a slot taken again by another request). Only the states of
    the slots with a run here are read and written.

    Returns ``(o (C, H, dv) float32, new state)``."""
    q, k, v, g, beta = _f32(q, k, v, g, beta)
    scalar = g.ndim == 2
    q, k, g = widen(q, k, v, g)
    C, H, dk = q.shape
    dv = v.shape[-1]
    buf, layer = _stacked(state, layer)
    pad = -C % CHUNK
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
        slot, pos = (jnp.pad(a, (0, pad)) for a in (slot, pos))
        valid = jnp.pad(valid, (0, pad))
    Cp = C + pad
    N = Cp // CHUNK
    keep = valid[:, None, None]
    q, k, v, g = (jnp.where(keep, a, 0.0) for a in (q, k, v, g))
    beta = jnp.where(valid[:, None], beta, 0.0)
    _, opens, first = _segments(slot, pos, valid)

    def chunks(a):           # (Cp, H, ...) -> (N, H, c, ...)
        return jnp.moveaxis(a.reshape((N, CHUNK) + a.shape[1:]), 2, 1)

    fc, vc = first.reshape(N, CHUNK), valid.reshape(N, CHUNK)
    same = (fc[:, :, None] == fc[:, None, :]) \
        & vc[:, :, None] & vc[:, None, :]
    kc = chunks(k)
    Gs, W, U0, Qe, Pq = _chunk_parts(
        chunks(q), kc, chunks(v), chunks(g), chunks(beta), fc, same,
        scalar)

    # the pieces: a chunk's rows of one run, in pack order
    n_max = N + min(buf.shape[1], Cp)
    starts = jnp.nonzero(opens, size=n_max, fill_value=0)[0]
    n_pieces = jnp.sum(opens)
    rows = jnp.arange(CHUNK)

    def piece(p, carry):
        o, buf = carry
        r0 = starts[p]
        n, lo = r0 // CHUNK, r0 % CHUNK
        m = vc[n] & (fc[n] == lo)                            # (c,)
        mf = m.astype(jnp.float32)[None, :, None]
        s_in = jax.lax.dynamic_slice(
            buf, (layer, slot[r0], 0, 0, 0), (1, 1, H, dk, dv))[0, 0]
        s_in = jnp.where(pos[r0] == 0, 0.0, s_in)
        u = mf * (U0[n] - jnp.matmul(W[n], s_in, precision=_HI))
        out = mf * (jnp.matmul(Qe[n], s_in, precision=_HI)
                    + jnp.matmul(Pq[n], u, precision=_HI))
        last = jnp.max(jnp.where(m, rows, 0))
        g_last = jax.lax.dynamic_index_in_dim(Gs[n], last, axis=1)
        kd = kc[n] * mf * jnp.exp(jnp.minimum(g_last - Gs[n], 0.0))
        s_out = jnp.exp(g_last[:, 0])[:, :, None] * s_in \
            + jnp.einsum("hid,hiv->hdv", kd, u, precision=_HI)
        buf = jax.lax.dynamic_update_slice(
            buf, s_out[None, None], (layer, slot[r0], 0, 0, 0))
        o = jax.lax.dynamic_update_index_in_dim(o, o[n] + out, n, 0)
        return o, buf

    o, buf = jax.lax.fori_loop(
        0, n_pieces, piece, (jnp.zeros((N, H, CHUNK, dv), jnp.float32),
                             buf))
    o = jnp.moveaxis(o, 1, 2).reshape(Cp, H, dv)[:C]
    return o, (buf if state.ndim == 5 else buf[0])


# -- the short convolution -----------------------------------------------------
def conv_sequence(a, taps):
    """The causal depthwise convolution over ONE sequence: ``a (T, N)``,
    ``taps (K, N)`` -> ``y_t = sum_j taps[j] a_{t-K+1+j}`` (zeros before
    the sequence)."""
    K = taps.shape[0]
    a, taps = _f32(a, taps)
    ap = jnp.pad(a, ((K - 1, 0), (0, 0)))
    return sum(taps[j] * ap[j:j + a.shape[0]] for j in range(K))


def conv_rows(a, taps, tail, live, *, layer=None, fresh=None):
    """One token a slot (the decode rows): ``a (S, N)``, ``tail (S, K -
    1, N)`` float32 (or the stacked leaf with ``layer=``): a slot's
    last ``K - 1`` input rows. Returns ``(y (S, N), new tail)``; only
    the ``live`` slots' tails are read and written."""
    a, taps = _f32(a, taps)
    buf, layer = _stacked(tail, layer)
    old, at = _live_rows(buf, layer, live, fresh)
    win = jnp.concatenate([old, a[:, None]], axis=1)          # (S, K, N)
    y = jnp.einsum("skn,kn->sn", win, taps, precision=_HI)
    buf = buf.at[layer, at].set(win[:, 1:], mode="drop")
    return y, (buf if tail.ndim == 4 else buf[0])


def conv_pack(a, taps, tail, slot, pos, valid, *, layer=None):
    """A pack of ``C`` tokens (``kda_scan``'s contract): ``a (C, N)``;
    ``tail (S, K - 1, N)`` (or stacked, ``layer=``). A row's window
    reaches back into its own run's rows in the pack, then into its
    slot's tail — zeros for a run that starts at position 0. Returns
    ``(y (C, N), new tail)``: the tails of the slots with a run here
    become the last ``K - 1`` input rows of their run."""
    a, taps = _f32(a, taps)
    K = taps.shape[0]
    C = a.shape[0]
    buf, layer = _stacked(tail, layer)
    S = buf.shape[1]
    start, _, _ = _segments(slot, pos, valid)
    idx = jnp.arange(C)
    head = jax.lax.cummax(jnp.where(start, idx, 0))   # the run's first row
    off = idx - head
    a = jnp.where(valid[:, None], a, 0.0)
    ap = jnp.pad(a, ((K - 1, 0), (0, 0)))
    # inside the pack: rows of the same run only
    y = sum(taps[j] * jnp.where((off >= K - 1 - j)[:, None],
                                ap[j:j + C], 0.0) for j in range(K))
    # the runs (at most one a slot): their first rows, slots and tails
    n_runs = min(S, C)
    r0 = jnp.nonzero(start, size=n_runs, fill_value=C)[0]
    there = r0 < C
    r0c = jnp.minimum(r0, C - 1)
    at = jnp.where(there, slot[r0c], S)
    old = buf.at[layer, at].get(mode="fill", fill_value=0.0)
    old = jnp.where((pos[r0c] == 0)[:, None, None], 0.0, old)
    # the run's tokens in the pack
    length = jnp.zeros((C + 1,), jnp.int32).at[head].add(
        valid.astype(jnp.int32))[r0]
    # the first K - 1 rows of a run reach into the tail: row o takes
    # taps[j] * tail[o + j] for j < K - 1 - o
    fix_rows, fix = [], []
    for o in range(K - 1):
        fix.append(sum(taps[j] * old[:, o + j]
                       for j in range(K - 1 - o)))
        fix_rows.append(jnp.where(there & (o < length), r0 + o, C))
    y = y.at[jnp.concatenate(fix_rows)].add(
        jnp.concatenate(fix), mode="drop")
    # the new tail: the last K - 1 of [old tail, the run's rows]
    new = []
    for m in range(K - 1):
        o = length - (K - 1) + m                   # offset in the run
        row = jnp.take(a, jnp.clip(r0c + o, 0, C - 1), axis=0)
        prev = jnp.take_along_axis(
            old, jnp.clip(o + K - 1, 0, K - 2)[:, None, None], axis=1)[:, 0]
        new.append(jnp.where((o >= 0)[:, None], row, prev))
    buf = buf.at[layer, at].set(jnp.stack(new, axis=1), mode="drop")
    return y, (buf if tail.ndim == 4 else buf[0])
