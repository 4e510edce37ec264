"""Power retention of degree 2 over a per-slot recurrent state.

Per kv head (``G`` query heads read one kv head's state), with ``phi``
the symmetric second tensor power — ``phi(x) . phi(y) = (x . y)^2`` —,
a gate ``g_t`` in (0, 1] and a float32 state ``S`` ``(D, dv + 1)``:

    S_t = g_t S_{t-1} + phi(k_t / d^{1/4}) [v_t, 1]^T
    [n_t, z_t] = phi(q_t / d^{1/4})^T S_t,    y_t = n_t / (z_t + eps)

which is ``y_t = sum_s a_ts v_s / (sum_s a_ts + eps)`` over ``s <= t``
with ``a_ts = exp(G_t - G_s) (q_t . k_s / sqrt(d))^2``, ``G`` the
running sum of ``log g``. No softmax; an even degree keeps every weight
non-negative.

* :func:`phi` — the ``D = d (d + 1) / 2`` features as the equation has
  them (squares, then ``sqrt(2) x_a x_b``, ``a < b``);
* :func:`retention_recurrence` — a token at a time over ONE sequence
  (the oracle), on that natural state;
* :func:`phi_tiles` — the SAME features as the program lays them out:
  ``(d / 2 + 1, d)``, whole lane rows. Row ``a < d / 2`` holds ``sqrt(2)
  x_a x_b`` at ``b > a`` and ``sqrt(2) x_{d-1-a} x_{d-1-b}`` at ``b <
  a`` (the pairs of ``a`` and of its mirror fill one row between them;
  lane ``a`` itself is zero), the last row the squares: ``d / 2`` spare
  zeros in ``(d / 2 + 1) d`` places — 8,320 for 8,256 at ``d = 128`` —
  where the full ``d x d`` power would hold twice the model's state.
  The state is ``(d / 2 + 1, R, d)``: feature row, value row (``dv``
  values, the normaliser, then zeros to ``R``, a multiple of 8),
  feature lane (:func:`state_tiles` lays a natural state out so);
* :func:`retention_update` — one token a slot: the decode rows;
* :func:`retention_scan` — a PACK of tokens of several slots: within a
  block the gated quadratic form, across blocks the state. Only decays
  ``<= 1`` are formed.

The state, the gate's sums and the normaliser are float32; ``q``, ``k``,
``v`` keep the operand type they come in. The chip runs the Pallas
kernels of ``ops.retention_pallas``; the two ``jax.numpy`` forms here
are what the tests hold those to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def feature_rows(d: int) -> int:
    """Rows of :func:`phi_tiles` (``d`` lanes each)."""
    if d % 2:
        raise ValueError(f"a head size of {d}: the tiles pair a row with "
                         f"its mirror, so an even one")
    return d // 2 + 1


def value_rows(dv: int) -> int:
    """Value rows of the tiled state: ``dv`` values and the normaliser,
    in whole sublane tiles."""
    return -(-(dv + 1) // 8) * 8


def phi(x):
    """``(..., d)`` -> ``(..., d (d + 1) / 2)``: ``x_a^2``, then
    ``sqrt(2) x_a x_b`` over ``a < b``."""
    d = x.shape[-1]
    a, b = jnp.triu_indices(d, 1)
    return jnp.concatenate(
        [x * x, 2.0 ** 0.5 * x[..., a] * x[..., b]], axis=-1)


def phi_tiles(x):
    """``(..., d)`` -> ``(..., d / 2 + 1, d)``: :func:`phi`'s features,
    laid out in whole rows (the module's note)."""
    d = x.shape[-1]
    h = feature_rows(d) - 1
    xr = x[..., ::-1]
    top = x[..., :h, None] * x[..., None, :]
    bot = xr[..., :h, None] * xr[..., None, :]
    row = jnp.arange(h)[:, None]
    lane = jnp.arange(d)[None, :]
    off = 2.0 ** 0.5 * jnp.where(lane > row, top,
                                 jnp.where(lane < row, bot, 0.0))
    return jnp.concatenate([off, (x * x)[..., None, :]], axis=-2)


def values_one(v, valid=None):
    """``v (..., dv)`` -> ``(..., R)`` float32: the values, a one (the
    normaliser's share; zero where not ``valid``), zeros."""
    dv = v.shape[-1]
    one = jnp.ones(v.shape[:-1] + (1,), jnp.float32)
    if valid is not None:
        one = one * valid[..., None]
    pad = jnp.zeros(v.shape[:-1] + (value_rows(dv) - dv - 1,), jnp.float32)
    return jnp.concatenate([v.astype(jnp.float32), one, pad], axis=-1)


def state_tiles(state):
    """A natural state ``(..., D, dv + 1)`` -> its tiled layout ``(...,
    d / 2 + 1, R, d)`` (for comparisons: the program never holds the
    natural one)."""
    D, dv = state.shape[-2], state.shape[-1] - 1
    d = int(((8 * D + 1) ** 0.5 - 1) / 2)
    ia, ib = jnp.triu_indices(d, 1)
    h = feature_rows(d) - 1
    # (value row, feature row, lane), then the value rows to the middle
    out = jnp.zeros(state.shape[:-2] + (value_rows(dv), h + 1, d),
                    state.dtype)
    out = out.at[..., :dv + 1, h, :].set(
        jnp.swapaxes(state[..., :d, :], -1, -2))
    row = jnp.where(ia < h, ia, d - 1 - ia)
    lane = jnp.where(ia < h, ib, d - 1 - ib)
    out = out.at[..., :dv + 1, row, lane].set(
        jnp.swapaxes(state[..., d:, :], -1, -2))
    return jnp.moveaxis(out, -3, -2)


def read(n1, dv: int, eps: float):
    """``(..., R)`` numerators and normaliser -> ``y (..., dv)``."""
    return n1[..., :dv] / (n1[..., dv:dv + 1] + eps)


def retention_recurrence(q, k, v, log_g, *, eps: float, state=None):
    """The token recurrence over ONE sequence: ``q (T, H, d)``, ``k``
    ``(T, Hkv, d)``, ``v (T, Hkv, dv)``, ``log_g (T, Hkv)`` -> ``(y (T,
    H, dv) float32, state (Hkv, D, dv + 1))`` on the NATURAL features."""
    T, H, d = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    if state is None:
        state = jnp.zeros((Hkv, d * (d + 1) // 2, dv + 1), jnp.float32)

    def step(S, x):
        qt, kt, vt, gt = (a.astype(jnp.float32) for a in x)
        v1 = jnp.concatenate([vt, jnp.ones((Hkv, 1))], axis=-1)
        S = jnp.exp(gt)[:, None, None] * S \
            + phi(kt * d ** -0.25)[:, :, None] * v1[:, None, :]
        fq = phi(qt * d ** -0.25).reshape(Hkv, H // Hkv, -1)
        n1 = jnp.einsum("hgf,hfr->hgr", fq, S, precision=_HI)
        return S, read(n1, dv, eps).reshape(H, dv)

    state, y = jax.lax.scan(step, state, (q, k, v, log_g))
    return y, state


def retention_update(q, k, v, log_g, state, live, *, eps: float):
    """One token a slot: ``q (S, H, d)``, ``k (S, Hkv, d)``, ``v (S,
    Hkv, dv)``, ``log_g (S, Hkv)``, ``state (S, Hkv, d / 2 + 1, R, d)``
    float32 tiles -> ``(y (S, H, dv) float32, new state)``. A slot that
    is not ``live`` keeps its state to the bit (its row of ``y`` is
    zeros)."""
    S, H, d = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    f32 = jnp.float32
    fk = phi_tiles(k.astype(f32) * d ** -0.25)            # (S, Hkv, A, d)
    fq = phi_tiles(q.astype(f32).reshape(S, Hkv, H // Hkv, d)
                   * d ** -0.25)
    new = jnp.exp(log_g.astype(f32))[:, :, None, None, None] * state \
        + fk[:, :, :, None, :] * values_one(v)[:, :, None, :, None]
    n1 = jnp.einsum("shgab,sharb->shgr", fq, new, precision=_HI)
    y = read(n1, dv, eps).reshape(S, H, dv)
    return jnp.where(live[:, None, None], y, 0.0), \
        jnp.where(live[:, None, None, None, None], new, state)


def retention_scan(q, k, v, log_g, state, slot, pos, valid, *, eps: float,
                   block: int = 64):
    """A pack of ``C`` tokens: ``q (C, H, d)``, ``k (C, Hkv, d)``, ``v
    (C, Hkv, dv)``, ``log_g (C, Hkv)``; ``slot``, ``pos`` ``(C,)`` int32
    and ``valid (C,)`` bool — the tokens of one slot contiguous with
    ascending positions; ``state (S, Hkv, d / 2 + 1, R, d)`` float32
    tiles, each slot's state after the position before its first token
    here. A slot whose first token stands at position 0 starts from
    zeros, whatever its state held; a slot without a token here keeps
    its state to the bit.

    Returns ``(y (C, H, dv) float32 — zeros on rows that are not valid
    —, new state)``."""
    C, H, d = q.shape
    Hkv, dv = k.shape[1], v.shape[-1]
    G, S = H // Hkv, state.shape[0]
    f32 = jnp.float32
    block = min(block, C)
    pad = -C % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        log_g = jnp.pad(log_g, ((0, pad), (0, 0)))
        slot, pos = (jnp.pad(a, (0, pad)) for a in (slot, pos))
        valid = jnp.pad(valid, (0, pad))
    hot_all = (slot[:, None] == jnp.arange(S)[None, :]) & valid[:, None]
    fresh = jnp.any(hot_all & (pos == 0)[:, None], axis=0)
    held = jnp.any(hot_all, axis=0)
    start = jnp.where(fresh[:, None, None, None, None], 0.0, state)
    nb = (C + pad) // block
    idx = jnp.arange(block)

    def body(st, x):
        qb, kb, vb, gb, sb, hot = x
        live = jnp.any(hot, axis=1)
        hotf = hot.astype(f32)
        gb = jnp.where(live[:, None], gb.astype(f32), 0.0)
        # keys at or below the query, of the query's slot
        below = (sb[:, None] == sb[None, :]) & live[:, None] \
            & live[None, :] & (idx[None, :] <= idx[:, None])
        Gc = jnp.einsum("ij,jh->ih", below.astype(f32), gb,
                        precision=_HI)                    # inclusive
        dec = jnp.where(below[:, :, None], jnp.exp(jnp.minimum(
            Gc[:, None, :] - Gc[None, :, :], 0.0)), 0.0)  # (B, B, Hkv)
        qg = qb.reshape(block, Hkv, G, d)
        sc = jnp.einsum("ihgd,jhd->ijhg", qg, kb,
                        preferred_element_type=f32,
                        precision=_HI) * d ** -0.5
        v1 = values_one(vb, live[:, None])                # (B, Hkv, R)
        n1 = jnp.einsum("ijhg,jhr->ihgr", sc * sc * dec[..., None], v1,
                        precision=_HI)
        # the token's slot's state, decayed to the token
        fq = phi_tiles(qg.astype(f32) * d ** -0.25)
        mine = jnp.einsum("is,sharb->iharb", hotf, st, precision=_HI)
        n1 = n1 + jnp.exp(Gc)[:, :, None, None] * jnp.einsum(
            "ihgab,iharb->ihgr", fq, mine, precision=_HI)
        # every slot's state after its last token of the block
        total = jnp.einsum("is,ih->sh", hotf, gb, precision=_HI)
        w = jnp.exp(jnp.minimum(
            jnp.einsum("is,sh->ih", hotf, total, precision=_HI) - Gc, 0.0))
        fk = phi_tiles(kb.astype(f32) * d ** -0.25)
        add = jnp.einsum("is,ih,ihab,ihr->sharb", hotf, w, fk, v1,
                         precision=_HI)
        st = jnp.exp(total)[:, :, None, None, None] * st + add
        return st, jnp.where(live[:, None, None],
                             read(n1, dv, eps).reshape(block, H, dv), 0.0)

    def blocks(a):
        return a.reshape((nb, block) + a.shape[1:])

    new, y = jax.lax.scan(body, start, (
        blocks(q), blocks(k), blocks(v), blocks(log_g), blocks(slot),
        blocks(hot_all)))
    new = jnp.where(held[:, None, None, None, None], new, state)
    return y.reshape(nb * block, H, dv)[:C], new
