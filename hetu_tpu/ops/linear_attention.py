"""Linear attention with a per-head decay (Lightning Attention) over a
per-slot recurrent state.

Per head, with slope ``s`` and a float32 state ``S`` ``(dk, dv)``:
``S_t = e^{-s} S_{t-1} + k_t v_t^T`` and ``o_t = S_t^T q_t * scale``.

* :func:`linear_recurrence` — that, a token at a time (the oracle);
* :func:`linear_update` — one token a slot: the decode rows;
* :func:`linear_scan` — a PACK of tokens of several slots, in blocks
  (the prefill lane). Within a block the same arithmetic is a masked
  product, ``O = ((Q K^T) ⊙ D) V + Λ Q S`` with ``D_ij = e^{-s (p_i -
  p_j)}`` for tokens of one slot at positions ``p_j <= p_i`` and ``Λ_i
  = e^{-s (p_i - first + 1)}``, and the states of ALL slots ride the
  scan over the blocks: a block's tokens read and advance the state of
  their own slot through a one-hot over the slots (a slot without a
  token in the block keeps its state to the bit). Only decays ``<= 1``
  are ever formed.

Everything the state touches is float32; ``q``, ``k``, ``v`` keep the
operand type they come in (bf16 to serve) with float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decay_slopes(num_heads: int):
    """``s_h = 2^(-8 (h + 1) / H)``: the ALiBi / Lightning-Attention
    slope table (the same in every layer)."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / num_heads)


def linear_recurrence(q, k, v, slopes, *, scale: float, state=None):
    """The token recurrence over ONE sequence: ``q``, ``k`` ``(T, H,
    dk)``, ``v`` ``(T, H, dv)`` -> ``(o (T, H, dv) float32, state (H,
    dk, dv))``."""
    T, H, dk = q.shape
    lam = jnp.exp(-slopes)[:, None, None]
    if state is None:
        state = jnp.zeros((H, dk, v.shape[-1]), jnp.float32)

    def step(S, x):
        qt, kt, vt = (a.astype(jnp.float32) for a in x)
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt) * scale

    state, o = jax.lax.scan(step, state, (q, k, v))
    return o, state


def linear_update(q, k, v, state, live, slopes, *, scale: float):
    """One token a slot: ``q``, ``k`` ``(S, H, dk)``, ``v`` ``(S, H,
    dv)``, ``state`` ``(S, H, dk, dv)`` float32 -> ``(o (S, H, dv)
    float32, new state)``. A slot that is not ``live`` keeps its state
    (its row of ``o`` is whatever its stale operands give)."""
    lam = jnp.exp(-slopes)[None, :, None, None]
    kv = k.astype(jnp.float32)[..., :, None] \
        * v.astype(jnp.float32)[..., None, :]
    new = lam * state + kv
    o = jnp.einsum("shkv,shk->shv", new, q.astype(jnp.float32)) * scale
    return o, jnp.where(live[:, None, None, None], new, state)


def linear_scan(q, k, v, state, slot, pos, valid, slopes, *,
                scale: float, block: int = 256):
    """A pack of ``C`` tokens: ``q``, ``k`` ``(C, H, dk)``, ``v`` ``(C,
    H, dv)``; ``slot``, ``pos`` ``(C,)`` int32 and ``valid`` ``(C,)``
    bool — the tokens of one slot are contiguous with ascending
    positions; ``state`` ``(S, H, dk, dv)`` float32, each slot's state
    after the position before its first token here. A slot whose first
    token stands at position 0 starts from zeros, whatever its state
    held (a slot taken again by another request).

    Returns ``(o (C, H, dv) float32, new state)``."""
    C, H, dk = q.shape
    dv = v.shape[-1]
    S = state.shape[0]
    block = min(block, C)
    pad = -C % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        slot, pos = (jnp.pad(a, (0, pad)) for a in (slot, pos))
        valid = jnp.pad(valid, (0, pad))
    hot_all = (slot[:, None] == jnp.arange(S)[None, :]) & valid[:, None]
    fresh = jnp.any(hot_all & (pos == 0)[:, None], axis=0)
    state = jnp.where(fresh[:, None, None, None], 0.0, state)
    nb = (C + pad) // block
    big = jnp.iinfo(jnp.int32).max

    def body(state, x):
        qb, kb, vb, sb, pb, hot = x                  # hot (B, S)
        hotf = hot.astype(jnp.float32)
        live = jnp.any(hot, axis=1)
        # each slot's first and last position in the block
        first = jnp.min(jnp.where(hot, pb[:, None], big), axis=0)   # (S,)
        n = jnp.sum(hot, axis=0)
        last = first + n - 1
        f_tok = jnp.where(live, hotf @ jnp.where(n > 0, first, 0)
                          .astype(jnp.float32), 0.0)                # (B,)
        l_tok = jnp.where(live, hotf @ jnp.where(n > 0, last, 0)
                          .astype(jnp.float32), 0.0)
        pf = pb.astype(jnp.float32)
        # intra-block: same slot, key at or below the query
        same = (sb[:, None] == sb[None, :]) & live[:, None] \
            & live[None, :] & (pb[None, :] <= pb[:, None])
        dist = jnp.where(same, pf[:, None] - pf[None, :], 0.0)
        D = jnp.where(same[None], jnp.exp(
            -slopes[:, None, None] * dist[None]), 0.0)       # (H, B, B)
        a = jnp.einsum("ihk,jhk->hij", qb, kb,
                       preferred_element_type=jnp.float32) * D
        o = jnp.einsum("hij,jhv->ihv", a.astype(vb.dtype), vb,
                       preferred_element_type=jnp.float32)
        # inter-block: the token's slot's state, decayed to the token
        lam = jnp.exp(-slopes[None, :] * (pf - f_tok + 1.0)[:, None])
        qs = jnp.einsum("ihk,shkv->ishv", qb.astype(jnp.float32), state)
        o = o + lam[:, :, None] * jnp.einsum("ishv,is->ihv", qs, hotf)
        # every slot's state after its last token of the block
        w = jnp.exp(-slopes[None, :] * (l_tok - pf)[:, None])   # (B, H)
        kw = kb.astype(jnp.float32) * w[:, :, None]
        add = jnp.einsum("sihk,ihv->shkv",
                         hotf.T[:, :, None, None] * kw[None],
                         vb.astype(jnp.float32))
        keep = jnp.exp(-slopes[None, :] * n[:, None].astype(jnp.float32))
        state = keep[:, :, None, None] * state + add
        return state, o * scale

    def blocks(a):
        return a.reshape((nb, block) + a.shape[1:])

    state, o = jax.lax.scan(body, state, (
        blocks(q), blocks(k), blocks(v), blocks(slot), blocks(pos),
        blocks(hot_all)))
    return o.reshape(nb * block, H, dv)[:C], state
