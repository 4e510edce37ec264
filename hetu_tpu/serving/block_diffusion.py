"""The block lane's sampler: generation by diffusion over blocks.

A model that states a ``generation`` (``models.sdar_moe.
BlockDiffusion``) does not yield a token a slot and step. A slot holds
a BLOCK of ``B`` positions ``pos .. pos + B - 1``; each iteration the
fused step runs the block's ``B`` current tokens (``mask_token_id``
where still masked) as q rows against the committed cache and the
block's own keys — the verify lane's shape, ``docs/SERVING.md`` — and
:func:`denoise_slots` unmasks, on the device. Every pass of a live slot
is a **denoise pass**: at every masked position the top token and its
probability (a float32 softmax over the slice, the mask id held at
``-inf``: never drawn), then unmask — ``low_confidence_static`` the
``n`` most confident masked positions, ``n = B // steps`` and the
remainder on the first passes; ``low_confidence_dynamic`` every masked
position whose confidence passes the slot's threshold, or those ``n`` if
they are fewer. The pass's K/V were written from a block that still held
a mask and will be overwritten.

The pass that removes a block's last mask FINISHES it: its ``B`` tokens
are handed on with that iteration's fetch, ``pos`` moves by ``B`` and
the next block begins as ``B`` masks. Its clean K/V are not in the arena
yet. They are written INSIDE the next block's first pass: the slot's
pass carries ``2B`` q rows at ``pos - B .. pos + B - 1`` — the finished
block's tokens (the slot's **carry**: ``prev (S, B)``, live while
``carry (S,)`` is set) below the current block's. The attention's block
bound is a function of a row's position, so the carry rows see the cache
and their own block, the current rows the cache, the carry block —
written by this same pass: a layer writes all its rows' K/V before it
attends — and themselves: the arithmetic of a commit pass and of the
first pass after it, in one call. A carry row that is not live does not
write; a request's last block needs no commit at all. A block of ``B``
costs its denoise passes and no other.

Everything is data per slot (tokens, masks, the pass, the steps, the
rule, the threshold, the carry): slots at different passes, with and
without a carry, share the one step.
"""

from __future__ import annotations

import numpy as np


class BlockGenerationNotSupported(NotImplementedError):
    """What assumes a token a slot and step — or hands a slot's cache
    on mid-block — asked of a model that generates by diffusion over
    blocks: the verify lane (``spec_depth``), the prefix cache,
    preemption and spill, the fleet's KV export, import and
    replication, the prefill tier's hand-off, the CP-prefill lane,
    tenancy, the int8 arena, W8A8, a tp plan, and sampling at a
    temperature (``docs/SERVING.md``, "The block lane")."""


REMASKING = {"low_confidence_static": False, "low_confidence_dynamic": True}


def refuse(**asked) -> None:
    """Raise :class:`BlockGenerationNotSupported` for the first thing
    ``asked`` (``{what: whether it was asked for}``) of a model that
    generates by diffusion over blocks."""
    for what, on in asked.items():
        if on:
            raise BlockGenerationNotSupported(
                f"{what} is not available to a model that generates "
                f"by diffusion over blocks")


def denoise_slots(logits, tok, masked, passes, steps, dynamic, thresh,
                  live, prev, carry, *, mask_id: int):
    """One pass of every live slot's block (see the module docstring).

    ``logits (S, B, V)`` of the rows ``tok (S, B)`` (position ``i``'s
    logits predict position ``i``), ``masked (S, B)`` bool, ``passes
    (S,)`` the passes the block has had, ``steps (S,)``, ``dynamic
    (S,)`` bool, ``thresh (S,)`` float32, ``live (S,)`` bool, ``prev
    (S, B)`` the block finished before this one and ``carry (S,)`` bool
    whether its rows were still to run. Returns ``(committed (S, B),
    ncommit (S,): B or 0, tok, masked, passes, prev, carry)`` — the last
    five the state the next iteration takes; a slot that is not live
    keeps its own. A slot whose pass leaves nothing masked hands its
    block on (``committed``), keeps it as ``prev`` with ``carry`` set
    and begins the next as ``B`` masks; any other live slot's carry rows
    ran in this pass, and ``carry`` is cleared. No sort and no gather:
    the rank of a position among its block's confidences is a ``(B, B)``
    compare (ties to the lower position)."""
    import jax.numpy as jnp
    S, B, V = logits.shape
    lg = jnp.where(jnp.arange(V) == mask_id, -jnp.inf,
                   logits.astype(jnp.float32))
    top = jnp.max(lg, axis=-1)
    x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    # softmax(lg)[x0]: the top's share
    conf = 1.0 / jnp.sum(jnp.exp(lg - top[..., None]), axis=-1)
    conf = jnp.where(masked, conf, -jnp.inf)
    at = jnp.arange(B)
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)                       # (S, B)
    n = B // steps + (passes < B % steps)
    pick = masked & (rank < n[:, None])
    high = masked & (conf > thresh[:, None])
    pick = jnp.where((dynamic & (high.sum(-1) >= n))[:, None], high, pick)
    pick &= live[:, None]
    final = jnp.where(pick, x0, tok)
    left = masked & ~pick
    done = live & ~left.any(-1)
    whole = done[:, None]
    # a finished block leaves; the next begins as B masks
    return (jnp.where(whole, final, 0),
            jnp.where(done, B, 0).astype(jnp.int32),
            jnp.where(whole, mask_id, final).astype(jnp.int32),
            left | whole,
            jnp.where(done, 0, passes + live).astype(jnp.int32),
            jnp.where(whole, final, prev).astype(jnp.int32),
            jnp.where(live, done, carry))


def lane_rows(pos, tok, prev, carry, live, *, mask_id: int):
    """The ``2B`` q rows of every slot's pass: ``(tokens, positions,
    writes)``, each ``(S, 2B)``, and ``early (S,)``.

    The carry rows (``prev``, at ``pos - B ..``) stand below the current
    block's (``tok``, at ``pos ..``); ``writes`` is ``live`` on the
    current block's rows and ``live & carry`` on the carry rows — the
    rest scatter nowhere. A slot whose first block starts below ``B``
    (``early``: a prompt shorter than a block) has no position for carry
    rows and no carry: its rows stand at ``0 .. 2B - 1``, the current
    block's FIRST, the rest masks that write nothing — the caller takes
    such a slot's logits from rows ``0 .. B - 1``, every other's from
    ``B .. 2B - 1``."""
    import jax.numpy as jnp
    B = tok.shape[1]
    early = pos < B
    upper = jnp.arange(2 * B)[None] >= B
    tokens = jnp.where(
        early[:, None],
        jnp.concatenate([tok, jnp.full_like(tok, mask_id)], axis=1),
        jnp.concatenate([prev, tok], axis=1))
    writes = live[:, None] & jnp.where(early[:, None], ~upper,
                                       upper | carry[:, None])
    positions = jnp.maximum(pos - B, 0)[:, None] + jnp.arange(2 * B)[None]
    return tokens, positions, writes, early


def first_block(prompt: np.ndarray, B: int, mask_id: int):
    """``(pos, tok (B,), masked (B,))`` of a request's first generated
    block: the prompt's whole blocks are prefilled (``pos`` of them),
    its tail of ``len(prompt) % B`` tokens begins the block, unmasked."""
    pos = len(prompt) // B * B
    tail = len(prompt) - pos
    tok = np.full(B, mask_id, np.int32)
    tok[:tail] = prompt[pos:]
    return pos, tok, np.arange(B) >= tail
