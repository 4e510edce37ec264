"""The block lane's sampler: generation by diffusion over blocks.

A model that states a ``generation`` (``models.sdar_moe.
BlockDiffusion``) does not yield a token a slot and step. A slot holds
a BLOCK of ``B`` positions ``pos .. pos + B - 1``; each iteration the
fused step runs the block's ``B`` current tokens (``mask_token_id``
where still masked) as ``B`` q rows against the committed cache and the
block's own keys — the verify lane's shape, ``docs/SERVING.md`` — and
:func:`denoise_slots` decides, on the device, what the pass was:

- a **denoise pass** (some position still masked): at every masked
  position the top token and its probability (a float32 softmax over
  the slice, the mask id held at ``-inf``: never drawn), then unmask —
  ``low_confidence_static`` the ``n`` most confident masked positions,
  ``n = B // steps`` and the remainder on the first passes;
  ``low_confidence_dynamic`` every masked position whose confidence
  passes the slot's threshold, or those ``n`` if they are fewer. The
  pass's K/V were written and will be overwritten;
- a **commit pass** (nothing masked going in): the block's final tokens
  ran once more and their K/V stay; the ``B`` tokens are committed,
  ``pos`` moves by ``B`` and the next block begins as ``B`` masks.

Everything is data per slot (tokens, masks, the pass, the steps, the
rule, the threshold): slots at different passes, and a slot that
commits beside one that denoises, share the one step.
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
                  live, *, mask_id: int):
    """One pass of every live slot's block (see the module docstring).

    ``logits (S, B, V)`` of the rows ``tok (S, B)`` (position ``i``'s
    logits predict position ``i``), ``masked (S, B)`` bool, ``passes
    (S,)`` the passes the block has had, ``steps (S,)``, ``dynamic
    (S,)`` bool, ``thresh (S,)`` float32, ``live (S,)`` bool. Returns
    ``(committed (S, B), ncommit (S,): B or 0, tok, masked, passes)`` —
    the last three the state the next iteration takes; a slot that is
    not live keeps its own. No sort and no gather: the rank of a
    position among its block's confidences is a ``(B, B)`` compare (ties
    to the lower position)."""
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
    commit = live & ~masked.any(-1)
    denoise = live & ~commit
    new_tok = jnp.where(pick & denoise[:, None], x0, tok)
    new_masked = masked & ~(pick & denoise[:, None])
    # a committed block leaves; the next begins as B masks
    new_tok = jnp.where(commit[:, None], mask_id, new_tok)
    new_masked = new_masked | commit[:, None]
    new_passes = jnp.where(commit, 0, passes + denoise)
    return (jnp.where(commit[:, None], tok, 0),
            jnp.where(commit, B, 0).astype(jnp.int32),
            new_tok.astype(jnp.int32), new_masked,
            new_passes.astype(jnp.int32))


def first_block(prompt: np.ndarray, B: int, mask_id: int):
    """``(pos, tok (B,), masked (B,))`` of a request's first generated
    block: the prompt's whole blocks are prefilled (``pos`` of them),
    its tail of ``len(prompt) % B`` tokens begins the block, unmasked."""
    pos = len(prompt) // B * B
    tail = len(prompt) - pos
    tok = np.full(B, mask_id, np.int32)
    tok[:tail] = prompt[pos:]
    return pos, tok, np.arange(B) >= tail
