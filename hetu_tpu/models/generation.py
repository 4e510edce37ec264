"""Autoregressive generation with KV caches.

The reference's inference path appends KV via a dynamic-concat op
(``hetu/graph/ops`` dynamic concat; ``NDArrayMeta`` deprecated
dynamic_shape was for padded inference). TPU-native: fixed-capacity KV
buffers + ``dynamic_update_slice`` (static shapes for jit), prefill in one
pass, then a ``lax.scan`` over decode steps with greedy / temperature /
top-k / nucleus (top-p) sampling.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


class PromptTooLongError(ValueError):
    """A prompt (plus its decode budget) exceeds a hard length limit.

    Structured so callers (the serving scheduler's admission gate,
    :func:`generate`) can report WHICH limit was hit and what would
    lift it, instead of a bare refusal: ``prompt_len`` + ``max_tokens``
    against ``limit`` (the per-slot / cache budget) and — where a
    serving CP-prefill lane exists — ``cp_limit`` (its larger budget).
    """

    def __init__(self, *, prompt_len: int, max_tokens: int, limit: int,
                 cp_limit: Optional[int] = None, source: str = "decode",
                 hint: Optional[str] = None):
        self.prompt_len = int(prompt_len)
        self.max_tokens = int(max_tokens)
        self.limit = int(limit)
        self.cp_limit = int(cp_limit) if cp_limit is not None else None
        self.source = source
        worst = self.prompt_len + self.max_tokens
        msg = (f"prompt of {self.prompt_len} tokens + {self.max_tokens} "
               f"decode tokens = {worst} exceeds the {self.limit}-token "
               f"{source} budget")
        if self.cp_limit is not None:
            msg += (f" and the {self.cp_limit}-token CP-prefill lane "
                    f"budget")
        if hint:
            msg += f" ({hint})"
        super().__init__(msg)


def _head_weight(model, params):
    if hasattr(model, "_head_weight"):
        return model._head_weight(params)
    return params["wte"]["weight"]


def init_kv_caches(model, batch: int, max_len: int, dtype=jnp.float32):
    """(k, v) buffers stacked over layers: (L, b, max_len, hkv, d) — or
    whatever leaves the model's attention declares (its
    ``kv_leaf_shapes()``: a latent attention has ONE, ``(L, b, max_len,
    1, row)``); ``dtype=jnp.int8`` builds the quantized cache
    (``nn.parallel.kv_leaves``)."""
    from hetu_tpu.nn.parallel import SlotStateNotSupported, kv_leaves
    if model.blocks.slot_state:
        raise SlotStateNotSupported(
            "the dense cache (generate, the draft model): this model "
            "caches pages for some layers and a slot's state for others")
    return kv_leaves(model.blocks.block.attn,
                     (model.blocks.num_layers, batch, max_len), dtype)


def init_paged_caches(model, n_blocks: int, block_size: int,
                      dtype=jnp.float32, sharding=None, slots: int = 0):
    """The block-paged arena, as the model's stack of blocks builds it
    (``model.blocks.init_paged_caches``): ``(L, n_blocks, block_size,
    hkv*d)`` leaves of what its attention caches a token; a model whose
    layers cache different things has paged leaves over the layers
    that have keys, and a state per SLOT over those that have none —
    ``slots`` is for that leaf."""
    return model.blocks.init_paged_caches(n_blocks, block_size, dtype,
                                          slots, sharding)


def decode(model, params, input_ids, positions, caches, *,
           slot_mask=None, block_tables=None, row_mask=None,
           attn_kernel: str = "reference", w8a8_mask=None,
           w8a8_wq=None, lora=None, with_stats: bool = False):
    """Run a chunk through the model in decode mode.

    ``positions`` (b, s) absolute positions. Without ``slot_mask`` they
    must be identical across the batch (batched decode, one shared write
    index). With ``slot_mask`` (b,) bool every row decodes at ITS OWN
    ``positions[r, 0]`` — the serving engine's slot-pooled path — and
    masked-off rows leave their KV rows untouched. ``block_tables``
    (b, W) switches the caches to the block-paged arena layout
    (``(L, n_blocks, block_size, hkv*d)`` leaves; see
    ``ParallelAttention._decode``). ``row_mask`` (b, s) bool gates KV
    writes per CELL within a row (paged mode only) — the speculative
    verify lane's guard against draft rows beyond a slot's allocated
    blocks. ``attn_kernel`` ("reference" | "paged") picks the paged
    arena's attention read path (Pallas kernel vs XLA gather — see
    ``ops.paged_pallas``); ``w8a8_mask`` ((layers,) bool) flips decode
    FFNs to the W8A8 int8 lane per layer, and ``w8a8_wq`` (a stacked
    ``prequantize`` tree) feeds that lane pre-quantized int8 weights
    so the per-step weight quantize disappears. ``lora`` (the
    multi-tenant adapter arena — ``{"ids": (b, s) pages, "pages":
    stacked (L, P, ...) A/B tree}``) adds the per-token batched
    multi-adapter BGMV deltas (``nn.parallel.lora_apply``); None is
    the historical base-only lane. Returns (logits
    (b, s, V), new caches), and with ``with_stats`` the layers' stats
    (``StackedBlocks.decode``) as a third."""
    h = model.embed(params, input_ids, positions=positions)
    h, caches, stats = model.blocks.decode(
        params["blocks"], h, caches, positions=positions,
        slot_mask=slot_mask, block_tables=block_tables,
        row_mask=row_mask, attn_kernel=attn_kernel,
        w8a8_mask=w8a8_mask, w8a8_wq=w8a8_wq, lora=lora,
        with_stats=True)
    logits = head_logits(model, params, h)
    return (logits, caches, stats) if with_stats else (logits, caches)


def head_logits(model, params, h):
    """The final norm and the head over hidden rows ``h (b, s, E)``:
    float32 logits ``(b, s, V)``."""
    h = model.hidden_norm(params, h)
    w = _head_weight(model, params)
    return jnp.einsum("bse,ve->bsv", h.astype(jnp.float32),
                      w.astype(jnp.float32))


def _sample(logits, *, temperature: float, top_k: int, top_p: float, rng):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if 0.0 < top_p < 1.0:
        # nucleus: keep the smallest prefix of the sorted distribution
        # whose mass exceeds top_p (the top token always survives)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p           # mass *before* this token
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def generate(model, params, input_ids, *, max_new_tokens: int,
             max_len: Optional[int] = None, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 0.0,
             rng: Optional[jax.Array] = None,
             eos_id: Optional[int] = None, pad_id: Optional[int] = None,
             prompt_lens=None, cache_dtype=jnp.float32):
    """Generate ``max_new_tokens`` continuations for a (b, s) prompt.

    Returns (b, s + max_new_tokens) token ids; positions after an EOS
    are filled with ``pad_id`` when given, else with ``eos_id`` (the
    historical behavior — callers that need to tell a real EOS from
    fill must pass a distinct ``pad_id``). jit-able end to end.

    ``prompt_lens`` (b,) enables RAGGED prompts: row r's real prompt is
    ``input_ids[r, :prompt_lens[r]]`` (right-padded to s). Prefill then
    samples at each row's LAST REAL position instead of column s-1 (a
    padded batch otherwise samples at a pad position), and decode
    writes row r's tokens at positions ``prompt_lens[r] + t`` with a
    per-row causal mask, so stale pad KV rows are never attended.
    Generated tokens still occupy the trailing ``max_new_tokens``
    columns of the output for every row. When omitted, every prompt is
    assumed to span the full s columns (the historical batched path,
    bit-for-bit unchanged).
    """
    b, s = input_ids.shape
    total = max_len or (s + max_new_tokens)
    # fail with a structured error instead of the cryptic downstream
    # gather/embed failure: either the caller's own cache budget
    # (max_len) or the model's positional capacity bounds the request
    if s + max_new_tokens > total:
        raise PromptTooLongError(
            prompt_len=s, max_tokens=max_new_tokens, limit=total,
            source="generate KV-cache (max_len)",
            hint="raise max_len or trim the prompt")
    max_positions = getattr(getattr(model, "cfg", None),
                            "max_positions", None)
    if max_positions is not None and total > max_positions:
        raise PromptTooLongError(
            prompt_len=s, max_tokens=max_new_tokens,
            limit=int(max_positions),
            source="model max_positions",
            hint="the model cannot address positions past its trained "
                 "context window")
    caches = init_kv_caches(model, b, total, cache_dtype)
    rng = rng if rng is not None else jax.random.key(0)
    ragged = prompt_lens is not None
    fill_id = pad_id if pad_id is not None else eos_id

    # prefill the prompt in one pass
    prefill_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    logits, caches = decode(model, params, input_ids, prefill_pos, caches)
    rng, sub = jax.random.split(rng)
    if ragged:
        plens = jnp.asarray(prompt_lens, jnp.int32)
        # pad-aware gather: sample at each row's last REAL position
        last_logits = jnp.take_along_axis(
            logits, (plens - 1)[:, None, None], axis=1)[:, 0]
        pos0 = plens                       # next write index per row
    else:
        last_logits = logits[:, -1]
        pos0 = None
    tok = _sample(last_logits, temperature=temperature, top_k=top_k,
                  top_p=top_p, rng=sub)
    done = jnp.zeros((b,), bool) if eos_id is None else (tok == eos_id)

    def step(carry, i):
        caches, tok, done, rng = carry
        if ragged:
            pos = (pos0 + i)[:, None]
            logits, caches = decode(model, params, tok[:, None], pos,
                                    caches,
                                    slot_mask=jnp.ones((b,), bool))
        else:
            pos = jnp.broadcast_to((s + i)[None, None], (b, 1))
            logits, caches = decode(model, params, tok[:, None], pos,
                                    caches)
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits[:, -1], temperature=temperature,
                      top_k=top_k, top_p=top_p, rng=sub)
        if eos_id is not None:
            raw = nxt
            nxt = jnp.where(done, fill_id, raw)
            done = done | (raw == eos_id)
        return (caches, nxt, done, rng), tok

    (_, last, _, _), toks = jax.lax.scan(
        step, (caches, tok, done, rng), jnp.arange(max_new_tokens - 1))
    out = jnp.concatenate(
        [input_ids, jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
    return out
