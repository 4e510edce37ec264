"""Speculative-decoding draft plane: who proposes the k tokens the
fused serving step verifies.

Decode emits one token per active slot per fused-step iteration, so at
production TPOT targets most of each step's FLOPs sit idle — the
memory-bound decode wall speculative decoding (Leviathan et al., "Fast
Inference from Transformers via Speculative Decoding") climbs by
verifying k DRAFTED tokens in one forward pass. The serving engine's
verify lane (``ServingEngine(spec_depth=k)``) does the checking; this
module is where drafts come from:

- :class:`NgramDraftsman` — self-drafting prompt-lookup (Saxena,
  "Prompt Lookup Decoding" / LLMA): a host-side per-slot suffix index
  over the request's OWN tokens (prompt + emitted). The last n-gram is
  looked up in the history; if it occurred before, the tokens that
  followed it are the draft. No second model, no device work, and on
  the repetitive traffic real serving sees (code edits, RAG quoting
  its context, multi-turn echoes) acceptance is high exactly when the
  tokens were cheapest to predict;
- :class:`ModelDraftsman` — the small-model path through the existing
  model zoo (a tiny GPT drafting for a Llama, etc.): the draft model
  keeps its own per-slot KV arena and ONE jitted step per iteration
  first *catches up* on the tokens the target committed last iteration
  (a ``(S, k+1)``-wide masked window — no separate prefill lane: a
  fresh slot warms up over its first ``ceil(P/(k+1))`` iterations,
  drafting meanwhile disabled for it), then greedily drafts k tokens.
  Draft KV for rejected tokens is overwritten by the next catch-up
  before anything attends it, the same rewind discipline the target
  arena uses.

Both draftsmen are PROPOSERS only: the engine's verify lane accepts a
draft token iff the rejection-sampling test passes — at temperature 0
that reduces to "equals what sequential greedy decode would have
emitted"; at temperature > 0 the Leviathan et al. correction accepts a
draft with probability ``min(1, p_target/q_draft)`` and resamples the
first rejection from the normalized residual ``max(0, p - q)``, which
provably preserves the target sampler's output distribution. Either
way a bad draftsman can only cost speed, never correctness
(``docs/SERVING.md`` — "Speculation + QoS" / "Sampled speculation").

To support the sampled lane, draftsmen surface per-token proposal
probabilities ``q`` (``surfaces_q = True``): :class:`NgramDraftsman`
proposals are deterministic so their q is a degenerate one-hot (the
engine synthesizes it on-device); :class:`ModelDraftsman` SAMPLES its
draft chain from its own adjusted softmax at the request's knobs and
returns those rows — drafts must be distributed ~q for the accept
test to be exact.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def sample_needs(live, temperature, top_k, top_p):
    """What the LIVE rows' knobs ask of the sampler, reduced over the
    batch: ``(draws, sorts)`` scalars. ``draws``: some live row is not
    greedy (the complement of the ``temperature == 0.0`` its token is
    selected on); ``sorts``: some such row masks by top-k or top-p.
    Rows that are not live do not count — a freed slot keeps its last
    request's knobs.

    One expression for both sides: the fused step gates the sampler's
    work on it from device arrays, the engine's loop counts
    ``serving_sample_path_total`` with it from its numpy mirrors."""
    hot = live & (temperature != 0)
    masks = (top_k > 0) | ((top_p > 0) & (top_p < 1))
    return hot.any(), (hot & masks).any()


def sample_path(draws, sorts) -> str:
    """The branch :func:`sample_needs`' answer selects, by name."""
    return "sort" if sorts else "draw" if draws else "greedy"


def adjust_logits(logits, temperature, top_k, top_p, sorts=None):
    """Apply the serving sampler's temperature/top-k/top-p masking to
    ``logits`` (..., V) and return the masked, scaled logits.

    This is the single source of truth for BOTH the fused verify lane's
    target distribution p and the draft models' proposal distribution q
    — value-identical to ``generation._sample``, so a sampled serving
    token drawn from these logits matches the one-shot reference.

    ``temperature``/``top_k``/``top_p`` are traced scalars or arrays
    broadcastable against the leading dims of ``logits`` — knob churn
    is DATA, never a recompile.

    ``sorts`` (a traced scalar bool, :func:`sample_needs`) says whether
    ANY row that counts masks at all: where none does, the sort, the
    sorted softmax and the cumulative sum are not run, the thresholds
    read -inf and the result is the scaled logits — to the bit what
    the masks leave of a row without top-k and top-p. It has to be a
    scalar of the whole batch, taken OUTSIDE any ``vmap`` — a per-row
    predicate under ``vmap`` turns the ``cond`` into a select that runs
    both sides. ``None``: always mask."""
    import jax
    import jax.numpy as jnp

    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / t[..., None].astype(logits.dtype)

    def thresholds():
        """Per row, the k-th largest value and the nucleus' smallest:
        all that the masks below need of the sorted table."""
        V = scaled.shape[-1]
        sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        kth = jnp.take_along_axis(
            sorted_desc,
            jnp.broadcast_to(jnp.clip(top_k - 1, 0, V - 1)[..., None],
                             scaled.shape[:-1] + (1,)),
            axis=-1)
        sd = jnp.where((top_k <= 0)[..., None] | (sorted_desc >= kth),
                       sorted_desc, -jnp.inf)
        probs = jax.nn.softmax(sd, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p[..., None]
        cutoff = jnp.min(jnp.where(keep, sd, jnp.inf), axis=-1,
                         keepdims=True)
        return kth, cutoff

    def keep_all():
        low = jnp.full(scaled.shape[:-1] + (1,), -jnp.inf, scaled.dtype)
        return low, low

    # the cond hands back two numbers a row, never a table: the masks
    # fuse into whatever reads them, as they do without the gate (the
    # barrier keeps the compiler from moving them INTO the branches,
    # which made the cond return two tables)
    kth, cutoff = thresholds() if sorts is None \
        else jax.lax.optimization_barrier(
            jax.lax.cond(sorts, thresholds, keep_all))
    keep_k = (top_k <= 0)[..., None] | (scaled >= kth)
    masked = jnp.where(keep_k, scaled, -jnp.inf)
    use_p = ((top_p > 0) & (top_p < 1))[..., None]
    return jnp.where(use_p & (masked < cutoff), -jnp.inf, masked)


def _split_chain(key_data, n):
    """``n`` successive splits of one slot's raw key state: ``ks[i]``
    is the carry after i+1 splits (the new key state if i+1 tokens
    commit), ``subs[i]`` the subkey that samples token i — both
    ``(n, KW)`` raw key data."""
    import jax
    import jax.numpy as jnp

    carry = jax.random.wrap_key_data(key_data)
    ks, subs = [], []
    for _ in range(n):
        carry, sub = jax.random.split(carry)
        ks.append(jax.random.key_data(carry))
        subs.append(jax.random.key_data(sub))
    return jnp.stack(ks), jnp.stack(subs)


def _commit(drafts, a, tok_a, ks):
    """One slot's results: the ``a`` accepted drafts, then ``tok_a`` at
    column ``a``; the key state after ``a + 1`` splits."""
    import jax.numpy as jnp

    cols = jnp.arange(drafts.shape[0] + 1)
    drafts_pad = jnp.concatenate(
        [drafts.astype(jnp.int32), jnp.zeros((1,), jnp.int32)])
    committed = jnp.where(cols < a, drafts_pad, 0)
    committed = jnp.where(cols == a, tok_a, committed)
    return (committed.astype(jnp.int32), (a + 1).astype(jnp.int32),
            tok_a, jnp.take(ks, a, axis=0))


def greedy_verify(logits, drafts, depth, key_data):
    """What :func:`speculative_verify` yields at temperature 0, to the
    bit, for ONE slot, and nothing else computed: the argmax of the raw
    rows, drafts accepted by leading match under ``depth``, the key
    state advanced one split per committed token (a greedy slot burns
    its stream like a sampled one). No adjusted logits, no softmax, no
    q, no draw."""
    import jax.numpy as jnp

    K = drafts.shape[0]
    ks, _ = _split_chain(key_data, K + 1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (K+1,)
    ok = (drafts == greedy[:K]) & (jnp.arange(K) < depth)
    a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))  # accepted count
    return _commit(drafts, a, jnp.take(greedy, a), ks)


def speculative_verify(logits, drafts, depth, q, temperature, top_k,
                       top_p, key_data, sorts=None):
    """Rejection-sampling verify for ONE slot — traced, vmapped over
    the slot axis by :func:`verify_slots`.

    Inputs: ``logits`` (K+1, V) target rows over the draft window,
    ``drafts`` (K,) proposed tokens, ``depth`` scalar per-slot draft
    length, ``q`` (K, V) proposal probabilities the drafts were sampled
    from, scalar sampling knobs, ``key_data`` (KW,) the slot's raw
    PRNG key state (``jax.random.key_data`` layout), and ``sorts``, the
    BATCH's scalar for :func:`adjust_logits` (closed over by the
    ``vmap``, never a row's own).

    Per Leviathan et al.: draft i is accepted with probability
    ``min(1, p_i[d_i] / q_i[d_i])`` (evaluated as ``u * q < p`` with an
    independent uniform); at the first rejection the token is resampled
    from the normalized residual ``max(0, p - q)``; if every draft is
    accepted the bonus token is a fresh sample from the last row. At
    temperature 0 the accept test collapses to ``draft == argmax`` and
    the emitted values are bitwise :func:`greedy_verify`'s.

    PRNG discipline mirrors ``generation.generate``: exactly ONE
    ``jax.random.split`` is consumed per COMMITTED token (so a slot
    that speculates is stream-compatible with one that does not, and a
    no-draft sampled slot is bitwise identical to the one-shot
    reference at the same seed); accept uniforms and residual draws
    ride fold_in side-channels off the per-token subkeys.

    Returns ``(committed (K+1,) int32, ncommit scalar int32,
    last_tok scalar int32, new_key_data (KW,))``."""
    import jax
    import jax.numpy as jnp

    K = drafts.shape[0]
    V = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)

    # one split per potentially-committed token
    ks, subs = _split_chain(key_data, K + 1)
    u = jax.vmap(lambda sub: jax.random.uniform(jax.random.fold_in(
        jax.random.wrap_key_data(sub), 0xACC)))(subs[:K])

    masked = adjust_logits(logits, temperature, top_k, top_p, sorts)
    p = jax.nn.softmax(masked.astype(jnp.float32), axis=-1)  # (K+1, V)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (K+1,)

    lane = jnp.arange(K)
    p_d = jnp.take_along_axis(p[:K], drafts[:, None], axis=-1)[:, 0]
    q_d = jnp.take_along_axis(q, drafts[:, None], axis=-1)[:, 0]
    samp_ok = u * q_d < p_d
    greedy_ok = drafts == greedy[:K]
    ok = jnp.where(temperature > 0, samp_ok, greedy_ok) \
        & (lane < depth)
    a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))  # accepted count

    # token at column a: greedy / residual-resample / fresh sample
    q_pad = jnp.concatenate([q, jnp.zeros((1, V), q.dtype)], axis=0)
    p_a = jnp.take(p, a, axis=0)
    residual = jnp.maximum(p_a - jnp.take(q_pad, a, axis=0), 0.0)
    r_sum = jnp.sum(residual)
    use_resid = (temperature > 0) & (a < depth) & (r_sum > 0)
    sub_a = jax.random.wrap_key_data(jnp.take(subs, a, axis=0))
    masked_a = jnp.take(masked, a, axis=0)
    drawn_full = jax.random.categorical(sub_a, masked_a)
    resid_logits = jnp.where(residual > 0, jnp.log(residual), -jnp.inf)
    drawn_resid = jax.random.categorical(sub_a, resid_logits)
    tok_a = jnp.where(
        temperature == 0.0, jnp.take(greedy, a),
        jnp.where(use_resid, drawn_resid, drawn_full)).astype(jnp.int32)
    return _commit(drafts, a, tok_a, ks)


def verify_slots(logits, drafts, depth, q, temperature, top_k, top_p,
                 key_data, live):
    """The fused step's verify over the slot axis, doing only what the
    LIVE slots' knobs need (:func:`sample_needs`): a batch whose live
    slots are all greedy takes :func:`greedy_verify`; one sampling live
    slot and every slot takes :func:`speculative_verify`, the sort
    inside it only where a live sampling slot masks. The gates are
    ``cond``s on scalars of the batch, outside the ``vmap``s, so the
    branch not taken is not run; either way a live slot's results are
    the ungated ``jax.vmap(speculative_verify)``'s to the bit (a slot
    that is not live gets SOME branch's: its results are dropped).

    Leading slot axis on everything; ``q`` ``None`` is the one-hot of
    the drafts (a deterministic proposer), made only where it is read.
    """
    import jax
    import jax.numpy as jnp

    draws, sorts = sample_needs(live, temperature, top_k, top_p)

    def sampled():
        qp = jax.nn.one_hot(drafts, logits.shape[-1], dtype=jnp.float32) \
            if q is None else q.astype(jnp.float32)
        return jax.vmap(
            lambda *slot: speculative_verify(*slot, sorts=sorts))(
            logits, drafts, depth, qp, temperature, top_k, top_p,
            key_data)

    def greedy():
        return jax.vmap(greedy_verify)(logits, drafts, depth, key_data)

    return jax.lax.cond(draws, sampled, greedy)


def sample_rows(logits, temperature, top_k, top_p, key_data, live):
    """First tokens for ``(R, V)`` rows, mirroring ``generate``'s
    prefill: split each row's key once, draw with the sub — gated like
    :func:`verify_slots` on what the live rows need, so greedy rows
    cost an argmax. Returns ``(tokens (R,) int32, advanced key data
    (R, KW))``."""
    import jax
    import jax.numpy as jnp

    def split(kd):
        k, sub = jax.random.split(jax.random.wrap_key_data(kd))
        return jax.random.key_data(k), sub

    new_kd, subs = jax.vmap(split)(key_data)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    draws, sorts = sample_needs(live, temperature, top_k, top_p)

    def drawn():
        masked = adjust_logits(logits, temperature, top_k, top_p, sorts)
        toks = jax.vmap(jax.random.categorical)(subs, masked)
        return jnp.where(temperature == 0.0, greedy,
                         toks).astype(jnp.int32)

    return jax.lax.cond(draws, drawn, lambda: greedy), new_kd


def check_sampled_draft(draftsman) -> None:
    """Refuse speculation at temperature > 0 with a draftsman that
    cannot satisfy the sampled-verify contract.

    The rejection-sampling accept test needs per-token proposal
    probabilities ``q`` (``surfaces_q = True`` on the draftsman) and a
    per-request PRNG key (seeded via ``SamplingParams.seed``) so
    sampled runs are reproducible; a draftsman without q would force
    the engine to guess the proposal distribution and silently skew
    the output distribution — fail loudly at submit instead."""
    if draftsman is None:
        return
    if not getattr(draftsman, "surfaces_q", False):
        raise SpeculativeConfigError(
            f"draftsman {type(draftsman).__name__} does not surface "
            f"per-token proposal probabilities (q): speculation at "
            f"temperature > 0 runs the rejection-sampling accept test "
            f"min(1, p/q), which needs the draftsman's q rows "
            f"(surfaces_q = True) and a per-request seed "
            f"(SamplingParams.seed) for a reproducible PRNG stream — "
            f"add q support to the draftsman or submit the request "
            f"with temperature == 0")


class SpeculativeConfigError(ValueError):
    """A speculation configuration that could never run soundly.

    Raised at :class:`~hetu_tpu.serving.engine.ServingEngine`
    construction (never mid-decode, where the failure mode would be a
    silently corrupted ``pos``): a draft depth whose verify window
    cannot fit a slot, or a draft model whose gate couples co-batched
    rows (its routing depends on which OTHER requests share the batch,
    so its drafts — and its own KV — are not a function of the request
    alone)."""

    def __init__(self, msg: str):
        super().__init__(msg)


def check_draft_depth(spec_depth: int, max_len: int) -> int:
    """Validate the engine-level draft depth against the slot budget.

    The verify lane feeds ``spec_depth + 1`` rows per slot, so a depth
    that cannot fit even an empty slot (``spec_depth + 1 > max_len``)
    would force every write past the blocks the table owns — raise the
    named error instead of letting the clamp arithmetic corrupt
    ``pos``."""
    k = int(spec_depth)
    if k < 0:
        raise SpeculativeConfigError(
            f"spec_depth must be >= 0, got {k}")
    if k and k + 1 > int(max_len):
        raise SpeculativeConfigError(
            f"spec_depth {k} would overflow a slot: the verify lane "
            f"writes {k + 1} rows per iteration but max_len is "
            f"{max_len} — lower spec_depth or raise max_len")
    return k


def check_draft_model(draft_model) -> None:
    """Refuse draft models whose routing is batch-coupled.

    A gate with ``batch_coupled = True`` (the PR 9 marker on
    Sinkhorn-style balance gates) routes each row as a function of the
    WHOLE batch, so the draft model's proposals for one request change
    with its co-batched neighbors — its KV cache is not replayable and
    its drafts are not a pure function of the request. The verify lane
    would still be correct (bad drafts just get rejected), but the
    draft cache's catch-up replay would diverge from what was drafted;
    fail loudly at construction instead."""
    seen: set[int] = set()
    stack = [draft_model]
    while stack:
        obj = stack.pop()
        if id(obj) in seen or not hasattr(obj, "__dict__"):
            continue
        seen.add(id(obj))
        if getattr(obj, "batch_coupled", False):
            raise SpeculativeConfigError(
                f"draft model uses a batch-coupled gate "
                f"({type(obj).__name__}): its routing depends on which "
                f"other requests share the batch, so its drafts are "
                f"not a function of one request — use a per-token "
                f"gate (topk/ktop1/sam) for the draft model")
        for v in vars(obj).values():
            if hasattr(v, "__dict__"):
                stack.append(v)


class NgramDraftsman:
    """Per-slot prompt-lookup drafting over the request's own tokens.

    For each slot, an incremental suffix index maps every n-gram
    (``n = ngram`` down to 1) to the position of its most recent
    occurrence. :meth:`propose` looks up the current tail n-gram
    (longest first) and drafts the tokens that followed its previous
    occurrence. Pure host bookkeeping — O(appended tokens) per
    iteration, nothing on the device."""

    #: draftsmen are proposal-only: the engine treats this flag as "no
    #: device work per iteration" (cheap enough to run under the lock)
    host_only = True

    #: proposals are deterministic (a history lookup), so the proposal
    #: distribution is a one-hot on the drafted token — the engine
    #: synthesizes that q on-device, no host work here
    surfaces_q = True

    def __init__(self, slots: int, *, ngram: int = 3):
        self.ngram = max(1, int(ngram))
        self._index: list[dict] = [dict() for _ in range(slots)]
        self._prev: list[dict] = [dict() for _ in range(slots)]
        self._seq: list[list[int]] = [[] for _ in range(slots)]

    def reset(self, slot: int, tokens: Sequence[int]) -> None:
        """(Re)bind ``slot`` to a fresh request whose history is
        ``tokens`` (the prompt at admission; prompt + emitted on a
        spill-resume)."""
        self._index[slot] = {}
        self._prev[slot] = {}
        self._seq[slot] = []
        self.extend(slot, tokens)

    def extend(self, slot: int, tokens: Sequence[int]) -> None:
        """Append committed tokens and index the new suffixes. Index
        values are the position AFTER the n-gram (where its
        continuation starts); the previous occurrence is kept too —
        the current TAIL's latest occurrence is always itself, and the
        draft is whatever followed it last time around."""
        seq = self._seq[slot]
        idx = self._index[slot]
        prev = self._prev[slot]
        for t in tokens:
            seq.append(int(t))
            end = len(seq)
            for n in range(1, self.ngram + 1):
                if end >= n:
                    key = tuple(seq[end - n:end])
                    old = idx.get(key)
                    if old is not None:
                        prev[key] = old
                    idx[key] = end

    def propose(self, slot: int, k: int) -> list[int]:
        """Up to ``k`` draft tokens continuing the slot's current tail
        (longest matching n-gram wins; an n-gram whose only occurrence
        is the tail itself proposes nothing)."""
        if k <= 0:
            return []
        seq = self._seq[slot]
        idx = self._index[slot]
        prev = self._prev[slot]
        end = len(seq)
        for n in range(min(self.ngram, end), 0, -1):
            key = tuple(seq[end - n:end])
            j = idx.get(key)
            if j == end:                 # the tail is its own latest hit
                j = prev.get(key)
            if j is not None and j < end:
                return seq[j:j + k]
        return []


class ModelDraftsman:
    """Small-model drafting with a per-slot KV cache and one jitted
    step (catch-up + k-token greedy scan), compiled once.

    The draft arena is the paged layout with ONE wide block per slot
    (identity block tables), so the masked per-cell writes ride the
    same ``row_mask`` scatter path the verify lane uses. Per slot the
    draftsman tracks ``draft_pos`` — how many committed positions its
    cache has consumed; a slot drafts only when fully caught up
    (``draft_pos == pos + 1``), so admissions and spill-resumes warm up
    over a few iterations instead of needing a draft prefill lane."""

    host_only = False

    #: sampled drafting: the chain is SAMPLED from the draft model's
    #: adjusted softmax at the request's knobs and those rows are
    #: returned as q — the rejection test's proposal distribution
    surfaces_q = True

    def __init__(self, model, params, *, slots: int, max_len: int,
                 spec_depth: int, cache_dtype=None,
                 target_vocab: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from hetu_tpu.models.generation import init_paged_caches

        check_draft_model(model)
        self.model = model
        self.params = params
        # the verify lane's p lives over the TARGET vocab; q rows must
        # match it, so draft logits past target_vocab are masked to
        # -inf before sampling (a draft model may pad its vocab)
        self.target_vocab = (int(target_vocab)
                            if target_vocab is not None else None)
        self.K = int(spec_depth)
        self.H = self.K + 1                  # catch-up window width
        self.slots = int(slots)
        # one wide block per slot, sized so the deepest speculative
        # write (pos + K - 1 <= max_len + K - 2) never clamps
        self.row_len = int(max_len) + self.K + 1
        max_pos = getattr(getattr(model, "cfg", None), "max_positions",
                          None)
        if max_pos is not None and self.row_len > max_pos:
            raise SpeculativeConfigError(
                f"draft model max_positions {max_pos} cannot address "
                f"the target's max_len {max_len} + spec_depth "
                f"{self.K} rows — use a draft model with a longer "
                f"context or lower spec_depth")
        # the draft arena lives beside the draft params, like the
        # engine's beside its params (parallel.sharding.home_sharding)
        from hetu_tpu.parallel.sharding import home_sharding
        self._rep = home_sharding(params)
        self.caches = init_paged_caches(
            model, self.slots + 1, self.row_len,
            cache_dtype if cache_dtype is not None else jnp.float32,
            sharding=self._rep)
        # identity tables: slot r owns arena block r+1 (0 = null)
        self._tables = jnp.asarray(
            np.arange(1, self.slots + 1, dtype=np.int32)[:, None])
        self.draft_pos = np.zeros(self.slots, np.int64)
        self._fn = self._build(jax, jnp)

    def _build(self, jax, jnp):
        model, K, H = self.model, self.K, self.H
        Vt = self.target_vocab

        def draft_step(params, caches, hist_tok, hist_pos, hist_len,
                       active, tables, temps, topks, topps, keys):
            from hetu_tpu.engine.train_step import record_trace
            from hetu_tpu.models import generation
            record_trace("serving_draft_step")   # 1 compile, ever
            # per-slot draft PRNG: a fold_in side-channel off the
            # slot's commit key (which advances every committed token,
            # so draft draws differ across iterations without touching
            # the commit stream the verify lane replays)
            kbase = jax.vmap(lambda kd: jax.random.fold_in(
                jax.random.wrap_key_data(kd), 0xD4AF7))(keys)

            def pick(lg_rows, j):
                """Sample draft token j from the adjusted softmax (or
                argmax at temperature 0) and return (tok, q_row)."""
                Vd = lg_rows.shape[-1]
                Vq = Vt if Vt is not None else Vd
                if Vt is not None and Vd > Vt:
                    lg_rows = jnp.where(
                        jnp.arange(Vd) < Vt, lg_rows, -jnp.inf)
                masked = adjust_logits(lg_rows, temps, topks, topps)
                g = jnp.argmax(lg_rows, axis=-1).astype(jnp.int32)
                kj = jax.vmap(lambda k: jax.random.fold_in(k, j))(kbase)
                drawn = jax.vmap(jax.random.categorical)(kj, masked)
                tok = jnp.where(temps == 0.0, g, drawn).astype(jnp.int32)
                pq = jax.nn.softmax(masked.astype(jnp.float32), axis=-1)
                if Vd > Vq:
                    pq = pq[..., :Vq]       # masked rows carry 0 there
                elif Vd < Vq:
                    pq = jnp.pad(pq, ((0, 0), (0, Vq - Vd)))
                qrow = jnp.where(
                    (temps == 0.0)[:, None],
                    jax.nn.one_hot(tok, Vq, dtype=jnp.float32), pq)
                return tok, qrow

            lane = jnp.arange(H)[None, :]
            positions = hist_pos[:, None] + lane
            valid = (lane < hist_len[:, None]) & active[:, None] \
                & (positions < self.row_len)
            logits, caches = generation.decode(
                model, params, hist_tok, positions, caches,
                slot_mask=active, block_tables=tables, row_mask=valid)
            seed_row = jnp.clip(hist_len - 1, 0, H - 1)
            lg = jnp.take_along_axis(
                logits, seed_row[:, None, None], axis=1)[:, 0]
            first, q1 = pick(lg, 0)
            base = hist_pos + hist_len            # first draft's write

            def body(carry, j):
                caches, tok, qrow = carry
                pos = (base + j)[:, None]
                # rows that consumed nothing this call have no seed —
                # their scan output is garbage and must not write
                ok = active[:, None] & (hist_len > 0)[:, None] \
                    & (pos < self.row_len)
                lg, caches = generation.decode(
                    model, params, tok[:, None], pos, caches,
                    slot_mask=active, block_tables=tables, row_mask=ok)
                nxt, qn = pick(lg[:, 0], j + 1)
                return (caches, nxt, qn), (tok, qrow)

            if K > 1:
                (caches, last, q_last), (toks, qs) = jax.lax.scan(
                    body, (caches, first, q1), jnp.arange(K - 1))
                drafts = jnp.concatenate(
                    [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
                q = jnp.concatenate(
                    [jnp.moveaxis(qs, 0, 1), q_last[:, None]], axis=1)
            else:
                drafts = first[:, None]
                q = q1[:, None]
            return caches, drafts, q           # (S, K), (S, K, Vq)

        return jax.jit(draft_step, donate_argnums=(1,),
                       out_shardings=(self._rep, None, None))

    def reset(self, slot: int, tokens: Sequence[int]) -> None:
        """A new (or resumed) request owns ``slot``: its draft KV is
        cold — catch-up restarts from position 0."""
        self.draft_pos[slot] = 0

    def extend(self, slot: int, tokens: Sequence[int]) -> None:
        """Committed tokens are consumed via catch-up, not eagerly."""

    def propose_all(self, seqs: list[Optional[Sequence[int]]],
                    pos: np.ndarray, active: np.ndarray,
                    budget: np.ndarray, *, temps=None, topks=None,
                    topps=None, keys=None):
        """One draft pass for the whole slot pool.

        ``seqs[r]`` is slot r's full committed history (prompt +
        emitted tokens, ``None`` for empty slots), ``pos[r]`` the
        target's next KV write index (history[pos] is the not-yet-fed
        last token), ``budget[r]`` the engine's per-slot depth clamp.
        ``temps``/``topks``/``topps`` are the per-slot sampling knobs
        (defaults: greedy) and ``keys`` the per-slot raw commit-key
        state ``(S, KW) uint32`` the sampled chain derives its draws
        from. Returns ``(draft_tok (S, K) int32, draft_len (S,) int32,
        q (S, K, V) device array)`` — zero length for cold (still
        catching up) or inactive slots."""
        import numpy as _np
        S, H = self.slots, self.H
        hist_tok = _np.zeros((S, H), _np.int32)
        hist_pos = _np.zeros(S, _np.int32)
        hist_len = _np.zeros(S, _np.int32)
        warm = _np.zeros(S, bool)
        for r in range(S):
            if not active[r] or seqs[r] is None:
                continue
            avail = int(pos[r]) + 1 - int(self.draft_pos[r])
            if avail <= 0:
                continue       # nothing new to consume — skip this turn
            h = min(H, avail)
            lo = int(self.draft_pos[r])
            hist_tok[r, :h] = seqs[r][lo:lo + h]
            hist_pos[r] = lo
            hist_len[r] = h
            self.draft_pos[r] = lo + h
            warm[r] = (lo + h) == int(pos[r]) + 1
        if temps is None:
            temps = _np.zeros(S, _np.float32)
        if topks is None:
            topks = _np.zeros(S, _np.int32)
        if topps is None:
            topps = _np.zeros(S, _np.float32)
        if keys is None:
            import jax
            kw = jax.random.key_data(jax.random.key(0)).shape[-1]
            keys = _np.zeros((S, kw), _np.uint32)
        self.caches, drafts, q = self._fn(
            self.params, self.caches, hist_tok, hist_pos, hist_len,
            active, self._tables,
            _np.asarray(temps, _np.float32),
            _np.asarray(topks, _np.int32),
            _np.asarray(topps, _np.float32),
            _np.asarray(keys, _np.uint32))
        drafts = _np.asarray(drafts)
        draft_len = _np.where(warm & active, budget, 0).astype(_np.int32)
        return drafts.astype(_np.int32), draft_len, q
