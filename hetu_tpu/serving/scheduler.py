"""Request lifecycle + FCFS slot scheduler for the serving engine.

Orca-style continuous batching (iteration-level scheduling, OSDI'22)
reduces, on the scheduling side, to a small amount of bookkeeping: a
FCFS queue, a free-slot list over the KV pool, and an admission gate
that answers one question — does this request's worst case
(``len(prompt) + max_tokens``) fit a slot? Everything dynamic
(admission, completion, eviction) is a host-side list operation; the
device only ever sees fixed-shape control vectors.

The scheduler is deliberately free of jax and telemetry: pure logic the
engine drives (and tests exercise without a device). Preemption is a
non-goal — admission guarantees a request admitted to a slot runs to
completion (no swapping, no recompute-on-resume), which is the right
trade for fixed-shape slots where eviction can't free partial bytes.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import uuid
from collections import deque
from typing import Optional

import numpy as np

from hetu_tpu.models.generation import PromptTooLongError


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode knobs — traced per-slot operands in the engine
    step (so changing them across requests never recompiles).

    ``priority`` is the request's QoS class: LOWER is more urgent
    (0 = interactive, 1 = standard/default, 2+ = batch). Admission is
    deficit-weighted across classes (class ``c`` gets a ``2^-c`` share
    of admissions when everything is backlogged — urgent traffic goes
    first but batch traffic never starves), and a queued request may
    PREEMPT a running strictly-lower-priority one when slots or blocks
    run dry — the victim's KV spills to the host arena and resumes
    later without re-running prefill (docs/SERVING.md)."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    eos_id: Optional[int] = None
    max_tokens: int = 16
    priority: int = 1
    #: multi-tenant serving (serving/tenancy.py): ``tenant`` names the
    #: request's QoS identity (token-bucket rate limits + slot caps at
    #: admission), ``adapter`` the tenant's LoRA adapter to decode
    #: under (None = the shared base model). Both are host-side
    #: routing/admission data — the traced step only ever sees the
    #: adapter's arena page id, so tenant churn never recompiles.
    tenant: Optional[str] = None
    adapter: Optional[str] = None
    #: per-request PRNG seed for sampled decoding (temperature > 0):
    #: the engine derives the slot's traced key stream from it, so a
    #: sampled run replays bit-for-bit — and matches one-shot
    #: ``generate(rng=jax.random.key(seed))``. None derives a stream
    #: from the engine seed + request id (reproducible per engine).
    seed: Optional[int] = None
    #: generation by diffusion over blocks (a model that states one:
    #: ``models.sdar_moe.BlockDiffusion``; refused of any other): the
    #: passes a block is unmasked over, the rule that picks what a pass
    #: unmasks (``low_confidence_static`` | ``low_confidence_dynamic``)
    #: and the dynamic rule's threshold — None: the model's defaults
    denoising_steps: Optional[int] = None
    remasking: Optional[str] = None
    confidence_threshold: Optional[float] = None


@dataclasses.dataclass
class Request:
    """One request's full lifecycle: queued → prefill → decode → done
    (or rejected at admission).

    ``trace_id`` + ``events`` make the lifecycle reconstructable after
    the fact: every phase transition appends ``(phase, ts_s, dur_s)``
    (``mark``), the engine renders them as a per-request Perfetto track,
    and :meth:`timing` folds them into the breakdown the ``RESULT``
    protocol verb returns."""

    id: int
    prompt: np.ndarray                 # (P,) int32
    sampling: SamplingParams
    submit_s: float
    status: str = "queued"
    slot: Optional[int] = None
    tokens: list = dataclasses.field(default_factory=list)
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None
    error: Optional[str] = None
    cached_tokens: int = 0             # prompt tokens served by the
    #                                    prefix cache (skipped prefill)
    cp_lane: bool = False              # admitted into the CP-prefill
    #                                    lane: worst case exceeds one
    #                                    slot's budget but fits the
    #                                    long_max_len lane — prefill
    #                                    runs cp-sharded in one pass
    #                                    instead of the packed chunk
    #                                    loop (docs/SERVING.md)
    weight_version: int = 0            # weight generation the request
    #                                    was admitted (and decoded) under
    #                                    — swaps only land on drained
    #                                    engines, so one request is one
    #                                    version, end to end
    lock_wait_s: float = 0.0           # what submit() waited for the
    #                                    engine's lock (the loop holds
    #                                    it through admit, pack, commit)
    handoff: bool = False              # prefill-tier mode (ISSUE 15):
    #                                    the engine parks the request
    #                                    after its FIRST token (status
    #                                    "prefilled", slot inactive but
    #                                    owned) instead of decoding on —
    #                                    the fleet layer evicts its KV
    #                                    and streams it to a decode-tier
    #                                    replica (docs/SERVING.md)
    admit: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)  # paged admission plan
    # -- speculation + QoS ledgers (ISSUE 11) --
    drafted: int = 0                   # draft tokens this request saw
    accepted: int = 0                  # drafts the verify lane accepted
    preemptions: int = 0               # times evicted mid-decode
    spilled_blocks: int = 0            # KV blocks copied to the host
    #                                    spill arena across preemptions
    resumed_blocks: int = 0            # KV blocks mapped back on resume
    spill: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)  # live SpillEntry while
    #                                    preempted/queued-for-resume —
    #                                    its presence is what routes
    #                                    admission through the resume
    #                                    path instead of prefill
    # -- multi-tenant adapter plane (serving/tenancy.py) --
    adapter_ref: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)  # AdapterSpec pinned at
    #                                    admission (refcount held until
    #                                    finish — the arena page cannot
    #                                    be evicted under this request)
    kv_adapter: int = 0                # adapter KV-compat uid this
    #                                    request's KV is written under
    #                                    (0 = base-compatible): tags its
    #                                    prefix-cache inserts + spills
    #                                    and filters its prefix matches
    trace_id: str = dataclasses.field(
        default_factory=lambda: uuid.uuid4().hex[:12])
    traceparent: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)  # inbound wire context
    #                                    ("<trace_id>-<span_id>", ISSUE
    #                                    16) — when set, trace_id above
    #                                    is overridden to match it so
    #                                    every process stamps the
    #                                    originating id
    events: list = dataclasses.field(default_factory=list,
                                     repr=False, compare=False)
    unmask_pass: list = dataclasses.field(
        default_factory=list, repr=False, compare=False)  # block
    #                                    diffusion: for every output
    #                                    token, the pass of its block at
    #                                    which it was unmasked (a byte a
    #                                    token: the request's account of
    #                                    quality against steps)
    chunk_iters: list = dataclasses.field(
        default_factory=list, repr=False, compare=False)  # engine
    #                                    iteration of each
    #                                    "prefill_chunk" event, in order
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False, compare=False)

    def mark(self, phase: str, dur_s: float = 0.0,
             ts_s: Optional[float] = None,
             iter: Optional[int] = None) -> None:
        """Append one lifecycle event (``ts_s`` defaults to now; the
        clock is ``time.monotonic`` — the same one ``submit_s`` uses).
        ``iter`` is the engine iteration that ran a prefill chunk: the
        request's track then names the ``serve/step`` span that caused
        it."""
        self.events.append(
            (phase, time.monotonic() if ts_s is None else ts_s,
             float(dur_s)))
        if iter is not None:
            self.chunk_iters.append(int(iter))

    def timing(self) -> dict:
        """Phase breakdown in milliseconds for the RESULT verb: queued
        (submit → admit), prefill (admit → first token), decode (first
        token → finish), total, plus per-prefill-chunk count."""
        out = {"trace_id": self.trace_id}
        admit_s = next((t for p, t, _ in self.events if p == "admit"),
                       None)
        if admit_s is not None:
            out["queued_ms"] = round((admit_s - self.submit_s) * 1e3, 3)
        if self.first_token_s is not None and admit_s is not None:
            out["prefill_ms"] = round(
                (self.first_token_s - admit_s) * 1e3, 3)
            out["ttft_ms"] = round(
                (self.first_token_s - self.submit_s) * 1e3, 3)
        if self.finish_s is not None and self.first_token_s is not None:
            out["decode_ms"] = round(
                (self.finish_s - self.first_token_s) * 1e3, 3)
        if self.finish_s is not None:
            out["total_ms"] = round(
                (self.finish_s - self.submit_s) * 1e3, 3)
        out["prefill_chunks"] = sum(
            1 for p, _, _ in self.events if p == "prefill_chunk")
        out["cached_tokens"] = self.cached_tokens
        # speculation + QoS breakdown (ISSUE 11): how many tokens the
        # draft plane proposed/landed for this request, and what the
        # scheduler did to it under pressure
        out["priority"] = self.sampling.priority
        out["drafted"] = self.drafted
        out["accepted"] = self.accepted
        out["preemptions"] = self.preemptions
        out["spilled_blocks"] = self.spilled_blocks
        out["resumed_blocks"] = self.resumed_blocks
        # the chaos-soak contract (ISSUE 18): a request recovered from
        # a buddy's replicated KV reports that it RESUMED mid-decode
        # rather than replaying the prompt — RESULT carries the proof
        out["resumed"] = any(p == "resumed" for p, _, _ in self.events)
        return out

    def result(self) -> dict:
        out = {"id": self.id, "status": self.status,
               "tokens": list(self.tokens), "error": self.error,
               "weight_version": self.weight_version,
               "timing": self.timing()}
        if self.unmask_pass:
            out["unmask_pass"] = list(self.unmask_pass)
        return out


class Scheduler:
    """FCFS admission over a fixed slot pool.

    ``max_len`` gating is the HBM-budget gate in disguise: the pool was
    sized so ``slots * max_len`` rows fit the budget
    (``engine.memory.size_kv_pool``), so "fits a slot" == "fits HBM".

    With a paged pool (``blocks=`` a BlockManager, ``block_size=``),
    admission moves from slot-count to FREE-BLOCK accounting: a request
    is admitted when a control slot is free AND its worst case fits in
    NEW blocks — where "new" is net of the prefix cache
    (``prefix_cache=``), so a full-prefix hit costs ~0 blocks and
    admits even into a nearly-full pool. When blocks run short the
    scheduler first LRU-evicts unpinned cache leaves; if still short,
    the chosen head WAITS (head-of-line within its class — a later
    cheaper request never jumps it, which is what keeps
    ``generate_many`` outputs in submission order under churn).

    **QoS (ISSUE 11)**: admission is no longer pure FCFS.
    ``SamplingParams.priority`` names the request's class (lower = more
    urgent), and the scheduler runs deficit-weighted selection across
    the classes present in the queue: every selection round each
    backlogged class earns credits proportional to its weight
    (``2^-priority`` by default, override via ``class_weights=``), the
    richest class admits its OLDEST request and pays one credit. With a
    single class this degenerates to exact FCFS (the historical
    contract, relied on by ``generate_many``'s submission-order
    guarantee); with mixed classes, urgent traffic takes a ``2^Δ``
    share of admissions over batch traffic while the credit accrual
    makes starvation impossible. A request carrying a KV spill
    (``req.spill``) is priced and admitted through the RESUME path —
    fresh blocks, no prefill, no prefix-cache interaction.
    """

    def __init__(self, slots: int, max_len: int, *, blocks=None,
                 prefix_cache=None, block_size: Optional[int] = None,
                 long_max_len: Optional[int] = None,
                 class_weights: Optional[dict] = None,
                 token_block: int = 1):
        self.slots = int(slots)
        #: positions a decode step fills at once (1: a token a step; a
        #: block-diffusion model's block): a request's last block is
        #: generated whole, so its worst case ends on a whole block
        self.token_block = int(token_block)
        self.max_len = int(max_len)
        #: CP-prefill lane budget: requests whose worst case exceeds
        #: one slot's max_len but fits here are admitted with
        #: ``cp_lane=True`` instead of rejected (engine runs their
        #: prefill as one cp-sharded pass). None = lane off (historical
        #: rejection behavior, now with a structured error).
        self.long_max_len = int(long_max_len) if long_max_len else None
        if self.long_max_len is not None \
                and self.long_max_len <= self.max_len:
            raise ValueError(
                f"long_max_len {self.long_max_len} must exceed the "
                f"per-slot max_len {self.max_len}")
        self.queue: deque[Request] = deque()
        self.free: list[int] = list(range(self.slots))
        self.blocks = blocks              # BlockManager | None (legacy)
        self.cache = prefix_cache         # PrefixCache | None
        self.block_size = int(block_size) if block_size else None
        self.evictions_total = 0          # host ledger (engine syncs
        #                                   the telemetry counter)
        self.class_weights = dict(class_weights) if class_weights else {}
        self._credit: dict[int, float] = {}   # deficit counters by class
        self.preemptions_total = 0        # host ledger by-product
        #: optional per-request admission gate (the engine's tenant
        #: QoS hook, serving/tenancy.py): ``callable(req) -> bool``.
        #: False = the request is NOT eligible this round (rate-limited
        #: tenant, slot-capped tenant, adapter arena full) — the
        #: deficit selection simply skips it, so a throttled tenant's
        #: backlog never blocks other tenants' admissions (noisy-
        #: neighbor isolation), and never burns its class's credits.
        self.admission_gate = None

    # -- admission ----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue ``req`` FCFS; False = rejected (can never fit a slot).

        Rejection carries a STRUCTURED :class:`PromptTooLongError`
        message naming the per-slot budget and — when the CP-prefill
        lane exists — its larger budget, so a caller knows which knob
        (max_len / long_max_len / max_tokens) would admit the request.
        """
        worst = self._span(len(req.prompt) + req.sampling.max_tokens)
        limit = self.long_max_len or self.max_len
        if len(req.prompt) == 0:
            req.status, req.error = "rejected", "empty prompt"
        elif worst > limit:
            err = PromptTooLongError(
                prompt_len=len(req.prompt),
                max_tokens=req.sampling.max_tokens,
                limit=self.max_len, cp_limit=self.long_max_len,
                source="serving slot",
                hint="raise long_max_len (CP-prefill lane) or trim "
                     "the prompt" if self.long_max_len is not None
                else "pass long_max_len= to enable the CP-prefill "
                     "lane for prompts beyond one slot")
            req.status, req.error = "rejected", str(err)
        elif worst > self.max_len and req.spill is None:
            # beyond one slot's budget but inside the lane: the engine
            # prefills it cp-sharded in one pass, decode is normal.
            # (A resume request never re-routes through the lane — its
            # KV already exists; admission maps it back in.)
            req.cp_lane = True
        if req.status == "rejected":
            req.done.set()
            return False
        req.mark("queued")
        self.queue.append(req)
        return True

    def _span(self, positions: int) -> int:
        """``positions`` up to the end of their last ``token_block``."""
        b = self.token_block
        return -(-positions // b) * b

    def requeue_preempted(self, req: Request) -> None:
        """Put an evicted request back at the HEAD of the queue (it was
        already admitted once — it resumes before its class peers; the
        deficit selection still decides WHEN its class runs again)."""
        req.status = "preempted"
        self.queue.appendleft(req)

    # -- QoS class selection ------------------------------------------------
    def _weight(self, c: int) -> float:
        w = self.class_weights.get(c)
        if w is None:
            return 2.0 ** (-max(int(c), 0))
        # a zero/negative override would deadlock the credit accrual —
        # clamp to a tiny share instead (≈ "only when alone")
        return max(float(w), 1e-6)

    def _eligible(self) -> list:
        """The queue minus requests the admission gate defers (tenant
        rate limits / slot caps / adapter waits) — the population the
        deficit selection runs over this round."""
        if self.admission_gate is None:
            return list(self.queue)
        return [r for r in self.queue if self.admission_gate(r)]

    def _select_class(self, queue=None) -> tuple[
            Optional[int], Optional[dict]]:
        """Deficit-weighted pick among classes present in the queue
        (pure — commits nothing). Every backlogged class earns its
        weight per round until one can afford an admission (credit
        >= 1); richest wins, urgency breaks ties. Returns
        ``(class, credits-after-accrual)``."""
        if queue is None:
            queue = self.queue
        present = {r.sampling.priority for r in queue}
        if not present:
            return None, None
        eff = {c: self._credit.get(c, 0.0) for c in present}
        while max(eff.values()) < 1.0:
            for c in eff:
                eff[c] += self._weight(c)
        win = min(present, key=lambda c: (-eff[c], c))
        return win, eff

    def peek_candidate(self) -> Optional[Request]:
        """The request :meth:`next_admission` would try next (oldest of
        the deficit-selected class) — the engine's preemption planner
        asks this to decide whether a blocked urgent request justifies
        evicting a running batch one."""
        eligible = self._eligible()
        win, _ = self._select_class(eligible)
        if win is None:
            return None
        return next(r for r in eligible
                    if r.sampling.priority == win)

    def blocks_needed(self, req: Request) -> int:
        """Worst-case NEW blocks ``req`` needs (gross of prefix
        sharing — the preemption planner's conservative bound).
        Handoff requests decode elsewhere: a prefill-tier replica only
        ever writes the prompt + the first token before releasing the
        reservation, so price P+1 instead of P+max_tokens."""
        if self.blocks is None:
            return 0          # no arena: a free slot is the whole price
        bs = self.block_size or self.max_len
        tail = 1 if req.handoff else req.sampling.max_tokens
        return -(-self._span(len(req.prompt) + tail) // bs)

    def preemption_victim(self, candidate: Request,
                          running) -> Optional[int]:
        """Pick the slot to evict for ``candidate``: among running
        requests with STRICTLY lower priority (higher class number),
        the lowest-priority one, least-progressed first (fewest decoded
        tokens = fewest spilled bytes = least wasted work if it never
        resumes). ``running`` is ``[(slot, Request), ...]``; None = no
        eligible victim (equal-or-higher-priority work never preempts,
        so uniform-priority traffic keeps the historical run-to-
        completion guarantee)."""
        pc = candidate.sampling.priority
        victims = [(s, r) for s, r in running
                   if r.sampling.priority > pc]
        if not victims:
            return None
        slot, _ = max(victims, key=lambda sr: (
            sr[1].sampling.priority, -len(sr[1].tokens), sr[0]))
        return slot

    def next_admission(self) -> Optional[tuple[Request, int]]:
        """Pop the deficit-selected class's oldest request into a free
        slot, or None (no queue, no slot, or — paged — not enough free
        blocks even after cache eviction: the chosen head waits).

        Paged pools attach the admission plan as ``req.admit``:
        ``{"table": [block ids], "first_uncached": int,
        "cow": (src, dst) | None}`` — blocks already allocated/shared,
        so the engine only maps them into control vectors. A request
        carrying a KV spill instead gets
        ``{"table": ..., "resume": True, ...}``: all-fresh blocks the
        engine refills from the host arena (no prefill lane work)."""
        if not self.queue or not self.free:
            return None
        eligible = self._eligible()
        win, eff = self._select_class(eligible)
        if win is None:
            return None
        req = next(r for r in eligible
                   if r.sampling.priority == win)
        plan = None
        if self.blocks is not None:
            plan = self._resume_plan(req) if req.spill is not None \
                else self._page_plan(req)
            if plan is None:
                return None
        # commit the deficit round only on a real admission (a blocked
        # head must not burn its class's credits while it waits)
        self._credit = eff
        self._credit[win] -= 1.0
        self.queue.remove(req)
        slot = self.free.pop(0)
        req.slot = slot
        req.status = "resuming" if req.spill is not None else "prefill"
        req.admit = plan
        req.mark("admit")
        return req, slot

    def _resume_plan(self, req: Request) -> Optional[dict]:
        """Price a spill-resume: the full worst case in FRESH blocks
        (no prefix sharing — the spilled bytes are this request's own
        history and flow back from the host arena), evicting cache
        leaves if the free list is short. None = cannot fit yet."""
        total = self.blocks_needed(req)
        if total > self.blocks.free_blocks and self.cache is not None:
            self.evictions_total += self.cache.evict(
                total - self.blocks.free_blocks)
        if total > self.blocks.free_blocks:
            return None
        fresh = [self.blocks.alloc() for _ in range(total)]
        req.cached_tokens = 0
        return {"table": fresh, "first_uncached": 0, "cow": None,
                "resume": True}

    def _page_plan(self, req: Request) -> Optional[dict]:
        """Price ``req`` in blocks net of the prefix cache, evicting
        LRU cache leaves if the free list is short; None = cannot fit
        yet. On success every table block is live (shared or freshly
        allocated) and charged to this request."""
        bs = self.block_size
        P = len(req.prompt)
        # handoff requests never decode here: the prefill tier writes
        # the prompt + first token, ships the KV, and releases the
        # blocks — reserving max_tokens of decode room would only
        # throttle this tier's admission for space it never uses
        tail = 1 if req.handoff else req.sampling.max_tokens
        total = -(-self._span(P + tail) // bs)            # worst case
        shared: list[int] = []
        partial = None
        # CP-lane requests skip the prefix cache: their prefill is one
        # cp-sharded pass over the WHOLE prompt (a partial-skip offset
        # would re-shape the lane's bucketed executable), and they do
        # not insert on completion either — long-prompt prefix sharing
        # is future work (docs/SERVING.md)
        if self.cache is not None and not req.cp_lane:
            shared, partial = self.cache.match(req.prompt.tolist(),
                                               adapter=req.kv_adapter)
            shared = shared[:total]
        matched = len(shared) * bs + (partial[1] if partial else 0)
        # a FULL-prompt hit still recomputes the last token (its logits
        # seed decoding); the rewrite of position P-1 into a possibly
        # shared block is benign — same tokens, same values
        first_uncached = min(matched, P - 1)
        if partial is not None and first_uncached <= len(shared) * bs:
            partial = None                 # tail match buys nothing
            first_uncached = min(len(shared) * bs, P - 1)
        n_new = total - len(shared)        # incl. the CoW destination
        # pin the matched path BEFORE evicting: evict() reclaims any
        # refcount-1 trie leaf, and peeling a cached chain tail-first
        # can reach the very blocks we just matched — unpinned, they
        # would be freed (and possibly re-allocated) out from under
        # this request's table
        pins = list(shared)
        if partial is not None:
            pins.append(partial[0])
        for b in pins:
            self.blocks.share(b)
        if n_new > self.blocks.free_blocks and self.cache is not None:
            self.evictions_total += self.cache.evict(
                n_new - self.blocks.free_blocks)
        if n_new > self.blocks.free_blocks:
            for b in pins:                 # unwind; the trie ref remains
                self.blocks.release(b)
            return None
        fresh = [self.blocks.alloc() for _ in range(n_new)]
        if partial is not None:
            # the src pin only guarded eviction: the table never maps
            # the src (the engine copies it into fresh[0] this step)
            self.blocks.release(partial[0])
        table = shared + fresh
        cow = (partial[0], fresh[0]) if partial is not None else None
        req.cached_tokens = first_uncached
        return {"table": table, "first_uncached": first_uncached,
                "cow": cow}

    def release(self, slot: int, table=None) -> None:
        """Return a slot (and, paged, every block its table maps —
        shared blocks just drop a holder; blocks the prefix cache
        adopted at insert stay cached)."""
        self.free.append(slot)
        if self.blocks is not None and table is not None:
            for b in table:
                if b:
                    self.blocks.release(int(b))

    # -- introspection ------------------------------------------------------
    @property
    def depth(self) -> int:
        return len(self.queue)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self.free) / self.slots
