"""Continuous-batching inference engine: one jit, any request churn.

The static-batch ``models.generation.generate`` compiles one program per
(batch, prompt length) — admitting a request means retracing, the exact
control-plane tax PR 2 spent a subsystem killing on the training side.
This engine is the serving-plane answer, built from the techniques that
turn a decode loop into a serving engine, mapped onto TPU idioms:

- **iteration-level scheduling** (Orca, OSDI'22): the unit of work is
  ONE engine iteration — one decode token for every active slot plus a
  fixed token budget of prefill — so new requests join and finished
  ones leave between iterations, never mid-batch;
- **block-paged KV** (vLLM's PagedAttention, SOSP'23): requests live in
  a ``(layers, n_blocks, block_size, hkv*d)`` arena indexed through
  per-slot BLOCK TABLES (:class:`~hetu_tpu.serving.kv_pool.KVPool`),
  so bytes are allocated per block, not per worst-case slot;
- **radix-tree prefix caching** (SGLang's RadixAttention): admission
  maps a cached prompt prefix's blocks into the new slot's table
  (refcounted, CoW for a partial tail block —
  :mod:`~hetu_tpu.serving.prefix_cache`) and prefill starts at the
  first uncached token — a fleet-wide system prompt is prefilled once;
- **packed multi-request prefill**: the prefill lane carries a fixed
  ``prefill_chunk``-token budget PACKED from every admitting request
  (cu_seqlens-style per-token slot/position operands), so a burst of
  arrivals shares each iteration's prefill bandwidth instead of
  serializing one admission per iteration — TTFT p99 stops growing
  linearly with queue depth;
- **CP-sharded long-prompt prefill** (``long_max_len=``, the shape
  plane's serving half): prompts whose worst case exceeds one slot's
  ``max_len`` budget stop being rejected — they admit into a
  wide-block-table slot and prefill as ONE training-mode forward
  (ring/ulysses over the plan's cp axis when ``cp > 1``,
  ``StackedBlocks.prefill``) whose per-layer KV scatters straight into
  the paged arena; decode then rides the normal fused step. Lane
  prompt lengths snap to a geometric bucket ladder, so the lane owns
  at most ``n_buckets`` executables
  (``record_trace("serving_cp_prefill")``) while the fused step keeps
  its single compile;
- **speculative decoding** (``spec_depth=k``, Leviathan et al.): the
  decode lane becomes a VERIFY lane — each active slot feeds its last
  token plus up to k drafted tokens as ``k+1`` q rows spanning
  positions ``pos..pos+k`` (the per-row causal offsets
  ``attention_reference(q_offset=array)`` already speaks), so one
  forward checks k guesses and commits every leading match plus one
  bonus token. Draft tokens and per-slot depths are DATA (the step
  compiles once for any draft mix, including depth 0 = classic
  decode); accepted tokens are ordinary paged writes, rejected
  suffixes just rewind ``pos`` (blocks are refcounted, nothing is
  zeroed — the stale rows are overwritten before anything can attend
  them). Drafts come from :mod:`~hetu_tpu.serving.speculative`: the
  self-drafting n-gram/prompt-lookup index by default, or a small
  model from the zoo (``draft_model=``). Greedy output is
  token-identical to non-speculative decode for EVERY
  acceptance/rejection pattern — a draftsman can only cost speed;
- **QoS + resumable preemption**: ``SamplingParams.priority`` classes
  with deficit-weighted admission (``Scheduler``), and when slots or
  blocks run dry an urgent arrival PREEMPTS a strictly-lower-priority
  running request — its KV blocks spill to a host arena
  (:class:`~hetu_tpu.serving.kv_pool.HostSpillArena`, a table edit
  plus one device→host gather), and resume maps them back into fresh
  blocks with ZERO prefill-lane work. The router's death-requeue and
  the weight publisher's drains ride the same spill entries
  (``Router``/``WeightPublisher``), so a killed replica's mid-decode
  requests resume on peers instead of re-prefilling.

The fused step is jitted once: CoW block copies, the all-slot decode
(per-row KV writes + per-row causal offsets —
``ParallelAttention._decode``'s paged slot mode) and the packed prefill
lane run in the same program, with per-slot ``SamplingParams``, block
tables, pack layouts and prefix offsets all as traced operands — DATA,
never shapes. Request churn, cache hits and evictions therefore never
recompile — audited with the PR 2 ``record_trace`` counter
(``trace_counts()["serving_step"]`` stays at its initial compile count,
asserted in ``tests/test_serving.py`` / ``tests/test_paged_serving.py``).

TP-sharded serving rides the existing ``Strategy``/``make_plan`` path:
pass ``plan=`` and the step traces under ``plan.act`` against sharded
params, exactly like ``generate`` under a tp mesh. The arena and the
control state the step returns live beside the params from
construction (``__init__``: ``_rep`` / ``_arena_sh``), so committed or
mesh-typed params — a plan's, a checkpoint's, a ``Trainer``'s handed
over as they are — cost no second compile.
"""

from __future__ import annotations

import contextlib
import threading
import time
import types
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu import telemetry
from hetu_tpu.engine.train_step import record_trace
from hetu_tpu.models import generation
from hetu_tpu.serving import block_diffusion
from hetu_tpu.serving.block_diffusion import (
    REMASKING, denoise_slots, first_block, lane_rows,
)
from hetu_tpu.serving.kv_pool import (
    BlockManager, HostSpillArena, KVPool, NoBlocks, SpillEntry,
)
from hetu_tpu.serving.prefix_cache import PrefixCache
from hetu_tpu.serving.scheduler import Request, SamplingParams, Scheduler
from hetu_tpu.serving.speculative import (
    ModelDraftsman, NgramDraftsman, check_draft_depth,
    check_sampled_draft, sample_needs, sample_path, sample_rows,
    verify_slots,
)
from hetu_tpu.serving.step_io import PackedFields
from hetu_tpu.serving.tenancy import AdapterArenaFull
from hetu_tpu.telemetry.flight import HangWatchdog, flight_record
from hetu_tpu.telemetry.slo import SLOEngine, default_serving_rules
from hetu_tpu.telemetry.spans import REQ_TRACK_BASE  # noqa: F401 — re-export


def _bind_metrics(reg) -> types.SimpleNamespace:
    """The engine's metric handles, taken from the registry ONCE (the
    help strings live here): the loop calls ``.inc/.set/.observe`` on
    them and never does a locked get-or-create per token or per
    iteration. ``MetricRegistry.clear()`` keeps the metric objects, so
    the handles stay live across ``telemetry.reset()``."""
    return types.SimpleNamespace(
        tokens=reg.counter(
            "serving_tokens_total",
            "serving tokens by kind"),
        requests=reg.counter(
            "serving_requests_total",
            "serving requests by outcome"),
        ttft=reg.histogram(
            "serving_ttft_seconds",
            "time submit -> first token"),
        tpot=reg.histogram(
            "serving_tpot_seconds",
            "per-output-token time after the first"),
        step_seconds=reg.histogram(
            "serving_step_seconds",
            "one fused engine iteration"),
        slot_steps=reg.counter(
            "serving_decode_slot_steps_total",
            "slot×iteration decode opportunities (each active slot in "
            "each fused step counts once); 1 + accepted/this is the "
            "mean tokens committed per slot-step — the speculation "
            "win, 1.0 without drafts"),
        attn_kernel=reg.counter(
            "serving_attn_kernel_total",
            "fused decode/verify steps by attention path (paged = "
            "Pallas block-table kernel, reference = XLA gather, none = "
            "a step without attention: no layer keeps token rows)"),
        prefill_kernel=reg.counter(
            "prefill_attn_kernel_total",
            "prefill-lane executions by attention path (flash = "
            "packed/CP flash lane, reference = per-token gather math)"),
        hist_tiles=reg.counter(
            "serving_prefill_hist_tiles_total",
            "tiles of the flash prefill lane's history read by state "
            "(live = a run with resident history, one pass over its "
            "pages per tile; empty = a run without: its tiles stream "
            "and compute nothing)"),
        hist_rows=reg.counter(
            "serving_prefill_hist_rows_total",
            "pack tokens in live history tiles (over the live tiles: "
            "rows that share one pass over a request's pages)"),
        hist_chunks=reg.counter(
            "serving_prefill_hist_chunks_total",
            "grid steps of ONE call of the history read by state and "
            "by the kind of layer that makes it (layer = full: no "
            "window; window: the model's smallest, where it has one). "
            "A step is a live tile's key tile of history_tile_pages "
            "pages: live = it computes, dead = the tile walks it only "
            "because another tile's cap is deeper (the grid's second "
            "bound is the deepest cap) or its window starts above it"),
        hist_tile_keys=reg.counter(
            "serving_prefill_hist_tile_keys_total",
            "key positions in the history read's live steps by state "
            "and layer kind: live = the keys some row of the tile may "
            "see, masked = the rest of the chunk its cap ends in and "
            "of the chunk its window starts in (what joining the "
            "pages costs)"),
        decode_chunks=reg.counter(
            "serving_decode_chunks_total",
            "(slot, table chunk) pairs of the decode rows' paged call "
            "by state, per iteration that decodes, as a full-attention "
            "layer sees them (live = a pair of the call's work list: "
            "an active slot's chunk at or below its last row; skipped "
            "= the rest of slots x chunks: freed and prefilling slots, "
            "chunks above a context — no grid step)"),
        decode_tile_keys=reg.counter(
            "serving_decode_tile_keys_total",
            "key positions in the live pairs' tiles by state (the "
            "paged call scores a chunk's span of keys per head as one "
            "tile): live = the keys an active slot's rows see, masked "
            "= the rest of its last chunk, above its context"),
        sample_path=reg.counter(
            "serving_sample_path_total",
            "sampler executions of the fused step by lane (decode, "
            "prefill) and by the path its LIVE rows' knobs selected, "
            "once per iteration the lane runs (greedy = argmax alone: "
            "no live row has a temperature; draw = scaled logits, "
            "softmax and categorical draws; sort = draw plus the "
            "top-k / top-p sort, because some live sampling row masks)"),
        transfers=reg.counter(
            "serving_step_transfers_total",
            "host<->device transfers the serving loop issued, by "
            "direction (up = host to device, down = device to host). "
            "The common iteration: down 1 — the fused step's packed "
            "result vector — and up one per host array of the step's "
            "operands (pf, cow, spec: jit uploads each by itself); "
            "rewritten control state (nine up), a spill, a resume, a "
            "CP-lane prefill and a device draftsman add their own"),
        draft=reg.counter(
            "serving_draft_tokens_total",
            "draft tokens proposed to the verify lane"),
        accepted=reg.counter(
            "serving_accepted_tokens_total",
            "draft tokens the verify lane accepted (committed without "
            "their own decode iteration)"),
        sampled_accepted=reg.counter(
            "serving_sampled_accepted_tokens_total",
            "draft tokens accepted by the rejection-sampling verify "
            "lane (temperature > 0 slots)"),
        resample=reg.counter(
            "serving_resample_tokens_total",
            "tokens drawn from the rejection-sampling residual after "
            "a draft was rejected (sampled speculation)"),
        acceptance=reg.histogram(
            "serving_draft_acceptance_ratio",
            "per-request accepted/drafted ratio at finish (the "
            "speculation win tracks this), split by verify path "
            "(greedy match vs rejection sampling)"),
        prefix_hit=reg.counter(
            "serving_prefix_hit_tokens_total",
            "prompt tokens served from the prefix cache (prefill "
            "skipped)"),
        prefix_miss=reg.counter(
            "serving_prefix_miss_tokens_total",
            "prompt tokens that had to be prefilled"),
        evictions=reg.counter(
            "serving_block_evictions_total",
            "prefix-cache blocks LRU-evicted to refill the free list"),
        cp_requests=reg.counter(
            "serving_cp_prefill_requests_total",
            "long prompts prefilled through the CP lane (one cp- "
            "sharded pass instead of rejection)"),
        cp_tokens=reg.counter(
            "serving_cp_prefill_tokens_total",
            "prompt tokens prefilled through the CP lane"),
        spilled=reg.counter(
            "serving_kv_spilled_blocks_total",
            "KV blocks copied device→host when a request was "
            "preempted (resumable eviction)"),
        resumed=reg.counter(
            "serving_kv_resumed_blocks_total",
            "spilled KV blocks mapped back into fresh arena blocks on "
            "resume (prefill skipped entirely)"),
        preemptions=reg.counter(
            "serving_preemptions_total",
            "running requests evicted for more-urgent arrivals, by "
            "the VICTIM's priority class"),
        queue_depth=reg.gauge(
            "serving_queue_depth",
            "requests waiting for a slot"),
        occupancy=reg.gauge(
            "serving_slot_occupancy",
            "fraction of KV-pool slots in use"),
        kv_in_use=reg.gauge(
            "serving_kv_blocks_in_use",
            "live KV blocks (slot tables + prefix cache)"),
        slots=reg.gauge(
            "serving_slots",
            "slots by state (live = holding a request, free): what an "
            "engine without an arena admits by — a slot's recurrent "
            "state is the whole price of a request there"),
        spill_arena=reg.gauge(
            "serving_kv_spill_arena_blocks",
            "KV blocks parked in the host spill arena (preempted "
            "requests awaiting resume)"),
        spill_tiers=reg.gauge(
            "spill_tier_blocks",
            "KV blocks parked per spill tier (host arena, peer tier, "
            "buddy replica store) — the tier chain of ISSUE 18"),
        adapter_pages=reg.gauge(
            "adapter_pages_in_use",
            "adapter arena pages holding a resident adapter"),
        kv_row=reg.gauge(
            "kv_row_bytes",
            "bytes one token holds in one layer of the KV arena, by "
            "kind: stored = the arena leaves' rows as allocated, needed "
            "= what the model's attention has to keep (K and V of every "
            "kv head; ONE latent row under MLA — a row padded to a lane "
            "tile stores more than it needs)"),
        kv_state=reg.gauge(
            "kv_state_bytes",
            "bytes of recurrent state one SLOT holds beside the arena "
            "(kind=slot: all the layers that keep one), whatever its "
            "context — 0 series on a model that keeps none"),
        window_dead=reg.gauge(
            "kv_window_dead_blocks",
            "blocks held by decoding slots that lie wholly below the "
            "attention window: no query of a window layer can see them, "
            "and the uniform arena holds their rows for every layer"),
    )


def _bind_diffusion_metrics(reg) -> dict:
    """The block lane's handles (an engine whose model generates by
    diffusion over blocks binds them beside the rest)."""
    return dict(
        diff_passes=reg.counter(
            "serving_diffusion_passes_total",
            "slot-passes of the block lane by kind (denoise = a block "
            "with a masked position ran and some were unmasked — "
            "every pass is one, also the pass that carries the block "
            "finished before it; commit = a finished block ran once "
            "more for its K/V alone: retired, never incremented, kept "
            "for the readers that divide by the sum of the two)"),
        diff_blocks=reg.counter(
            "serving_diffusion_blocks_total",
            "blocks the block lane finished and handed on"),
        diff_carried=reg.counter(
            "serving_diffusion_carried_blocks_total",
            "finished blocks whose clean K/V were written inside the "
            "next block's first pass (every block but a request's "
            "last, whose K/V nobody reads)"),
        diff_tokens=reg.counter(
            "serving_diffusion_tokens_total",
            "tokens the committed blocks handed their requests (a "
            "first block's prompt tail and what lies beyond "
            "max_tokens or a stop id are not counted)"),
        diff_per_block=reg.histogram(
            "serving_diffusion_passes_per_block",
            "passes a finished block took (at most denoising_steps: "
            "its K/V are committed inside the next block's first)"))


#: the block lane's state a slot that the fused step takes and returns,
#: in the order ``block_diffusion.denoise_slots`` returns it
_BLK_STATE = ("blk_tok", "blk_masked", "blk_pass", "blk_prev", "blk_carry")

#: iterations between two refreshes of the spill-tier, replica-store and
#: adapter-page gauges when no submit or auxiliary job sets them sooner
_TIER_GAUGES_EVERY = 32


class _TimedLock:
    """``with`` on the engine's lock that adds what the ACQUIRE took to
    ``waited_s`` (a ``perf_counter`` pair; the hold time is the
    enclosing span's). One per engine, entered by the thread that holds
    the iteration lock only, which zeroes ``waited_s`` at a step's
    start."""

    __slots__ = ("_lock", "waited_s")

    def __init__(self, lock):
        self._lock = lock
        self.waited_s = 0.0

    def __enter__(self):
        t = time.perf_counter()
        self._lock.acquire()
        self.waited_s += time.perf_counter() - t

    def __exit__(self, *exc):
        self._lock.release()


class ServingEngine:
    """Slot-pooled continuous-batching engine over one model + params.

    Offline: :meth:`generate_many`. Online: :meth:`submit` +
    :meth:`result` with the :meth:`start` background loop (the
    ``rpc/py_server.py`` front end drives exactly that pair).
    """

    def __init__(self, model, params, *, slots: Optional[int] = None,
                 max_len: int = 256, prefill_chunk: int = 16,
                 cache_dtype=jnp.float32,
                 hbm_budget_bytes: Optional[float] = None,
                 block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 long_max_len: Optional[int] = None,
                 spec_depth: int = 0, draft: str = "ngram",
                 draft_ngram: int = 3,
                 draft_model=None, draft_params=None,
                 preempt: Optional[bool] = None,
                 spill_host_budget_bytes: Optional[float] = None,
                 spill_peer=None,
                 class_weights: Optional[dict] = None,
                 attn_kernel: str = "auto",
                 prefill_attn: str = "auto",
                 w8a8="off",
                 plan=None, seed: int = 0,
                 counter_sample_every: int = 32,
                 watchdog: bool = False, watchdog_factor: float = 8.0,
                 watchdog_min_timeout_s: float = 30.0,
                 slo: Union[bool, SLOEngine, None] = None,
                 slo_every_s: float = 1.0,
                 tenancy=None):
        # -- multi-tenant adapter plane (serving/tenancy.py):
        # tenancy=True mounts a default TenantPlane; pass a configured
        # one for custom arena size / rank / QoS policies. None is the
        # historical single-tenant engine, bit for bit.
        if tenancy is True:
            from hetu_tpu.serving.tenancy import TenantPlane
            tenancy = TenantPlane()
        self.tenancy = tenancy or None
        if block_size is None:
            # default paging: 16-token blocks when they divide max_len,
            # else one block per slot (degenerate = PR 5 slot arena)
            block_size = 16 if max_len % 16 == 0 else max_len
        # CP-prefill lane (long_max_len): prompts whose worst case
        # exceeds one slot's max_len budget stop being rejected — they
        # admit into a wide-table slot and their prefill runs as ONE
        # training-mode forward (ring/ulysses over the plan's cp axis
        # when cp > 1) whose per-layer KV scatters straight into the
        # paged arena; decode then proceeds in the normal fused step.
        # The lane's prompt lengths snap to a small geometric bucket
        # ladder so its executable count is bounded (the
        # record_trace("serving_cp_prefill") audit: <= n lane buckets).
        if model.blocks.block.attn.latent:
            # a latent arena (one leaf a token): what needs per-head
            # (K, V) refuses here, by name — the int8 arena does in
            # generation.init_kv_caches
            from hetu_tpu.nn.parallel import LatentKVNotSupported
            for what, on in (("long_max_len (the CP-prefill lane)",
                              long_max_len is not None),
                             ("draft_model", draft_model is not None),
                             ("w8a8", w8a8 not in (None, False, "off")),
                             ("tenancy (LoRA)", bool(tenancy)),
                             ("a tp plan", plan is not None
                              and plan.strategy.tp > 1)):
                if on:
                    raise LatentKVNotSupported(
                        f"{what} is not available over a latent KV "
                        f"arena")
        # a model that keeps a recurrent state per slot beside the
        # arena (``model.blocks.slot_state``): what assumes that a
        # request's cache is token rows in pages alone refuses here, by
        # name (every other model's blocks refuse nothing).
        # ``prefix_cache`` / ``preempt`` left at None are on for every
        # other model and off for such a one; asked for, they refuse
        # like the rest
        self._slot_state = model.blocks.slot_state
        # ... and one whose layers ALL do has no arena: no page, no
        # table, no block ledger; a free slot is the whole price of a
        # request and ``max_len`` bounds positions only. Read from what
        # the stack's leaves say (``blocks.paged``), never from a name
        self._paged = getattr(model.blocks, "paged", True)
        # a model that generates by diffusion over blocks states it
        # (``model.generation``); the decode lane takes its shape from
        # that: B rows a slot, denoised in place and committed together
        # (serving/block_diffusion.py). What assumes a token a slot and
        # step refuses, by name; ``prefix_cache`` / ``preempt`` left at
        # None are off
        self._gen = getattr(model, "generation", None)
        if self._gen is not None:
            block_diffusion.refuse(**{
                "spec_depth (the verify lane)": bool(spec_depth),
                "draft_model": draft_model is not None,
                "prefix_cache (a hit may end inside a block)":
                    bool(prefix_cache),
                "preempt (a slot spilled mid-block)": bool(preempt),
                "spill_host_budget_bytes (the spill arena)":
                    spill_host_budget_bytes is not None,
                "long_max_len (the CP-prefill lane)":
                    long_max_len is not None,
                "cache_dtype=int8 (the int8 arena)":
                    cache_dtype == jnp.int8,
                "w8a8": w8a8 not in (None, False, "off"),
                "tenancy (LoRA)": bool(tenancy),
                "a tp plan": plan is not None and plan.strategy.tp > 1})
            B = self._gen.block_length
            if max_len % B or prefill_chunk % B or block_size % B:
                raise ValueError(
                    f"max_len {max_len}, prefill_chunk {prefill_chunk} "
                    f"and block_size {block_size} must hold whole "
                    f"blocks of {B} positions")
            prefix_cache, preempt = False, False
        model.blocks.refuse_serving(**{
            "prefix_cache": bool(prefix_cache),
            "preempt (preemption and spill)": bool(preempt),
            "spill_host_budget_bytes (the spill arena)":
                spill_host_budget_bytes is not None,
            "long_max_len (the CP-prefill lane)":
                long_max_len is not None,
            "spec_depth (the verify lane)": bool(spec_depth),
            "draft_model": draft_model is not None,
            "cache_dtype=int8 (the int8 arena)":
                cache_dtype == jnp.int8,
            "w8a8": w8a8 not in (None, False, "off"),
            "tenancy (LoRA)": bool(tenancy),
            "a tp plan": plan is not None and plan.strategy.tp > 1})
        if prefix_cache is None:
            prefix_cache = not self._slot_state
        if preempt is None:
            preempt = not self._slot_state
        self._cp = plan.strategy.cp if plan is not None else 1
        self._cp_zigzag = (
            plan is not None and self._cp > 1
            and plan.strategy.effective_cp_layout == "zigzag")
        self._cp_buckets = None
        if long_max_len is not None:
            long_max_len = int(long_max_len)
            mult = (2 * self._cp) if self._cp_zigzag \
                else max(self._cp, 1)
            if long_max_len % mult != 0:
                raise ValueError(
                    f"long_max_len {long_max_len} must be a multiple "
                    f"of {mult} (cp sharding alignment: cp={self._cp}, "
                    f"{'zigzag' if self._cp_zigzag else 'contiguous'})")
            from hetu_tpu.data.bucket import SeqLenBuckets
            start = -(-int(max_len) // mult) * mult
            sizes = []
            v = max(start, mult)
            while v < long_max_len:
                sizes.append(v)
                v *= 2
            sizes.append(long_max_len)
            self._cp_buckets = SeqLenBuckets(sizes=sizes,
                                             multiple_of=mult)
        # One home for what the compiled steps hand back and take again
        # (the arena; pos/last_tok/key): beside the params, on their
        # mesh (a plan=, or the Trainer's params on a train→serve
        # handoff) or their one device — parallel.sharding.home_sharding
        # says why anything else costs extra compiles of the "one"
        # step. The arena is born there, control uploads go there, and
        # the steps pin what they return to the same. Under a tp plan
        # the arena's minor dim splits over the tp axis where kv heads
        # divide it (what engine/memory prices per device and the tp
        # paged kernel's shard_map reads).
        from hetu_tpu.parallel.sharding import home_sharding
        self._rep = self._arena_sh = home_sharding(
            params, plan.mesh if plan is not None else None)
        if plan is not None and isinstance(plan.act.tp, str):
            n_tp = plan.mesh.shape.get(plan.act.tp, 1)
            if n_tp > 1 and \
                    model.blocks.block.attn.num_kv_heads % n_tp == 0:
                from jax.sharding import NamedSharding, PartitionSpec
                self._arena_sh = NamedSharding(plan.mesh, PartitionSpec(
                    None, None, None, plan.act.tp))
        if slots is None:
            if hbm_budget_bytes is None:
                raise ValueError("pass slots= or hbm_budget_bytes=")
            if not self._paged:
                raise ValueError(
                    "hbm_budget_bytes= sizes an arena of pages; a model "
                    "whose layers keep no token rows is sized in slots: "
                    "pass slots= (kv_state_bytes{kind=slot} a slot)")
            if kv_blocks is not None:
                raise ValueError(
                    "kv_blocks= conflicts with hbm_budget_bytes= "
                    "sizing (the budget already fixes the arena) — "
                    "pass slots= alongside kv_blocks=")
            if self.tenancy is not None:
                # the adapter arena lives in the same HBM budget the
                # KV arena is sized from — price it FIRST so the
                # admission arithmetic stays honest (engine/memory
                # ledger, like the CP-prefill activation check below)
                from hetu_tpu.engine.memory import size_adapter_arena
                arena = size_adapter_arena(
                    model.cfg, r=self.tenancy.r,
                    max_adapters=self.tenancy.max_adapters)
                if arena >= 0.5 * hbm_budget_bytes:
                    raise ValueError(
                        f"adapter arena ({self.tenancy.max_adapters} "
                        f"pages x rank {self.tenancy.r}) needs "
                        f"~{arena / 1e9:.2f}GB — more than half the "
                        f"{hbm_budget_bytes / 1e9:.2f}GB HBM budget; "
                        f"shrink max_adapters / the arena rank, or "
                        f"raise the budget")
                hbm_budget_bytes = hbm_budget_bytes - arena
            tp = plan.strategy.tp if plan is not None else 1
            self.pool = KVPool.sized_for(
                model, hbm_budget_bytes=hbm_budget_bytes,
                max_len=max_len, cache_dtype=cache_dtype, tp=tp,
                block_size=block_size, table_len=long_max_len,
                sharding=self._arena_sh)
            if long_max_len is not None:
                # admission-gate honesty: the lane's one-pass prefill
                # carries real activation bytes the slot arithmetic
                # never priced — the ledger must confirm they fit in
                # the budget's headroom next to the arena
                from hetu_tpu.engine.memory import cp_prefill_act_bytes
                act = cp_prefill_act_bytes(model.cfg,
                                           seq_len=long_max_len,
                                           cp=self._cp)
                if act > 0.1 * hbm_budget_bytes:
                    raise ValueError(
                        f"CP-prefill activations at long_max_len="
                        f"{long_max_len} need ~{act / 1e9:.2f}GB — more "
                        f"than the {0.1 * hbm_budget_bytes / 1e9:.2f}GB "
                        f"headroom the {hbm_budget_bytes / 1e9:.2f}GB "
                        f"budget leaves next to the KV arena; raise cp, "
                        f"shrink long_max_len, or raise the budget")
        else:
            # kv_blocks decouples CONCURRENCY from worst-case memory:
            # slots is how many requests decode in parallel (cheap —
            # control vectors + table rows), kv_blocks is the arena's
            # actual byte budget. Oversubscribed slots (slots *
            # blocks_per_slot > kv_blocks - 1) are the PagedAttention
            # win: short requests reserve only their own ceil((P +
            # max_tokens)/block_size) blocks, so the same bytes that
            # held S worst-case slots run more than S live requests —
            # admission's free-block gate keeps it sound.
            self.pool = KVPool(model, slots, max_len, cache_dtype,
                               block_size=block_size, n_blocks=kv_blocks,
                               table_len=long_max_len,
                               sharding=self._arena_sh)
        self.model = model
        self.params = params
        #: weight generation currently loaded — bumped by
        #: :meth:`swap_params` (the HotSPa train→serve push path);
        #: every request is tagged with the version it was admitted
        #: under, and the KV pool / prefix cache carry the same tag so
        #: stale prefills can never survive a swap
        self.weight_version = 0
        self.prefill_chunk = int(prefill_chunk)  # PACK budget/iteration
        self.blocks = BlockManager(self.pool.n_blocks) if self._paged \
            else NoBlocks()
        self.prefix_cache: Optional[PrefixCache] = PrefixCache(
            self.pool.block_size, self.blocks) if prefix_cache else None
        self.scheduler = Scheduler(
            self.pool.slots, self.pool.max_len,
            # (no arena: no ledger, a free slot is the whole price)
            blocks=self.blocks if self._paged else None,
            prefix_cache=self.prefix_cache,
            block_size=self.pool.block_size,
            long_max_len=long_max_len, class_weights=class_weights,
            token_block=self._gen.block_length if self._gen else 1)
        self._plan = plan
        self._counter_sample_every = counter_sample_every

        # -- speculation plane (ISSUE 11): draft depth is a SHAPE knob
        # (the verify lane's width), per-slot effective depth is data —
        # spec_depth=0 keeps the lane at the classic one-row decode
        self.spec_depth = check_draft_depth(spec_depth, max_len)
        #: token columns a slot's decode lane hands on: its last token
        #: and the drafts, or a block-diffusion model's block (whose
        #: lane RUNS twice the rows: the block finished before it rides
        #: below — ``block_diffusion.lane_rows``)
        self._lane_rows = self._gen.block_length if self._gen \
            else self.spec_depth + 1
        self._draftsman = None
        if draft_model is not None:
            if self.spec_depth == 0:
                raise ValueError(
                    "draft_model without spec_depth — pass spec_depth=k "
                    "to enable the verify lane")
            self._draftsman = ModelDraftsman(
                draft_model, draft_params, slots=self.pool.slots,
                max_len=max_len, spec_depth=self.spec_depth,
                target_vocab=model.cfg.vocab_size)
        elif self.spec_depth:
            if draft != "ngram":
                raise ValueError(f"unknown draft source {draft!r} "
                                 f"(ngram, or pass draft_model=)")
            self._draftsman = NgramDraftsman(self.pool.slots,
                                             ngram=draft_ngram)
        # -- QoS preemption: host spill arena, priced in the same
        # blocks the device pool allocates (engine/memory ledger)
        self.preempt = bool(preempt)
        if spill_host_budget_bytes is not None:
            from hetu_tpu.engine.memory import size_spill_arena
            from hetu_tpu.serving.kv_pool import cache_dtype_name
            max_blocks = size_spill_arena(
                model.cfg, host_budget_bytes=spill_host_budget_bytes,
                block_size=self.pool.block_size,
                cache_dtype=cache_dtype_name(cache_dtype),
                tp=plan.strategy.tp if plan is not None else 1)
        else:
            max_blocks = None
        # ``spill_peer`` chains a second spill tier behind the host
        # arena (device→host→peer, ISSUE 18): any object with the
        # arena's put/pop/get/can_fit surface — another HostSpillArena
        # in-process, or a wire-backed store. LRU demotion + promotion
        # live in the arena; ``engine/memory.size_spill_tiers`` prices
        # both tiers in the same arena blocks.
        self.spill_arena = HostSpillArena(max_blocks, peer=spill_peer)
        self._resume_pending: list[dict] = []    # admitted spill-resumes

        # -- decode-KV replication (ISSUE 18): a background thread
        # streams newly committed blocks of decoding slots to a
        # rendezvous-chosen buddy (the router wires the sink);
        # ``kv_replica_store`` is OUR buddy-side accumulator for peers
        # replicating here. Jax-free import — fleet.py has no jax.
        from hetu_tpu.serving.fleet import KVReplicaStore
        self.kv_replica_store = KVReplicaStore()
        self._repl_sink = None          # callable(doc) or None = off
        self._repl_origin = ""
        self._repl_cadence_s = 0.02
        self._repl_sent: dict[int, tuple] = {}   # req id -> (blocks, tid)
        self._repl_thread: Optional[threading.Thread] = None
        self._repl_stop: Optional[threading.Event] = None

        S = self.pool.slots
        W = self.pool.table_width
        self._pos = np.zeros(S, np.int32)        # next KV write index
        self._last_tok = np.zeros(S, np.int32)   # sampled, not yet fed
        self._active = np.zeros(S, bool)         # decoding slots
        self._temp = np.zeros(S, np.float32)
        self._topk = np.zeros(S, np.int32)
        self._topp = np.zeros(S, np.float32)
        self._bt = np.zeros((S, W), np.int32)    # per-slot block tables
        if self._gen is not None:
            # the block lane's state a slot (block_diffusion.py): the
            # block's tokens, which are still masked, the passes it has
            # had; the block finished before it and whether its rows
            # are still to run (the carry); the request's steps, rule
            # and threshold. The step takes and returns the first five;
            # the host's mirrors follow the packed fetch. ``_blk_at``:
            # the pass at which each position was unmasked (the host's
            # own account)
            B = self._gen.block_length
            self._blk = {
                "blk_tok": np.full((S, B), self._gen.mask_token_id,
                                   np.int32),
                "blk_masked": np.ones((S, B), bool),
                "blk_pass": np.zeros(S, np.int32),
                "blk_prev": np.zeros((S, B), np.int32),
                "blk_carry": np.zeros(S, bool),
                "blk_steps": np.ones(S, np.int32),
                "blk_dynamic": np.zeros(S, bool),
                "blk_thresh": np.zeros(S, np.float32)}
            self._blk_at = np.zeros((S, B), np.int8)
        # device-resident mirrors of the control vectors + block tables:
        # rebuilt from the np mirrors only when an admission / prefill
        # completion / finish dirtied them — steady decode iterations
        # reuse the compiled step's own (pos, last_tok) outputs and
        # upload NOTHING
        self._ctl_dev: Optional[dict] = None
        self._bt_dev = None
        self._ctl_dirty = True
        self._slot_req: list[Optional[Request]] = [None] * S
        # -- adapter arena (serving/tenancy.py): device-resident
        # stacked A/B pages per projection — (L, P, in, r) /
        # (L, P, r, out), page 0 all-zero (base). The registry rewrites
        # SINGLE pages via functional .at[:, page].set, so adapter
        # load/evict/hot-swap never changes a shape and never retraces
        # the fused step; _adapter_page maps slot -> page and rides ctl
        # as traced data.
        self._adapter_page = np.zeros(S, np.int32)
        self._lora_pages: dict = {}
        self._throttle_logged: set = set()   # reqs in a throttle episode
        self._wait_logged: set = set()       # reqs waiting on the arena
        self._qos_admitted: set = set()      # req ids that paid on_admit
        if self.tenancy is not None:
            self._lora_pages = self._init_adapter_arena()
            self.tenancy.registry.on_page_write = self._write_adapter_page
            self.scheduler.admission_gate = self._admission_gate
        self._prefilling: list[dict] = []        # FCFS in-flight prefills
        self._cp_pending: list[dict] = []        # admitted CP-lane reqs
        #: max requests that can FINISH prefill in one iteration (each
        #: needs >= 1 pack token) — the prefill lane's head/sample width
        self._fin_cap = max(1, min(S, self.prefill_chunk))
        self._evictions_synced = 0               # scheduler ledger → ctr
        self._key = jax.random.key(seed)
        # per-slot commit-key state (raw jax.random.key_data layout):
        # the sampled lane's traced PRNG stream — one split consumed
        # per committed token, exactly generate()'s discipline, so an
        # identical-seed sampled request replays bit-for-bit. Admission
        # seeds it (SamplingParams.seed, else engine seed + req id);
        # the fused step returns the advanced state every iteration.
        self._kw = int(jax.random.key_data(self._key).shape[-1])
        self._key_state = np.zeros((S, self._kw), np.uint32)
        self._iter = 0
        self._next_id = 0
        self._requests_by_id: dict[int, Request] = {}  # RPC poll map
        self._lock = threading.RLock()
        # the step's own way into _lock: what its acquisitions waited
        # is the serve/step span's lock_wait_s
        self._loop_lock = _TimedLock(self._lock)
        self._n_admitted = 0                     # admissions, ever
        self._step_end_pc: Optional[float] = None  # last step's end
        # serializes whole engine ITERATIONS: step() mutates _prefill
        # and passes pool.caches to a buffer-DONATING jit — two drivers
        # (the start() background loop + a direct run_until_drained)
        # must never interleave an iteration
        self._step_lock = threading.Lock()
        # push subscriptions (ISSUE 19): req id → (request, [subs]);
        # fed enqueue-only at the end of every step, drained by the
        # coordinator's per-connection writer threads OFF the step lock
        self._stream_subs: dict[int, tuple] = {}
        self._stream_lock = threading.Lock()
        self._stop: Optional[threading.Event] = None
        self._thread: Optional[threading.Thread] = None
        # production-observability side-band: a hang watchdog fed by the
        # background loop, and an SLO engine evaluated on its cadence
        # (slo=True installs the default TTFT/TPOT/step rules; pass a
        # pre-configured SLOEngine for custom objectives)
        self.watchdog: Optional[HangWatchdog] = HangWatchdog(
            name="serving", factor=watchdog_factor,
            min_timeout_s=watchdog_min_timeout_s,
            registry=telemetry.get_registry()) if watchdog else None
        if slo is True:
            self.slo: Optional[SLOEngine] = default_serving_rules(
                SLOEngine(telemetry.get_registry()))
        else:
            self.slo = slo or None
        self._slo_every_s = float(slo_every_s)
        self._slo_last_eval = 0.0

        # -- kernel plane (ISSUE 14): decode attention dispatch is
        # arena-layout-aware (the same call serves fp32/bf16/int8 —
        # the kernel streams int8 pages + scales and dequantizes per
        # tile) and resolved ONCE here: the choice is baked into the
        # compiled step, so the 1-compile audit is untouched.
        from hetu_tpu.ops.attention import resolve_decode_kernel
        tp = plan.strategy.tp if plan is not None else 1
        _attn_mod = model.blocks.block.attn
        # (a step without attention resolves nothing: "none")
        self.attn_kernel = resolve_decode_kernel(
            attn_kernel, tp=tp, site="serving_decode",
            num_heads=_attn_mod.num_heads,
            num_kv_heads=_attn_mod.num_kv_heads) if self._paged else "none"
        # prefill lanes: "flash" packs the chunk as ONE row — intra-pack
        # flash attention with segment isolation, LSE-combined with each
        # token's arena history through its block table; "reference" is
        # the historical per-token paged lane. "flash_pallas" forces the
        # Pallas intra kernel (interpret on CPU — quick-tier coverage).
        wants_slots = self._slot_state
        if prefill_attn == "auto":
            # (a model that advances a slot's state through a pack
            # needs the pack as ONE row with its tokens' slots)
            prefill_attn = "flash" if jax.default_backend() == "tpu" \
                or wants_slots else "reference"
        if wants_slots and prefill_attn == "reference":
            from hetu_tpu.nn.parallel import SlotStateNotSupported
            raise SlotStateNotSupported(
                "prefill_attn='reference' (a batch row a pack token) is "
                "not available over a per-slot recurrent state: a "
                "pack's tokens advance their slot's state in order")
        if prefill_attn not in ("reference", "flash", "flash_pallas"):
            raise ValueError(
                f"prefill_attn must be auto|reference|flash|"
                f"flash_pallas, got {prefill_attn!r}")
        self.prefill_attn = prefill_attn
        self._pack_impl = "pallas" if (
            prefill_attn == "flash_pallas"
            or (prefill_attn == "flash"
                and jax.default_backend() == "tpu")) else "reference"
        # the flash lane's history read on the kernel path: the tokens
        # of a request's run share one pass over its pages per TILE of
        # the chunk (ops.paged_pallas.paged_history_attention). Tile
        # size from the head shapes; the tile count is static: a pack
        # holds at most _fin_cap runs, each may open one more tile
        self._hist_tile = self._hist_tiles = self._hist_steps = 0
        self._hist_span = self._chunk_span = self._chunk_steps = 0
        if self._paged:
            self._size_history_tiles(_attn_mod, W, prefill_attn)
        # W8A8 decode-FFN compute: per-layer A/B as a (layers,) bool
        # baked into the step. Gated on the int8 arena — an operator
        # who priced the KV at 8 bits has already accepted 8-bit error
        # on the decode path; off by default on CPU ("auto").
        L = model.blocks.num_layers
        if w8a8 in (None, False, "off"):
            self._w8a8_mask = None
        else:
            if w8a8 == "auto":
                on = self.pool.quantized \
                    and jax.default_backend() == "tpu"
                mask = np.ones(L, bool) if on else None
            else:
                if not self.pool.quantized:
                    raise ValueError(
                        "w8a8 needs the int8 arena (cache_dtype="
                        "jnp.int8): the quantized-compute lane is "
                        "gated on pools already accepting 8-bit error")
                if w8a8 in (True, "on"):
                    mask = np.ones(L, bool)
                else:                     # iterable of layer indices
                    mask = np.zeros(L, bool)
                    mask[np.asarray(list(w8a8), int)] = True
            self._w8a8_mask = jnp.asarray(mask) if mask is not None \
                else None
        # pre-quantized W8A8 weight tree: the decode lane's weights
        # never change between steps, so quantize ONCE here (and again
        # on every swap_params — stale int8 weights would silently
        # serve old parameters) instead of per fused step
        self._w8a8_wq = self._prequantize_decode_weights()

        self._m = _bind_metrics(telemetry.get_registry())
        if self._gen is not None:
            vars(self._m).update(
                _bind_diffusion_metrics(telemetry.get_registry()))
        # a token's bytes by leaf (as stored and as needed, or by kind
        # where the layers keep different leaves), and a slot's state
        got = model.blocks.cache_bytes(self.pool.caches[0].dtype.itemsize)
        for kind, n in got["row"].items():
            self._m.kv_row.set(n, kind=kind)
        for kind, n in got["state"].items():
            self._m.kv_state.set(n, kind=kind)
        # the smallest window of the model's layers (None: no layer has
        # one) — for the kv_window_dead_blocks gauge only
        ld = model.blocks.layer_data or {}
        self._min_window = int(np.min(ld["window"])) \
            if "window" in ld else None
        self._prefill_path = "flash" if prefill_attn != "reference" \
            else "reference"
        self._results = self._result_layout()
        self._fn = self._build_step()
        self._scopes_registered = False
        self._cp_fn = self._build_cp_prefill() \
            if self._cp_buckets is not None else None
        self._spill_fn, self._resume_fn = self._build_spill_resume()

    def _size_history_tiles(self, _attn_mod, W, prefill_attn) -> None:
        """What the paged reads walk, from the arena's head shapes."""
        from hetu_tpu.ops.paged_pallas import (
            history_tile_count, history_tile_pages, history_tile_rows,
            pack_history_tiles, table_chunks,
        )
        # a tile map of the runs WITH history, or of every run: the
        # attention's read takes in the pack's own keys (the
        # block-sparse band)
        self._every_run = _attn_mod.history_tiles == "every_run"
        self._pack_tiles = pack_history_tiles
        # the kv heads a PAGE holds are the call's: the arena's row
        # over the head's width (a block-sparse page holds one)
        shapes = (_attn_mod.num_heads // _attn_mod.num_kv_heads,
                  _attn_mod.head_dim,
                  self.pool.caches[0].shape[-1] // _attn_mod.head_dim,
                  self.pool.block_size)
        itemsize = jnp.dtype(self.pool.caches[0].dtype).itemsize
        self._hist_tile = history_tile_rows(
            *shapes, kv_itemsize=itemsize,
            head_rows=_attn_mod.BAND_ROWS if self._every_run else None)
        self._hist_tiles = history_tile_count(
            self.prefill_chunk, self._hist_tile, self._fin_cap) \
            if prefill_attn != "reference" \
            and self.attn_kernel == "paged" \
            and _attn_mod.history_tiles else 0
        # a grid step of that read: a key tile of as many pages as fit
        # beside the cell — its span in positions and a table's steps,
        # for serving_prefill_hist_chunks_total
        pages, self._hist_steps = table_chunks(
            W, self.pool.block_size, history_tile_pages(
                *shapes, tile_rows=self._hist_tile, kv_itemsize=itemsize,
                latent=_attn_mod.latent))
        self._hist_span = pages * self.pool.block_size
        # the decode rows' paged call walks the live (slot, chunk)
        # pairs (ops.paged_pallas.decode_work_list): a chunk's span in
        # positions and a table's chunks, for
        # serving_decode_chunks_total (0: the gather path has no list)
        pages, steps = table_chunks(W, self.pool.block_size)
        self._chunk_span = pages * self.pool.block_size
        self._chunk_steps = steps if self.attn_kernel == "paged" else 0

    def _register_device_scopes(self, args) -> None:
        """At the first dispatch: keep the fused step's ABSTRACT
        operands (shapes, dtypes, shardings — no device array) so that
        ``telemetry.device_scopes`` can fetch the step's optimized HLO
        later, on demand: ``lower`` from abstract operands finds the
        trace of the first call (the Python body does not run again,
        ``trace_counts()["serving_step"]`` stays 1) and ``compile``
        yields a second, separate executable object
        (``step_executables()`` stays 1). Nothing is lowered, compiled
        or parsed unless someone asks for the map."""
        from hetu_tpu.telemetry import device_scopes
        self._scopes_registered = True

        def abstract(x):      # numpy operands are uncommitted: no home
            committed = isinstance(x, jax.Array) and x.committed
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if committed else None)

        sds = jax.tree.map(abstract, args)
        fn, plan = self._fn, self._plan

        def hlo_text() -> str:
            ctx = plan.act if plan is not None \
                else contextlib.nullcontext()
            with ctx:
                return fn.lower(*sds).compile().as_text()

        device_scopes.register_step("serving_step", hlo_text)

    def _refuse_slot_state(self, what: str) -> None:
        """What hands a request's cache to another engine, or takes one
        in, moves pages; a model that also keeps a state per slot
        refuses it by name (``model.blocks.refuse_serving``); so does
        one that generates by diffusion over blocks, whose slot holds a
        block in the making beside its pages."""
        if self._gen is not None:
            block_diffusion.refuse(**{what: True})
        self.model.blocks.refuse_serving(**{what: True})

    def step_executables(self) -> int:
        """How many executables the ONE fused step holds (the jit's
        cache entries). ``record_trace("serving_step")`` counts traces;
        an operand that changes its sharding or its committedness
        between calls compiles again under the SAME trace, and only
        this count sees it (committed params beside an uncommitted
        arena cost three compiles at one trace before the arena got its
        home — ``__init__``)."""
        return self._fn._cache_size()

    # -- KV spill / resume (resumable preemption) ---------------------------
    def _build_spill_resume(self):
        """Two tiny jits over the arena, both operating on a fixed
        ``table_width`` lane of block ids (DATA — one compile each,
        audited like the fused step):

        - spill: gather a request's blocks ``(L, W, bs, ...)`` for the
          device→host copy (pad lanes gather the null block and are
          sliced off host-side);
        - resume: scatter host-refilled block data into FRESH block
          ids (pad lanes target ``n_blocks`` → dropped). Donates the
          arena (the old buffer is dead the moment the new one lands).
        """
        def spill(caches, blk_ids):
            record_trace("serving_kv_spill")
            return jax.tree.map(
                lambda c: jnp.take(c, blk_ids, axis=1), caches)

        def resume(caches, data, blk_ids):
            record_trace("serving_kv_resume")
            return jax.tree.map(
                lambda c, d: c.at[:, blk_ids].set(
                    d.astype(c.dtype), mode="drop"), caches, data)

        return (jax.jit(spill),
                jax.jit(resume, donate_argnums=(0,),
                        out_shardings=self._arena_sh))

    def _prequantize_decode_weights(self):
        """Build the decode lane's pre-quantized W8A8 weight tree from
        the CURRENT params (None when the lane is off). The tree rides
        the fused step as a traced operand — not a closure — so
        :meth:`swap_params` only has to rebuild the tree, never the
        compiled step."""
        if self._w8a8_mask is None:
            return None
        mlp = self.model.blocks.block.mlp
        return mlp.prequantize(self.params["blocks"]["mlp"],
                               stacked=True)

    def _count_hist_chunks(self, tiles):
        """``serving_prefill_hist_chunks_total`` and ``..._tile_keys_
        total`` of one pack, from its live tiles' map (the kernel's own
        predicates, vectorised): a tile walks the table's steps under
        the DEEPEST cap; it computes those from the one its first row's
        window starts in to the one its cap ends in, and of their keys
        its rows may see the ones from that window's start to the
        cap."""
        m, span = self._m, self._hist_span
        _, _, lo, _, off, cap = tiles
        walked = tiles.shape[1] * min(int(cap.max()) // span + 1,
                                      self._hist_steps)
        kinds = [("full", np.zeros_like(cap))]
        if self._min_window is not None:
            # the first key the tile's FIRST row sees (the lowest any
            # does); above the cap: the window leaves the tile nothing
            kinds.append(("window", np.clip(
                off + lo - self._min_window + 1, 0, cap + 1)))
        for kind, first in kinds:
            live = int((cap // span - first // span + 1)[first <= cap].sum())
            keys = int((cap + 1 - first).sum())
            m.hist_chunks.inc(live, state="live", layer=kind)
            m.hist_chunks.inc(walked - live, state="dead", layer=kind)
            m.hist_tile_keys.inc(keys, state="live", layer=kind)
            m.hist_tile_keys.inc(live * span - keys, state="masked",
                                 layer=kind)

    # -- the jit-once fused step --------------------------------------------
    def _build_step(self):
        model = self.model
        R = self._fin_cap
        K = self._lane_rows - 1
        gen = self._gen
        kern = self.attn_kernel
        w8a8_mask = self._w8a8_mask
        flash_lane = self.prefill_attn != "reference"
        wants_slots = self._slot_state
        paged = self._paged
        pack_impl = self._pack_impl
        tile_rows = self._hist_tile
        # the draftsman's q rows: host-only draftsmen (and no
        # draftsman) propose deterministically, so q is the one-hot of
        # the draft — synthesized on-device; a device draftsman ships
        # its sampled softmax rows through spec["q"]
        host_q = self._draftsman is None \
            or getattr(self._draftsman, "host_only", True)
        results = self._results

        def step(params, caches, ctl, pf, bt, cow, spec, wq, lora):
            record_trace("serving_step")    # churn must never re-enter

            # copy-on-write block copies for this iteration's partial
            # prefix hits, and the whole pass is cond-gated — the
            # common decode-only iteration never pays it. The copies
            # land BEFORE any lane writes. Lane by lane, a block at a
            # time, in place: a gather of all lanes' blocks makes XLA
            # slice or re-tile the whole leaf first (gpt2-large's
            # 1280-wide leaf in 384-wide strips, the int8 arena's
            # scales), which the step would hold as temporaries even
            # where the pass never runs. An unused lane (dst = the
            # arena's size) rewrites its source block with itself; a
            # dst is a fresh private block, never a later lane's src.
            def apply_cow(cs):
                def lane(i, cs):
                    src, dst = cow["src"][i], cow["dst"][i]

                    def one(c):
                        blk = jax.lax.dynamic_slice_in_dim(c, src, 1, 1)
                        return jax.lax.dynamic_update_slice_in_dim(
                            c, blk, jnp.where(dst < c.shape[1], dst, src),
                            1)
                    return jax.tree.map(one, cs)
                return jax.lax.fori_loop(0, cow["src"].shape[0], lane, cs)

            # device scopes (telemetry/device_scopes.py) go around the
            # cond CALLS: a decorated branch function costs seconds of
            # tracing on the chip's host (PERF.md, PR 24)
            # (an engine without an arena has no block to copy and no
            # table: ``cow`` is empty, ``bt`` unread)
            if paged:
                with jax.named_scope("hetu.kv_arena"):
                    caches = jax.lax.cond(cow["run"], apply_cow,
                                          lambda cs: cs, caches)
            tables = bt if paged else None

            # the decode lane is a VERIFY lane (speculative decoding):
            # every slot feeds its last token plus up to K drafted
            # tokens as K+1 q rows spanning positions pos..pos+K — one
            # forward both writes their KV and yields each row's target
            # distribution, and ``speculative_verify`` runs the
            # rejection-sampling acceptance rule per slot: draft i
            # survives with prob min(1, p/q) (exactly the greedy
            # leading-match rule at temperature 0, where q is one-hot),
            # and the first rejection resamples from the normalized
            # residual max(0, p - q) — so the committed stream is
            # distributed exactly as sequential sampling. Per-slot
            # draft depth (spec["len"]) is DATA: depth 0 reduces to the
            # classic one-token decode, bit for bit. Rows past a slot's
            # depth are masked from writing (row_mask) — their
            # positions may lie beyond the blocks its table owns.
            # Free/prefilling slots attend nothing (the paged kernel
            # walks the active slots' chunks alone; the gather path
            # computes garbage); what their rows carry the masks keep
            # out of the pool and the host ignores; cond-gated so
            # prefill-only iterations skip the discarded forward.
            def do_decode(caches):
                lane = jnp.arange(K + 1)[None, :]
                tok_in = jnp.concatenate(
                    [ctl["last_tok"][:, None], spec["tok"]], axis=1)
                positions = ctl["pos"][:, None] + lane
                row_valid = (lane <= spec["len"][:, None]) \
                    & ctl["active"][:, None]
                # multi-tenant BGMV: every token row carries its slot's
                # adapter arena page as DATA (page 0 = base, bitwise) —
                # adapter load/evict/mixed-tenant churn never retraces
                logits, caches, stats = generation.decode(
                    model, params, tok_in, positions, caches,
                    slot_mask=ctl["active"], block_tables=tables,
                    row_mask=row_valid, attn_kernel=kern,
                    w8a8_mask=w8a8_mask, w8a8_wq=wq,
                    lora={"ids": jnp.broadcast_to(
                        ctl["adapter"][:, None], tok_in.shape),
                        "pages": lora} if lora else None,
                    with_stats=True)
                # proposal probs q: host draftsmen propose
                # deterministically — their q is the one-hot of the
                # draft, synthesized on the device (q=None) so the host
                # never ships a (S, K, V) table; a device draftsman's
                # sampled softmax rows ride in through spec["q"]. The
                # verify does what the ACTIVE slots' knobs need and no
                # more (a freed slot keeps its last request's): the
                # scope goes around the call, its conds' branches
                # inherit it
                with jax.named_scope("hetu.sample"):
                    committed, ncommit, last_tok, new_kd = verify_slots(
                        logits, spec["tok"], spec["len"],
                        None if host_q else spec["q"],
                        ctl["temp"], ctl["topk"], ctl["topp"],
                        ctl["key"], live=ctl["active"])
                # inactive slots must not burn PRNG state — their
                # sampling stream has to match one-shot generate
                new_kd = jnp.where(ctl["active"][:, None],
                                   new_kd, ctl["key"])
                return caches, committed, ncommit, last_tok, new_kd, stats

            # the decode lane of a model that generates by diffusion
            # over blocks is the BLOCK lane (block_diffusion.py): every
            # slot feeds 2B q rows at pos-B..pos+B-1 — its block's B
            # current tokens and, below them, the B of the block it
            # finished last, live on the pass after the one that
            # finished it (the verify lane's shape; the attention's
            # block bound is a row's position's: each row sees its own
            # block whole and what lies before it). The current rows'
            # K/V are ordinary paged writes that a later pass
            # overwrites, the carry rows' the block's clean ones,
            # written once; ``denoise_slots`` unmasks by the slot's own
            # state — data, like the pass count — and the new state
            # leaves the step beside pos. The head runs on a block's
            # rows, not on both
            def do_block(caches):
                tok_in, positions, writes, early = lane_rows(
                    ctl["pos"], ctl["blk_tok"], ctl["blk_prev"],
                    ctl["blk_carry"], ctl["active"],
                    mask_id=gen.mask_token_id)
                h = model.embed(params, tok_in, positions=positions)
                h, caches, stats = model.blocks.decode(
                    params["blocks"], h, caches, positions=positions,
                    slot_mask=ctl["active"], block_tables=bt,
                    row_mask=writes, attn_kernel=kern, with_stats=True)
                lower, upper = jnp.split(h, 2, axis=1)
                logits = generation.head_logits(model, params, jnp.where(
                    early[:, None, None], lower, upper))
                with jax.named_scope("hetu.diffusion_sample"):
                    committed, ncommit, *blk = denoise_slots(
                        logits, ctl["blk_tok"], ctl["blk_masked"],
                        ctl["blk_pass"], ctl["blk_steps"],
                        ctl["blk_dynamic"], ctl["blk_thresh"],
                        ctl["active"], ctl["blk_prev"], ctl["blk_carry"],
                        mask_id=gen.mask_token_id)
                return (caches, committed, ncommit, ctl["last_tok"],
                        ctl["key"], stats, tuple(blk))

            def no_decode(caches):
                S = ctl["pos"].shape[0]
                z = jnp.zeros((S,), jnp.int32)
                out = (caches, jnp.zeros((S, K + 1), jnp.int32),
                       z, z, ctl["key"],
                       model.blocks.layer_stats_zeros())
                if gen is not None:
                    out += (tuple(ctl[f] for f in _BLK_STATE),)
                return out

            # what the layers of each lane report beside their result
            # (``StackedBlocks.decode(with_stats=)``; nothing, from most
            # models) leaves the step among its results: a host callback
            # inside it would hold the device and keep the executable
            # out of the compile cache
            with jax.named_scope("hetu.decode_lane"):
                caches, committed, ncommit, last_tok, new_kd, dec_stats, \
                    *blk = jax.lax.cond(
                        ctl["active"].any(),
                        do_decode if gen is None else do_block,
                        no_decode, caches)

            # packed prefill: a C-token budget shared by every
            # admitting request — per-token (slot, position) operands
            # are the cu_seqlens of this lane. On the reference lane
            # each pack token is one batch row of the per-row paged
            # decode: layer l writes every row's K/V before attending,
            # so rows of the same request see their in-pack
            # predecessors exactly like a dense chunk. The flash lane
            # is one (1, C) row: flash inside the pack, and the
            # resident history read once per TILE of a request's run
            # (pf["tiles"]). (cond keeps idle iterations free.)
            def do_prefill(caches):
                if flash_lane:
                    # packed FLASH prefill: the whole chunk as ONE
                    # (1, C) row — intra-pack flash with segment
                    # isolation (ids = slots, -1 pads), LSE-combined
                    # with each token's arena history (positions
                    # < its chunk-start offset) through the paged
                    # read path. KV writes stay per-token scatters.
                    pos = pf["pos"][None, :]                 # (1, C)
                    h = model.embed(params, pf["tokens"][None, :],
                                    positions=pos)
                    pack = {"segment_ids": pf["seg"][None, :],
                            "hist": pf["hist"], "valid": pf["valid"],
                            "impl": pack_impl}
                    if wants_slots:
                        # a model that keeps a state per slot: whose
                        # each token is, and the slots' own tables
                        pack["slot"] = pf["slot"]
                        if paged:
                            pack["slot_tables"] = bt
                    if "tiles" in pf:
                        # (fields, tiles): row 0 is each tile's slot
                        pack["tiles"] = {
                            "map": pf["tiles"], "rows": tile_rows,
                            "tables": jnp.take(bt, pf["tiles"][0],
                                               axis=0)}
                    h, caches, stats = model.blocks.decode(
                        params["blocks"], h, caches, positions=pos,
                        block_tables=jnp.take(bt, pf["slot"], axis=0)
                        if paged else None,
                        attn_kernel=kern, pack=pack,
                        lora={"ids": jnp.take(ctl["adapter"],
                                              pf["slot"])[None, :],
                              "pages": lora} if lora else None,
                        with_stats=True)
                    hrow = h[0]                              # (C, E)
                else:
                    pos = pf["pos"][:, None]                 # (C, 1)
                    h = model.embed(params, pf["tokens"][:, None],
                                    positions=pos)
                    h, caches, stats = model.blocks.decode(
                        params["blocks"], h, caches, positions=pos,
                        slot_mask=pf["valid"],
                        block_tables=jnp.take(bt, pf["slot"], axis=0),
                        attn_kernel=kern,
                        lora={"ids": jnp.take(ctl["adapter"],
                                              pf["slot"])[:, None],
                              "pages": lora} if lora else None,
                        with_stats=True)
                    hrow = h[:, 0]                           # (C, E)
                # FIRST tokens for the <= R requests whose prefill
                # completes this iteration: head only on their last
                # real rows (never the full pack's vocab projection)
                if gen is not None:
                    # a prompt's whole blocks yield no token: its first
                    # comes with its first block
                    return no_prefill(caches)[:3] + (stats,)
                hf = jnp.take(hrow, pf["fin_row"], axis=0)[:, None]
                hf = model.hidden_norm(params, hf)
                w = generation._head_weight(model, params)
                lg = jnp.einsum("bse,ve->bsv", hf.astype(jnp.float32),
                                w.astype(jnp.float32))[:, 0]
                fs = pf["fin_slot"]

                # first-token sampling mirrors generate's prefill
                # exactly: split the slot's key once, draw with the
                # sub — so an identical-seed request's whole sampling
                # stream is bitwise the one-shot generate stream. Only
                # the rows that really finish count towards what the
                # sampler has to do
                with jax.named_scope("hetu.sample"):
                    firsts, pf_kd = sample_rows(
                        lg, jnp.take(ctl["temp"], fs),
                        jnp.take(ctl["topk"], fs),
                        jnp.take(ctl["topp"], fs),
                        jnp.take(ctl["key"], fs, axis=0),
                        live=pf["fin_valid"])
                return caches, firsts, pf_kd, stats

            def no_prefill(caches):
                return (caches, jnp.zeros((R,), jnp.int32),
                        jnp.take(ctl["key"], pf["fin_slot"], axis=0),
                        model.blocks.layer_stats_zeros())

            with jax.named_scope("hetu.prefill_lane"):
                caches, first_toks, pf_kd, pf_stats = jax.lax.cond(
                    pf["run"], do_prefill, no_prefill, caches)
            # prefill completions ADOPT their post-sample key state:
            # scatter the <= R finished rows' keys over the slot axis
            # (unused fin rows target S and drop)
            S = ctl["pos"].shape[0]
            scat = jnp.where(pf["run"] & pf["fin_valid"],
                             pf["fin_slot"], S)
            new_key = new_kd.at[scat].set(pf_kd, mode="drop")
            # device-resident control advance: every active slot
            # committed ncommit tokens (accepted drafts + the verify
            # token — their KV landed at pos..pos+ncommit-1), so
            # pos+ncommit / last_tok — returned so the host can reuse
            # the control vectors NEXT iteration without re-uploading
            # them (it falls back to a host rebuild only when an
            # admission / prefill completion / finish rewrote control
            # state)
            new_pos = ctl["pos"] + jnp.where(ctl["active"], ncommit, 0)
            new_last = jnp.where(ctl["active"], last_tok,
                                 ctl["last_tok"])
            # the iteration's ONE fetch: what the host reads of it
            fields = {
                "committed": committed, "ncommit": ncommit,
                "first_toks": first_toks, "key": new_key,
                "stats": (dec_stats, pf_stats)}
            if gen is None:
                return (caches, new_pos, new_last, new_key,
                        results.pack_device(fields))
            # the block state: to the next iteration on the device, and
            # to the host's mirrors in the one fetch (the carry rides it
            # as ``committed`` and ``ncommit``: a finished block's
            # tokens ARE the next pass's carry rows)
            blk_tok, blk_masked, blk_pass = blk[0][:3]
            fields["blk"] = (blk_tok, blk_masked.astype(jnp.int32),
                             blk_pass)
            return (caches, new_pos, new_last, new_key,
                    results.pack_device(fields),
                    dict(zip(_BLK_STATE, blk[0])))

        # what the step returns AND takes again keeps its home
        # (__init__): the arena, and the advanced pos/last_tok/key (and
        # the block lane's state)
        rep = self._rep
        return jax.jit(step, donate_argnums=(1,), out_shardings=(
            self._arena_sh, rep, rep, rep, None)
            + ((rep,) if gen is not None else ()))

    def _result_layout(self) -> PackedFields:
        """The layout of the ONE vector the fused step hands the host
        (``step_io.PackedFields``; ``docs/OBSERVABILITY.md`` has the
        table), from what the engine knows at construction — slots,
        draft depth, the finishing rows, the key's words, the block's
        ``layer_stats``."""
        S, R, K = self.pool.slots, self._fin_cap, self._lane_rows - 1
        stats = jax.eval_shape(self.model.blocks.layer_stats_zeros)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, np.int32)

        fields = {
            "committed": i32(S, K + 1), "ncommit": i32(S),
            "first_toks": i32(R), "key": self._key_state,
            "stats": (stats, stats)}
        if self._gen is not None:
            # the block lane's state after the step: tokens, masks, pass
            fields["blk"] = (i32(S, K + 1), i32(S, K + 1), i32(S))
        return PackedFields(fields)

    # -- the CP-prefill lane ------------------------------------------------
    def _build_cp_prefill(self):
        """jit of the long-prompt one-pass prefill: a TRAINING-mode
        forward (so attention routes through ring/ulysses when the
        plan's cp axis is live) whose per-layer rotary-applied KV
        (``StackedBlocks.prefill``) scatters into the paged arena
        through the slot's wide block table, plus the first sampled
        token from the prompt's last real row.

        Prompt length is a BUCKETED shape (``self._cp_buckets``); the
        real length ``fin_pos + 1`` is data, so one executable per lane
        bucket serves any prompt in it —
        ``record_trace("serving_cp_prefill")`` audits exactly that.
        """
        model = self.model
        n_blk, blk = self.pool.n_blocks, self.pool.block_size
        quant = self.pool.quantized
        # the lane's attention impl: the flash prefill lanes route the
        # training-mode forward through flash_attention_pallas ("auto"
        # lets the dispatch gate check tiling support on the real chip;
        # "pallas" is the explicit/interpret test mode), reference is
        # the dense oracle — the ring/zigzag cp split reuses whichever
        # kernel per shard (ring_attention(impl=...))
        cp_impl = {"reference": "reference", "flash": "auto",
                   "flash_pallas": "pallas"}[self.prefill_attn]

        def cp_prefill(params, caches, tokens, positions, table,
                       fin_pos, temp, topk, topp, key):
            record_trace("serving_cp_prefill")   # <= n lane buckets
            h = model.embed(params, tokens, positions=positions)
            # segment ids split the bucket row into prompt (0) vs pad
            # (1): pad rows — whose KV the scatter drops anyway — stop
            # attending the prompt, and the flash kernel gets the
            # packed-varlen operands data/packing.py standardized
            seg = (positions > fin_pos).astype(jnp.int32)
            h, (ks, vs) = model.blocks.prefill(params["blocks"], h,
                                               positions=positions,
                                               segment_ids=seg,
                                               attn_impl=cp_impl)
            # scatter each layer's (L,) prompt rows into the arena at
            # the rows the slot's table maps; pad rows (beyond the real
            # prompt) target n_blk*blk and drop. Zigzag cp layouts feed
            # PERMUTED rows — positions ride along, so every row still
            # lands at its own absolute index.
            pos = positions[0]
            blk_ids = jnp.take(table[0], pos // blk)
            rows = jnp.where(pos <= fin_pos,
                             blk_ids * blk + pos % blk, n_blk * blk)

            def scat(buf, new):
                flat = buf.reshape((buf.shape[0], n_blk * blk)
                                   + buf.shape[3:])
                flat = flat.at[:, rows].set(
                    new.reshape(new.shape[:2] + buf.shape[3:])
                    .astype(buf.dtype), mode="drop")
                return flat.reshape(buf.shape)

            k_new, v_new = ks[:, 0], vs[:, 0]    # (layers, L, hkv, d)
            if quant:
                from hetu_tpu.ops.quantization import quantize_int8
                kq, ksc = quantize_int8(k_new, axis=-1)
                vq, vsc = quantize_int8(v_new, axis=-1)
                caches = (scat(caches[0], kq), scat(caches[1], ksc),
                          scat(caches[2], vq), scat(caches[3], vsc))
            else:
                caches = (scat(caches[0], k_new),
                          scat(caches[1], v_new))
            # first token: head only on the last REAL row (found by
            # position match — layout-permutation proof)
            fin_row = jnp.argmax(pos == fin_pos)
            hf = model.hidden_norm(params, h[:, fin_row][:, None])
            w = generation._head_weight(model, params)
            lg = jnp.einsum("bse,ve->bsv", hf.astype(jnp.float32),
                            w.astype(jnp.float32))[:, 0]
            # per-request key chain, same as the packed lane: split
            # once, draw with the sub, return the advanced state
            tok, kd = sample_rows(lg, temp, topk, topp, key[None],
                                  live=jnp.ones((1,), bool))
            return caches, tok[0], kd[0]

        return jax.jit(cp_prefill, donate_argnums=(1,),
                       out_shardings=(self._arena_sh, None, None))

    def _prep_cp_prefill_locked(self) -> Optional[dict]:
        """Pop ONE pending CP-lane request and build its host operands
        (caller holds ``self._lock``). One per engine iteration: a
        burst of long prompts interleaves with decode iterations
        instead of starving every active slot back-to-back — the lane's
        analogue of the packed lane's per-iteration chunk budget."""
        if not self._cp_pending:
            return None
        ent = self._cp_pending.pop(0)
        req, slot = ent["req"], ent["slot"]
        P = len(req.prompt)
        L = self._cp_buckets.bucket_for(P)
        tokens = np.zeros((1, L), np.int32)
        tokens[0, :P] = req.prompt
        positions = np.arange(L, dtype=np.int32)[None, :]
        if self._cp_zigzag:
            from hetu_tpu.data.packing import zigzag_permute
            tokens = zigzag_permute(tokens, self._cp, axis=1)
            positions = zigzag_permute(positions, self._cp, axis=1)
        return {"req": req, "slot": slot, "P": P, "bucket": L,
                "tokens": tokens, "positions": positions,
                "table": self._bt[slot:slot + 1].copy(),
                # the slot's admission-seeded commit key (raw state):
                # the CP lane samples the first token from the SAME
                # per-request stream the packed lane would have
                "key": self._key_state[slot].copy()}

    def _exec_cp_prefill(self, job: dict, t0: float) -> None:
        """Run one prepared CP-lane prefill. The device call happens
        WITHOUT ``self._lock`` (submit()/load stay responsive through a
        multi-second cold-bucket compile or a 100k-token forward; the
        operands were snapshotted under the lock, and everything the
        call touches — arena, params, tables — is only ever mutated by
        ``_step_lock`` holders, which we are)."""
        req, slot, P = job["req"], job["slot"], job["P"]
        sp = req.sampling
        ctx = self._plan.act if self._plan is not None \
            else contextlib.nullcontext()
        with ctx:
            caches, tok, kd = self._cp_fn(
                self.params, self.pool.caches, job["tokens"],
                job["positions"], job["table"], np.int32(P - 1),
                np.asarray([sp.temperature], np.float32),
                np.asarray([sp.top_k], np.int32),
                np.asarray([sp.top_p], np.float32), job["key"])
        self.pool.caches = caches
        now = time.monotonic()
        # the lane's eight numpy operands up, its key and token down
        self._m.transfers.inc(8, dir="up")
        self._m.transfers.inc(2, dir="down")
        with self._loop_lock:
            self._key_state[slot] = np.asarray(kd)
            self._pos[slot] = P
            self._active[slot] = True
            self._ctl_dirty = True
            req.status = "decode"
            req.first_token_s = now
            req.mark("prefill_chunk", dur_s=now - t0, ts_s=t0,
                     iter=self._iter + 1)
            req.mark("first_token", ts_s=now)
            ttft = now - req.submit_s
            m = self._m
            m.ttft.observe(ttft)
            if self.slo is not None:
                self.slo.observe("serving_ttft_seconds", ttft)
            m.tokens.inc(P, kind="prompt")
            m.cp_requests.inc()
            m.cp_tokens.inc(P)
            m.prefill_kernel.inc(path=self._prefill_path)
            flight_record("serving_cp_prefill", req=req.id,
                          trace=req.trace_id, slot=slot, tokens=P,
                          bucket=job["bucket"])
            # no prefix-cache insert: lane blocks stay private to the
            # slot (long-prompt prefix sharing is future work)
            self._on_token(slot, int(tok), now)
            m.tokens.inc(kind="generated")

    # -- resumable preemption (QoS) -----------------------------------------
    def _plan_preemption_locked(self) -> Optional[dict]:
        """Decide whether this iteration evicts a running request for a
        blocked more-urgent one (caller holds ``self._lock``, and the
        admission pass has ALREADY run — so a head still queued here
        genuinely could not admit, even net of prefix-cache credit and
        cache eviction). At most one preemption per iteration; the
        spill itself (a device→host gather) runs outside the lock.
        Fires only when (a) that blocked head exists, (b) a STRICTLY
        lower-priority request is decoding, and (c) the host arena can
        hold its blocks — so uniform-priority traffic keeps the
        historical run-to-completion guarantee untouched."""
        if not self.preempt or not self.scheduler.queue:
            return None
        cand = self.scheduler.peek_candidate()
        if cand is None:
            return None
        running = [(s, r) for s, r in enumerate(self._slot_req)
                   if r is not None and self._active[s]]
        slot = self.scheduler.preemption_victim(cand, running)
        if slot is None:
            return None
        nb = max(1, -(-int(self._pos[slot]) // self.pool.block_size))
        if not self.spill_arena.can_fit(nb):
            return None
        return {"req": self._slot_req[slot], "slot": slot, "nb": nb,
                "ids": self._bt[slot].copy()}

    def _spill_blocks(self, ids: np.ndarray, nb: int) -> tuple:
        """Device→host copy of ``nb`` blocks (the compiled gather runs
        over the fixed table width; pad lanes read the null block and
        are sliced off)."""
        lane_ids = np.zeros(self.pool.table_width, np.int32)
        lane_ids[:nb] = ids[:nb]
        ctx = self._plan.act if self._plan is not None \
            else contextlib.nullcontext()
        with ctx:
            gathered = self._spill_fn(self.pool.caches,
                                      jnp.asarray(lane_ids))
        return tuple(np.asarray(g)[:, :nb].copy() for g in gathered)

    def _detach_locked(self, req: Request, slot: int) -> None:
        """Free ``slot`` and everything ``req`` holds on the device
        (caller holds ``self._lock``); the request's fate — requeue,
        resume elsewhere, or drop — is the caller's."""
        self.scheduler.release(slot, table=self._bt[slot])
        self._bt[slot, :] = 0
        self._active[slot] = False
        self._slot_req[slot] = None
        self._adapter_page[slot] = 0
        self._ctl_dirty = True

    def _exec_spill(self, job: dict) -> None:
        """Evict one running request into the host arena and requeue it
        at the head of its class — the resumable half of preemption."""
        req, slot, nb = job["req"], job["slot"], job["nb"]
        data = self._spill_blocks(job["ids"], nb)
        self._m.transfers.inc(dir="up")                  # the block ids
        self._m.transfers.inc(len(data), dir="down")     # a leaf each
        now = time.monotonic()
        with self._loop_lock:
            entry = SpillEntry(
                req_id=req.id, data=data, n_blocks=nb,
                block_size=self.pool.block_size,
                pos=int(self._pos[slot]),
                last_tok=int(self._last_tok[slot]),
                tokens=list(req.tokens),
                weight_version=req.weight_version,
                key_state=self._key_state[slot].copy(),
                adapter=req.kv_adapter)
            self.spill_arena.put(entry)
            req.spill = entry
            req.preemptions += 1
            req.spilled_blocks += nb
            req.mark("preempted", ts_s=now)
            self._detach_locked(req, slot)
            self.scheduler.requeue_preempted(req)
            self.scheduler.preemptions_total += 1
            self._m.spilled.inc(nb)
            self._m.preemptions.inc(
                priority=str(req.sampling.priority))
        flight_record("serving_preempt", req=req.id, trace=req.trace_id,
                      slot=slot, blocks=nb,
                      priority=req.sampling.priority)

    def _exec_resume(self, job: dict) -> None:
        """Map one spilled request's KV back into its freshly allocated
        blocks and flip its slot live — ZERO prefill-lane work (the
        acceptance bar for resumable preemption)."""
        req, slot = job["req"], job["slot"]
        entry = req.spill
        nb = entry.n_blocks
        W = self.pool.table_width
        lane_ids = np.full(W, self.pool.n_blocks, np.int32)  # pad→drop
        lane_ids[:nb] = self._bt[slot, :nb]
        data = []
        for src in entry.data:
            pad = np.zeros((src.shape[0], W) + src.shape[2:], src.dtype)
            pad[:, :nb] = src
            data.append(pad)
        ctx = self._plan.act if self._plan is not None \
            else contextlib.nullcontext()
        with ctx:
            self.pool.caches = self._resume_fn(
                self.pool.caches, tuple(data), jnp.asarray(lane_ids))
        self._m.transfers.inc(len(data) + 1, dir="up")   # leaves + ids
        now = time.monotonic()
        with self._loop_lock:
            if self.spill_arena.get(req.id) is entry:
                self.spill_arena.pop(req.id)
            req.spill = None
            req.resumed_blocks += nb
            self._pos[slot] = entry.pos
            self._last_tok[slot] = entry.last_tok
            if entry.key_state is not None:
                # the commit-key stream resumes mid-request: sampling
                # continues bit-for-bit where the spill cut it
                self._key_state[slot] = np.asarray(entry.key_state)
            self._active[slot] = True
            self._ctl_dirty = True
            req.status = "decode"
            if req.first_token_s is None:
                # a cross-engine resume starts a fresh Request: give it
                # a first-token stamp so TPOT math stays defined (no
                # TTFT observation — its real first token happened on
                # the engine it was spilled from)
                req.first_token_s = now
            req.mark("resumed", ts_s=now)
            if self._draftsman is not None:
                self._draftsman.reset(
                    slot, req.prompt.tolist() + list(req.tokens))
            self._m.resumed.inc(nb)
        flight_record("serving_resume", req=req.id, trace=req.trace_id,
                      slot=slot, blocks=nb, pos=entry.pos)

    def evict_request(self, req: Request, *,
                      lock_timeout_s: Optional[float] = None
                      ) -> Optional[SpillEntry]:
        """Force ``req`` out of this engine RIGHT NOW, returning its
        spill entry when it had resident KV (a decoding slot, or a
        not-yet-mapped resume) and None otherwise (queued/prefilling —
        nothing worth moving). The fleet layer's half of resumable
        requeue: ``Router`` calls this on replica death and on
        preemptive drains, then re-dispatches the request — with the
        entry — onto a peer, which resumes it without re-prefilling.
        The request's ``done`` event is NOT set (the router owns its
        completion).

        ``lock_timeout_s`` bounds the wait for the engine's iteration
        lock: a replica declared dead because its step is WEDGED (the
        watchdog scenario) still holds that lock, and a caller that
        blocked on it forever would freeze whatever it holds — the
        router passes a small timeout and degrades to a fresh requeue
        (the pre-spill behavior) when salvage cannot be had."""
        self._refuse_slot_state(
            "evict_request (a request's KV handed to a peer)")
        got = self._step_lock.acquire(
            timeout=-1 if lock_timeout_s is None else lock_timeout_s)
        if not got:
            return None
        try:
            entry = self._evict_request_steplocked(req)
        finally:
            self._step_lock.release()
        if req.status in ("evicted", "cancelled"):
            self._stream_interrupt(req)
        if entry is not None and entry.traceparent is None:
            # stamp the originating trace context onto the spill so the
            # decode-tier resume joins the same fleet trace (ISSUE 16)
            entry.traceparent = req.traceparent \
                or telemetry.make_traceparent(req.trace_id)
        if entry is not None and req.handoff:
            # a parked (P/D handoff) request never reaches _finish in
            # this process — emit its queued/prefill spans now so the
            # prefill tier's fragment exists for fleet_trace to merge
            self._emit_request_trace(req)
        return entry

    def _evict_request_steplocked(self, req: Request
                                  ) -> Optional[SpillEntry]:
        spill_plan = None
        with self._lock:
            if req.done.is_set():
                return None
            if req in self.scheduler.queue:
                self.scheduler.queue.remove(req)
                entry = req.spill
                if entry is not None \
                        and self.spill_arena.get(req.id) is entry:
                    self.spill_arena.pop(req.id, resumed=False)
                req.status = "evicted"
                self._release_tenancy(req)
                return entry
            for ent in list(self._resume_pending):
                if ent["req"] is req:
                    self._resume_pending.remove(ent)
                    entry = req.spill
                    if entry is not None and \
                            self.spill_arena.get(req.id) is entry:
                        self.spill_arena.pop(req.id, resumed=False)
                    self._detach_locked(req, ent["slot"])
                    req.status = "evicted"
                    self._release_tenancy(req)
                    return entry
            for ent in list(self._prefilling):
                if ent["req"] is req:
                    self._prefilling.remove(ent)
                    self._detach_locked(req, ent["slot"])
                    req.status = "evicted"
                    self._release_tenancy(req)
                    return None
            for ent in list(self._cp_pending):
                if ent["req"] is req:
                    self._cp_pending.remove(ent)
                    self._detach_locked(req, ent["slot"])
                    req.status = "evicted"
                    self._release_tenancy(req)
                    return None
            slot = req.slot
            # a "prefilled" request is PARKED (P/D handoff): its slot is
            # inactive but still owns the request and its KV blocks —
            # exactly what the prefill tier evicts to stream downstream
            parked = req.status == "prefilled" and slot is not None \
                and self._slot_req[slot] is req
            if slot is None or self._slot_req[slot] is not req \
                    or not (self._active[slot] or parked):
                return None
            nb = max(1, -(-int(self._pos[slot])
                          // self.pool.block_size))
            spill_plan = {"slot": slot, "nb": nb,
                          "ids": self._bt[slot].copy(),
                          "pos": int(self._pos[slot]),
                          "last_tok": int(self._last_tok[slot]),
                          "key_state": self._key_state[slot].copy()}
        # the device gather runs without self._lock (submit()/load
        # stay responsive) but under the iteration lock we hold
        data = self._spill_blocks(spill_plan["ids"],
                                  spill_plan["nb"])
        with self._lock:
            entry = SpillEntry(
                req_id=req.id, data=data,
                n_blocks=spill_plan["nb"],
                block_size=self.pool.block_size,
                pos=spill_plan["pos"],
                last_tok=spill_plan["last_tok"],
                tokens=list(req.tokens),
                weight_version=req.weight_version,
                key_state=spill_plan["key_state"],
                adapter=req.kv_adapter)
            self._detach_locked(req, spill_plan["slot"])
            req.status = "evicted"
            self._release_tenancy(req)
            req.spilled_blocks += spill_plan["nb"]
            telemetry.get_registry().counter(
                "serving_kv_spilled_blocks_total",
                "KV blocks copied device→host when a request was "
                "preempted (resumable eviction)").inc(
                spill_plan["nb"])
        flight_record("serving_evict", req=req.id,
                      trace=req.trace_id, slot=spill_plan["slot"],
                      blocks=spill_plan["nb"])
        return entry

    # -- fleet-global KV plane (ISSUE 18) -----------------------------------
    def export_prefix(self, tokens: Sequence[int], *,
                      lock_timeout_s: Optional[float] = 2.0
                      ) -> Optional[SpillEntry]:
        """Gather this engine's cached whole-block prefix of ``tokens``
        into a :class:`SpillEntry` for a peer pull (the KVEXPORT verb).

        Read-only: the prefix cache keeps its refs and LRU order is the
        only state touched — the gather runs under the iteration lock,
        which freezes all block churn (admission, eviction and the trie
        flush all run step-locked), so no pin/unpin dance is needed.
        None on a whole-block miss or a wedged step (``lock_timeout_s``
        bounds the wait — a pull is best-effort, the puller prefills)."""
        self._refuse_slot_state("export_prefix (the fleet's KV export)")
        if self.prefix_cache is None:
            return None
        got = self._step_lock.acquire(
            timeout=-1 if lock_timeout_s is None else lock_timeout_s)
        if not got:
            return None
        try:
            with self._lock:
                toks = [int(t) for t in tokens]
                shared, _partial = self.prefix_cache.match(toks)
                nb = min(len(shared), self.pool.table_width)
                if nb == 0:
                    return None
                version = self.weight_version
                ids = np.asarray(shared[:nb], np.int32)
            data = self._spill_blocks(ids, nb)
        finally:
            self._step_lock.release()
        bs = self.pool.block_size
        entry = SpillEntry(
            req_id=-1, data=data, n_blocks=nb, block_size=bs,
            pos=nb * bs, last_tok=0, tokens=toks[:nb * bs],
            weight_version=version)
        flight_record("fleet_kv_export", blocks=nb, tokens=nb * bs)
        return entry

    def import_prefix(self, entry: Optional[SpillEntry], *,
                      lock_timeout_s: Optional[float] = 2.0) -> bool:
        """Map a peer-exported prefix into THIS engine's prefix cache
        (the KVIMPORT verb): allocate fresh arena blocks, scatter the
        wire data in, insert the token runs into the radix trie — from
        here on it is an ordinary same-replica prefix hit (refcounted,
        CoW rules unchanged, LRU-evictable like any cached prefix).

        Refuses — returns False, caller falls back to a plain
        prefill — an entry whose weight version or arena layout does
        not match (:meth:`SpillEntry.compatible_with` is the staleness
        rule: a weight push between export and import MUST degrade to
        a prefill, never silently serve old weights' KV), and degrades
        the same way when no blocks can be freed."""
        self._refuse_slot_state("import_prefix (the fleet's KV import)")
        if self.prefix_cache is None or entry is None:
            return False
        if not entry.compatible_with(self.pool, self.weight_version):
            flight_record("fleet_kv_import_refused",
                          blocks=entry.n_blocks,
                          entry_version=entry.weight_version,
                          our_version=self.weight_version)
            return False
        toks = [int(t) for t in entry.tokens]
        nb = entry.n_blocks
        if nb < 1 or len(toks) < nb * self.pool.block_size:
            return False
        got = self._step_lock.acquire(
            timeout=-1 if lock_timeout_s is None else lock_timeout_s)
        if not got:
            return False
        try:
            with self._lock:
                shared, _partial = self.prefix_cache.match(toks)
                if len(shared) >= nb:
                    return True          # already fleet-warm here
                new_ids = []
                for _ in range(nb):
                    b = self.blocks.alloc()
                    if b is None and self.prefix_cache.evict(
                            nb - len(new_ids)):
                        b = self.blocks.alloc()
                    if b is None:        # arena genuinely full of
                        for x in new_ids:    # pinned work: no import
                            self.blocks.release(x)
                        return False
                    new_ids.append(b)
            # scatter outside self._lock (submit/load stay responsive)
            # but under the iteration lock we hold — the resume jit
            # DONATES the arena, exactly like _exec_resume
            W = self.pool.table_width
            lane_ids = np.full(W, self.pool.n_blocks, np.int32)
            lane_ids[:nb] = new_ids
            data = []
            for src in entry.data:
                pad = np.zeros((src.shape[0], W) + src.shape[2:],
                               src.dtype)
                pad[:, :nb] = src
                data.append(pad)
            ctx = self._plan.act if self._plan is not None \
                else contextlib.nullcontext()
            with ctx:
                self.pool.caches = self._resume_fn(
                    self.pool.caches, tuple(data),
                    jnp.asarray(lane_ids))
            with self._lock:
                self.prefix_cache.insert(
                    toks[:nb * self.pool.block_size], new_ids)
                # insert() took the trie's own ref on every node it
                # adopted; dropping ours leaves the trie sole holder
                # (LRU-evictable). A block whose token run was cached
                # concurrently goes straight back to the free list.
                for b in new_ids:
                    self.blocks.release(b)
        finally:
            self._step_lock.release()
        flight_record("fleet_kv_import", blocks=nb)
        return True

    # -- decode-KV replication, origin side (ISSUE 18) ----------------------
    def configure_replication(self, sink, *, origin: str = "",
                              cadence_s: float = 0.02) -> None:
        """Point this engine's decode-KV replication stream at ``sink``
        — a callable taking one JSON-safe shipment doc (in-process: the
        buddy's ``KVReplicaStore.put``; cross-process: a KVREPL wire
        closure installed by the KVBUDDY verb). ``sink=None`` stops the
        stream. The router (re)wires this whenever rendezvous buddy
        assignment changes."""
        self._refuse_slot_state(
            "configure_replication (decode-KV replication)")
        with self._lock:
            self._repl_sink = sink
            self._repl_origin = origin
            self._repl_cadence_s = float(cadence_s)
            if sink is None:
                self._repl_sent.clear()
        if sink is None:
            if self._repl_stop is not None:
                self._repl_stop.set()
            self._repl_thread = None
            return
        if self._repl_thread is None or not self._repl_thread.is_alive():
            self._repl_stop = threading.Event()
            self._repl_thread = threading.Thread(
                target=self._repl_loop, args=(self._repl_stop,),
                daemon=True, name="serving-kv-repl")
            self._repl_thread.start()

    def _repl_loop(self, stop: threading.Event) -> None:
        while not stop.is_set():
            try:
                self._replicate_once()
            except Exception as e:                    # noqa: BLE001
                from hetu_tpu.utils.logging import get_logger
                get_logger().debug(f"kv replication cadence: {e}")
            stop.wait(self._repl_cadence_s)

    def _replicate_once(self) -> None:
        """One replication cadence: for every decoding slot with a new
        COMPLETE block since its last shipment, ship the delta range
        (plus the partial tail block and a consistent pos/tokens/PRNG
        snapshot, captured in the same step-locked breath), then
        tombstone finished requests on the buddy. The step lock is held
        only for the snapshot + device→host gather — never across the
        sink's wire I/O — and is acquired with a cadence-sized timeout
        so a busy step just skips a beat."""
        if self._repl_sink is None:
            return
        bs = self.pool.block_size
        got = self._step_lock.acquire(timeout=self._repl_cadence_s)
        if not got:
            return
        jobs, drops = [], []
        try:
            with self._lock:
                sink = self._repl_sink
                if sink is None:
                    return
                live_ids = set()
                for slot, req in enumerate(self._slot_req):
                    if req is None or not self._active[slot] \
                            or req.handoff:
                        continue
                    live_ids.add(req.id)
                    pos = int(self._pos[slot])
                    complete = pos // bs
                    rec = self._repl_sent.get(req.id)
                    sent = rec[0] if rec is not None else -1
                    if sent >= 0 and complete <= sent:
                        continue    # no new whole block: nothing to do
                    start = max(0, sent)    # re-ship the old tail block
                    cur = max(1, -(-pos // bs))
                    jobs.append({
                        "req": req, "start": start, "cur": cur,
                        "pos": pos, "complete": complete,
                        "last_tok": int(self._last_tok[slot]),
                        "tokens": list(req.tokens),
                        "key_state": self._key_state[slot].copy(),
                        "ids": self._bt[slot, start:cur].copy()})
                for rid, rec in list(self._repl_sent.items()):
                    if rid not in live_ids:
                        drops.append(rec[1])
                        self._repl_sent.pop(rid, None)
            # device→host gathers still under the step lock (the fused
            # step DONATES the arena — unsynchronized reads race)
            for job in jobs:
                job["data"] = self._spill_blocks(
                    job["ids"], job["cur"] - job["start"])
        finally:
            self._step_lock.release()
        if not jobs and not drops:
            return
        from hetu_tpu.serving.fleet import array_to_wire
        reg = telemetry.get_registry()
        sink = self._repl_sink
        if sink is None:
            return
        for job in jobs:
            req = job["req"]
            doc = {"trace_id": req.trace_id,
                   "origin": self._repl_origin,
                   "req_id": req.id,
                   "weight_version": req.weight_version,
                   "block_size": bs, "pos": job["pos"],
                   "last_tok": job["last_tok"],
                   "tokens": job["tokens"], "start": job["start"],
                   "key_state": array_to_wire(job["key_state"]),
                   "traceparent": req.traceparent
                   or telemetry.make_traceparent(req.trace_id),
                   "data": [array_to_wire(a) for a in job["data"]]}
            try:
                sink(doc)
            except Exception:                         # noqa: BLE001
                continue      # buddy unreachable: same range retries
            with self._lock:
                self._repl_sent[req.id] = (job["complete"],
                                           req.trace_id)
            reg.counter(
                "fleet_kv_replicated_blocks_total",
                "decode-KV blocks streamed to the rendezvous buddy "
                "replica (block-granular cadence — the recovery set "
                "SIGKILL resumes from)").inc(job["cur"] - job["start"])
            flight_record("fleet_kv_replicate", req=req.id,
                          trace=req.trace_id, start=job["start"],
                          blocks=job["cur"] - job["start"],
                          pos=job["pos"])
        for tid in drops:
            try:
                sink({"drop": tid})
            except Exception:                         # noqa: BLE001
                pass          # cap-bounded store ages it out instead

    def prefill_only(self, prompt: Sequence[int],
                     sampling: Optional[SamplingParams] = None, *,
                     timeout_s: Optional[float] = None,
                     traceparent: Optional[str] = None
                     ) -> tuple[Request, Optional[SpillEntry]]:
        """Prefill-tier entry point (P/D disaggregation): admit
        ``prompt``, run its prefill (packed or CP lane) through the
        normal iteration machinery, and return ``(req, entry)`` where
        ``entry`` is the evicted :class:`SpillEntry` holding the
        finished KV blocks + the first token — ready to stream to a
        decode-tier replica's ``submit(resume=entry)``. ``entry`` is
        None when the request FINISHED within its first token (EOS or
        ``max_tokens=1`` — nothing left to decode; ``req.result()`` is
        the answer) or was rejected at admission.

        Works both driven (no background loop: iterations run here)
        and with :meth:`start` running (this just waits)."""
        self._refuse_slot_state(
            "prefill_only (prefill/decode disaggregation)")
        req = self.submit(prompt, sampling, handoff=True,
                          traceparent=traceparent)
        if req.status == "rejected":
            return req, None
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        while req.status != "prefilled" and not req.done.is_set():
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"prefill_only: request #{req.id} not prefilled "
                    f"within {timeout_s}s (status {req.status!r})")
            if self._thread is None:
                self.step()
            else:
                time.sleep(0.001)
        if req.done.is_set():
            return req, None
        return req, self.evict_request(req)

    # -- submission ---------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               sampling: Optional[SamplingParams] = None, *,
               resume: Optional[SpillEntry] = None,
               handoff: bool = False,
               traceparent: Optional[str] = None) -> Request:
        """Queue one request (deficit-selected by its priority class;
        pure FCFS when every request shares one class). Returns the
        live Request — poll ``req.done`` / :meth:`result`, or drive
        :meth:`step` yourself.

        ``resume`` attaches a KV spill from a peer engine (the
        router's resumable requeue): when the entry still speaks this
        pool's layout AND weight version, the request admits through
        the resume path — already-emitted tokens preloaded, zero
        prefill-lane work. An incompatible entry (e.g. the fleet
        swapped weights since the spill) silently degrades to a fresh
        replay, which under greedy decoding regenerates the same
        tokens.

        ``handoff`` is the prefill-tier mode (P/D disaggregation): the
        request runs admission + prefill here, then PARKS after its
        first token (status ``"prefilled"``, slot inactive but still
        holding its KV blocks) instead of decoding on — the caller
        (``prefill_only`` / the fleet router) evicts the KV and resumes
        it on a decode-tier replica."""
        sampling = sampling or SamplingParams()
        self._check_generation(sampling, resume=resume, handoff=handoff)
        if sampling.adapter is not None and self.tenancy is None:
            raise ValueError(
                "SamplingParams.adapter without tenancy= — construct "
                "the engine with ServingEngine(..., tenancy=True) and "
                "load_adapter first")
        if sampling.temperature > 0 and self.spec_depth \
                and self._draftsman is not None:
            # sampled speculation runs the rejection-sampling verify
            # lane, which needs the draftsman's proposal probs (q) —
            # fail the submit loudly instead of silently mis-sampling
            check_sampled_draft(self._draftsman)
        if handoff and resume is not None:
            raise ValueError(
                "handoff with resume makes no sense: a resumed "
                "request's KV already exists — submit it to the "
                "decode tier directly")
        # adopt the wire trace context: an explicit traceparent wins,
        # else the spill's (a decode-tier resume inherits the trace the
        # prefill tier stamped into the KV stream) — ISSUE 16
        tp = traceparent or (resume.traceparent
                             if resume is not None else None)
        # what this handler thread waits for the loop's lock goes on
        # the request (the wire's server/submit event carries it)
        t_lock = time.perf_counter()
        with self._lock:
            req = Request(id=self._next_id,
                          prompt=np.asarray(prompt, np.int32).ravel(),
                          sampling=sampling, submit_s=time.monotonic(),
                          handoff=bool(handoff),
                          lock_wait_s=time.perf_counter() - t_lock)
            self._next_id += 1
            if tp:
                tid, _span = telemetry.parse_traceparent(tp)
                if tid:
                    req.trace_id = tid
                    req.traceparent = tp
            exp_adapter = 0
            if self.tenancy is not None and sampling.adapter is not None:
                registry = self.tenancy.registry
                if not registry.has(sampling.tenant, sampling.adapter):
                    req.status = "rejected"
                    req.error = (f"unknown adapter {sampling.tenant}/"
                                 f"{sampling.adapter} — load_adapter "
                                 f"first")
                    req.done.set()
                    admitted = False
                else:
                    exp_adapter = registry.kv_tag(
                        registry.get(sampling.tenant, sampling.adapter))
                    req.kv_adapter = exp_adapter
            if req.status != "rejected":
                if resume is not None and resume.compatible_with(
                        self.pool, self.weight_version,
                        adapter=exp_adapter):
                    req.spill = resume
                    req.tokens = list(resume.tokens)
                    req.weight_version = resume.weight_version
                admitted = self.scheduler.submit(req)
                if admitted and req.cp_lane \
                        and sampling.adapter is not None:
                    # the CP-prefill lane is base-only (its one-pass
                    # training-mode forward has no BGMV thread yet —
                    # docs/SERVING.md): refuse loudly instead of
                    # serving the base model under the tenant's name
                    self.scheduler.queue.remove(req)
                    req.status = "rejected"
                    req.error = (
                        "adapter requests cannot take the CP-prefill "
                        "lane (base-only long-prompt path) — shorten "
                        "the prompt or raise max_len")
                    req.done.set()
                    admitted = False
        self._m.requests.inc(
            outcome="submitted" if admitted else "rejected")
        flight_record("serving_submit", req=req.id, trace=req.trace_id,
                      prompt_len=len(req.prompt),
                      outcome="queued" if admitted else "rejected")
        self._record_gauges(tiers=True)
        return req

    def _check_generation(self, sp: SamplingParams, *, resume,
                          handoff: bool) -> None:
        """What a request may ask of this engine's way of generating:
        the block-diffusion knobs of a model that states one and of no
        other; of such a model greedy tokens alone, and neither a
        resumed cache nor the prefill tier's hand-off."""
        asked = [n for n in ("denoising_steps", "remasking",
                             "confidence_threshold")
                 if getattr(sp, n) is not None]
        g = self._gen
        if g is None:
            if asked:
                raise ValueError(
                    f"SamplingParams.{asked[0]} is for a model that "
                    f"generates by diffusion over blocks; this one "
                    f"yields a token a step")
            return
        block_diffusion.refuse(**{
            "temperature > 0 (the block sampler is greedy)":
                sp.temperature > 0,
            "resume= (a cache spilled mid-block)": resume is not None,
            "handoff (the prefill tier parks after a first token; a "
            "block lane has none before its first block)": handoff})
        steps = sp.denoising_steps
        if steps is not None and not 1 <= steps <= g.block_length:
            raise ValueError(f"denoising_steps {steps} of a block of "
                             f"{g.block_length}")
        if sp.remasking is not None and sp.remasking not in REMASKING:
            raise ValueError(f"remasking {sp.remasking!r}: one of "
                             f"{sorted(REMASKING)}")

    def result(self, req: Request,
               timeout: Optional[float] = None) -> Optional[dict]:
        """Wait for ``req`` to finish; None on timeout."""
        if not req.done.wait(timeout):
            return None
        return req.result()

    # -- push subscriptions (ISSUE 19) --------------------------------------
    def stream_subscribe(self, req: Request, *, offset: int = 0,
                         max_queue: int = 256):
        """Subscribe to ``req``'s token stream from token ``offset``:
        the backlog past the offset is replayed immediately (an
        already-finished request yields its single terminal event),
        then the end-of-step pump feeds newly committed tokens. The
        returned :class:`~hetu_tpu.serving.streaming.TokenSubscription`
        is a bounded queue — a consumer that stops draining is dropped,
        never waited on."""
        from hetu_tpu.serving.streaming import (
            TokenSubscription, push_delta,
        )
        sub = TokenSubscription(req.id, offset=offset,
                                max_queue=max_queue)
        with self._stream_lock:
            push_delta(req, sub)        # replay (possibly terminal)
            if not sub.closed:
                ent = self._stream_subs.get(req.id)
                if ent is None:
                    self._stream_subs[req.id] = (req, [sub])
                else:
                    ent[1].append(sub)
        return sub

    def _pump_stream_subs(self) -> int:
        """End-of-step push: fold each subscribed request's newly
        committed tokens (and finish/interrupt markers) into its
        subscriber queues; returns the number of events made (the
        step's ``frames``). Enqueue-only, pure host work — the fused
        step's 1-compile audit is untouched and a slow subscriber
        overflows its own bounded queue instead of stalling the
        iteration (drop-to-poll, counted)."""
        if not self._stream_subs:
            return 0
        from hetu_tpu.serving.streaming import push_delta
        n = 0
        with self._stream_lock:
            for rid in list(self._stream_subs):
                req, subs = self._stream_subs[rid]
                for sub in subs:
                    n += push_delta(req, sub) is not None
                live = [s for s in subs
                        if not (s.closed or s.dropped)]
                if live:
                    self._stream_subs[rid] = (req, live)
                else:
                    del self._stream_subs[rid]
        return n

    def _stream_interrupt(self, req: Request) -> None:
        """Close ``req``'s subscriptions after an out-of-band exit
        (evict / cancel, which happen between steps): the final delta
        plus an ``end`` marker tells subscribers to fall back — the
        router's requeue owns the request now."""
        if not self._stream_subs:
            return
        from hetu_tpu.serving.streaming import push_delta
        with self._stream_lock:
            ent = self._stream_subs.pop(req.id, None)
        if ent is None:
            return
        for sub in ent[1]:
            push_delta(req, sub)
            sub.close()

    # -- the host loop ------------------------------------------------------
    def has_work(self) -> bool:
        with self._lock:
            return bool(self.scheduler.queue) or self._active.any() \
                or bool(self._prefilling) or bool(self._cp_pending) \
                or bool(self._resume_pending)

    @property
    def load(self) -> int:
        """Instantaneous work on this engine — queued + prefilling +
        decoding requests. The router's least-loaded dispatch reads
        exactly this (it is what the ``serving_queue_depth`` /
        ``serving_slot_occupancy`` gauges sample, as one number)."""
        with self._lock:
            return self.scheduler.depth + len(self._prefilling) \
                + len(self._cp_pending) + len(self._resume_pending) \
                + int(self._active.sum())

    # -- fleet lifecycle (router drain / live weight push) ------------------
    def cancel_queued(self, ids=None) -> list[Request]:
        """Pull QUEUED (not yet admitted) requests out of the scheduler
        and return them — the router's drain path re-dispatches them
        onto peer replicas. ``ids`` restricts the pull to those request
        ids (the router passes the set it owns, so a request submitted
        DIRECTLY to this engine is never orphaned — it stays queued and
        drains through normal admission). Admitted requests are always
        untouched: their KV is resident, so finishing them here is
        strictly cheaper than regenerating elsewhere."""
        with self._lock:
            if ids is None:
                out = list(self.scheduler.queue)
                self.scheduler.queue.clear()
            else:
                out = [r for r in self.scheduler.queue if r.id in ids]
                for r in out:
                    self.scheduler.queue.remove(r)
            # a preempted request leaving the engine takes its spill
            # with it (the peer that resumes it counts the map-back)
            for r in out:
                if r.spill is not None \
                        and self.spill_arena.get(r.id) is r.spill:
                    self.spill_arena.pop(r.id, resumed=False)
                self._release_tenancy(r)
        return out

    def swap_params(self, params, *, version: Optional[int] = None) -> dict:
        """Install a new parameter pytree on a DRAINED engine — the
        replica-local leg of a zero-downtime fleet weight push.

        Grabs the iteration lock (so a live :meth:`start` loop is
        between iterations — it never stops), requires no in-flight
        work (drain first: queued work was re-dispatched by the router,
        admitted work ran out under the old weights), bumps the weight
        generation on the engine + KV pool, and flushes the prefix
        cache's now-stale residents. The caller owns ``params``'s
        placement: pass buffers that nothing will donate later
        (``serving.router.materialize_params``)."""
        with self._step_lock:
            with self._lock:
                if self.scheduler.queue or self._prefilling \
                        or self._cp_pending or self._resume_pending \
                        or self._active.any():
                    raise RuntimeError(
                        "swap_params on a busy engine — drain first "
                        "(cancel_queued + wait for has_work() to clear)"
                        ": in-flight KV was prefilled under the old "
                        "weights")
                self.params = params
                # stale int8 decode weights would silently serve the
                # OLD parameters — re-quantize from the new tree
                self._w8a8_wq = self._prequantize_decode_weights()
                self.weight_version = int(version) \
                    if version is not None else self.weight_version + 1
                self.pool.weight_version = self.weight_version
                flushed = 0
                if self.prefix_cache is not None:
                    flushed = self.prefix_cache.set_version(
                        self.weight_version)
                if flushed:
                    telemetry.get_registry().counter(
                        "serving_prefix_flushed_total",
                        "prefix-cache blocks flushed because their KV "
                        "was computed under superseded weights").inc(
                        flushed)
                self._record_gauges()
        flight_record("weight_swap", version=self.weight_version,
                      flushed_blocks=flushed)
        return {"version": self.weight_version,
                "flushed_blocks": flushed}

    # -- multi-tenant adapter plane (serving/tenancy.py) --------------------
    def _init_adapter_arena(self) -> dict:
        """Zero-filled device pages for every LoRA-targetable stacked
        projection in the param tree: projection name → ``{"A":
        (L, P, in, r), "B": (L, P, r, out)}`` float32, P =
        ``max_adapters``. Page 0 stays all-zero forever — the base
        model's delta is exactly 0.0, and ``lora_apply``'s masked
        select keeps id-0 tokens BITWISE base. MoE FFNs carry no dense
        fc_in/gate/up leaves, so expert weights are never paged —
        attention adapters still apply there."""
        from hetu_tpu.serving.tenancy import DEFAULT_TARGETS
        P, r = self.tenancy.max_adapters, self.tenancy.r
        pages: dict = {}
        blocks = self.params.get("blocks", {})
        for group in ("attn", "mlp"):
            sub = blocks.get(group) if isinstance(blocks, dict) else None
            if not isinstance(sub, dict):
                continue
            for name, node in sub.items():
                if name not in DEFAULT_TARGETS \
                        or not isinstance(node, dict):
                    continue
                w = node.get("weight")
                if w is None or getattr(w, "ndim", 0) != 3:
                    continue
                L, d_in, d_out = w.shape
                pages[name] = {
                    "A": jnp.zeros((L, P, d_in, r), jnp.float32),
                    "B": jnp.zeros((L, P, r, d_out), jnp.float32)}
        if not pages:
            raise ValueError(
                "tenancy= on a model with no LoRA-targetable stacked "
                "projections (expected blocks/attn/{q,k,v,out}_proj "
                "and/or dense-FFN leaves in the param tree)")
        return pages

    def _write_adapter_page(self, page: int, spec) -> None:
        """Registry hook: (re)write one arena page. ``spec`` None
        zeroes the page (evict — a later gather of a freed page must
        read exact zeros, not the evicted tenant's weights). Functional
        ``.at[:, page].set`` builds NEW buffers and rebinds the tree —
        an in-flight fused step keeps its own operands; the next
        iteration picks up the rewrite. Shapes never change, so the
        step never retraces."""
        new = {}
        for name, ab in self._lora_pages.items():
            src = spec.weights.get(name) if spec is not None else None
            if src is None:
                new[name] = {"A": ab["A"].at[:, page].set(0.0),
                             "B": ab["B"].at[:, page].set(0.0)}
            else:
                new[name] = {
                    "A": ab["A"].at[:, page].set(
                        jnp.asarray(src["A"], jnp.float32)),
                    "B": ab["B"].at[:, page].set(
                        jnp.asarray(src["B"], jnp.float32))}
        self._lora_pages = new

    def load_adapter(self, tenant: Optional[str], name: str,
                     weights=None, *, path: Optional[str] = None,
                     version: Optional[int] = None,
                     scaling: float = 1.0) -> dict:
        """Register (or hot-swap) a tenant's LoRA adapter and make it
        arena-resident when a page can be had.

        ``weights`` is projection → ``{"A": (L, in, ra), "B":
        (L, ra, out)}`` host arrays (``peft.lora`` order — pass the
        model's ``tenancy.lora_scaling`` as ``scaling`` for merge
        parity); ``path=`` instead loads a
        :func:`~hetu_tpu.serving.tenancy.save_adapter_distributed`
        checkpoint (version/scaling from its manifest unless
        overridden). Replacing a live version is safe under traffic:
        the old version's page drains when its last in-flight request
        releases, its prefix-cache spans flush eagerly, and the new
        version's fresh uid means no stale KV can ever match."""
        if self.tenancy is None:
            raise RuntimeError(
                "load_adapter on an engine without tenancy= — "
                "construct with ServingEngine(..., tenancy=True)")
        if (weights is None) == (path is None):
            raise ValueError("pass exactly one of weights= or path=")
        if path is not None:
            from hetu_tpu.serving.tenancy import load_adapter_distributed
            weights, fver, scaling = load_adapter_distributed(path)
            if version is None:
                version = fver
        unknown = set(weights) - set(self._lora_pages)
        if unknown:
            raise ValueError(
                f"adapter targets projections this model does not "
                f"page: {sorted(unknown)} (arena pages: "
                f"{sorted(self._lora_pages)})")
        for proj, ab in weights.items():
            pg = self._lora_pages[proj]
            L, _, d_in, _ = pg["A"].shape
            d_out = pg["B"].shape[-1]
            a, b = np.asarray(ab["A"]), np.asarray(ab["B"])
            if a.shape[0] != L or a.shape[1] != d_in \
                    or b.shape[-1] != d_out:
                raise ValueError(
                    f"{proj}: adapter pages {a.shape}/{b.shape} do not "
                    f"fit this model's ({L}, {d_in}, ·)/(·, {d_out}) "
                    f"projection")
        registry = self.tenancy.registry
        with self._lock:
            prev_uid = None
            if registry.has(tenant, name):
                prev_uid = registry.get(tenant, name).uid
            spec = registry.register(tenant, name, weights,
                                     version=version, scaling=scaling)
            flushed = 0
            if prev_uid is not None and self.prefix_cache is not None:
                # the replaced version's cached spans are already
                # unmatchable (fresh uid) but still pin blocks —
                # return them to the free list now
                flushed = self.prefix_cache.flush_adapter(prev_uid)
            try:
                registry.ensure_resident(tenant, name)
            except AdapterArenaFull:
                pass    # loads lazily at this adapter's first admission
        if flushed:
            telemetry.get_registry().counter(
                "serving_prefix_flushed_total",
                "prefix-cache blocks flushed because their KV "
                "was computed under superseded weights").inc(flushed)
        return {"tenant": tenant, "name": name,
                "version": spec.version, "uid": spec.uid,
                "page": spec.page, "flushed_blocks": flushed}

    def evict_adapter(self, tenant: Optional[str], name: str) -> dict:
        """Deregister a tenant's adapter: the arena page frees now when
        idle (else when its last in-flight request releases), and its
        prefix-cache spans return their blocks eagerly."""
        if self.tenancy is None:
            raise RuntimeError(
                "evict_adapter on an engine without tenancy=")
        registry = self.tenancy.registry
        with self._lock:
            uid = None
            if registry.has(tenant, name):
                uid = registry.get(tenant, name).uid
            registry.deregister(tenant, name)
            flushed = 0
            if uid is not None and self.prefix_cache is not None:
                flushed = self.prefix_cache.flush_adapter(uid)
        return {"flushed_blocks": flushed}

    def _admission_gate(self, req: Request) -> bool:
        """Scheduler eligibility filter (installed when tenancy is on;
        the scheduler calls it under the engine lock). False DEFERS the
        request without burning its class's deficit credits: tenant
        token-bucket / slot-cap throttles and adapter-arena-full waits
        — so a throttled tenant's backlog never blocks other tenants.
        Also refreshes ``req.kv_adapter`` so the page plan the
        scheduler prices next matches the adapter version that will
        actually serve the request (a hot-swap between submit and
        admission re-tags it here)."""
        sp = req.sampling
        if req.adapter_ref is not None:
            return True      # preempted resume: already pinned + paid
        reason = None if req.id in self._qos_admitted \
            else self.tenancy.qos.check(sp.tenant)
        if reason is not None:
            if req.id not in self._throttle_logged:
                self._throttle_logged.add(req.id)
                telemetry.get_registry().counter(
                    "tenant_throttled_total",
                    "admissions deferred by tenant QoS (token-bucket "
                    "rate or concurrent-slot cap), one per throttle "
                    "episode").inc(tenant=sp.tenant or "base",
                                   reason=reason)
                flight_record("tenant_throttle", req=req.id,
                              tenant=sp.tenant, reason=reason)
            return False
        if sp.adapter is not None:
            registry = self.tenancy.registry
            if registry.has(sp.tenant, sp.adapter):
                if not registry.resident(sp.tenant, sp.adapter) \
                        and not registry.can_load():
                    # every page pinned by in-flight requests: wait
                    # (loud, once per episode) instead of failing
                    if req.id not in self._wait_logged:
                        self._wait_logged.add(req.id)
                        flight_record("adapter_wait", req=req.id,
                                      tenant=sp.tenant,
                                      adapter=sp.adapter)
                    return False
                req.kv_adapter = registry.kv_tag(
                    registry.get(sp.tenant, sp.adapter))
        return True

    def _bind_adapter_locked(self, req: Request, slot: int) -> bool:
        """Pin the request's tenancy state at admission (caller holds
        the lock): acquire an adapter-page ref — held across preemption,
        so a resume is guaranteed the same uid/page — stamp the slot's
        arena page + the request's KV-compat tag, and pay the tenant's
        QoS admit exactly once per request lifetime. False = the
        adapter vanished between submit and admission (deregistered):
        the request fails loudly and its slot/blocks unwind."""
        sp = req.sampling
        reg_ = telemetry.get_registry()
        if sp.adapter is not None and req.adapter_ref is None:
            try:
                spec = self.tenancy.registry.acquire(sp.tenant,
                                                     sp.adapter)
            except (KeyError, AdapterArenaFull) as err:
                # KeyError: deregistered since submit. AdapterArenaFull
                # is defensive — the admission gate defers requests the
                # arena cannot page, so admission never sees it.
                req.status, req.error = "rejected", str(err)
                self.scheduler.release(
                    slot, table=np.asarray(req.admit["table"],
                                           np.int32))
                reg_.counter("serving_requests_total",
                             "serving requests by outcome").inc(
                    outcome="rejected")
                flight_record("serving_reject", req=req.id,
                              trace=req.trace_id, reason=str(err))
                req.done.set()
                return False
            req.adapter_ref = spec
            req.kv_adapter = self.tenancy.registry.kv_tag(spec)
        self._adapter_page[slot] = req.adapter_ref.page \
            if req.adapter_ref is not None else 0
        if req.id not in self._qos_admitted:
            self._qos_admitted.add(req.id)
            self.tenancy.qos.on_admit(sp.tenant)
            reg_.counter("tenant_requests_total",
                         "admitted serving requests per tenant").inc(
                tenant=sp.tenant or "base")
        self._throttle_logged.discard(req.id)
        self._wait_logged.discard(req.id)
        return True

    def _release_tenancy(self, req: Request) -> None:
        """Drop a request's tenancy holds as it leaves the engine
        (finish, eviction out to the fleet, drain): the adapter-page
        ref and the tenant's QoS slot. The slot's arena-page stamp is
        cleared by ``_detach_locked``/``_finish``. Preemption does NOT
        come through here — a preempted request keeps its ref so its
        resume is guaranteed the same adapter uid."""
        if self.tenancy is None:
            return
        if req.adapter_ref is not None:
            self.tenancy.registry.release(req.adapter_ref)
            req.adapter_ref = None
        if req.id in self._qos_admitted:
            self._qos_admitted.discard(req.id)
            self.tenancy.qos.on_finish(req.sampling.tenant)
        self._throttle_logged.discard(req.id)
        self._wait_logged.discard(req.id)

    def step(self) -> bool:
        """One engine iteration; False when there was nothing to do.
        Safe to call while the :meth:`start` loop runs (iterations are
        serialized), though one driver is the intended mode."""
        with self._step_lock:
            return self._step_locked()

    def _admit_locked(self, now: float) -> list[tuple[int, int]]:
        """Admit every admissible queued request (slots + free blocks
        permitting): map its prefix-cache plan into the slot's block
        table and queue its prefill. Returns this iteration's CoW
        (src, dst) block pairs."""
        cows: list[tuple[int, int]] = []
        while True:
            adm = self.scheduler.next_admission()
            if adm is None:
                break
            req, slot = adm
            if self.tenancy is not None \
                    and not self._bind_adapter_locked(req, slot):
                continue
            self._n_admitted += 1
            req.weight_version = self.weight_version
            sp = req.sampling
            self._temp[slot] = sp.temperature
            self._topk[slot] = sp.top_k
            self._topp[slot] = sp.top_p
            # seed the slot's commit-key stream: an explicit
            # SamplingParams.seed replays bit-for-bit against one-shot
            # generate(rng=jax.random.key(seed)); otherwise derive a
            # per-request stream from the engine seed
            k0 = jax.random.key(int(sp.seed)) if sp.seed is not None \
                else jax.random.fold_in(self._key, req.id)
            self._key_state[slot] = np.asarray(jax.random.key_data(k0))
            self._slot_req[slot] = req
            # (no arena: the slot is the whole plan)
            plan = req.admit or {"table": (), "first_uncached": 0,
                                 "cow": None}
            self._bt[slot, :] = 0
            self._bt[slot, :len(plan["table"])] = plan["table"]
            if plan["cow"] is not None:
                cows.append(plan["cow"])
            if plan.get("resume"):
                # a preempted request coming back: its KV re-maps from
                # the host spill arena — no prefill lane, no cp lane
                self._resume_pending.append({"req": req, "slot": slot})
            elif req.cp_lane:
                # beyond one slot's budget: one cp-sharded prefill pass
                # instead of the packed chunk loop
                self._cp_pending.append({"req": req, "slot": slot})
            elif self._gen is None:
                self._prefilling.append(
                    {"req": req, "slot": slot,
                     "off": plan["first_uncached"],
                     "end": len(req.prompt)})
            else:
                # the prompt's whole blocks are prefilled; its tail
                # begins the first generated block. The request's own
                # steps, rule and threshold, or the model's
                g, B = self._gen, self._gen.block_length
                b = self._blk
                b["blk_steps"][slot] = sp.denoising_steps \
                    or g.denoising_steps
                b["blk_dynamic"][slot] = REMASKING[
                    sp.remasking or g.remasking]
                b["blk_thresh"][slot] = g.confidence_threshold \
                    if sp.confidence_threshold is None \
                    else sp.confidence_threshold
                end = len(req.prompt) // B * B
                if end:
                    self._prefilling.append(
                        {"req": req, "slot": slot, "off": 0, "end": end})
                else:
                    self._begin_blocks(slot, req)
            if self._draftsman is not None:
                # the slot's draft state belongs to its NEW occupant
                # (resumes re-seed with the full history at map-back)
                self._draftsman.reset(slot, req.prompt.tolist())
            self._ctl_dirty = True           # new sampling params + bt
            hit = req.cached_tokens
            if hit:
                self._m.prefix_hit.inc(hit)
            self._m.prefix_miss.inc(len(req.prompt) - hit)
            flight_record("serving_admit", req=req.id,
                          trace=req.trace_id, slot=slot,
                          cached_tokens=hit, cp_lane=req.cp_lane,
                          queued_s=round(now - req.submit_s, 4))
        ev = self.scheduler.evictions_total
        if ev > self._evictions_synced:
            self._m.evictions.inc(ev - self._evictions_synced)
            self._evictions_synced = ev
        return cows

    def _aux_jobs_locked(self) -> tuple:
        """This iteration's CP-lane prefill and spill-resume, if any
        (caller holds ``self._lock``). They run as their own
        (bucket-audited) executables before the fused step — at most
        ONE of each per iteration, device call and upload OUTSIDE the
        lock."""
        return (self._prep_cp_prefill_locked(),
                self._resume_pending.pop(0)
                if self._resume_pending else None)

    def _step_locked(self) -> bool:
        if not self.has_work():
            return False            # an idle turn records nothing
        # one span per phase, none per token: serve/step and its
        # children are what a jax.profiler trace shows above the fused
        # step's ops (docs/OBSERVABILITY.md); the tracer records them
        # too when telemetry is on
        with telemetry.span("serve/step", iter=self._iter + 1) as sp:
            return self._step_spanned(sp)

    def _step_spanned(self, step_span) -> bool:
        # the iteration's own account (docs/OBSERVABILITY.md, "the
        # serving loop's account"): the loop thread's CPU clock beside
        # the spans' wall clock, and what acquiring self._lock cost —
        # a dozen clock reads an iteration, nothing per token or slot
        pc0 = time.perf_counter()
        cpu0 = time.thread_time()
        lock = self._loop_lock
        lock.waited_s = 0.0
        admitted0 = self._n_admitted
        t0 = time.monotonic()
        span = telemetry.span
        m = self._m
        C = self.prefill_chunk
        R = self._fin_cap
        K = self._lane_rows - 1
        S = self.pool.slots
        it = self._iter + 1
        active_prev = None
        with span("serve/admit"), lock:
            cows = self._admit_locked(t0)
            # preemption runs AFTER admission, so it fires only when
            # the deficit-selected head genuinely could not admit —
            # prefix-cache credit and cache eviction (which _page_plan
            # already spends) admit for free before anyone is evicted
            spill_job = self._plan_preemption_locked()
            if spill_job is None:
                cp_job, resume_job = self._aux_jobs_locked()
                if cp_job is None and resume_job is None:
                    # the common iteration: no executable of its own
                    # runs before the fused step
                    active_prev = np.nonzero(self._active)[0]
        did_aux = active_prev is None
        if did_aux:
            if spill_job is not None:
                with span("serve/aux", what="spill"):
                    self._exec_spill(spill_job)
                with span("serve/admit"), lock:
                    # second admission pass picks up the freed
                    # slot/blocks in THIS iteration (the urgent head
                    # does not wait one)
                    cows += self._admit_locked(t0)
                    cp_job, resume_job = self._aux_jobs_locked()
            if resume_job is not None:
                with span("serve/aux", what="resume"):
                    self._exec_resume(resume_job)
            if cp_job is not None:
                with span("serve/aux", what="cp_prefill"):
                    self._exec_cp_prefill(cp_job, t0)
            with span("serve/admit"), lock:
                active_prev = np.nonzero(self._active)[0]
        if not self._prefilling and active_prev.size == 0 and not cows:
            # nothing for the fused step (the loop thread alone changes
            # _prefilling, under the iteration lock it holds)
            if did_aux:
                with lock:
                    self._record_gauges(tiers=True)
            return did_aux
        # speculative drafts: per-slot depth + tokens are DATA
        # operands rebuilt every iteration. Depth clamps: never
        # beyond the request's remaining token budget - 1 (so
        # commits can't blow past max_tokens or the slot's
        # allocated blocks). Sampled (temperature > 0) slots
        # speculate too — the rejection-sampling verify lane keeps
        # their output distribution exact (``speculative_verify``).
        # The n-gram index is host-only and proposes here; the
        # model draftsman's DEVICE step runs between the lock
        # windows below (submit()/load stay responsive through it —
        # the iteration lock we hold keeps its inputs frozen).
        d_tok = d_len = d_q = None
        if K and self._draftsman is not None:
            with span("serve/draft"):
                d_tok = np.zeros((S, K), np.int32)
                d_len = np.zeros(S, np.int32)
                if not self._draftsman.host_only:
                    # device draftsman: its q rows ride the spec
                    # operand — ALWAYS present so the step's pytree
                    # signature (and the 1-compile audit) never
                    # depends on churn
                    d_q = np.zeros((S, K, self.model.cfg.vocab_size),
                                   np.float32)
                if active_prev.size:
                    d_tok, d_len, d_q = self._draft(
                        active_prev, d_tok, d_len, d_q)
        with span("serve/pack"), lock:
            if d_tok is None:                    # no draftsman
                d_tok = np.zeros((S, K), np.int32)
                d_len = np.zeros(S, np.int32)
            if self._ctl_dirty:
                # uploaded to the step's home: pos/last_tok/key come
                # back from the step on it, and a differently-typed
                # upload would be a different program
                self._ctl_dev, self._bt_dev = jax.device_put(
                    ({"pos": self._pos, "last_tok": self._last_tok,
                      "active": self._active, "temp": self._temp,
                      "topk": self._topk, "topp": self._topp,
                      "key": self._key_state,
                      "adapter": self._adapter_page,
                      **(self._blk if self._gen else {})}, self._bt),
                    self._rep)
                self._ctl_dirty = False
                m.transfers.inc(9 + (len(self._blk) if self._gen else 0),
                                dir="up")
            ctl = self._ctl_dev
            if self._active.any():
                # the decode lane's sampler: the step's own predicate
                # (speculative.sample_needs), on the uploaded vectors
                # (the block lane has its own: its passes are counted
                # at the commit)
                if self._gen is None:
                    m.sample_path.inc(lane="decode", path=sample_path(
                        *sample_needs(self._active, self._temp,
                                      self._topk, self._topp)))
                if self._chunk_steps:
                    # an active slot's K + 1 rows end at position pos +
                    # K, in chunk (pos + K) // span: its pairs are that
                    # chunk and those below, each a tile of chunk-span keys
                    # of which the rows see those up to that position
                    tile, steps = self._chunk_span, self._chunk_steps
                    last = self._pos[self._active] + K
                    if self._gen is not None:
                        # (a block's rows stand at 0..2B-1 where it
                        # starts below B: block_diffusion.lane_rows)
                        last = np.maximum(last, 2 * K + 1)
                    live = int(np.minimum(last // tile + 1, steps).sum())
                    m.decode_chunks.inc(live, state="live")
                    m.decode_chunks.inc(S * steps - live, state="skipped")
                    keys = int(np.minimum(last + 1, steps * tile).sum())
                    m.decode_tile_keys.inc(keys, state="live")
                    m.decode_tile_keys.inc(live * tile - keys,
                                           state="masked")
            # pack the prefill budget FCFS over in-flight prefills: the
            # oldest request fills first (so a lone request's chunk
            # count matches the PR 5 single-admission engine), the rest
            # share what remains — a burst's TTFT now scales with total
            # prompt tokens / C, not with queue depth
            tokens = np.zeros(C, np.int32)
            tpos = np.zeros(C, np.int32)
            tslot = np.zeros(C, np.int32)
            tvalid = np.zeros(C, bool)
            tseg = np.full(C, -1, np.int32)      # -1 isolates pad lanes
            thist = np.zeros(C, np.int32)        # per-token chunk start
            fin_row = np.zeros(R, np.int32)
            fin_slot = np.zeros(R, np.int32)
            fin_valid = np.zeros(R, bool)        # rows really finishing
            fills: list[tuple[dict, int]] = []   # (entry, n) this iter
            fin_ents: list[dict] = []            # completes this iter
            runs = []                # (slot, first row, tokens, hist)
            used = 0
            for ent in self._prefilling:         # empty on the common
                if used >= C:                    # decode-only iteration
                    break
                req, off = ent["req"], ent["off"]
                n = int(min(C - used, ent["end"] - off))
                tokens[used:used + n] = req.prompt[off:off + n]
                tpos[used:used + n] = np.arange(off, off + n)
                tslot[used:used + n] = ent["slot"]
                tvalid[used:used + n] = True
                # flash-lane operands: segment id = the slot (one
                # contiguous run per request per pack, so index-causal
                # == position-causal within it); hist = the run's
                # start offset — arena rows below it (earlier chunks,
                # prefix-cache hits) belong to the history part
                tseg[used:used + n] = ent["slot"]
                thist[used:used + n] = off
                runs.append((ent["slot"], used, n, off))
                if off + n >= ent["end"]:
                    fin_row[len(fin_ents)] = used + n - 1
                    fin_slot[len(fin_ents)] = ent["slot"]
                    fin_valid[len(fin_ents)] = True
                    fin_ents.append(ent)
                fills.append((ent, n))
                used += n
            if used and self._gen is None:
                # the prefill lane's sampler sees the finishing rows
                m.sample_path.inc(lane="prefill", path=sample_path(
                    *sample_needs(fin_valid, self._temp[fin_slot],
                                  self._topk[fin_slot],
                                  self._topp[fin_slot])))
            pf = {"run": np.bool_(used > 0), "tokens": tokens,
                  "pos": tpos, "slot": tslot, "valid": tvalid,
                  "seg": tseg, "hist": thist, "fin_row": fin_row,
                  "fin_slot": fin_slot, "fin_valid": fin_valid}
            if self._hist_tiles:
                # the history read's tile map: runs with history first,
                # every other tile dead — data, on the one upload
                pf["tiles"], (live, empty, rows) = self._pack_tiles(
                    runs, tile_rows=self._hist_tile,
                    n_tiles=self._hist_tiles, every_run=self._every_run)
                if live:
                    m.hist_tiles.inc(live, state="live")
                    m.hist_rows.inc(rows)
                    if not self._every_run:   # (chunks under a cap)
                        self._count_hist_chunks(pf["tiles"][:, :live])
                if empty:
                    m.hist_tiles.inc(empty, state="empty")
            # CoW lanes: unused dst = n_blocks scatters out of bounds
            cow_src = np.zeros(S, np.int32)
            cow_dst = np.full(S, self.pool.n_blocks, np.int32)
            for i, (src, dst) in enumerate(cows):
                cow_src[i], cow_dst[i] = src, dst
            cow = {"run": np.bool_(bool(cows)), "src": cow_src,
                   "dst": cow_dst} if self._paged else {}
            bt = self._bt_dev
            spec = {"tok": d_tok, "len": d_len}
            if d_q is not None:
                spec["q"] = d_q
            args = (self.params, self.pool.caches, ctl, pf, bt, cow,
                    spec, self._w8a8_wq, self._lora_pages)
        if not self._scopes_registered:
            self._register_device_scopes(args)
        ctx = self._plan.act if self._plan is not None \
            else contextlib.nullcontext()
        # ``iter`` on both: a reader of the profiler's trace pairs this
        # iteration's program on the device with the two spans around it
        # (launch lag, fetch lag). The host operands (pf, cow, spec) are
        # uploaded inside the call, an array at a time, by jit's own
        # argument handling; the ONE fetch of what the host reads of
        # the step starts as soon as the call returns
        with span("serve/dispatch", iter=it), ctx:
            caches, pos_dev, last_dev, key_dev, out, *blk_dev = \
                self._fn(*args)
            del args                # the arena was donated
            self.pool.caches = caches
            out.copy_to_host_async()
            m.transfers.inc(len(pf) + len(cow) + len(spec), dir="up")
        with span("serve/device_wait", iter=it):
            cpu_w = time.thread_time()
            out_host = np.asarray(out)
            cpu_w = time.thread_time() - cpu_w
            now = time.monotonic()
            m.transfers.inc(dir="down")

        n_generated = 0
        with span("serve/commit"), lock:
            self._iter += 1
            res = self._results.unpack_host(out_host)
            em = res["committed"]                # (S, K+1)
            nc = res["ncommit"]                  # (S,)
            ft = res["first_toks"]
            # the host mirror of the per-slot commit keys always tracks
            # the device: the step advanced them (verify consumption +
            # prefill first-token draws) for exactly the slots that
            # sampled this iteration
            self._key_state[:] = res["key"]
            if active_prev.size:
                m.slot_steps.inc(int(active_prev.size))
                m.attn_kernel.inc(path=self.attn_kernel)
            if used:
                m.prefill_kernel.inc(path=self._prefill_path)
            if telemetry.enabled():
                # the layers' stats of the lanes that ran, to the host
                # functions the model's block names for them, with the
                # token rows a call of that lane's layers takes
                emit = self.model.blocks.layer_stats
                # (the block lane runs two blocks of rows a slot)
                for ran, rows, stats in zip(
                        (active_prev.size, used),
                        (em.size * (2 if self._gen else 1),
                         pf["tokens"].size), res["stats"]):
                    for name, values in stats.items() if ran else ():
                        emit[name][2](values, tokens=rows)
            # decode results for the slots that were active going in:
            # each commits ncommit tokens (accepted drafts + bonus) —
            # EOS or budget can finish the request mid-commit, in which
            # case the remaining committed tokens are discarded (the
            # _finish path marks control state dirty, so the device's
            # advanced pos is rebuilt from the host mirrors)
            token_rows = active_prev
            if self._gen is not None:
                # (the block lane hands on whole blocks, by its own rule)
                handed, live_rows = self._commit_blocks(
                    active_prev, em, nc, res["blk"], now)
                n_generated += handed
                token_rows = ()
            for r in token_rows:
                req = self._slot_req[int(r)]
                n = int(nc[r])
                if req is None or n == 0:
                    continue
                taken = 0
                for j in range(n):
                    self._on_token(int(r), int(em[r, j]), now)
                    taken += 1
                    if self._slot_req[int(r)] is not req:
                        break                    # finished mid-commit
                n_generated += taken
                dr = int(d_len[r])
                if dr:
                    # count only what the request KEPT: of the `taken`
                    # committed tokens, all but the bonus (column
                    # n-1, landed only when taken == n) were accepted
                    # drafts — an EOS mid-commit discards the tail,
                    # and the acceptance ledgers must not claim it
                    kept = min(taken, n - 1)
                    sampled = float(self._temp[r]) > 0.0
                    req.drafted += dr
                    req.accepted += kept
                    m.draft.inc(dr)
                    if kept:
                        m.accepted.inc(kept)
                        if sampled:
                            m.sampled_accepted.inc(kept)
                    if sampled and n - 1 < dr:
                        # the device rejected draft column n-1 and
                        # drew the commit token from the normalized
                        # residual max(0, p - q)
                        m.resample.inc(1)
            # prefill progress for every request that got pack tokens
            for ent, n in fills:
                ent["off"] += n
                ent["req"].mark("prefill_chunk", dur_s=now - t0,
                                ts_s=t0, iter=self._iter)
            if used:
                m.tokens.inc(used, kind="prompt")
            if self._gen is not None:
                for ent in fin_ents:
                    self._begin_blocks(ent["slot"], ent["req"])
                    self._prefilling.remove(ent)
                fin_ents = []
            for i, ent in enumerate(fin_ents):
                req, slot = ent["req"], ent["slot"]
                self._pos[slot] = len(req.prompt)
                self._active[slot] = True
                self._ctl_dirty = True       # slot turned on mid-flight
                req.status = "decode"
                self._first_token(req, now)
                # the finished prompt's whole blocks enter the radix
                # cache (the trie takes refs, so they outlive the slot)
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(req.prompt.tolist(),
                                             self._bt[slot],
                                             adapter=req.kv_adapter)
                self._on_token(slot, int(ft[i]), now)
                self._prefilling.remove(ent)
            n_generated += len(fin_ents)
            if n_generated:
                # once per iteration by the number committed, not once
                # per token
                m.tokens.inc(n_generated, kind="generated")
            # steady decode: adopt the step's own control advance (no
            # host→device upload next iteration). Any event above set
            # _ctl_dirty, which forces a rebuild from the np mirrors.
            if not self._ctl_dirty:
                self._ctl_dev = dict(self._ctl_dev, pos=pos_dev,
                                     last_tok=last_dev, key=key_dev,
                                     **(blk_dev[0] if blk_dev else {}))
            self._record_gauges(
                tiers=did_aux or self._iter % _TIER_GAUGES_EVERY == 0)
        with span("serve/pump"):
            frames = self._pump_stream_subs()
        with span("serve/account"):
            # the step's device results are let go HERE, not when the
            # function returns: their release hands the interpreter to
            # the wire threads the pump just woke (2-3 ms of a backlog
            # iteration on the chip), and that wait belongs to a child
            del caches, pos_dev, last_dev, key_dev, out, blk_dev
            step_s = time.monotonic() - t0
            m.step_seconds.observe(step_s)
            if self.slo is not None:
                self.slo.observe("serving_step_seconds", step_s)
            if self._counter_sample_every and \
                    self._iter % self._counter_sample_every == 0 \
                    and telemetry.enabled():
                # the process beside this loop (CPU of every thread,
                # threads, resident peak), on the tracks' cadence
                telemetry.process.sample()
                telemetry.get_tracer().record_counters(
                    telemetry.get_registry().scalars())
        # set once, at the end: host CPU = cpu_s - wait_cpu_s, host wall
        # = the step - serve/device_wait, and what is off the CPU is
        # the lock (lock_wait_s) or the interpreter and blocking calls
        # (the rest). since_prev_s is the loop's turn between two steps
        # (has_work, the watchdog's beat, the SLO rules, idle sleeps)
        end = self._step_end_pc
        self._step_end_pc = time.perf_counter()
        step_span.set(
            active=int(active_prev.size), prefill_tokens=used,
            cpu_s=time.thread_time() - cpu0, wait_cpu_s=cpu_w,
            lock_wait_s=lock.waited_s,
            admitted=self._n_admitted - admitted0, frames=frames,
            since_prev_s=pc0 - end if end is not None else 0.0)
        if self._gen is not None:
            # the block lane's LIVE q rows of this iteration: a block a
            # live slot, and one more a slot that carried
            step_span.set(lane_rows=live_rows)
        return True

    # -- the block lane's host half (serving/block_diffusion.py) -----------
    def _begin_blocks(self, slot: int, req: Request) -> None:
        """The prompt's whole blocks are in the arena (or it has none):
        the slot joins the block lane with its first block — the
        prompt's tail, then masks — and no carry: what the slot's last
        request left pending nobody reads (caller holds the lock)."""
        g, b = self._gen, self._blk
        self._pos[slot], b["blk_tok"][slot], b["blk_masked"][slot] = \
            first_block(req.prompt, g.block_length, g.mask_token_id)
        b["blk_pass"][slot] = 0
        b["blk_carry"][slot] = False
        self._blk_at[slot] = 0
        self._active[slot] = True
        self._ctl_dirty = True
        req.status = "decode"

    def _commit_blocks(self, active_prev, em, nc, blk, now) -> tuple:
        """What the fetch says of the slots that ran, each a denoise
        pass: which positions it unmasked (and at which pass: the
        request's own account) and which blocks it FINISHED — their
        tokens go to their requests, all at once, and stay the slot's
        carry: their K/V are written by its next pass. Returns the
        tokens handed on and the lane's live q rows (caller holds the
        lock)."""
        m, b, B = self._m, self._blk, self._gen.block_length
        tok, masked, passes = blk
        ran = np.asarray(active_prev)
        done = nc[ran] > 0
        carried = int(b["blk_carry"][ran].sum())
        # what the pass unmasked, it did at this pass (a finished block
        # comes back as the next one's masks: all it still had)
        newly = b["blk_masked"][ran] & (done[:, None] | (masked[ran] == 0))
        self._blk_at[ran] = np.where(
            newly, b["blk_pass"][ran][:, None], self._blk_at[ran])
        # the mirrors follow the device (a finished slot's are
        # rewritten when it is taken again)
        b["blk_prev"][ran[done]] = em[ran[done]]
        b["blk_carry"][ran] = done
        n_tokens = 0
        for r in map(int, ran[done]):
            req = self._slot_req[r]
            m.diff_per_block.observe(int(b["blk_pass"][r]) + 1)
            # the prompt's tail in a first block is not output
            skip = max(0, len(req.prompt) - int(self._pos[r]))
            self._pos[r] += B
            n_tokens += self._on_block(
                r, em[r, skip:], self._blk_at[r, skip:], now)
            self._blk_at[r] = 0
        b["blk_tok"][ran], b["blk_masked"][ran] = tok[ran], masked[ran] != 0
        b["blk_pass"][ran] = passes[ran]
        if len(ran):
            m.diff_passes.inc(len(ran), kind="denoise")
        if carried:
            m.diff_carried.inc(carried)
        if done.any():
            m.diff_blocks.inc(int(done.sum()))
            m.diff_tokens.inc(n_tokens)
        return n_tokens, B * (len(ran) + carried)

    def _on_block(self, slot: int, toks, at, now: float) -> int:
        """A committed block's tokens for ``slot``'s request, cut at
        ``max_tokens`` and after a stop id; the first block's arrival
        is the request's first token."""
        req = self._slot_req[slot]
        sp = req.sampling
        toks = [int(t) for t in toks[:sp.max_tokens - len(req.tokens)]]
        hit_eos = sp.eos_id is not None and sp.eos_id in toks
        if hit_eos:
            toks = toks[:toks.index(sp.eos_id) + 1]
        if not req.tokens:
            self._first_token(req, now)
        req.tokens.extend(toks)
        req.unmask_pass.extend(int(p) for p in at[:len(toks)])
        if hit_eos or len(req.tokens) >= sp.max_tokens:
            self._finish(slot, now)
        return len(toks)

    def _draft(self, active_prev, d_tok, d_len, d_q):
        """This iteration's draft proposals (speculation only): the
        n-gram index proposes on the host under the lock; the model
        draftsman's DEVICE step runs outside it."""
        K = self.spec_depth
        S = self.pool.slots
        model_draft_in = None
        with self._loop_lock:
            budget = np.zeros(S, np.int32)
            for r in active_prev:
                req = self._slot_req[r]
                sp = req.sampling
                budget[r] = max(0, min(
                    K, sp.max_tokens - len(req.tokens) - 1))
            if budget.any():
                if self._draftsman.host_only:
                    for r in active_prev:
                        b = int(budget[r])
                        if b <= 0:
                            continue
                        prop = self._draftsman.propose(int(r), b)
                        if prop:
                            n = min(len(prop), b)
                            d_tok[r, :n] = prop[:n]
                            d_len[r] = n
                else:
                    seqs: list = [None] * S
                    for r in active_prev:
                        req = self._slot_req[r]
                        seqs[r] = req.prompt.tolist() \
                            + list(req.tokens)
                    model_draft_in = (seqs, self._pos.copy(),
                                      self._active.copy(), budget,
                                      self._temp.copy(),
                                      self._topk.copy(),
                                      self._topp.copy(),
                                      self._key_state.copy())
        if model_draft_in is not None:
            d_tok, d_len, dq = self._draftsman.propose_all(
                *model_draft_in[:4], temps=model_draft_in[4],
                topks=model_draft_in[5], topps=model_draft_in[6],
                keys=model_draft_in[7])
            d_tok = np.asarray(d_tok)
            d_len = np.minimum(np.asarray(d_len), model_draft_in[3])
            d_q = np.asarray(dq, np.float32)
            # the q rows come to the host (and go up again as
            # spec["q"]); the draftsman's own step is not the loop's
            self._m.transfers.inc(dir="down")
            # a zoo draft model may have a larger vocab than the
            # target: clamp (the draftsman already masks its sampling
            # to the target vocab; this guards legacy draft paths)
            v = getattr(self.model.cfg, "vocab_size", None)
            if v:
                np.clip(d_tok, 0, v - 1, out=d_tok)
        return d_tok, d_len, d_q

    def _first_token(self, req: Request, now: float) -> None:
        """``req``'s first token (or first block) arrives ``now``."""
        req.first_token_s = now
        req.mark("first_token", ts_s=now)
        ttft = now - req.submit_s
        self._m.ttft.observe(ttft)
        if self.slo is not None:
            self.slo.observe("serving_ttft_seconds", ttft)

    def _on_token(self, slot: int, tok: int, now: float) -> None:
        """Record one sampled token for ``slot`` (caller holds lock):
        append, advance the slot cursor, finish on EOS / budget. The
        caller counts it into ``serving_tokens_total{kind=generated}``
        — once per iteration by the number committed, not per token."""
        req = self._slot_req[slot]
        req.tokens.append(tok)
        self._last_tok[slot] = tok
        # the cursor only advances once the token is FED (next decode
        # writes its KV at the current pos) — pos was set by prefill
        if req.status == "decode" and len(req.tokens) > 1:
            self._pos[slot] += 1
        if self._draftsman is not None and self._draftsman.host_only:
            self._draftsman.extend(slot, (tok,))
        sp = req.sampling
        hit_eos = sp.eos_id is not None and tok == sp.eos_id
        if hit_eos or len(req.tokens) >= sp.max_tokens:
            self._finish(slot, now)
        elif req.handoff and req.status == "decode":
            # prefill-tier park (P/D disaggregation): the first token
            # landed, so prefill is DONE — stop decoding here. The slot
            # goes inactive but keeps its request and KV blocks; the
            # fleet layer evicts the spill and streams it to a
            # decode-tier replica, which resumes token-for-token.
            self._active[slot] = False
            self._ctl_dirty = True
            req.status = "prefilled"
            req.mark("prefilled", ts_s=now)
            flight_record("serving_prefill_handoff", req=req.id,
                          trace=req.trace_id, slot=slot,
                          prompt_len=len(req.prompt))

    def _finish(self, slot: int, now: float) -> None:
        req = self._slot_req[slot]
        req.status = "done"
        req.finish_s = now
        req.mark("finish", ts_s=now)
        self._active[slot] = False
        self._ctl_dirty = True               # slot turned off
        self._slot_req[slot] = None
        self._adapter_page[slot] = 0
        self._release_tenancy(req)
        # drop this slot's hold on every block it mapped; blocks the
        # prefix cache adopted stay resident (trie refs), the rest free
        self.scheduler.release(slot, table=self._bt[slot])
        self._bt[slot, :] = 0
        self._m.requests.inc(outcome="completed")
        n = len(req.tokens)
        if n > 1 and req.first_token_s is not None:
            tpot = (now - req.first_token_s) / (n - 1)
            self._m.tpot.observe(tpot)
            if self.slo is not None:
                self.slo.observe("serving_tpot_seconds", tpot)
        if req.drafted:
            self._m.acceptance.observe(
                req.accepted / req.drafted,
                path="sampled" if req.sampling.temperature > 0
                else "greedy")
        # a finished request can still own a spill entry (preempted,
        # resumed elsewhere... or cancelled paths) — never leak it
        if req.spill is not None \
                and self.spill_arena.get(req.id) is req.spill:
            self.spill_arena.pop(req.id, resumed=False)
            req.spill = None
        flight_record("serving_finish", req=req.id, trace=req.trace_id,
                      slot=slot, tokens=n)
        self._emit_request_trace(req)
        req.done.set()

    def _emit_request_trace(self, req: Request) -> None:
        """Render the request's lifecycle as its own Perfetto track:
        one span per phase (queued / prefill chunks / decode), on a
        synthetic tid named after the ``trace_id``. Host-side, only
        when the tracer is on — the fused step never sees any of it."""
        tracer = telemetry.get_tracer()
        if not tracer.enabled:
            return
        # request events use time.monotonic; the tracer epoch is
        # perf_counter-based — bridge via the current offset (both are
        # monotonic clocks, so the offset is constant)
        off = (time.perf_counter() - tracer.epoch) - time.monotonic()
        tid = REQ_TRACK_BASE + req.id
        tracer.name_track(tid, f"req {req.trace_id}")

        def span(name, start, dur, **attrs):
            tracer.complete(name, max(dur, 0.0), cat="request",
                            ts_s=max(start + off, 0.0), tid=tid,
                            trace_id=req.trace_id, req=req.id, **attrs)

        admit = next((t for p, t, _ in req.events if p == "admit"), None)
        if admit is not None:
            span("queued", req.submit_s, admit - req.submit_s)
        chunks = [(ts, dur) for phase, ts, dur in req.events
                  if phase == "prefill_chunk"]
        for (ts, dur), it in zip(chunks, req.chunk_iters):
            # iter: the serve/step span that ran this chunk
            span("prefill_chunk", ts, dur, iter=it)
        if req.first_token_s is not None and req.finish_s is not None:
            span("decode", req.first_token_s,
                 req.finish_s - req.first_token_s,
                 tokens=len(req.tokens))

    def _record_gauges(self, tiers: bool = False) -> None:
        """The gauges an operator scrapes (``docs/OBSERVABILITY.md``
        names each one's reader). Every iteration: the three that move
        with every admission and finish, and the window's dead blocks.
        ``tiers``: the spill tiers, the replica store and the adapter
        pages too — they move with a preemption, a resume, a push from
        a peer or an adapter load, so they are set on a submit, after an
        iteration that ran such a job and every ``_TIER_GAUGES_EVERY``
        iterations, not six to nine ``set`` calls in every one."""
        m = self._m
        m.queue_depth.set(self.scheduler.depth)
        m.occupancy.set(self.scheduler.occupancy)
        if self._paged:
            m.kv_in_use.set(self.blocks.blocks_in_use)
        else:
            live = self.pool.slots - len(self.scheduler.free)
            m.slots.set(live, state="live")
            m.slots.set(self.pool.slots - live, state="free")
        if self._min_window is not None:
            # the next query of an active slot sits at pos: blocks whose
            # last row is at or below pos - window are dead to it
            below = self._pos[self._active] - self._min_window + 1
            m.window_dead.set(int(
                (np.maximum(below, 0) // self.pool.block_size).sum()))
        if not tiers:
            return
        m.spill_arena.set(self.spill_arena.blocks_held)
        for tier, n in self.spill_arena.tier_counts().items():
            m.spill_tiers.set(n, tier=tier)
        m.spill_tiers.set(self.kv_replica_store.blocks_held,
                          tier="replica")
        if self.tenancy is not None:
            m.adapter_pages.set(self.tenancy.registry.pages_in_use)

    def run_until_drained(self, max_steps: int = 1_000_000) -> int:
        """Drive :meth:`step` until queue + slots are empty; returns the
        number of iterations run."""
        n = 0
        while self.has_work():
            if n >= max_steps:
                raise RuntimeError(
                    f"serving engine not drained after {max_steps} "
                    f"iterations")
            self.step()
            n += 1
        return n

    # -- offline API --------------------------------------------------------
    def generate_many(
            self, prompts: Sequence[Sequence[int]],
            sampling: Union[SamplingParams, Sequence[SamplingParams],
                            None] = None) -> list[list[int]]:
        """Submit every prompt, run to drain, return per-request tokens
        **in submission order** — requests routinely FINISH out of order
        (short decodes overtake long ones across slot recycling), so
        results are keyed by the submitted Request, never by completion
        order. Continuous batching under the hood: arrival order and
        slot assignment do not change any request's tokens. When the
        :meth:`start` background loop is running, this waits on each
        request instead of stepping the engine from a second thread."""
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling or SamplingParams()] * len(prompts)
        reqs = [self.submit(p, sp) for p, sp in zip(prompts, sampling)]
        bad = [r for r in reqs if r.status == "rejected"]
        if bad:
            # fail FAST and loud (a silent [] is indistinguishable from
            # a legitimate empty generation); un-queue the siblings so
            # the engine is left clean
            with self._lock:
                for r in reqs:
                    if r.status == "queued":
                        try:
                            self.scheduler.queue.remove(r)
                        except ValueError:
                            pass
                        r.status = "cancelled"
                        r.error = "batch aborted: sibling rejected"
                        r.done.set()
            raise ValueError(
                f"{len(bad)} request(s) rejected at admission: "
                + "; ".join(f"#{r.id}: {r.error}" for r in bad[:3]))
        if self._thread is not None:
            for r in reqs:          # loop thread owns the iterations
                r.done.wait()
        else:
            self.run_until_drained()
        return [list(r.tokens) for r in reqs]

    # -- background loop (online front ends) --------------------------------
    def start(self, idle_sleep_s: float = 0.002) -> None:
        if self._thread is not None:
            return
        self._stop = threading.Event()
        if self.watchdog is not None:
            self.watchdog.start()

        def loop():
            while not self._stop.is_set():
                busy = self.step()
                # a beat per loop turn (idle included): the watchdog
                # watches for a WEDGED iteration, not an empty queue
                if self.watchdog is not None:
                    self.watchdog.beat()
                if self.slo is not None:
                    now = time.monotonic()
                    if now - self._slo_last_eval >= self._slo_every_s:
                        self._slo_last_eval = now
                        for a in self.slo.evaluate():
                            from hetu_tpu.utils.logging import get_logger
                            get_logger().warning(
                                f"SLO alert: {a.message}")
                if not busy:
                    self._stop.wait(idle_sleep_s)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._repl_stop is not None:   # decode-KV replication stream
            self._repl_stop.set()
            if self._repl_thread is not None:
                self._repl_thread.join(timeout=5.0)
            self._repl_thread = None
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10.0)
        self._thread = None
        if self.watchdog is not None:
            self.watchdog.stop()
