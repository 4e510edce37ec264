"""Block-paged KV cache: one fixed-shape arena, indexed through block
tables.

PR 5's slot arena ((layers, slots, max_len, hkv, d)) solved "dynamic
work on static shapes" but allocated every slot its WORST CASE: a
10-token request in a 2048-token slot wastes 99.5% of its bytes, and a
shared system prompt is stored once per slot. This module is the
PagedAttention answer (vLLM, SOSP'23) mapped onto the jit-once TPU
discipline:

- the arena is ``(layers, n_blocks, block_size, kv_heads*head_dim)``
  (heads merged into the minor dim — the TPU-tileable page layout),
  allocated once; a request maps onto a per-slot BLOCK TABLE (fixed
  ``max_len/block_size`` width, padded with the null block 0), and the
  compiled step indexes KV through the table — tables are DATA, never
  shapes, so block churn never recompiles. Inside the fused step the
  arena stays this ONE donated buffer: the layer scan carries the
  stacked leaves (``nn.parallel.StackedBlocks.decode``), layer ``l``
  scatters its new rows at ``[l, block*block_size + offset]`` and
  reads pages at ``(l, block)`` — the paged kernel takes the stacked
  leaf and the layer (``ops.paged_pallas``), the CPU path gathers
  from ``leaf[l]`` (``ops.attention.gather_block_rows``). No layer's
  leaf is sliced out or stacked back;
- blocks are refcounted (:class:`BlockManager`): the radix-tree prefix
  cache (``serving/prefix_cache.py``) maps one physical block into many
  slots' tables, so a fleet-wide system prompt is prefilled once and
  costs one set of pages total;
- the fp32/bf16/int8 leaves come from
  ``generation.init_paged_caches`` — the int8 pool
  quarters decode's HBM bandwidth with per-(position, head) scales, and
  quantized blocks are shared bit-for-bit like fp blocks.

Sizing is delegated to the memory-plane ledger
(:func:`hetu_tpu.engine.memory.size_kv_blocks`): blocks are whatever
HBM remains next to the weights, so the scheduler's free-block
admission gate and the planner price bytes with the same arithmetic.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import jax.numpy as jnp
import numpy as np

from hetu_tpu.models.generation import init_paged_caches

#: block table entries point here when a position is unallocated; the
#: null block is never handed out and never written, so its rows stay
#: exact zeros (masked by every live row's causal offset anyway)
NULL_BLOCK = 0


def cache_dtype_name(dtype) -> str:
    """Canonical ledger name for a cache dtype (fp32 | bf16 | int8)."""
    if dtype == jnp.int8:
        return "int8"
    if dtype == jnp.bfloat16:
        return "bf16"
    return "fp32"


class BlockManager:
    """Host-side free list + refcounts over the paged arena.

    Pure bookkeeping (no jax): the device only ever sees block ids as
    traced table entries. A block's refcount is the number of HOLDERS —
    slots whose table maps it, plus the prefix-cache trie when a node
    caches it. ``release`` returns it to the free list at zero; blocks
    are never zeroed on reuse (the per-row causal mask guarantees a
    reused block is written before it is attended).
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need at least one non-null block")
        self.n_blocks = int(n_blocks)
        self.free: deque[int] = deque(range(1, self.n_blocks))
        self.refs = np.zeros(self.n_blocks, np.int32)

    def alloc(self) -> Optional[int]:
        """Pop a free block (refcount 1), or None when the pool is dry
        (the caller evicts prefix-cache leaves and retries)."""
        if not self.free:
            return None
        b = self.free.popleft()
        self.refs[b] = 1
        return b

    def share(self, block: int) -> None:
        """Add a holder to an already-live block (prefix hit / trie)."""
        if block == NULL_BLOCK:
            raise ValueError("cannot share the null block")
        if self.refs[block] <= 0:
            raise ValueError(f"share of dead block {block}")
        self.refs[block] += 1

    def release(self, block: int) -> None:
        """Drop one holder; the block frees when the last one leaves."""
        if block == NULL_BLOCK:
            return
        self.refs[block] -= 1
        if self.refs[block] < 0:
            raise ValueError(f"double release of block {block}")
        if self.refs[block] == 0:
            self.free.append(block)

    @property
    def free_blocks(self) -> int:
        return len(self.free)

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - 1 - len(self.free)


class NoBlocks:
    """The ledger of an engine WITHOUT an arena (no layer of its model
    keeps token rows): nothing to hand out and nothing in use. What
    reads the ledger of any engine (gauges, the benchmark's samplers)
    reads zeros here; the scheduler is handed no ledger at all and
    admits by free slots."""
    n_blocks = free_blocks = blocks_in_use = 0


class KVPool:
    """The block-paged arena plus its shape metadata (block/refcount
    bookkeeping belongs to :class:`BlockManager` and the scheduler; the
    pool is just bytes).

    ``slots`` remains the engine's max CONCURRENCY (the width of the
    control vectors and block tables); capacity in bytes is now
    ``n_blocks`` — by default one null block plus ``slots`` worst-case
    requests' worth, but prefix sharing means the effective capacity in
    requests is higher.
    """

    def __init__(self, model, slots: int, max_len: int,
                 cache_dtype=jnp.float32, block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None,
                 table_len: Optional[int] = None, sharding=None):
        max_positions = getattr(getattr(model, "cfg", None),
                                "max_positions", None)
        if max_positions is not None and max_len > max_positions:
            raise ValueError(
                f"pool max_len {max_len} exceeds the model's "
                f"max_positions {max_positions}")
        self.slots = int(slots)
        self.max_len = int(max_len)
        #: does any layer keep token rows in pages? Where none does
        #: (``model.blocks.paged`` False: every layer keeps a state a
        #: SLOT) there is NO arena: no page, no table, and the caches
        #: are the slot leaves alone — ``max_len`` bounds positions only
        self.paged = getattr(model.blocks, "paged", True)
        if not self.paged:
            if n_blocks or table_len:
                raise ValueError(
                    f"kv_blocks={n_blocks} / long_max_len={table_len}: "
                    f"no layer of this model keeps token rows, so it "
                    f"has no arena to size (kv_blocks 0 or None)")
            self.block_size = self.table_len = self.max_len
            self.blocks_per_slot = self.table_width = self.n_blocks = 0
            self.cache_dtype, self.weight_version = cache_dtype, 0
            self.caches = init_paged_caches(
                model, 0, self.max_len, cache_dtype, sharding=sharding,
                slots=self.slots)
            return
        self.block_size = int(block_size) if block_size else self.max_len
        if self.max_len % self.block_size != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of block_size "
                f"{self.block_size} (block tables have a fixed "
                f"max_len/block_size width)")
        # table_len > max_len widens every slot's BLOCK TABLE (control
        # ints, not arena bytes) so the CP-prefill lane can map requests
        # beyond one slot's admission budget (ServingEngine long_max_len)
        self.table_len = int(table_len) if table_len else self.max_len
        if self.table_len < self.max_len \
                or self.table_len % self.block_size != 0:
            raise ValueError(
                f"table_len {self.table_len} must be a multiple of "
                f"block_size {self.block_size} and >= max_len "
                f"{self.max_len}")
        if max_positions is not None and self.table_len > max_positions:
            raise ValueError(
                f"table_len {self.table_len} exceeds the model's "
                f"max_positions {max_positions}")
        self.blocks_per_slot = self.max_len // self.block_size
        self.table_width = self.table_len // self.block_size
        # default arena: slots worst-case NORMAL requests, plus (when a
        # wide table enables the long lane) headroom for one worst-case
        # LONG request beyond a slot's share
        self.n_blocks = int(n_blocks) if n_blocks else (
            1 + self.slots * self.blocks_per_slot
            + (self.table_width - self.blocks_per_slot))
        if self.n_blocks <= self.table_width:
            raise ValueError(
                f"n_blocks {self.n_blocks} cannot hold even one "
                f"worst-case request ({self.table_width} blocks "
                f"+ the null block)")
        self.cache_dtype = cache_dtype
        #: weight generation whose forward wrote the arena's live
        #: blocks. Bumped by ``ServingEngine.swap_params`` on a live
        #: weight push (HotSPa train→serve): the engine only swaps
        #: drained (no slot holds blocks), and the prefix cache flushes
        #: its stale residents, so every block written after the bump
        #: belongs to the new generation — the tag is how audits (and
        #: the version-tagged prefix trie) tell the two apart.
        self.weight_version = 0
        # ``sharding``: where the engine's compiled steps keep the
        # arena (ServingEngine places it with its params)
        # (a model that also keeps a state per slot gets ``slots`` of
        # them beside the arena: generation.init_paged_caches)
        self.caches = init_paged_caches(model, self.n_blocks,
                                        self.block_size, cache_dtype,
                                        sharding=sharding,
                                        slots=self.slots)

    @classmethod
    def sized_for(cls, model, *, hbm_budget_bytes: float, max_len: int,
                  cache_dtype=jnp.float32, tp: int = 1,
                  max_slots: Optional[int] = None,
                  block_size: Optional[int] = None,
                  table_len: Optional[int] = None,
                  sharding=None) -> "KVPool":
        """Build the largest pool the HBM budget allows (ledger-sized:
        whole worst-case slots, so admission can never strand a request
        that passed the budget gate)."""
        from hetu_tpu.engine.memory import size_kv_pool
        slots = size_kv_pool(model.cfg,
                             hbm_budget_bytes=hbm_budget_bytes,
                             max_len=max_len,
                             cache_dtype=cache_dtype_name(cache_dtype),
                             tp=tp)
        if max_slots is not None:
            slots = min(slots, max_slots)
        # budget-derived arenas stay exactly budget-sized: a wide table
        # (long lane) widens the control ints, never the arena bytes —
        # the long request's blocks come out of the budgeted pool
        eff_bs = int(block_size) if block_size else int(max_len)
        n_blocks = (1 + slots * (int(max_len) // eff_bs)) \
            if table_len else None
        return cls(model, slots, max_len, cache_dtype,
                   block_size=block_size, table_len=table_len,
                   n_blocks=n_blocks, sharding=sharding)

    @property
    def quantized(self) -> bool:
        return self.cache_dtype == jnp.int8

    def nbytes(self) -> int:
        return sum(int(x.size) * x.dtype.itemsize for x in self.caches)


# -- resumable preemption: the host spill arena ------------------------------


@dataclasses.dataclass
class SpillEntry:
    """One preempted request's KV, parked in host memory.

    ``data`` holds the request's first ``n_blocks`` table blocks per
    cache leaf (``(layers, n_blocks, block_size, ...)`` numpy — valid
    rows ``0..pos-1``; the tail block's trailing rows are rewound
    speculation garbage and ride along harmlessly, the same way they
    do on-device). Resume maps the data back into freshly allocated
    arena blocks — zero prefill-lane work — provided the target pool
    still speaks the same layout AND the same ``weight_version`` (KV
    encodes the forward of the weights that wrote it; resuming it
    under swapped weights would splice two models' states)."""

    req_id: int
    data: tuple                      # per-leaf np arrays (L, nb, bs, ..)
    n_blocks: int
    block_size: int
    pos: int                         # next KV write index at spill time
    last_tok: int                    # sampled, not yet fed
    tokens: list                     # emitted so far (replayed on a
    #                                  cross-engine resume's Request)
    weight_version: int
    traceparent: Optional[str] = None  # originating trace context — a
    #                                  decode-tier resume adopts it so
    #                                  the cross-process spans share one
    #                                  trace_id (ISSUE 16); absent on
    #                                  wire docs from older peers
    key_state: Optional[object] = None  # (KW,) uint32 raw PRNG key
    #                                  state at spill time — a sampled
    #                                  request must resume its commit
    #                                  key stream exactly where it
    #                                  stopped or its replay diverges
    adapter: int = 0                 # adapter KV-compat uid the forward
    #                                  ran under (0 = base; see
    #                                  serving/tenancy.py) — resuming a
    #                                  tenant's KV under a different (or
    #                                  reloaded) adapter would splice
    #                                  two adapters' activations

    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.data)

    def compatible_with(self, pool: "KVPool", weight_version: int,
                        adapter: int = 0) -> bool:
        """Can this spill resume into ``pool`` at ``weight_version``
        under adapter KV-compat uid ``adapter``?"""
        if self.weight_version != int(weight_version) \
                or self.adapter != int(adapter) \
                or self.block_size != pool.block_size:
            return False
        if len(self.data) != len(pool.caches):
            return False
        return all(a.shape[0] == c.shape[0]
                   and a.shape[2:] == tuple(c.shape[2:])
                   and a.dtype == c.dtype
                   for a, c in zip(self.data, pool.caches))


class HostSpillArena:
    """Bounded host-memory parking lot for preempted requests' KV.

    Capacity is counted in ARENA BLOCKS (the same unit the device pool
    allocates and :func:`hetu_tpu.engine.memory.size_spill_arena`
    prices from a host-byte budget), so the scheduler's preemption
    planner can gate an eviction with the same arithmetic the resume
    will be charged. ``max_blocks=None`` = unbounded (the default for
    in-process fleets where host RAM dwarfs the arena).

    ``peer`` chains a second tier behind this one (device→host→peer):
    when the host tier is full, the LEAST-RECENTLY-SPILLED entries are
    demoted whole into the peer store, and an oversized entry that
    cannot fit the host tier at all passes straight through. ``pop``
    and ``get`` look through to the peer, so callers never care which
    tier holds an entry. Any object speaking the arena's
    put/pop/get/can_fit/``blocks_held`` surface works as a peer —
    another ``HostSpillArena`` in-process, or a wire-backed store."""

    def __init__(self, max_blocks: Optional[int] = None,
                 peer: Optional["HostSpillArena"] = None):
        self.max_blocks = int(max_blocks) if max_blocks else None
        self._entries: dict[int, SpillEntry] = {}
        self._peer = peer
        self.blocks_held = 0
        self.spilled_total = 0           # host ledgers (telemetry syncs)
        self.resumed_total = 0
        self.demoted_total = 0           # blocks pushed down the chain
        self.promoted_total = 0          # blocks pulled back up

    def attach_peer(self, peer) -> None:
        self._peer = peer

    def _demotion_plan(self, n_blocks: int):
        """Entry ids to demote (oldest first) so a put of ``n_blocks``
        fits the host tier, ``None`` if no placement exists. A put that
        fits as-is plans ``[]``; an entry wider than the whole host
        tier plans a pass-through (also ``[]``) if the peer takes it."""
        if self.max_blocks is None \
                or self.blocks_held + n_blocks <= self.max_blocks:
            return []
        if self._peer is None:
            return None
        if n_blocks > self.max_blocks:      # pass straight through
            return [] if self._peer.can_fit(n_blocks) else None
        plan, freed = [], 0
        need = self.blocks_held + n_blocks - self.max_blocks
        for rid, e in self._entries.items():     # insertion order = LRU
            if freed >= need:
                break
            plan.append(rid)
            freed += e.n_blocks
        if freed < need or not self._peer.can_fit(freed):
            return None
        return plan

    def can_fit(self, n_blocks: int) -> bool:
        return self._demotion_plan(int(n_blocks)) is not None

    def put(self, entry: SpillEntry) -> None:
        plan = self._demotion_plan(entry.n_blocks)
        if plan is None:
            raise ValueError(
                f"spill arena full: {self.blocks_held} + "
                f"{entry.n_blocks} blocks exceed max_blocks="
                f"{self.max_blocks}")
        if entry.req_id in self:
            raise ValueError(f"request {entry.req_id} already spilled")
        for rid in plan:
            old = self._entries.pop(rid)
            self.blocks_held -= old.n_blocks
            self._peer.put(old)
            self.demoted_total += old.n_blocks
        if self.max_blocks is not None \
                and entry.n_blocks > self.max_blocks:
            self._peer.put(entry)        # oversized: pass-through
            self.demoted_total += entry.n_blocks
        else:
            self._entries[entry.req_id] = entry
            self.blocks_held += entry.n_blocks
        self.spilled_total += entry.n_blocks

    def pop(self, req_id: int, *, resumed: bool = True
            ) -> Optional[SpillEntry]:
        """Remove an entry: ``resumed=True`` counts it in the resume
        ledger (a real map-back); ``resumed=False`` is a detach (the
        router pulled the request to a peer — that engine's resume
        counts it there). Looks through to the peer tier."""
        entry = self._entries.pop(req_id, None)
        if entry is None and self._peer is not None:
            entry = self._peer.pop(req_id, resumed=False)
            if entry is not None:
                self.promoted_total += entry.n_blocks
        elif entry is not None:
            self.blocks_held -= entry.n_blocks
        if entry is not None and resumed:
            self.resumed_total += entry.n_blocks
        return entry

    def get(self, req_id: int) -> Optional[SpillEntry]:
        entry = self._entries.get(req_id)
        if entry is None and self._peer is not None:
            entry = self._peer.get(req_id)
        return entry

    def tier_counts(self) -> dict:
        """Blocks held per tier, for the ``spill_tier_blocks`` gauge."""
        out = {"host": self.blocks_held}
        if self._peer is not None:
            out["peer"] = int(self._peer.blocks_held)
        return out

    def __contains__(self, req_id: int) -> bool:
        return req_id in self._entries \
            or (self._peer is not None and req_id in self._peer)

    def __len__(self) -> int:
        return len(self._entries) \
            + (len(self._peer) if self._peer is not None else 0)
