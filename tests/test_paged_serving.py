"""Paged KV + radix prefix cache + packed prefill (ISSUE 7).

Acceptance discipline: paging and prefix caching are MEMORY transforms
and packed prefill is a SCHEDULING transform — none of them may change
a single output token. Every test therefore pins greedy outputs to the
one-shot ``models.generation.generate`` oracle at the pool's cache
capacity, across cache on/off, arrival-order permutations, LRU eviction
churn, the int8 pool, and copy-on-write partial-prefix hits — while the
``record_trace`` counter keeps asserting the fused step compiles
exactly once across all of it (tables, pack layouts and prefix offsets
are data, never shapes).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.engine import trace_counts
from hetu_tpu.models import GPTConfig, GPTLMHeadModel, generate
from hetu_tpu.serving import (
    BlockManager, KVPool, PrefixCache, SamplingParams, ServingEngine,
)

MAX_LEN = 32
CHUNK = 8
BLOCK = 8


@pytest.fixture(scope="module")
def gpt():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (L,)).tolist() for L in lens]


def _ref(model, params, prompt, max_tokens, **kw):
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=max_tokens, max_len=MAX_LEN, **kw)
    return np.asarray(out[0, len(prompt):]).tolist()


def _engine(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("block_size", BLOCK)
    return ServingEngine(model, kw.pop("params"), **kw)


# -- host-side units (no device work) ---------------------------------------

def test_block_manager_refcounts_and_ledger(gpt):
    cfg, model, params = gpt
    mgr = BlockManager(5)                     # null + 4 usable
    assert mgr.free_blocks == 4 and mgr.blocks_in_use == 0
    a, b = mgr.alloc(), mgr.alloc()
    assert a != 0 and b != 0 and a != b
    mgr.share(a)                              # second holder
    mgr.release(a)
    assert mgr.blocks_in_use == 2             # still held once
    mgr.release(a)
    mgr.release(b)
    assert mgr.free_blocks == 4
    with pytest.raises(ValueError):
        mgr.release(b)                        # double release
    with pytest.raises(ValueError):
        mgr.share(0)                          # null block is pinned

    # the paged arena: (L, n_blocks, block_size, hkv*d), null included
    pool = KVPool(model, slots=2, max_len=MAX_LEN, block_size=BLOCK)
    W = MAX_LEN // BLOCK
    assert pool.blocks_per_slot == W
    assert pool.n_blocks == 1 + 2 * W
    assert pool.caches[0].shape[1:3] == (pool.n_blocks, BLOCK)
    with pytest.raises(ValueError, match="multiple of block_size"):
        KVPool(model, slots=2, max_len=MAX_LEN, block_size=5)

    # ledger: a slot prices as W blocks, and the back-compat wrapper
    # is exactly one max_len-sized block
    from hetu_tpu.engine.memory import (
        kv_bytes_per_block, kv_bytes_per_slot, size_kv_blocks,
        size_kv_pool,
    )
    per_block = kv_bytes_per_block(cfg, block_size=BLOCK)
    assert kv_bytes_per_slot(cfg, max_len=MAX_LEN) == W * per_block
    budget = 4e9
    assert size_kv_blocks(cfg, hbm_budget_bytes=budget,
                          block_size=MAX_LEN) \
        == size_kv_pool(cfg, hbm_budget_bytes=budget, max_len=MAX_LEN)


def test_prefix_cache_trie_match_insert_evict():
    mgr = BlockManager(10)
    cache = PrefixCache(4, mgr)
    # a request owning blocks for tokens [1..8] inserts its two whole
    # blocks; the trie takes a ref on each
    t1 = [1, 2, 3, 4, 5, 6, 7, 8]
    b1, b2 = mgr.alloc(), mgr.alloc()
    assert cache.insert(t1, [b1, b2]) == 2
    assert mgr.refs[b1] == 2 and mgr.refs[b2] == 2
    mgr.release(b1), mgr.release(b2)          # request finishes
    assert mgr.free_blocks == 7               # trie keeps both alive

    # exact whole-block match, depth 2
    assert cache.match(t1) == ([b1, b2], None)
    # prefix-only match + partial tail (2 rows into block 2) → CoW src
    assert cache.match([1, 2, 3, 4, 5, 6, 99]) == ([b1], (b2, 2))
    # divergence inside block 1: partial at the root
    assert cache.match([1, 2, 9, 9, 9]) == ([], (b1, 2))
    # no match at all
    assert cache.match([7, 7, 7, 7]) == ([], None)
    # insert a sibling branch [1..4, 50..53]: shares block 1's node
    b3 = mgr.alloc()
    assert cache.insert([1, 2, 3, 4, 50, 51, 52, 53], [b1, b3]) == 1
    mgr.release(b3)
    assert cache.cached_blocks == 3

    # eviction: only LEAVES with a trie-only ref go, LRU first.
    # b2 was touched more recently than b3? touch b3's branch now:
    cache.match([1, 2, 3, 4, 50, 51, 52, 53])
    assert cache.evict(1) == 1                # b2 (older leaf) dropped
    assert mgr.refs[b2] == 0 and mgr.refs[b3] == 1
    # b1 is interior (b3's parent): evicting 2 more takes b3 THEN b1
    assert cache.evict(2) == 2
    assert cache.cached_blocks == 0 and mgr.free_blocks == 9
    # nothing left to evict
    assert cache.evict(1) == 0


def test_admission_pins_matched_blocks_against_eviction():
    """REGRESSION: under memory pressure, _page_plan's eviction can
    peel a cached chain all the way into the blocks the request just
    matched — unpinned, they were freed (share() then raised on a dead
    block, or worse the block was re-allocated and double-mapped).
    Matched blocks must be pinned before evicting and admission must
    WAIT (head-of-line) when eviction can't cover the shortfall."""
    from hetu_tpu.serving.scheduler import Request, Scheduler

    mgr = BlockManager(9)                      # null + 8 usable
    cache = PrefixCache(4, mgr)
    live = mgr.alloc()                         # a live slot's block:
    #                                            not cached, not free
    chain_tokens = list(range(100, 128))       # 28 tokens = 7 blocks
    chain = [mgr.alloc() for _ in range(7)]    # pool now exhausted
    cache.insert(chain_tokens, chain)
    for b in chain:
        mgr.release(b)                         # request finished; the
    assert mgr.free_blocks == 0                # trie keeps all 7 alive

    sched = Scheduler(2, MAX_LEN, blocks=mgr, prefix_cache=cache,
                      block_size=4)
    # matches chain block 1 only, needs 8 blocks worst case: eviction
    # must free 7 but only 6 unmatched chain blocks are reclaimable
    req = Request(0, np.asarray(chain_tokens[:4] + list(range(200, 225)),
                                np.int32),
                  SamplingParams(max_tokens=3), submit_s=0.0)
    assert sched.submit(req)
    assert sched.next_admission() is None      # waits — no crash
    assert sched.evictions_total == 6          # unmatched tail peeled
    assert cache.cached_blocks == 1            # the matched block
    assert mgr.refs[chain[0]] == 1             # survives, trie-only
    assert sched.depth == 1                    # still head of line

    mgr.release(live)                          # the live request ends
    got = sched.next_admission()
    assert got is not None
    _, slot = got
    table = req.admit["table"]
    assert len(table) == 8 and table[0] == chain[0]
    assert req.admit["first_uncached"] == 4 and req.cached_tokens == 4
    assert mgr.refs[chain[0]] == 2             # trie + this table
    assert mgr.free_blocks == 0
    sched.release(slot, table=table)
    assert mgr.refs[chain[0]] == 1 and mgr.free_blocks == 7


def test_handoff_requests_price_one_decode_token():
    """SATELLITE (ISSUE 17): a handoff (prefill-tier) request only ever
    writes prompt + first token before shipping the KV downstream —
    pricing it at P + max_tokens throttles this tier's admission for
    decode room it never uses. Both the preemption bound
    (blocks_needed) and the admission plan (_page_plan) charge P+1."""
    from hetu_tpu.serving.scheduler import Request, Scheduler

    prompt = np.arange(1, 8, dtype=np.int32)          # P = 7
    plain = Request(0, prompt, SamplingParams(max_tokens=8),
                    submit_s=0.0)
    hand = Request(1, prompt.copy(), SamplingParams(max_tokens=8),
                   submit_s=0.0, handoff=True)

    sched = Scheduler(2, MAX_LEN, blocks=BlockManager(3),  # 2 usable
                      block_size=4)
    assert sched.blocks_needed(plain) == 4            # ceil((7+8)/4)
    assert sched.blocks_needed(hand) == 2             # ceil((7+1)/4)

    # two free blocks: the plain request can't fit and waits...
    assert sched.submit(plain)
    assert sched.next_admission() is None
    # ...but an identical handoff request admits into the same pool,
    # and its table holds exactly the P+1 worst case
    sched2 = Scheduler(2, MAX_LEN, blocks=BlockManager(3), block_size=4)
    assert sched2.submit(hand)
    got = sched2.next_admission()
    assert got is not None
    assert len(hand.admit["table"]) == 2


# -- engine acceptance -------------------------------------------------------

def test_cache_on_off_identical_across_arrival_permutations(gpt):
    """ACCEPTANCE: greedy outputs token-identical with the prefix cache
    on vs off, for every arrival-order permutation of a shared-prefix
    workload — and identical to per-request one-shot generate."""
    cfg, model, params = gpt
    sys_p = _prompts(cfg, [BLOCK + 4], seed=20)[0]      # 12 shared
    tails = _prompts(cfg, [4, 7, 2], seed=21)
    prompts = [sys_p + t for t in tails]
    sp = SamplingParams(max_tokens=5)
    want = {tuple(p): _ref(model, params, p, 5) for p in prompts}
    eng_on = _engine(model, params=params, prefix_cache=True)
    eng_off = _engine(model, params=params, prefix_cache=False)
    before = trace_counts().get("serving_step", 0)
    for perm in list(itertools.permutations(range(3)))[:4]:
        order = [prompts[i] for i in perm]
        expect = [want[tuple(p)] for p in order]
        assert eng_on.generate_many(order, sp) == expect, perm
        assert eng_off.generate_many(order, sp) == expect, perm
    # two engines, arbitrary hit/miss churn: <= 2 step compiles total
    assert trace_counts().get("serving_step", 0) - before <= 2
    # the cached engine actually hit (same prompts resubmitted) while
    # the uncached one never did
    assert eng_on.prefix_cache.cached_blocks > 0
    assert eng_off.prefix_cache is None


def test_shared_system_prompt_prefill_shrinks(gpt):
    """ACCEPTANCE: the second request carrying a shared system prompt
    prefills strictly fewer chunks (the cached prefix is mapped, not
    recomputed) and still matches its one-shot tokens — including the
    copy-on-write partial tail block."""
    cfg, model, params = gpt
    telemetry.reset()
    telemetry.enable(True)
    try:
        sys_p = _prompts(cfg, [BLOCK + 4], seed=22)[0]  # 12: 1 whole
        #                                                 block + 4 rows
        a = sys_p + _prompts(cfg, [6], seed=23)[0]
        b = sys_p + _prompts(cfg, [5], seed=24)[0]
        sp = SamplingParams(max_tokens=4)
        eng = _engine(model, params=params)
        ra = eng.submit(a, sp)
        eng.run_until_drained()
        rb = eng.submit(b, sp)
        eng.run_until_drained()
        ta, tb = ra.result()["timing"], rb.result()["timing"]
        assert ta["cached_tokens"] == 0
        # b shares sys_p's whole block AND CoW-copies the 4-row tail
        assert tb["cached_tokens"] == len(sys_p)
        assert tb["prefill_chunks"] < ta["prefill_chunks"]
        assert list(ra.tokens) == _ref(model, params, a, 4)
        assert list(rb.tokens) == _ref(model, params, b, 4)
        # telemetry: hits/misses/blocks-in-use all live
        reg = telemetry.get_registry()
        assert reg.counter(
            "serving_prefix_hit_tokens_total").value() == len(sys_p)
        assert reg.counter(
            "serving_prefix_miss_tokens_total").value() \
            == len(a) + len(b) - len(sys_p)
        assert reg.gauge("serving_kv_blocks_in_use").value() \
            == eng.blocks.blocks_in_use
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_eviction_churn_token_identical_one_compile(gpt):
    """ACCEPTANCE: a tiny block pool under repeated-prefix traffic
    LRU-evicts cache leaves, yet outputs stay token-identical and the
    fused step never re-traces across admit/evict/prefix-hit churn."""
    cfg, model, params = gpt
    eng = _engine(model, params=params)        # 2 slots × 4 blocks + 1
    sp = SamplingParams(max_tokens=4)
    families = [_prompts(cfg, [BLOCK * 2], seed=s)[0] for s in (30, 31,
                                                                32)]
    prompts = [f[:BLOCK * 2 - 2] + t for f in families
               for t in ([7, 7], [9, 9])]
    want = [_ref(model, params, p, 4) for p in prompts]
    before = trace_counts().get("serving_step", 0)
    assert eng.generate_many(prompts, sp) == want
    # the 3 families × 3 blocks each cannot all stay cached in 4
    # usable blocks → LRU eviction ran
    assert eng.scheduler.evictions_total > 0
    # second pass over the same traffic: still identical, still hot
    assert eng.generate_many(prompts, sp) == want
    assert trace_counts().get("serving_step", 0) - before == 1, \
        "paging/eviction churn re-traced the fused step"
    # ledger sanity after drain: every non-cached block is free again
    assert eng.blocks.free_blocks + eng.prefix_cache.cached_blocks \
        == eng.blocks.n_blocks - 1
    assert (eng.blocks.refs[1:] >= 0).all()


def test_int8_paged_pool_matches_and_hits(gpt):
    """ACCEPTANCE: the quantized paged pool reproduces one-shot int8
    generation, and a rerun served from cached int8 blocks is
    bit-identical to the cold run (quantized pages share exactly)."""
    cfg, model, params = gpt
    prompts = _prompts(cfg, [BLOCK * 2 + 3, 5], seed=40)
    sp = SamplingParams(max_tokens=5)
    eng = _engine(model, params=params, cache_dtype=jnp.int8)
    assert eng.pool.quantized
    want = [_ref(model, params, p, 5, cache_dtype=jnp.int8)
            for p in prompts]
    assert eng.generate_many(prompts, sp) == want
    r = eng.submit(prompts[0], sp)
    eng.run_until_drained()
    assert r.cached_tokens > 0                 # served from int8 pages
    assert list(r.tokens) == want[0]


def test_oversubscribed_slots_share_the_arena(gpt):
    """kv_blocks= decouples concurrency from worst-case reservation:
    3 control slots run over an arena sized for 2 worst-case requests,
    admission gates on free blocks, outputs stay token-identical."""
    cfg, model, params = gpt
    eng = _engine(model, params=params, slots=3,
                  kv_blocks=1 + 2 * (MAX_LEN // BLOCK))
    assert eng.pool.n_blocks == 9 and eng.pool.slots == 3
    # short requests (2 blocks worst case each) → 3 genuinely run at
    # once inside 2 slots' bytes; long ones wait on the block gate
    lens = [6, 9, 4, 11, 5, 8, 20, 3]
    budgets = [4, 3, 4, 2, 5, 3, 6, 4]
    prompts = _prompts(cfg, lens, seed=60)
    sps = [SamplingParams(max_tokens=m) for m in budgets]
    outs = eng.generate_many(prompts, sps)
    assert outs == [_ref(model, params, p, m)
                    for p, m in zip(prompts, budgets)]
    # drained: every block back on the free list or cached
    assert eng.blocks.free_blocks + eng.prefix_cache.cached_blocks == 8
    # an arena that cannot hold even one worst-case request is refused
    with pytest.raises(ValueError, match="worst-case"):
        _engine(model, params=params, slots=2,
                kv_blocks=MAX_LEN // BLOCK)
    # kv_blocks= cannot ride along budget sizing (it would be silently
    # ignored — the budget already fixes the arena)
    with pytest.raises(ValueError, match="conflicts"):
        ServingEngine(model, params, hbm_budget_bytes=1e9,
                      max_len=MAX_LEN, kv_blocks=9)


def test_generate_many_returns_submission_order(gpt):
    """SATELLITE: results align with submission order even when
    requests finish far out of order (short decodes overtake long ones
    across slot recycling)."""
    cfg, model, params = gpt
    prompts = _prompts(cfg, [9, 3, 11, 4, 6], seed=50)
    # first request decodes LONGEST → finishes last; later ones lap it
    budgets = [8, 2, 3, 2, 8]
    sps = [SamplingParams(max_tokens=m) for m in budgets]
    eng = _engine(model, params=params)
    outs = eng.generate_many(prompts, sps)
    assert outs == [_ref(model, params, p, m)
                    for p, m in zip(prompts, budgets)]
    assert [len(o) for o in outs] == budgets
    # and the background-loop path preserves order the same way
    eng.start()
    try:
        outs2 = eng.generate_many(prompts, sps)
    finally:
        eng.stop()
    assert outs2 == outs
