"""Speculation + QoS plane (ISSUE 11): speculative decoding in the
fused serving step, priority scheduling, resumable KV-spill preemption.

Acceptance discipline:

- greedy speculative decode is TOKEN-IDENTICAL to non-speculative
  decode (and to one-shot ``generate``) for every acceptance/rejection
  pattern — a draftsman can only cost speed, never correctness — and
  ``record_trace("serving_step")`` stays at 1 compile with speculation
  and preemption churn enabled;
- preempt→spill→resume produces identical output to an undisturbed
  run, with ZERO prefill-lane work on resume;
- the scheduler's deficit-weighted classes degrade to exact FCFS for
  single-class traffic (the historical submission-order contract).

Quick-tier tests here are host-side (no compiled serving step); every
compile-bearing test is marked slow (ROADMAP quick-tier budget).
"""

import numpy as np
import pytest

from hetu_tpu.serving.kv_pool import HostSpillArena, SpillEntry
from hetu_tpu.serving.scheduler import Request, SamplingParams, Scheduler
from hetu_tpu.serving.speculative import (
    NgramDraftsman, SpeculativeConfigError,
)

MAX_LEN = 32
CHUNK = 8


def _mk(i, plen, max_tokens=4, priority=1):
    return Request(id=i, prompt=np.arange(1, plen + 1, dtype=np.int32),
                   sampling=SamplingParams(max_tokens=max_tokens,
                                           priority=priority),
                   submit_s=0.0)


# ---------------------------------------------------------------------------
# host-side: draft plane
# ---------------------------------------------------------------------------

def test_ngram_draftsman_proposes_continuations():
    d = NgramDraftsman(2, ngram=3)
    pat = [5, 9, 2, 7]
    d.reset(0, pat * 4)
    # the tail 3-gram occurred before: the draft is what followed it
    assert d.propose(0, 4) == pat
    assert d.propose(0, 2) == pat[:2]
    # novel history proposes nothing (the tail's only occurrence is
    # itself)
    d.reset(1, [1, 2, 3, 4, 5, 6, 7])
    assert d.propose(1, 4) == []
    # emitted tokens extend the index incrementally
    d.extend(1, [1, 2])       # tail [1, 2] matched earlier -> continue 3
    assert d.propose(1, 3) == [3, 4, 5]
    # k <= 0 is a no-op, slots are independent
    assert d.propose(0, 0) == []
    assert d.propose(0, 4) == pat


def test_speculative_config_errors_are_named():
    """SATELLITE: the two guard rails raise the named error at
    construction, never corrupting pos mid-decode."""
    from hetu_tpu.serving.speculative import (
        check_draft_depth, check_draft_model,
    )
    with pytest.raises(SpeculativeConfigError,
                       match="would overflow a slot"):
        check_draft_depth(MAX_LEN, MAX_LEN)
    assert check_draft_depth(4, MAX_LEN) == 4
    assert check_draft_depth(0, MAX_LEN) == 0

    class Gate:
        batch_coupled = True

    class MLP:
        def __init__(self):
            self.gate = Gate()

    class Model:
        def __init__(self):
            self.mlp = MLP()

    with pytest.raises(SpeculativeConfigError,
                       match="batch-coupled gate"):
        check_draft_model(Model())
    check_draft_model(object())          # benign models pass


# ---------------------------------------------------------------------------
# host-side: rejection-sampling verify math (ISSUE 17)
# ---------------------------------------------------------------------------

def test_rejection_sampling_verify_matches_target_distribution():
    """TENTPOLE math: the verify lane's committed-token marginal equals
    softmax(adjust_logits(target)) — for a smooth proposal q (drafts
    sampled ~ q, the ModelDraftsman contract) AND for one-hot q (host
    draftsmen with deterministic proposals), which Leviathan rejection
    sampling keeps exact for ANY proposal. Monte Carlo over PRNG keys,
    total-variation distance on a tiny vocab."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.serving.speculative import (
        adjust_logits, speculative_verify,
    )

    V, K, N = 5, 2, 8192
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(0.0, 1.5, (K + 1, V)), jnp.float32)
    temp, topk, topp = 0.7, 0, 1.0
    target = np.asarray(jax.nn.softmax(
        adjust_logits(logits, temp, topk, topp)[0].astype(jnp.float32)))

    keys = np.asarray(jax.vmap(
        lambda s: jax.random.key_data(jax.random.key(s)))(jnp.arange(N)))
    verify = jax.jit(jax.vmap(
        speculative_verify,
        in_axes=(None, 0, None, 0, None, None, None, 0)))

    def marginal(drafts, q):
        committed, n, _, _ = verify(
            logits, jnp.asarray(drafts, jnp.int32), jnp.int32(K),
            jnp.asarray(q, jnp.float32), jnp.float32(temp),
            jnp.int32(topk), jnp.float32(topp), jnp.asarray(keys))
        first = np.asarray(committed[:, 0])
        emp = np.bincount(first, minlength=V) / N
        return emp, np.asarray(n)

    # smooth q: drafts sampled from an (intentionally wrong) proposal
    q_probs = np.asarray(jax.nn.softmax(
        jnp.asarray(rng.normal(0.0, 1.0, (K, V)), jnp.float32)))
    drafts = np.stack(
        [rng.choice(V, size=N, p=q_probs[i]) for i in range(K)], axis=1)
    emp, _ = marginal(drafts, np.broadcast_to(q_probs, (N, K, V)))
    assert 0.5 * np.abs(emp - target).sum() < 0.04

    # one-hot q: a deterministic draftsman proposing a FIXED token is
    # still exact (accept w.p. p(d); residual renormalizes to
    # p(y)/(1-p(d)) for y != d — the marginal telescopes back to p)
    d_fix = np.full((N, K), 3, np.int64)
    onehot = np.zeros((N, K, V), np.float32)
    onehot[..., 3] = 1.0
    emp1, n1 = marginal(d_fix, onehot)
    assert 0.5 * np.abs(emp1 - target).sum() < 0.04
    # ...and the lane-0 accept rate is exactly p(draft)
    assert abs(float((n1 >= 2).mean()) - target[3]) < 0.03


def test_sampled_verify_reduces_bitwise_to_greedy_at_temp0():
    """At temperature 0 the accept test collapses to draft == argmax
    and the outputs are exactly the greedy verify lane's: leading-match
    acceptance plus the argmax bonus, for every accept/reject pattern,
    with the key advanced one split per committed token."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.serving.speculative import speculative_verify

    V, K = 7, 3
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(0.0, 2.0, (K + 1, V)), jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    q = jnp.zeros((K, V), jnp.float32)       # ignored at temp 0
    kd = jax.random.key_data(jax.random.key(42))

    for pattern in range(2 ** K):
        drafts = np.asarray(
            [greedy[i] if (pattern >> i) & 1 else (greedy[i] + 1) % V
             for i in range(K)], np.int32)
        committed, n, last, new_kd = speculative_verify(
            logits, jnp.asarray(drafts), jnp.int32(K), q,
            jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0), kd)
        a = 0
        while a < K and drafts[a] == greedy[a]:
            a += 1
        want = list(drafts[:a]) + [greedy[a]]
        got = np.asarray(committed)[:a + 1]
        assert int(n) == a + 1 and got.tolist() == want
        assert int(last) == greedy[a]
        # PRNG stream parity: exactly ncommit splits consumed
        carry = jax.random.wrap_key_data(kd)
        for _ in range(a + 1):
            carry, _sub = jax.random.split(carry)
        np.testing.assert_array_equal(
            np.asarray(new_kd), np.asarray(jax.random.key_data(carry)))


def test_check_sampled_draft_names_the_contract():
    """SATELLITE: the submit-time guard names every lever of the
    sampled-speculation contract (q rows, surfaces_q, seed) so a
    misconfigured draftsman fails loudly, and the shipped draftsmen
    both satisfy it."""
    from hetu_tpu.serving.speculative import (
        ModelDraftsman, check_sampled_draft,
    )

    check_sampled_draft(None)                         # spec off: fine
    check_sampled_draft(NgramDraftsman(1))
    assert NgramDraftsman.surfaces_q and ModelDraftsman.surfaces_q

    class NoQ:
        pass

    with pytest.raises(SpeculativeConfigError) as ei:
        check_sampled_draft(NoQ())
    msg = str(ei.value)
    for needle in ("NoQ", "surfaces_q", "SamplingParams.seed",
                   "temperature"):
        assert needle in msg


def test_adjust_logits_matches_generation_sampler():
    """adjust_logits + categorical is BITWISE generation._sample for
    the full temperature/top-k/top-p grid — the serving sampler and the
    one-shot reference share one masking arithmetic."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.models.generation import _sample
    from hetu_tpu.serving.speculative import adjust_logits

    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0.0, 2.0, (4, 17)), jnp.float32)
    for i, (t, k, p) in enumerate([(0.7, 0, 0.0), (1.0, 5, 0.0),
                                   (0.6, 0, 0.9), (1.3, 4, 0.8),
                                   (0.25, 1, 0.0), (2.0, 17, 0.999)]):
        key = jax.random.key(100 + i)
        want = _sample(logits, temperature=t, top_k=k, top_p=p, rng=key)
        got = jax.random.categorical(
            key, adjust_logits(logits, t, k, p), axis=-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# knob mixes over four rows: (temperature, top_k, top_p, live, the path
# the LIVE rows select). The last two hold a sampling row that is not
# live — a freed slot keeping its last request's knobs; a finishing row
# that is not valid — beside live greedy rows.
_KNOB_MIXES = {
    "all_greedy": ([0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                   [1, 1, 1, 1], "greedy"),
    "temperature": ([.7, 1., 1.3, .5], [0, 0, 0, 0], [0, 1., 0, 0],
                    [1, 1, 1, 1], "draw"),
    "top_k": ([1., 1., .6, 1.], [5, 3, 0, 7], [0, 0, 0, 0],
              [1, 1, 1, 1], "sort"),
    "top_p": ([1., .8, 1., 1.], [0, 0, 0, 0], [.9, .5, 0, .8],
              [1, 1, 1, 1], "sort"),
    "top_k_and_top_p": ([1., .8, 1.2, 1.], [5, 0, 3, 9], [.9, .5, 0, 1.],
                        [1, 1, 1, 1], "sort"),
    "one_sampling_row": ([0, 0, .8, 0], [0, 0, 4, 0], [0, 0, .9, 0],
                         [1, 1, 1, 1], "sort"),
    "stale_freed_slot": ([0, .8, 0, 0], [0, 4, 0, 0], [0, .9, 0, 0],
                         [1, 0, 1, 1], "greedy"),
    "stale_unmasked_row": ([1.1, 0, 0, .9], [0, 0, 0, 6], [0, 0, 0, 0],
                           [0, 1, 1, 0], "greedy"),
}


@pytest.mark.parametrize("depth", [0, 2])
@pytest.mark.parametrize("mix", sorted(_KNOB_MIXES))
def test_gated_sampler_is_bitwise_the_ungated(mix, depth):
    """ISSUE 31: the fused step's sampler does only what the live rows'
    knobs need, and what it yields for a live row is the ungated
    arithmetic's to the bit — ``jax.vmap(speculative_verify)`` in the
    verify lane, a split + ``adjust_logits`` + categorical per row in
    the prefill lane — tokens, ``ncommit``, ``last_tok`` and key data,
    for every mix of knobs at verify depth 0 and 2. A row that is not
    live never selects the sampled path, whatever knobs it kept."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.serving.speculative import (
        adjust_logits, sample_needs, sample_path, sample_rows,
        speculative_verify, verify_slots,
    )

    temp, topk, topp, live, path = _KNOB_MIXES[mix]
    temp = np.asarray(temp, np.float32)
    topk = np.asarray(topk, np.int32)
    topp = np.asarray(topp, np.float32)
    live = np.asarray(live, bool)
    # what the engine's loop counts is the step's own predicate
    assert sample_path(*sample_needs(live, temp, topk, topp)) == path
    assert sample_path(*jax.jit(sample_needs)(live, temp, topk, topp)) \
        == path

    S, K, V = 4, depth, 23
    rng = np.random.default_rng(31)
    logits = jnp.asarray(rng.normal(0.0, 2.0, (S, K + 1, V)), jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    # every accept pattern: all drafts match, the first alone, none
    drafts = rng.integers(0, V, (S, K)).astype(np.int32)
    drafts[0] = greedy[0, :K]
    drafts[1, :1] = greedy[1, :1]
    depths = np.asarray([K, K, min(1, K), 0], np.int32)
    keys = np.asarray(jax.vmap(lambda s: jax.random.key_data(
        jax.random.key(s)))(jnp.arange(S)))

    want = jax.jit(jax.vmap(speculative_verify))(
        logits, drafts, depths, jax.nn.one_hot(drafts, V), temp, topk,
        topp, keys)
    got = jax.jit(lambda *a: verify_slots(
        logits, drafts, depths, None, *a))(temp, topk, topp, keys, live)
    for w, g in zip(want, got):
        assert w.dtype == g.dtype and w.shape == g.shape
        np.testing.assert_array_equal(np.asarray(g)[live],
                                      np.asarray(w)[live])
    # a device draftsman's q rows take the same gate
    if path != "greedy":
        q = jax.nn.softmax(jnp.asarray(
            rng.normal(0.0, 1.0, (S, K, V)), jnp.float32))
        want_q = jax.jit(jax.vmap(speculative_verify))(
            logits, drafts, depths, q, temp, topk, topp, keys)
        got_q = jax.jit(verify_slots)(logits, drafts, depths, q, temp,
                                      topk, topp, keys, live)
        for w, g in zip(want_q, got_q):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def sample_row(lg, t, k, p, kd):              # the parent's lane
        key, sub = jax.random.split(jax.random.wrap_key_data(kd))
        drawn = jax.random.categorical(sub, adjust_logits(lg, t, k, p))
        tok = jnp.where(t == 0.0, jnp.argmax(lg, axis=-1), drawn)
        return tok.astype(jnp.int32), jax.random.key_data(key)

    rows = logits[:, 0]
    want_f = jax.jit(jax.vmap(sample_row))(rows, temp, topk, topp, keys)
    got_f = jax.jit(sample_rows)(rows, temp, topk, topp, keys, live)
    for w, g in zip(want_f, got_f):
        assert w.dtype == g.dtype and w.shape == g.shape
        np.testing.assert_array_equal(np.asarray(g)[live],
                                      np.asarray(w)[live])
    if path == "greedy":
        # the branch that ran drew nothing, for the stale rows either
        np.testing.assert_array_equal(
            np.asarray(got[2]), greedy[np.arange(S), np.asarray(got[1]) - 1])
        np.testing.assert_array_equal(np.asarray(got_f[0]), greedy[:, 0])


def test_sample_needs_is_one_predicate_on_host_and_device():
    """SATELLITE: the host counts ``serving_sample_path_total`` with
    the expression the step gates on — numpy mirrors in, device arrays
    in, the same two scalars out, over random control vectors with
    stale knobs on rows that are not live."""
    import jax

    from hetu_tpu.serving.speculative import sample_needs, sample_path

    rng = np.random.default_rng(5)
    on_device = jax.jit(sample_needs)
    seen = set()
    for _ in range(200):
        S = int(rng.integers(1, 9))
        live = rng.random(S) < rng.random()
        temp = np.where(rng.random(S) < rng.random(),
                        rng.random(S) * 2, 0).astype(np.float32)
        topk = np.where(rng.random(S) < 0.3,
                        rng.integers(1, 50, S), 0).astype(np.int32)
        topp = rng.choice(np.asarray([0, 0, .5, .9, 1.], np.float32), S)
        host = sample_needs(live, temp, topk, topp)
        dev = on_device(live, temp, topk, topp)
        assert (bool(host[0]), bool(host[1])) \
            == (bool(dev[0]), bool(dev[1]))
        hot = live & (temp > 0)
        assert bool(host[0]) == bool(hot.any())
        assert bool(host[1]) == bool(
            (hot & ((topk > 0) | ((topp > 0) & (topp < 1)))).any())
        seen.add(sample_path(*host))
    assert seen == {"greedy", "draw", "sort"}


# ---------------------------------------------------------------------------
# host-side: QoS scheduler
# ---------------------------------------------------------------------------

def test_scheduler_single_class_stays_exact_fcfs():
    """The historical contract: uniform-priority traffic admits in
    exact submission order (generate_many's ordering depends on it)."""
    sched = Scheduler(slots=2, max_len=16)
    for i in range(4):
        assert sched.submit(_mk(i, 4))
    a = sched.next_admission()
    b = sched.next_admission()
    assert (a[0].id, b[0].id) == (0, 1)
    assert sched.next_admission() is None      # no free slot
    sched.release(a[1])
    assert sched.next_admission()[0].id == 2


def test_scheduler_deficit_weighted_classes():
    """Backlogged classes share admissions ~2:1 per priority step
    (weight 2^-c), urgent first, and batch traffic never starves."""
    sched = Scheduler(slots=1, max_len=16)
    for i in range(8):
        assert sched.submit(_mk(i, 4, priority=0))
    for i in range(8, 16):
        assert sched.submit(_mk(i, 4, priority=2))
    order = []
    for _ in range(12):
        adm = sched.next_admission()
        order.append(adm[0].sampling.priority)
        sched.release(adm[1])
    # urgent goes first...
    assert order[0] == 0
    # ...batch is NOT starved while urgent is backlogged (a 2 shows up
    # well before the 8 queued 0s run out)...
    assert 2 in order[:8]
    # ...and while BOTH classes are backlogged (the first 10 — class 0
    # still has members), urgent takes the ~4x share its 2^-c weight
    # promises
    both = order[:10]
    assert both.count(0) >= 3 * both.count(2) >= 3
    # within a class, FCFS by id
    sched2 = Scheduler(slots=1, max_len=16)
    for i, pr in enumerate([2, 0, 2, 0]):
        sched2.submit(_mk(i, 4, priority=pr))
    adm = sched2.next_admission()
    assert (adm[0].id, adm[0].sampling.priority) == (1, 0)


def test_scheduler_preemption_victim_selection():
    """Victims: strictly lower priority only, lowest class first,
    least-progressed among equals."""
    sched = Scheduler(slots=2, max_len=16)
    cand = _mk(0, 4, priority=0)
    v1, v2 = _mk(1, 4, priority=2), _mk(2, 4, priority=2)
    v1.tokens = [7, 8, 9]
    v2.tokens = [7]
    assert sched.preemption_victim(cand, [(0, v1), (1, v2)]) == 1
    # equal priority never preempts (run-to-completion preserved)
    same = _mk(3, 4, priority=2)
    assert sched.preemption_victim(same, [(0, v1), (1, v2)]) is None
    # a higher-priority runner is never a victim of a lower candidate
    hi = _mk(4, 4, priority=0)
    assert sched.preemption_victim(_mk(5, 4, priority=1),
                                   [(0, hi)]) is None


def test_requeue_preempted_resumes_before_class_peers():
    sched = Scheduler(slots=1, max_len=16)
    sched.submit(_mk(0, 4, priority=1))
    sched.submit(_mk(1, 4, priority=1))
    victim = _mk(9, 4, priority=1)
    victim.tokens = [3]
    sched.requeue_preempted(victim)
    assert victim.status == "preempted"
    assert sched.next_admission()[0].id == 9


# ---------------------------------------------------------------------------
# host-side: spill arena + pricing
# ---------------------------------------------------------------------------

def _entry(req_id, nb, *, ver=0, bs=16):
    data = (np.zeros((2, nb, bs, 2, 4), np.float32),
            np.zeros((2, nb, bs, 2, 4), np.float32))
    return SpillEntry(req_id=req_id, data=data, n_blocks=nb,
                      block_size=bs, pos=8, last_tok=3, tokens=[3],
                      weight_version=ver)


def test_spill_arena_capacity_and_ledgers():
    arena = HostSpillArena(max_blocks=3)
    assert arena.can_fit(3) and not arena.can_fit(4)
    arena.put(_entry(0, 2))
    assert arena.blocks_held == 2 and not arena.can_fit(2)
    with pytest.raises(ValueError, match="spill arena full"):
        arena.put(_entry(1, 2))
    arena.put(_entry(1, 1))
    assert arena.pop(0).req_id == 0
    assert arena.blocks_held == 1
    assert arena.spilled_total == 3 and arena.resumed_total == 2
    # detach (router pull) is not a resume
    arena.pop(1, resumed=False)
    assert arena.resumed_total == 2 and arena.blocks_held == 0
    # unbounded arena
    assert HostSpillArena(None).can_fit(10 ** 9)


def test_spill_arena_pricing_matches_block_ledger():
    """SATELLITE: the host arena is priced with the SAME
    kv_bytes_per_block arithmetic the device pool allocates with."""
    from hetu_tpu.engine.memory import (
        kv_bytes_per_block, size_spill_arena,
    )
    from hetu_tpu.models import GPTConfig
    cfg = GPTConfig.tiny()
    per = kv_bytes_per_block(cfg, block_size=16)
    assert size_spill_arena(cfg, host_budget_bytes=10.5 * per,
                            block_size=16) == 10
    assert size_spill_arena(cfg, host_budget_bytes=10.5 * per / 4,
                            block_size=16, cache_dtype="bf16") == 5
    with pytest.raises(ValueError, match="does not fit"):
        size_spill_arena(cfg, host_budget_bytes=per / 2, block_size=16)


def test_spill_entry_compatibility_gates():
    class Pool:
        block_size = 16
        caches = (np.zeros((2, 9, 16, 2, 4), np.float32),
                  np.zeros((2, 9, 16, 2, 4), np.float32))

    e = _entry(0, 2, ver=3)
    assert e.compatible_with(Pool(), 3)
    assert not e.compatible_with(Pool(), 4)      # weight version moved

    class Pool8(Pool):
        block_size = 8
    assert not e.compatible_with(Pool8(), 3)     # layout mismatch

    class PoolQ(Pool):
        caches = (np.zeros((2, 9, 16, 2, 4), np.int8),) * 4
    assert not e.compatible_with(PoolQ(), 3)     # dtype/leaf mismatch


# ---------------------------------------------------------------------------
# host-side: RESULT verb roundtrip (no engine, no compile)
# ---------------------------------------------------------------------------

def test_result_verb_carries_spec_qos_timing():
    """SATELLITE: the RESULT payload's timing block reports
    drafted/accepted/spilled counts and the priority class — driven
    through the real protocol handler against a stub engine."""
    import threading

    from hetu_tpu.serving.server import (
        decode_payload, handle_serving_command,
    )

    req = _mk(7, 5, max_tokens=4, priority=0)
    req.tokens = [11, 12, 13, 14]
    req.status = "done"
    req.drafted = 6
    req.accepted = 5
    req.preemptions = 1
    req.spilled_blocks = 2
    req.resumed_blocks = 2
    req.mark("admit")
    req.done.set()

    class Stub:
        _requests_by_id = {7: req}
        _lock = threading.Lock()

        def result(self, r, timeout=None):
            return r.result()

    resp = handle_serving_command(Stub(), "RESULT", ["7", "0"])
    assert resp.startswith("VAL ")
    r = decode_payload(resp.split(" ", 1)[1])
    t = r["timing"]
    assert t["priority"] == 0
    assert t["drafted"] == 6 and t["accepted"] == 5
    assert t["preemptions"] == 1
    assert t["spilled_blocks"] == 2 and t["resumed_blocks"] == 2
    # and the priority knob decodes from the SUBMIT payload
    from hetu_tpu.serving.server import sampling_from_payload
    sp = sampling_from_payload({"prompt": [1], "priority": 2,
                                "max_tokens": 3})
    assert sp.priority == 2 and sp.max_tokens == 3


# ---------------------------------------------------------------------------
# compiled acceptance tests (slow tier)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt():
    import jax
    import jax.numpy as jnp

    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, model, params


def _ref(model, params, prompt, max_tokens, **kw):
    import jax.numpy as jnp

    from hetu_tpu.models import generate
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=max_tokens, max_len=MAX_LEN, **kw)
    return np.asarray(out[0, len(prompt):]).tolist()


def _corpus(cfg, seed=0):
    """Mixed repetitive (high n-gram acceptance) + random prompts."""
    rng = np.random.default_rng(seed)
    pat = rng.integers(1, cfg.vocab_size, (4,)).tolist()
    return [pat * 4, rng.integers(1, cfg.vocab_size, (7,)).tolist(),
            pat * 3 + pat[:2], rng.integers(1, cfg.vocab_size,
                                            (11,)).tolist(),
            pat * 2]


@pytest.mark.slow
def test_spec_greedy_token_identical_all_patterns(gpt):
    """ACCEPTANCE: speculative greedy decode == one-shot generate for
    every request across arrival orders, mixed draft depths, and a
    FORCED-rejection draftsman — at 1 fused-step compile."""
    from hetu_tpu import telemetry
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    prompts = _corpus(cfg)
    sp = SamplingParams(max_tokens=6)
    want = [_ref(model, params, p, 6) for p in prompts]

    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, spec_depth=3)
    before = trace_counts().get("serving_step", 0)
    assert eng.generate_many(prompts, sp) == want
    assert eng.generate_many(list(reversed(prompts)), sp) \
        == list(reversed(want))
    # forced rejection: a hostile draftsman that always proposes wrong
    # tokens — outputs must be bit-identical, speed is all it can lose
    class Hostile:
        host_only = True
        # deterministic proposals → one-hot q, synthesized on-device:
        # the sampled-lane contract a host draftsman declares
        surfaces_q = True

        def reset(self, *a):
            pass

        def extend(self, *a):
            pass

        def propose(self, slot, k):
            return [0] * k           # token 0 never sampled (prompts>0)

    eng._draftsman = Hostile()
    telemetry.reset()
    telemetry.enable(True)
    try:
        assert eng.generate_many(prompts, sp) == want
        # the acceptance floor: every draft rejected commits exactly
        # the non-speculative one token per decode slot-step
        reg = telemetry.get_registry()
        assert reg.counter("serving_draft_tokens_total").value() > 0
        assert reg.counter("serving_accepted_tokens_total").value() == 0
    finally:
        telemetry.enable(False)
        telemetry.reset()
    assert trace_counts().get("serving_step", 0) - before == 1, \
        "speculation churn re-traced the fused step"
    # mixed depths in one batch: depth riding per-slot data
    eng2 = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, spec_depth=1)
    assert eng2.generate_many(prompts, sp) == want
    # sampled requests coexist (they speculate through the rejection-
    # sampling verify lane; tokens stay in range)
    mixed = [SamplingParams(max_tokens=6),
             SamplingParams(max_tokens=6, temperature=1.0, top_k=10)]
    outs = eng.generate_many(prompts[:2], mixed)
    assert outs[0] == want[0]
    assert all(0 <= t < cfg.vocab_size for t in outs[1])


@pytest.mark.slow
def test_spec_int8_pool_matches_and_accepts(gpt):
    """ACCEPTANCE: the quantized paged pool under speculation still
    reproduces one-shot int8 generation, and drafts actually land."""
    import jax.numpy as jnp

    from hetu_tpu import telemetry
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    prompts = _corpus(cfg, seed=2)
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, cache_dtype=jnp.int8,
                            spec_depth=3)
        sp = SamplingParams(max_tokens=5)
        want = [_ref(model, params, p, 5, cache_dtype=jnp.int8)
                for p in prompts]
        assert eng.generate_many(prompts, sp) == want
        reg = telemetry.get_registry()
        ac = reg.counter("serving_accepted_tokens_total").value()
        assert ac > 0
        steps = reg.counter("serving_decode_slot_steps_total").value()
        assert 1.0 + ac / steps > 1.0    # tokens per slot-step beat 1
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_preempt_spill_resume_identity(gpt):
    """ACCEPTANCE: preempt→spill→resume output == undisturbed run, the
    resumed request does ZERO prefill-lane work, and the spill/resume
    executables stay at one compile each."""
    from hetu_tpu import telemetry
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    rng = np.random.default_rng(1)
    lo_p = rng.integers(1, cfg.vocab_size, (10,)).tolist()
    hi_p = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=1, max_len=MAX_LEN,
                            prefill_chunk=CHUNK)
        before = trace_counts().get("serving_step", 0)
        lo = eng.submit(lo_p, SamplingParams(max_tokens=16, priority=2))
        for _ in range(6):
            eng.step()                       # lo mid-decode
        assert len(lo.tokens) > 1
        hi = eng.submit(hi_p, SamplingParams(max_tokens=4, priority=0))
        eng.run_until_drained()
        assert lo.preemptions == 1
        assert lo.spilled_blocks >= 1
        assert lo.resumed_blocks == lo.spilled_blocks
        assert list(hi.tokens) == _ref(model, params, hi_p, 4)
        assert list(lo.tokens) == _ref(model, params, lo_p, 16)
        # zero prefill-lane work on resume: the only prefill chunks are
        # the ORIGINAL ones (ceil(10/8) = 2), and the event trail shows
        # preempted -> admit -> resumed with no prefill between
        assert lo.timing()["prefill_chunks"] == 2
        phases = [p for p, _, _ in lo.events]
        i = phases.index("preempted")
        assert phases[i:i + 3] == ["preempted", "admit", "resumed"]
        assert trace_counts().get("serving_step", 0) - before <= 1
        assert trace_counts().get("serving_kv_spill", 0) <= 1
        assert trace_counts().get("serving_kv_resume", 0) <= 1
        reg = telemetry.get_registry()
        assert reg.counter("serving_preemptions_total").value(
            priority="2") == 1
        t = lo.result()["timing"]
        assert t["preemptions"] == 1 and t["spilled_blocks"] >= 1
        # the arena drained (gauge parity)
        assert eng.spill_arena.blocks_held == 0
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_preempt_with_speculation_churn_one_compile(gpt):
    """Speculation AND preemption in the same engine: token identity
    holds through the combined churn at 1 fused-step compile."""
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    rng = np.random.default_rng(3)
    pat = rng.integers(1, cfg.vocab_size, (4,)).tolist()
    lo_p = pat * 4                      # repetitive: speculation bites
    hi_p = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    eng = ServingEngine(model, params, slots=1, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, spec_depth=3)
    before = trace_counts().get("serving_step", 0)
    lo = eng.submit(lo_p, SamplingParams(max_tokens=12, priority=2))
    for _ in range(4):
        eng.step()
    hi = eng.submit(hi_p, SamplingParams(max_tokens=4, priority=0))
    eng.run_until_drained()
    assert lo.preemptions >= 1
    assert list(lo.tokens) == _ref(model, params, lo_p, 12)
    assert list(hi.tokens) == _ref(model, params, hi_p, 4)
    assert trace_counts().get("serving_step", 0) - before <= 1


@pytest.mark.slow
def test_sampled_engine_matches_one_shot_generate_bitwise(gpt):
    """TENTPOLE ACCEPTANCE: identical-seed sampled serving equals
    one-shot sampled ``generate`` BITWISE across the
    temperature/top-k/top-p grid and arrival churn — the engine walks
    the same PRNG stream (one split per committed token off the
    per-request key) and the same masking arithmetic as the reference.
    Speculation stays off here: accepted drafts commit several tokens
    per iteration, which is distribution-equal (the host math test) but
    consumes the stream differently. One fused-step compile covers the
    whole knob grid — sampling knobs and keys are traced data."""
    import jax

    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    prompts = _corpus(cfg, seed=5)
    knobs = [(0.7, 0, 0.0, 11), (1.0, 10, 0.0, 12), (0.8, 0, 0.9, 13),
             (1.2, 6, 0.85, 14), (0.0, 0, 0.0, 15)]
    before = trace_counts().get("serving_step", 0)
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    reqs = []
    for p, (t, k, tp_, s) in zip(prompts, knobs):
        reqs.append(eng.submit(p, SamplingParams(
            max_tokens=6, temperature=t, top_k=k, top_p=tp_, seed=s)))
        eng.step()                              # stagger arrivals
    eng.run_until_drained()
    assert trace_counts().get("serving_step", 0) - before == 1
    for r, p, (t, k, tp_, s) in zip(reqs, prompts, knobs):
        want = _ref(model, params, p, 6, temperature=t, top_k=k,
                    top_p=tp_, rng=jax.random.key(s))
        assert list(r.tokens) == want, (t, k, tp_, s)


@pytest.mark.slow
def test_sampled_speculation_beats_one_token_per_slot_step(gpt):
    """SATELLITE CONTRACT: sampled slots actually SPECULATE — at
    temperature > 0 with a self-drafting model (q == p, the acceptance
    ceiling: accept prob min(1, p/q) == 1) the engine commits more
    than one token per decode slot-step, with the sampled-lane
    counters flowing."""
    from hetu_tpu import telemetry
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    prompts = _corpus(cfg, seed=6)[:3]
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, spec_depth=3,
                            draft_model=model, draft_params=params)
        sps = [SamplingParams(max_tokens=8, temperature=0.7,
                              seed=100 + i) for i in range(len(prompts))]
        outs = eng.generate_many(prompts, sps)
        assert all(0 <= t < cfg.vocab_size for o in outs for t in o)
        reg = telemetry.get_registry()
        acc = reg.counter(
            "serving_sampled_accepted_tokens_total").value()
        steps = reg.counter("serving_decode_slot_steps_total").value()
        assert acc > 0, "no sampled drafts accepted"
        tokens_per_slot_step = 1.0 + acc / steps
        assert tokens_per_slot_step > 1.0
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_model_draftsman_greedy_parity(gpt):
    """The small-model draft path: a zoo model drafting (here the
    target itself — the acceptance ceiling) stays token-identical and
    actually accepts drafts once warm, at 1 draft-step compile."""
    from hetu_tpu import telemetry
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    prompts = _corpus(cfg, seed=4)[:3]
    sp = SamplingParams(max_tokens=8)
    want = [_ref(model, params, p, 8) for p in prompts]
    telemetry.reset()
    telemetry.enable(True)
    try:
        before = trace_counts().get("serving_draft_step", 0)
        eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, spec_depth=3,
                            draft_model=model, draft_params=params)
        assert eng.generate_many(prompts, sp) == want
        assert trace_counts().get("serving_draft_step", 0) - before == 1
        reg = telemetry.get_registry()
        dr = reg.counter("serving_draft_tokens_total").value()
        ac = reg.counter("serving_accepted_tokens_total").value()
        assert dr > 0
        # self-drafting: once warm, acceptance is near-perfect
        assert ac / dr > 0.8, (ac, dr)
        # greedy requests never count in the sampled verify lane
        assert reg.counter(
            "serving_sampled_accepted_tokens_total").value() == 0
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_router_death_requeue_resumes_on_peer(gpt):
    """ACCEPTANCE: kill_replica mid-decode loses/duplicates nothing AND
    the dead replica's mid-decode request moves its KV to the peer
    (resumed dispatch, no re-prefill)."""
    from hetu_tpu import telemetry
    from hetu_tpu.serving import Router, SamplingParams, ServingEngine

    cfg, model, params = gpt
    telemetry.reset()
    telemetry.enable(True)
    router = Router(poll_s=0.001)
    try:
        engines = {}
        for name in ("r0", "r1"):
            engines[name] = ServingEngine(
                model, params, slots=2, max_len=MAX_LEN,
                prefill_chunk=CHUNK)
            router.register(name, engines[name])
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size, (6,)).tolist()
                   for _ in range(6)]
        sp = SamplingParams(max_tokens=12)
        want = [_ref(model, params, p, 12) for p in prompts]
        reqs = [router.submit(p, sp) for p in prompts]
        # wait until a replica has mid-decode work, then kill it
        victim = None
        for _ in range(2000):
            for name, eng in engines.items():
                if eng._active.any() and router._replicas[
                        name].state == "live":
                    victim = name
                    break
            if victim:
                break
            import time
            time.sleep(0.002)
        assert victim is not None
        router.kill_replica(victim)
        for r in reqs:
            assert r.done.wait(120.0)
        assert [list(r.tokens) for r in reqs] == want   # zero lost/dup
        # at least one request rode the resumable path to the peer
        resumed = sum(r.resumed_dispatches for r in reqs)
        assert resumed >= 1, "death requeue never used the KV spill"
        assert telemetry.get_registry().counter(
            "router_resumed_requeues_total").value() >= 1
    finally:
        router.stop()
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_publisher_preemptive_drain_resumes_on_peers(gpt):
    """WeightPublisher drains route through the resumable path: a
    replica with long-running decodes drains by SPILLING them to a
    same-version peer — no lost work, outputs complete, and the swap
    still lands."""
    import jax
    import jax.numpy as jnp

    from hetu_tpu.serving import (
        Router, SamplingParams, ServingEngine, WeightPublisher,
    )

    cfg, model, params = gpt
    router = Router(poll_s=0.001)
    try:
        engines = {}
        for name in ("r0", "r1"):
            engines[name] = ServingEngine(
                model, params, slots=2, max_len=MAX_LEN,
                prefill_chunk=CHUNK)
            router.register(name, engines[name])
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, cfg.vocab_size, (5,)).tolist()
                   for _ in range(4)]
        sp = SamplingParams(max_tokens=14)
        reqs = [router.submit(p, sp) for p in prompts]
        # let decodes get going, then push new weights mid-flight
        import time
        for _ in range(2000):
            if any(e._active.any() for e in engines.values()):
                break
            time.sleep(0.002)
        params2 = jax.tree.map(lambda x: x * (1.0 + 1e-3)
                               if isinstance(x, jax.Array) else x,
                               params)
        report = WeightPublisher(router).publish(params2, version=7)
        assert all("skipped" not in p for p in report["replicas"])
        for r in reqs:
            assert r.done.wait(120.0)
            assert r.status == "done"
        # requests admitted before the push finished under version 0 —
        # a preempted-and-resumed one must NOT have re-prefilled under
        # the new weights
        for r in reqs:
            assert r.weight_version == 0
        assert router.fleet_status()["weight_versions"] == [7]
        # outputs under the OLD weights match old-weight one-shots
        want = [_ref(model, params, p, 14) for p in prompts]
        assert [list(r.tokens) for r in reqs] == want
    finally:
        router.stop()
