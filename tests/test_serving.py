"""Serving plane (ISSUE 5): continuous-batching engine over the
slot-pooled KV cache.

Acceptance discipline: the engine is a SCHEDULING transform, not a
numerical one — every request's greedy tokens must be identical to a
one-shot ``models.generation.generate`` of that request alone (same
cache capacity), independent of arrival order, slot assignment, chunked
prefill, and cache dtype; and request churn must never recompile the
fused step (the PR 2 ``record_trace`` counter stays at its initial
compile count).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.engine import trace_counts
from hetu_tpu.models import (
    GPTConfig, GPTLMHeadModel, LlamaConfig, LlamaLMHeadModel, generate,
)
from hetu_tpu.serving import (
    KVPool, Request, SamplingParams, Scheduler, ServingEngine,
)

MAX_LEN = 32
CHUNK = 8


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
    """One-shot generate of a single request at the POOL's cache
    capacity (same reduction lengths as the slot arena)."""
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=max_tokens, max_len=MAX_LEN, **kw)
    return np.asarray(out[0, len(prompt):]).tolist()


def test_engine_matches_generate_any_arrival_order(gpt):
    """ACCEPTANCE: greedy tokens are identical to per-request one-shot
    generate, for every request, under both arrival orders — with only
    2 slots so later requests queue and recycle evicted slots."""
    cfg, model, params = gpt
    prompts = _prompts(cfg, [5, 11, 3, 8, 17, 2, 9, 6])
    sp = SamplingParams(max_tokens=6)
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    want = [_ref(model, params, p, 6) for p in prompts]
    assert eng.generate_many(prompts, sp) == want
    assert eng.generate_many(list(reversed(prompts)), sp) \
        == list(reversed(want))


def test_engine_zero_retraces_across_churn(gpt):
    """ACCEPTANCE: >= 8 admits/evictions churn one compiled step — the
    re-trace counter equals the initial compile count (exactly 1)."""
    cfg, model, params = gpt
    # committed params, as a checkpoint load or a device_put leaves
    # them: beside an arena that was born uncommitted they compiled the
    # step three times under its one trace
    params = jax.device_put(params, jax.devices()[0])
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    before = trace_counts().get("serving_step", 0)
    prompts = _prompts(cfg, [5, 11, 3, 8, 17, 2, 9, 6, 13, 4], seed=3)
    outs = eng.generate_many(prompts, SamplingParams(max_tokens=4))
    assert len(outs) == 10 and all(len(o) == 4 for o in outs)
    after = trace_counts().get("serving_step", 0)
    assert after - before == 1, (
        f"request churn re-traced the fused step "
        f"({after - before} traces for 10 admits/evictions)")
    assert eng.step_executables() == 1, "one trace, several compiles"
    # second engine over the SAME model/shapes: jit cache hit, still no
    # new trace even across engine objects
    eng2 = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                         prefill_chunk=CHUNK)
    eng2.generate_many(prompts[:3], SamplingParams(max_tokens=3))
    assert trace_counts().get("serving_step", 0) - after <= 1


def test_engine_int8_pool_matches_int8_generate(gpt):
    """ACCEPTANCE: the quantized pool reproduces one-shot int8-cache
    generation token for token (row-wise scales make chunked prefill
    quantization identical to one-pass quantization)."""
    cfg, model, params = gpt
    prompts = _prompts(cfg, [5, 11, 3, 14], seed=1)
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, cache_dtype=jnp.int8)
    assert eng.pool.quantized
    sp = SamplingParams(max_tokens=5)
    want = [_ref(model, params, p, 5, cache_dtype=jnp.int8)
            for p in prompts]
    assert eng.generate_many(prompts, sp) == want


PARENT_TOKENS = {
    "mixed": [[170] * 8, [239, 203, 203, 76, 221, 236, 248, 83],
              [52] * 8],
    "seeded": [[192, 161, 245, 245, 232, 8, 28, 16],
               [225, 225, 225, 225, 225, 225, 88, 88]]}


def test_engine_eos_and_sampling_params(gpt):
    """Per-slot sampling params are traced operands: mixed greedy and
    sampled requests run in one batch without retracing, EOS stops a
    request early, and sampled tokens stay in range."""
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    prompts = _prompts(cfg, [6, 9, 4], seed=2)
    before = trace_counts().get("serving_step", 0)
    greedy = SamplingParams(max_tokens=8)
    sampled = SamplingParams(temperature=1.0, top_k=10, top_p=0.9,
                             max_tokens=8)
    outs = eng.generate_many(prompts, [greedy, sampled, greedy])
    assert trace_counts().get("serving_step", 0) - before <= 1
    assert outs[0] == _ref(model, params, prompts[0], 8)
    assert outs[2] == _ref(model, params, prompts[2], 8)
    assert all(0 <= t < cfg.vocab_size for t in outs[1])
    # ISSUE 38: the packed step draws the tokens the nine-operand step
    # drew — these are the parent's (1ef4e70) outputs of this very
    # traffic, the sampled request's key from the engine's seed and its
    # id, and of a request with a seed of its own beside a greedy one
    assert outs == PARENT_TOKENS["mixed"]
    seeded = SamplingParams(temperature=0.8, top_p=0.95, max_tokens=8,
                            seed=38)
    assert eng.generate_many(prompts[:2], [seeded, greedy]) \
        == PARENT_TOKENS["seeded"]
    # EOS: pick the greedy run's first token as eos — request finishes
    # after exactly one token
    eos = outs[0][0]
    out = eng.generate_many([prompts[0]],
                            SamplingParams(max_tokens=8, eos_id=eos))[0]
    assert out == [eos]


def test_generate_many_rejection_raises(gpt):
    """Offline API: a request that can never fit a slot fails FAST and
    loud (not a silent empty output), and queued siblings are cleaned
    up so the engine stays drained."""
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    ok, too_long = _prompts(cfg, [4, MAX_LEN + 1], seed=9)
    with pytest.raises(ValueError, match="rejected at admission"):
        eng.generate_many([ok, too_long], SamplingParams(max_tokens=4))
    assert not eng.has_work()                # sibling was un-queued
    # the engine still serves fine afterwards
    assert eng.generate_many([ok], SamplingParams(max_tokens=4)) \
        == [_ref(model, params, ok, 4)]


def test_llama_engine_smoke():
    """The engine is model-agnostic: Llama (RoPE + GQA) greedy parity."""
    cfg = LlamaConfig.tiny()
    model = LlamaLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    prompts = _prompts(cfg, [5, 9], seed=4)
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    outs = eng.generate_many(prompts, SamplingParams(max_tokens=4))
    assert outs == [_ref(model, params, p, 4) for p in prompts]


def test_scheduler_fcfs_and_hbm_gating(gpt):
    """Pure-scheduler logic: FCFS order, slot recycling, and the
    max_len (= HBM budget) admission gate."""
    cfg, model, params = gpt
    sched = Scheduler(slots=2, max_len=16)

    def mk(i, plen, max_tokens=4):
        return Request(id=i, prompt=np.arange(1, plen + 1, dtype=np.int32),
                       sampling=SamplingParams(max_tokens=max_tokens),
                       submit_s=0.0)

    too_long = mk(0, 14, max_tokens=4)        # 14 + 4 > 16
    assert not sched.submit(too_long)
    # structured rejection (shape plane): names the slot budget and the
    # knob that would lift it
    assert too_long.status == "rejected"
    assert "16-token serving slot budget" in too_long.error
    assert "long_max_len" in too_long.error
    assert not sched.submit(mk(1, 0))         # empty prompt
    a, b, c = mk(2, 4), mk(3, 4), mk(4, 4)
    assert all(sched.submit(r) for r in (a, b, c))
    r1 = sched.next_admission()
    r2 = sched.next_admission()
    assert (r1[0].id, r2[0].id) == (2, 3)     # FCFS
    assert sched.next_admission() is None     # no free slot
    assert sched.depth == 1 and sched.occupancy == 1.0
    sched.release(r1[1])
    r3 = sched.next_admission()
    assert r3[0].id == 4 and r3[1] == r1[1]   # recycled slot

    # pool sizing from the memory ledger: budget -> slots, and the
    # engine accepts the ledger-sized pool end to end
    from hetu_tpu.engine.memory import kv_bytes_per_slot, size_kv_pool
    from hetu_tpu.tools.galvatron.cost_model import ModelDims
    per = kv_bytes_per_slot(cfg, max_len=MAX_LEN)
    weights = ModelDims.from_config(
        cfg, seq_len=MAX_LEN, global_batch=1).total_params() * 4
    budget = (weights + 5.2 * per) / 0.9
    assert size_kv_pool(cfg, hbm_budget_bytes=budget,
                        max_len=MAX_LEN) == 5
    with pytest.raises(ValueError, match="does not fit"):
        size_kv_pool(cfg, hbm_budget_bytes=weights, max_len=MAX_LEN)
    pool = KVPool.sized_for(model, hbm_budget_bytes=budget,
                            max_len=MAX_LEN)
    assert pool.slots == 5
    # int8 pool: >2x the slots of fp32 in the same budget
    assert size_kv_pool(cfg, hbm_budget_bytes=budget, max_len=MAX_LEN,
                        cache_dtype="int8") > 5


def test_serving_telemetry_and_trace_summary(gpt, tmp_path):
    """Request-level telemetry: token/request counters, TTFT/TPOT
    histograms, queue/occupancy gauges — and the trace_summary
    'serving plane' section renders them from the exported artifact."""
    cfg, model, params = gpt
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, counter_sample_every=2)
        prompts = _prompts(cfg, [5, 11, 3, 8], seed=5)
        eng.generate_many(prompts, SamplingParams(max_tokens=4))
        reg = telemetry.get_registry()
        assert reg.counter("serving_requests_total").value(
            outcome="submitted") == 4
        assert reg.counter("serving_requests_total").value(
            outcome="completed") == 4
        assert reg.counter("serving_tokens_total").value(
            kind="prompt") == sum(len(p) for p in prompts)
        assert reg.counter("serving_tokens_total").value(
            kind="generated") == 16
        assert reg.histogram("serving_ttft_seconds").summary()["count"] \
            == 4
        assert reg.histogram("serving_tpot_seconds").summary()["count"] \
            == 4
        assert reg.gauge("serving_slot_occupancy").value() == 0.0
        # Perfetto counter tracks sampled serving_* series
        assert any(s[0].startswith("serving_")
                   for s in telemetry.get_tracer().counter_samples())

        paths = telemetry.export_dir(str(tmp_path))
        from hetu_tpu.tools.trace_summary import summarize
        text = summarize(paths["jsonl"])
        assert "== serving plane ==" in text
        assert "ttft" in text and "tokens" in text
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_rpc_serving_roundtrip(gpt):
    """The line-protocol front end: SUBMIT/RESULT/GENERATE over the
    coordinator, engine loop running in the background."""
    import socket

    from hetu_tpu.rpc.client import CoordinatorClient
    from hetu_tpu.serving.server import ServingServer

    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = ServingServer(eng, port)
    srv.start()
    srv.wait_ready()
    try:
        cli = CoordinatorClient(port)
        assert cli.ping()                     # coordinator role intact
        prompt = _prompts(cfg, [6], seed=6)[0]
        want = _ref(model, params, prompt, 5)
        # blocking GENERATE
        r = cli.serving_generate(prompt, max_tokens=5)
        assert r["status"] == "done" and r["tokens"] == want
        # SUBMIT + RESULT poll
        rid = cli.serving_submit(prompt, max_tokens=5)
        for _ in range(200):
            r = cli.serving_result(rid, timeout_ms=100)
            if r is not None:
                break
        assert r is not None and r["tokens"] == want
        # admission gate surfaces as a protocol error
        with pytest.raises(RuntimeError, match="rejected"):
            cli.serving_submit(list(range(1, MAX_LEN + 2)), max_tokens=4)
        cli.close()
    finally:
        srv.stop()


def test_ttft_once_and_gauges_drain_under_churn(gpt):
    """SATELLITE (ISSUE 6): serving telemetry under churn — TTFT/TPOT
    observed exactly once per request even when requests queue behind 2
    slots and recycle them, queue/occupancy gauges return to zero after
    drain, and the fused step stays at <= 1 compile with per-request
    tracing enabled."""
    cfg, model, params = gpt
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK)
        before = trace_counts().get("serving_step", 0)
        prompts = _prompts(cfg, [5, 11, 3, 8, 17, 2, 9, 6], seed=11)
        reqs = [eng.submit(p, SamplingParams(max_tokens=4))
                for p in prompts]
        eng.run_until_drained()
        reg = telemetry.get_registry()
        # exactly once per request — churn (queueing + slot recycling)
        # must not re-observe
        assert reg.histogram("serving_ttft_seconds").summary()["count"] \
            == len(prompts)
        assert reg.histogram("serving_tpot_seconds").summary()["count"] \
            == len(prompts)
        # gauges drain to zero with the pool empty
        assert reg.gauge("serving_queue_depth").value() == 0.0
        assert reg.gauge("serving_slot_occupancy").value() == 0.0
        # per-request tracing is host-side only: still one compile
        assert trace_counts().get("serving_step", 0) - before <= 1
        # every request rendered its own Perfetto track with the
        # lifecycle spans
        req_spans = [e for e in telemetry.get_tracer().events()
                     if e.cat == "request"]
        by_trace = {}
        for e in req_spans:
            by_trace.setdefault(e.attrs["trace_id"], set()).add(e.name)
        assert len(by_trace) == len(prompts)
        for names in by_trace.values():
            assert {"queued", "prefill_chunk", "decode"} <= names
        # and the RESULT-style timing breakdown is complete + ordered.
        # Packed prefill (ISSUE 7) shares the chunk budget across
        # admitting requests, so a request's iteration count is no
        # longer exactly ceil(P/C): it floors there (FCFS fill) and can
        # gain one partial leading chunk when it joins a busy pack.
        for r in reqs:
            t = r.result()["timing"]
            assert t["trace_id"] == r.trace_id
            assert 0 <= t["queued_ms"] <= t["ttft_ms"] <= t["total_ms"]
            lo = -(-len(r.prompt) // CHUNK)
            assert lo <= t["prefill_chunks"] <= lo + 1
            assert t["cached_tokens"] == 0       # all prompts distinct
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_result_verb_returns_timing_breakdown(gpt):
    """The RESULT/SUBMIT protocol verbs carry the trace id + timing
    breakdown (no sockets: the handler is driven directly)."""
    from hetu_tpu.serving.server import (
        decode_payload, encode_payload, handle_serving_command,
    )
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    prompt = _prompts(cfg, [6], seed=12)[0]
    resp = handle_serving_command(
        eng, "SUBMIT", [encode_payload({"prompt": prompt,
                                        "max_tokens": 4})])
    assert resp.startswith("ID ")
    _, rid, trace_id = resp.split()
    eng.run_until_drained()
    resp = handle_serving_command(eng, "RESULT", [rid, "0"])
    assert resp.startswith("VAL ")
    r = decode_payload(resp.split(" ", 1)[1])
    assert r["status"] == "done" and len(r["tokens"]) == 4
    t = r["timing"]
    assert t["trace_id"] == trace_id
    for key in ("queued_ms", "prefill_ms", "ttft_ms", "decode_ms",
                "total_ms", "prefill_chunks"):
        assert key in t, key
    assert t["total_ms"] >= t["decode_ms"] >= 0
    assert t["ttft_ms"] >= t["prefill_ms"] >= 0


def test_online_submit_during_decode(gpt):
    """Continuous batching, not batch-boundary batching: a request
    submitted WHILE the engine decodes joins the running batch and
    still reproduces its one-shot tokens."""
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    p1, p2 = _prompts(cfg, [9, 5], seed=7)
    sp = SamplingParams(max_tokens=8)
    r1 = eng.submit(p1, sp)
    for _ in range(3):                        # p1 mid-flight
        eng.step()
    r2 = eng.submit(p2, sp)
    eng.run_until_drained()
    assert list(r1.tokens) == _ref(model, params, p1, 8)
    assert list(r2.tokens) == _ref(model, params, p2, 8)


def _sampler_eqns(jaxpr, vocab, conds=()):
    """``(what, conds)`` for every ``sort`` and every random draw of
    vocabulary width under ``jaxpr``; ``conds`` are the ``cond``
    equations it is nested in, outermost first."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        wide = any(vocab in getattr(v.aval, "shape", ())
                   for v in eqn.outvars)
        if name == "sort":
            yield "sort", conds
        elif wide and name.startswith(("random_", "threefry")):
            yield "draw", conds
        inner = conds + (id(eqn),) if name == "cond" else conds
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sampler_eqns(sub, vocab, inner)


@pytest.mark.parametrize("spec_depth", [0, 2])
def test_fused_step_sampler_runs_only_under_its_gates(gpt, spec_depth):
    """ISSUE 31, structure: in the fused step's program every sort and
    every random draw of vocabulary width sits inside a ``cond`` branch
    of its lane — the lane's own cond, then the batch's ``draws`` gate,
    the sorts under the ``sorts`` gate besides — in BOTH lanes, so a
    batch of greedy rows runs neither (a gate under ``vmap`` would have
    become a select and show here as no cond at all). The per-token key
    splits are small and stay outside."""
    import dataclasses
    cfg, _, _ = gpt
    cfg = dataclasses.replace(cfg, vocab_size=251)   # no other dim's
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, spec_depth=spec_depth)
    step, seen = eng._fn, []

    def spy(*args):
        seen.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           np.result_type(x)), args))
        return step(*args)

    eng._fn = spy
    prompt = _prompts(cfg, [5], seed=4)[0]
    assert eng.generate_many([prompt], SamplingParams(max_tokens=3)) \
        == [_ref(model, params, prompt, 3)]
    found = list(_sampler_eqns(jax.make_jaxpr(step)(*seen[0]).jaxpr,
                               cfg.vocab_size))
    lanes = {"sort": set(), "draw": set()}
    for what, conds in found:
        assert len(conds) >= (3 if what == "sort" else 2), (
            f"a {what} of the sampler runs whatever the knobs are "
            f"({len(conds)} conds around it)")
        lanes[what].add(conds[0])
    # one gated sort and gated draws in each of the two lanes
    assert len(lanes["sort"]) == 2 and lanes["draw"] == lanes["sort"]


def _packed_traffic(eng, cfg):
    """Churn that makes every field of the step's result vector carry
    news: two prompts finishing their prefill in one pack, a sampled
    request with a seed beside greedy ones (the key state moves),
    accepted drafts, and a prompt whose cached prefix ends inside a
    block."""
    short = _prompts(cfg, [3, 4], seed=5)
    shared = _prompts(cfg, [12], seed=6)[0]     # a block of 8 + 4 rows
    eng.generate_many(
        short + [shared + [7, 9, 7, 9, 7]],      # two whole blocks
        [SamplingParams(max_tokens=6),
         SamplingParams(temperature=0.7, top_k=5, top_p=0.9,
                        max_tokens=6, seed=3),
         SamplingParams(max_tokens=6)])
    eng.generate_many([shared + [11]], SamplingParams(max_tokens=4))


def test_packed_results_reach_the_host_bit_for_bit(gpt):
    """ISSUE 38 (a): the ONE vector the step returns is its results,
    field for field — the layout carries float32 and uint32 by their
    bits through a jit and back; in the engine's own step the key
    words sliced out of the vector are the key state the step also
    returns on the device, in every iteration of a churn, and the
    vector's other fields say what the requests received."""
    from hetu_tpu.serving.step_io import PackedFields
    knobs = {"temp": np.float32([0.7, -0.0, np.nan, 1e-42]),
             "key": np.uint32([[0, 2 ** 32 - 1], [38, 2 ** 31]]),
             "stats": ({"n": np.int32([[1, -2], [3, 4]])}, {})}
    lay = PackedFields(knobs)
    assert [f[:2] for f in lay.fields] == [
        ("['key']", 0), ("['stats'][0]['n']", 4), ("['temp']", 8)] \
        and lay.size == 12
    back = lay.unpack_host(np.asarray(jax.jit(lay.pack_device)(knobs)))
    assert jax.tree.structure(back) == jax.tree.structure(knobs)
    for want, have in zip(jax.tree.leaves(knobs), jax.tree.leaves(back)):
        assert (have.dtype, have.shape) == (want.dtype, want.shape)
        assert have.tobytes() == want.tobytes()
    # a field that is not 32 bits wide has no place in the vector, and
    # a value of another type than its field's is not cast on the way
    with pytest.raises(ValueError, match="32-bit"):
        PackedFields({"x": np.zeros(2, np.bool_)})
    with pytest.raises(ValueError, match="laid out as"):
        jax.jit(lay.pack_device)(
            dict(knobs, temp=knobs["temp"].astype(np.float16)))
    with pytest.raises(ValueError, match="laid out as"):
        lay.unpack_host(np.zeros(lay.size + 1, np.int32))

    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=3, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, block_size=8, spec_depth=2)
    step, seen = eng._fn, []

    def spy(*args):
        out = step(*args)
        seen.append((np.asarray(out[3]), np.asarray(out[4])))
        return out

    eng._fn = spy
    _packed_traffic(eng, cfg)
    news = set()
    for key_dev, vec in seen:
        res = eng._results.unpack_host(vec)
        assert res["key"].dtype == np.uint32
        assert res["key"].tobytes() == key_dev.tobytes()
        assert res["stats"] == ({}, {})          # a GPT-2 block: none
        news |= {name for name, hit in {
            "key": bool(res["key"].any()),
            "accepted drafts": bool((res["ncommit"] > 1).any()),
            "two first tokens": int((res["first_toks"] > 0).sum()) >= 2,
            "tokens": bool(res["committed"].any())}.items() if hit}
    assert news == {"key", "accepted drafts", "two first tokens", "tokens"}


def test_common_iteration_is_one_fetch(gpt, telem, monkeypatch):
    """ISSUE 38 (b): over a common iteration — slots decoding, nothing
    admitted or finished — the loop issues ONE ``np.asarray`` of a
    device array and no ``jax.device_put``, and
    ``serving_step_transfers_total`` says so: down 1, and up one per
    host array it hands the step (jit uploads each by itself: the
    packed upload is a later PR's — CHANGES.md, PR 38)."""
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    for p in _prompts(cfg, [5, 6], seed=8):
        eng.submit(p, SamplingParams(max_tokens=12))
    for _ in range(3):          # admit, prefill, turn both slots on
        eng.step()
    assert eng._active.all() and not eng._ctl_dirty
    count = telemetry.get_registry().counter(
        "serving_step_transfers_total").value
    before = {d: count(dir=d) for d in ("up", "down")}
    calls = {"put": 0, "down": 0, "host_operands": 0}
    put, asarray, step = jax.device_put, np.asarray, eng._fn

    def counting_put(x, *a, **kw):
        calls["put"] += 1
        return put(x, *a, **kw)

    def counting_asarray(x, *a, **kw):
        calls["down"] += isinstance(x, jax.Array)
        return asarray(x, *a, **kw)

    def spy(*args):
        calls["host_operands"] += sum(
            not isinstance(x, jax.Array) for x in jax.tree.leaves(args))
        return step(*args)

    eng._fn = spy
    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(np, "asarray", counting_asarray)
    eng.step()
    eng.step()
    monkeypatch.undo()
    assert calls == {"put": 0, "down": 2, "host_operands": 2 * 15}
    assert {d: count(dir=d) - before[d] for d in before} \
        == {"up": 2 * 15, "down": 2}
    eng._fn = step
    eng.run_until_drained()


def test_packed_step_is_one_trace_one_executable(gpt):
    """ISSUE 38 (d): the step with the packed result vector traces
    once (``record_trace("serving_step")``) and holds one executable
    through dirty and clean control state, empty and full packs, CoW
    and drafts."""
    cfg, model, params = gpt
    before = trace_counts().get("serving_step", 0)
    eng = ServingEngine(model, params, slots=3, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, block_size=8, spec_depth=2)
    for _ in range(2):          # the second round hits the prefix cache
        _packed_traffic(eng, cfg)
        assert trace_counts().get("serving_step", 0) - before == 1
        assert eng.step_executables() == 1


def test_sample_path_counter_follows_the_live_knobs(gpt):
    """ISSUE 31: ``serving_sample_path_total{lane, path}`` — counted on
    the host from the control vectors it uploads, with the step's own
    predicate — reads ``greedy`` on an all-greedy run, ``sort`` in the
    decode lane from the iteration a top-p request turns active until
    it finishes, and ``greedy`` again once its slot is freed, though
    the slot keeps the request's temperature. The prefill lane reads
    ``sort`` in the one iteration the top-p prompt finishes, not in the
    next, whose unused finishing rows point at that (now decoding)
    slot. Greedy -> top-p -> greedy on a running engine is one trace."""
    from hetu_tpu.serving.speculative import sample_path
    cfg, model, params = gpt
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=3, max_len=MAX_LEN,
                            prefill_chunk=CHUNK)
        traces = trace_counts().get("serving_step", 0)
        paths = telemetry.get_registry().counter(
            "serving_sample_path_total")

        def counts():
            return {(lane, path): paths.value(lane=lane, path=path)
                    for lane in ("decode", "prefill")
                    for path in ("greedy", "draw", "sort")}

        def stepped():
            """One iteration; the series it counted, one a lane."""
            before = counts()
            eng.step()
            ran = {k: v - before[k] for k, v in counts().items()
                   if v != before[k]}
            assert all(n == 1 for n in ran.values())
            assert len({lane for lane, _ in ran}) == len(ran)
            return dict(ran.keys())

        *p_first, p_samp, p_long = _prompts(cfg, [3, 2, 2, 5, 11],
                                            seed=9)
        first = [eng.submit(p, SamplingParams(max_tokens=2 + i))
                 for i, p in enumerate(p_first)]
        while not first[-1].done.is_set():
            assert set(stepped().values()) == {"greedy"}
        # slots come free in the order 0, 1, 2: the top-p request takes
        # slot 0 and finishes its prompt in the pack's first iteration;
        # the greedy request's prompt runs on into a second, whose
        # spare finishing rows name slot 0
        samp = eng.submit(p_samp, SamplingParams(
            temperature=0.8, top_p=0.9, max_tokens=4, seed=3))
        long_ = eng.submit(p_long, SamplingParams(max_tokens=12))
        assert stepped() == {"prefill": "sort"}
        assert samp.slot == 0 and eng._active[0] \
            and not eng._active[long_.slot]
        assert stepped() == {"prefill": "greedy", "decode": "sort"}
        while not samp.done.is_set():
            assert stepped() == {"decode": "sort"}
        # freed, and its knobs left standing: they no longer count
        assert not eng._active[0] and eng._temp[0] > 0 \
            and 0 < eng._topp[0] < 1
        while not long_.done.is_set():
            assert stepped() == {"decode": "greedy"}
        assert long_.tokens == _ref(model, params, p_long, 12)
        assert [r.tokens for r in first] == [
            _ref(model, params, p, 2 + i) for i, p in enumerate(p_first)]
        assert len(samp.tokens) == 4
        assert paths.value(lane="decode", path="draw") == 0
        # temperature alone: draws, and sorts nothing
        eng.generate_many([p_samp], SamplingParams(
            temperature=0.8, max_tokens=3, seed=3))
        assert paths.value(lane="decode", path="draw") == 2
        assert paths.value(lane="prefill", path="draw") == 1
        assert sample_path(True, False) == "draw"
        assert trace_counts().get("serving_step", 0) - traces == 1
        assert eng.step_executables() == 1
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_serving_under_tp2_mesh_matches_single_device(gpt):
    """ACCEPTANCE (degree-2 mesh): TP-sharded serving via the existing
    Strategy/make_plan path produces the single-device tokens."""
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan
    from hetu_tpu.parallel.sharding import shard_params
    from hetu_tpu.parallel.strategy import Strategy

    cfg, model, params = gpt
    prompts = _prompts(cfg, [5, 11, 3, 8], seed=8)
    sp = SamplingParams(max_tokens=6)
    ref_eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK)
    want = ref_eng.generate_many(prompts, sp)

    plan = make_plan(model, optim.adamw(1e-3), Strategy(tp=2))
    sp_params = shard_params(params, plan.mesh, plan.param_specs)
    eng = ServingEngine(model, sp_params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, plan=plan)
    assert eng.generate_many(prompts, sp) == want
    # and every request still matches its one-shot generate
    assert want == [_ref(model, params, p, 6) for p in prompts]


# -- the loop's account of its own iteration (ISSUE 35) -----------------------
@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable(True)
    yield telemetry
    telemetry.enable(False)
    telemetry.reset()


def _events(name):
    return [e for e in telemetry.get_tracer().events() if e.name == name]


def _host(step):
    """(host wall, host CPU) of one ``serve/step`` event: the step less
    its child ``serve/device_wait``, on both clocks."""
    wait = sum(e.dur_s for e in _events("serve/device_wait")
               if e.attrs["iter"] == step.attrs["iter"])
    return (step.dur_s - wait,
            step.attrs["cpu_s"] - step.attrs["wait_cpu_s"])


def test_step_span_carries_the_loops_account(gpt, telem):
    """Every ``serve/step`` event has the loop thread's CPU clock beside
    its wall clock, what acquiring the engine's lock cost, what it
    admitted and pushed, and the loop's turn since the step before; the
    two spans around the device carry the iteration they belong to."""
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    prompts = _prompts(cfg, [5, 11, 3, 8], seed=5)
    eng.generate_many(prompts, SamplingParams(max_tokens=4))
    steps = _events("serve/step")
    assert len(steps) >= 4
    slack = 1e-4                   # two clocks, read microseconds apart
    for e in steps:
        a = e.attrs
        assert {"cpu_s", "wait_cpu_s", "lock_wait_s", "admitted",
                "frames", "since_prev_s", "active",
                "prefill_tokens"} <= set(a)
        assert 0 <= a["wait_cpu_s"] <= a["cpu_s"] <= e.dur_s + slack
        assert 0 <= a["lock_wait_s"] <= e.dur_s
        assert a["since_prev_s"] >= 0 and a["frames"] == 0
        wall, cpu = _host(e)
        assert -slack <= cpu <= wall + slack
    assert steps[0].attrs["since_prev_s"] == 0.0     # nothing before it
    assert all(e.attrs["since_prev_s"] > 0 for e in steps[1:])
    assert sum(e.attrs["admitted"] for e in steps) == len(prompts)
    iters = [e.attrs["iter"] for e in steps]
    for name in ("serve/dispatch", "serve/device_wait"):
        assert [e.attrs["iter"] for e in _events(name)] == iters


def test_a_held_lock_shows_as_lock_wait_and_off_cpu(gpt, telem):
    """A thread that holds the engine's lock for 50 ms while a step
    wants it: the step's ``lock_wait_s`` has it, and it is off-CPU host
    time, not CPU time."""
    import threading
    import time

    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    eng.submit(_prompts(cfg, [6], seed=2)[0], SamplingParams(max_tokens=6))
    eng.step()                                  # compiles
    eng.step()
    calm = _events("serve/step")[-1]
    assert calm.attrs["lock_wait_s"] < 0.005
    holding = threading.Event()

    def hold():
        with eng._lock:
            holding.set()
            time.sleep(0.05)

    holder = threading.Thread(target=hold)
    fn = eng._fn

    def step_then_hold(*args):
        # after the dispatch (made outside the lock) and before the
        # commit (which needs it)
        out = fn(*args)
        holder.start()
        holding.wait()
        return out

    eng._fn = step_then_hold
    eng.step()
    holder.join()
    eng._fn = fn
    held = _events("serve/step")[-1]
    assert held.attrs["lock_wait_s"] >= 0.04
    wall, cpu = _host(held)
    assert wall - cpu >= 0.04                   # off the CPU ...
    assert cpu <= wall - 0.04                   # ... and not on it
    assert held.dur_s >= 0.04 > calm.dur_s
    eng.run_until_drained()


def test_children_cover_the_step(gpt, telem):
    """The span tree is closed: the children of ``serve/step`` cover at
    least 95 % of it (16 slots and a pack of 64 make an iteration of a
    few ms here, against ~10 us of span bookkeeping between two
    children), and ``serve/account`` is the last of them."""
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=16, max_len=128,
                        prefill_chunk=64)
    eng.generate_many(_prompts(cfg, [90] * 24, seed=9),
                      SamplingParams(max_tokens=12))
    evs = [e for e in telemetry.get_tracer().events()
           if e.name.startswith("serve/")]
    steps = [e for e in evs if e.name == "serve/step"][1:]  # compiled
    cover = []
    for st in steps:
        kids = sorted((e for e in evs if e.depth == st.depth + 1
                       and st.ts_s <= e.ts_s
                       and e.ts_s + e.dur_s <= st.ts_s + st.dur_s + 1e-9),
                      key=lambda e: e.ts_s)
        assert kids[0].name == "serve/admit"
        assert kids[-1].name == "serve/account"
        cover.append(sum(e.dur_s for e in kids) / st.dur_s)
    assert len(cover) >= 20
    assert float(np.median(cover)) >= 0.95, sorted(cover)[:5]


def test_wire_threads_say_what_they_took(gpt, telem):
    """One ``stream/drain`` event per finished subscription (its
    lifetime, its thread's CPU time, the frames it wrote) and one
    ``server/submit`` per submit served — none per frame; the frames
    counter's handle is bound once and survives a registry reset."""
    import socket
    import threading

    from hetu_tpu.rpc import stream
    from hetu_tpu.rpc.stream import StreamChannel
    from hetu_tpu.serving.server import ServingServer, encode_payload

    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = ServingServer(eng, port)
    srv.start()
    srv.wait_ready()
    n, got, done = 5, [], threading.Semaphore(0)

    def sink(fr):
        got.append(fr)
        if fr.get("done"):
            done.release()

    try:
        ch = StreamChannel(port)
        for i, p in enumerate(_prompts(cfg, [6] * n, seed=4)):
            ch.stream_submit(encode_payload(
                {"prompt": p, "max_tokens": 5, "temperature": 0.0,
                 "idem": f"acct{i}"}), sink=sink)
        for _ in range(n):
            assert done.acquire(timeout=60)
        for _ in range(200):            # the drainers record as they exit
            if len(_events("stream/drain")) == n:
                break
            threading.Event().wait(0.01)
        ch.close()
    finally:
        srv.stop()
    drains, submits = _events("stream/drain"), _events("server/submit")
    assert len(drains) == n and len(submits) == n
    pushed = sum(e.attrs["frames"] for e in _events("serve/step"))
    frames = sum(e.attrs["frames"] for e in drains)
    assert frames == pushed == len([f for f in got if f["k"] == "ev"])
    for e in drains + submits:
        assert e.cat == "wire" and 0 <= e.attrs["cpu_s"] <= e.dur_s + 1e-4
    assert sorted(e.attrs["req"] for e in drains) == list(range(n))
    assert all(e.attrs["lock_wait_s"] >= 0 for e in submits)
    # the per-frame counter: the server's and the client's ends share
    # this process's registry
    count = telemetry.get_registry().counter("rpc_stream_frames_total")
    assert count.value(kind="ev", dir="out") == frames
    assert count is stream._frames_counter()
    telemetry.reset()
    stream._count_frame("ev", "out")
    assert count.value(kind="ev", dir="out") == 1


def test_tier_gauges_move_with_their_events_not_every_iteration(gpt, telem):
    """The spill tiers, the replica store and the adapter pages are set
    on a submit and every ``_TIER_GAUGES_EVERY`` iterations (sooner
    after an iteration that ran a spill, resume or CP job); the gauges
    every admission and finish move are set in every iteration."""
    from hetu_tpu.serving import engine as engine_mod

    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=1, max_len=MAX_LEN,
                        prefill_chunk=CHUNK)
    walks = []
    counts = eng.spill_arena.tier_counts
    eng.spill_arena.tier_counts = lambda: walks.append(eng._iter) \
        or counts()
    reg = telemetry.get_registry()
    every = engine_mod._TIER_GAUGES_EVERY
    for p in _prompts(cfg, [4, 4], seed=6):        # one after the other
        eng.submit(p, SamplingParams(max_tokens=26))
    assert walks == [0, 0]                      # one walk a submit
    assert reg.gauge("spill_tier_blocks").value(tier="host") == 0
    assert reg.gauge("serving_queue_depth").value() == 2
    in_use = []
    while eng.has_work():
        eng.step()
        in_use.append(reg.gauge("serving_kv_blocks_in_use").value())
    assert eng._iter > every
    assert walks[2:] == list(range(every, eng._iter + 1, every))
    assert in_use[0] > 0 and in_use[-1] == 0    # set in every iteration
    assert reg.gauge("serving_queue_depth").value() == 0
