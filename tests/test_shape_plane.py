"""Shape-plane tests (ISSUE 10): seq-len-bucketed zero-recompile steps,
packing-aware training parity, CP-sharded long-prompt serving prefill.

Quick tier: host-side ladder/bucketer/dispatcher logic, the structured
too-long errors, the precompile key-enumeration lint, the packed-vs-
padded parity (tiny model), and the ragged-epoch re-trace audit (tiny
model, 3 buckets = 3 compiles). Compile-heavy serving parity matrices
are slow-tier.
"""

import inspect
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import optim
from hetu_tpu.data.bucket import (
    PAD_SEGMENT, SeqLenBuckets, ShapeBucketer,
)
from hetu_tpu.data.hydraulis import BucketPlan, DynamicDispatcher
from hetu_tpu.data.packing import pack_sequences, pad_batch
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.models.generation import PromptTooLongError, generate
from hetu_tpu.parallel.strategy import Strategy


@pytest.fixture(scope="module")
def gpt():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, model, params


# ---------------------------------------------------------------------------
# bucket ladder + ShapeBucketer (host-side)
# ---------------------------------------------------------------------------

def test_bucket_ladder_determinism():
    """Same inputs -> same ladder -> same bucket assignment, every
    time; the ladder is sorted, deduped, and alignment-validated."""
    a = SeqLenBuckets(sizes=(64, 16, 32, 32))
    b = SeqLenBuckets(sizes=[32, 64, 16])
    assert a.sizes == b.sizes == [16, 32, 64]
    lens = [1, 15, 16, 17, 40, 64, 200]
    assert [a.bucket_for(L) for L in lens] \
        == [b.bucket_for(L) for L in lens] \
        == [16, 16, 16, 32, 64, 64, 64]
    # grouping is index-stable
    assert a.group(lens) == b.group(lens)
    with pytest.raises(ValueError):
        SeqLenBuckets(sizes=(10,), multiple_of=4)


def test_shape_bucketer_fit_and_stats():
    bk = ShapeBucketer(SeqLenBuckets(sizes=(16, 32, 64)))
    # slice down: raw width 50, max real length 20 -> bucket 32
    batch = {"input_ids": np.ones((2, 50), np.int32),
             "labels": np.full((2, 50), -100, np.int32),
             "positions": np.tile(np.arange(50, dtype=np.int32), (2, 1)),
             "segment_ids": np.zeros((2, 50), np.int32)}
    batch["labels"][0, :20] = 1
    batch["labels"][1, :9] = 1
    out = bk.fit(batch)
    for k in ("input_ids", "labels", "positions", "segment_ids"):
        assert out[k].shape == (2, 32), k
    # pad up: raw width 10, all real -> bucket 16, pad values per key
    batch2 = {"input_ids": np.full((1, 10), 7, np.int32),
              "labels": np.full((1, 10), 7, np.int32),
              "positions": np.arange(10, dtype=np.int32)[None],
              "segment_ids": np.zeros((1, 10), np.int32)}
    out2 = bk.fit(batch2)
    assert out2["input_ids"].shape == (1, 16)
    assert (out2["labels"][0, 10:] == -100).all()
    assert (out2["input_ids"][0, 10:] == 0).all()
    assert (out2["segment_ids"][0, 10:] == PAD_SEGMENT).all()
    st = bk.stats
    assert st.batches == 2
    assert st.real_tokens == 20 + 9 + 10
    assert st.raw_tokens == 2 * 50 + 10
    assert st.bucket_tokens == 2 * 32 + 16
    assert st.pad_fraction_after < st.pad_fraction_before
    rec = st.to_record()
    assert rec["kind"] == "shape_plane"
    # labels-free batches fall back to input_ids != pad_id
    bk2 = ShapeBucketer(SeqLenBuckets(sizes=(8, 16)))
    ids = np.zeros((1, 16), np.int32)
    ids[0, :5] = 3
    assert bk2.fit({"input_ids": ids})["input_ids"].shape == (1, 8)
    # rows beyond the largest bucket truncate LOUDLY: one warning, and
    # every cut token counted (never a silent data loss)
    over = {"input_ids": np.full((1, 24), 3, np.int32),
            "labels": np.full((1, 24), 3, np.int32)}
    with pytest.warns(UserWarning, match="largest seq bucket is 16"):
        out3 = bk2.fit(over)
    assert out3["input_ids"].shape == (1, 16)
    assert bk2.stats.truncated_tokens == 8
    bk2.fit(dict(over))          # second over-long batch: no new warn
    assert bk2.stats.truncated_tokens == 16


def test_bucketer_loss_invariance(gpt):
    """Snapping a batch to its bucket must not change the loss: pad
    labels are ignored and pad KV sits after every real token (causal),
    so mean-over-valid is identical at raw width and bucket width."""
    cfg, model, params = gpt
    rng = np.random.default_rng(0)
    ids = rng.integers(1, cfg.vocab_size, (2, 50)).astype(np.int32)
    labels = np.full((2, 50), -100, np.int32)
    labels[0, :20] = ids[0, 1:21]
    labels[1, :13] = ids[1, 1:14]
    bk = ShapeBucketer(SeqLenBuckets(sizes=(16, 32, 64)))
    fitted = bk.fit({"input_ids": ids, "labels": labels})
    assert fitted["input_ids"].shape == (2, 32)
    loss_raw = model.loss(params, jnp.asarray(ids), jnp.asarray(labels))
    loss_fit = model.loss(params, jnp.asarray(fitted["input_ids"]),
                          jnp.asarray(fitted["labels"]))
    np.testing.assert_allclose(np.asarray(loss_raw),
                               np.asarray(loss_fit), rtol=1e-6)


# ---------------------------------------------------------------------------
# packed-vs-unpacked training parity
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_packed_vs_padded_parity_loss_and_grads(gpt):
    """A multi-doc packed batch trains identically to the same docs
    padded one-per-row: segment masks block cross-doc attention,
    positions reset per doc, boundary labels are ignored — so loss AND
    grads agree (the packing-aware loss-mask acceptance check; slow
    tier per the ISSUE — the quick tier is ~95% of its 870s budget)."""
    cfg, model, params = gpt
    rng = np.random.default_rng(1)
    docs = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (12, 7, 5)]
    packed = pack_sequences(docs, 24)
    padded = pad_batch(docs, 24)
    assert packed.input_ids.shape[0] == 1      # all three fit one row
    lp, gp = jax.value_and_grad(
        lambda p: model.loss(p, jnp.asarray(packed.input_ids),
                             jnp.asarray(packed.labels),
                             positions=jnp.asarray(packed.positions),
                             segment_ids=jnp.asarray(packed.segment_ids))
    )(params)
    lu, gu = jax.value_and_grad(
        lambda p: model.loss(p, jnp.asarray(padded.input_ids),
                             jnp.asarray(padded.labels),
                             positions=jnp.asarray(padded.positions),
                             segment_ids=jnp.asarray(padded.segment_ids))
    )(params)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lu),
                               rtol=2e-5)
    flat_p = jax.tree.leaves(gp)
    flat_u = jax.tree.leaves(gu)
    for a, b in zip(flat_p, flat_u):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-6)
    assert float(lp) > 0


def test_dispatcher_packed_cuts_pad_and_keeps_shapes():
    """pack=True packs short docs into full pack_len rows: pad fraction
    drops below the per-doc bucketed dispatch, emitted shapes stay
    fixed per bucket, and docs longer than pack_len still dispatch
    through their own unpacked buckets."""
    rng = np.random.default_rng(2)
    lens = list(rng.integers(4, 30, 60)) + [100, 90]   # short + long tail
    seqs = [np.arange(L + 1, dtype=np.int32) % 250 for L in lens]
    plans = {L: BucketPlan(L, max(1, 128 // L), Strategy(), 0.0)
             for L in (16, 32, 64, 128)}
    unpacked = DynamicDispatcher(plans)
    for batch, plan in unpacked.batches(seqs):
        assert batch["input_ids"].shape == (plan.batch_rows,
                                            plan.bucket_len)
    packed = DynamicDispatcher(plans, pack=True, pack_len=64)
    seen_long = 0
    for batch, plan in packed.batches(seqs):
        assert batch["input_ids"].shape == (plan.batch_rows,
                                            plan.bucket_len)
        if plan.bucket_len == 128:
            seen_long += 1
            assert "positions" not in batch        # unpacked emission
        elif plan.bucket_len == 64:
            # packed rows carry the packing layout
            assert "positions" in batch and "segment_ids" in batch
    assert seen_long >= 1                          # long docs unpacked
    assert packed.stats.pad_fraction < unpacked.stats.pad_fraction
    assert packed.stats.real_tokens > 0
    with pytest.raises(ValueError):
        DynamicDispatcher(plans, pack=True, pack_len=48)  # no such plan


# ---------------------------------------------------------------------------
# structured too-long errors
# ---------------------------------------------------------------------------

def test_generate_too_long_structured_error(gpt):
    cfg, model, params = gpt
    ids = jnp.zeros((1, 100), jnp.int32)
    with pytest.raises(PromptTooLongError, match="max_positions"):
        generate(model, params, ids, max_new_tokens=50)   # 150 > 128
    with pytest.raises(PromptTooLongError, match="max_len"):
        generate(model, params, ids, max_new_tokens=20, max_len=60)
    try:
        generate(model, params, ids, max_new_tokens=50)
    except PromptTooLongError as e:      # structured fields, not prose
        assert e.prompt_len == 100 and e.max_tokens == 50
        assert e.limit == cfg.max_positions


def test_scheduler_long_lane_admission_and_errors():
    from hetu_tpu.serving.scheduler import (
        Request, SamplingParams, Scheduler,
    )

    def mk(i, plen, max_tokens=4):
        return Request(id=i,
                       prompt=np.arange(1, plen + 1, dtype=np.int32),
                       sampling=SamplingParams(max_tokens=max_tokens),
                       submit_s=0.0)

    # lane off: rejection names the slot budget AND the knob
    sched = Scheduler(slots=2, max_len=16)
    r = mk(0, 20)
    assert not sched.submit(r)
    assert "16-token serving slot budget" in r.error
    assert "long_max_len" in r.error
    # lane on: beyond-slot-but-inside-lane admits with cp_lane=True
    sched = Scheduler(slots=2, max_len=16, long_max_len=48)
    ok = mk(1, 20)
    assert sched.submit(ok) and ok.cp_lane
    short = mk(2, 5)
    assert sched.submit(short) and not short.cp_lane
    # beyond even the lane: rejection names BOTH limits
    far = mk(3, 60)
    assert not sched.submit(far)
    assert "16-token serving slot budget" in far.error
    assert "48-token CP-prefill lane" in far.error
    with pytest.raises(ValueError):
        Scheduler(slots=2, max_len=16, long_max_len=16)  # must exceed


# ---------------------------------------------------------------------------
# precompile enumeration lint + bucketed candidates
# ---------------------------------------------------------------------------

def test_precompile_enumerates_every_step_cache_key_field():
    """Lint: every keyword field of StepCache.key_for (the cache-key
    contract, now incl. ``bucket``) must be accepted AND forwarded by
    engine.precompile._precompile_one — a field the AOT enumeration
    drops would compile into the wrong entry and the first step at that
    variant would re-trace on the critical path."""
    from hetu_tpu.engine import precompile
    from hetu_tpu.engine.train_step import StepCache

    key_fields = [p for p in inspect.signature(
        StepCache.key_for).parameters if p not in
        ("model", "opt", "strategy")]
    assert "bucket" in key_fields      # the shape-plane field exists
    one_params = set(inspect.signature(
        precompile._precompile_one).parameters)
    src = inspect.getsource(precompile._precompile_one)
    for field in key_fields:
        assert field in one_params, (
            f"_precompile_one does not accept key field {field!r}")
        assert re.search(rf"\b{field}\s*=\s*{field}\b", src), (
            f"_precompile_one does not forward {field!r} to key_for")


def test_precompile_bucketed_candidates(gpt):
    """buckets= expands the candidate set to (strategy x bucket), each
    landing under its own bucketed StepCache key (plan-only build:
    nothing traces, so this is quick-tier cheap)."""
    from hetu_tpu.engine.precompile import precompile_strategies
    from hetu_tpu.engine.train_step import StepCache

    cfg, model, _ = gpt
    opt = optim.adamw(1e-3)
    cache = StepCache()
    h = precompile_strategies(model, opt, [Strategy()],
                              buckets=(16, 32), cache=cache,
                              background=False)
    res = h.wait()
    assert sorted(r.bucket for r in res) == [16, 32]
    assert all(r.ok for r in res)
    for b in (16, 32):
        key = cache.key_for(model, opt, Strategy(), bucket=b)
        assert cache.lookup(key) is not None
    # the unbucketed key is a DIFFERENT entry
    assert cache.lookup(cache.key_for(model, opt, Strategy())) is None


# ---------------------------------------------------------------------------
# ragged-epoch re-trace audit (acceptance: compiles <= n_buckets)
# ---------------------------------------------------------------------------

def test_ragged_epoch_retrace_audit():
    """An epoch of ragged widths through a seq_buckets Trainer compiles
    at most n_buckets train-step programs (trace_counts), every batch
    lands on the ladder, and the pad accounting prices the win."""
    from hetu_tpu.engine.train_step import trace_counts
    from hetu_tpu.engine.trainer import Trainer, TrainerConfig

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    tr = Trainer(model, opt, Strategy(),
                 TrainerConfig(total_steps=10, log_every=0, prefetch=0,
                               precision="fp32",
                               seq_buckets=(16, 32, 64)))
    rng = np.random.default_rng(0)

    def mk(width, real):
        ids = rng.integers(1, cfg.vocab_size, (2, width)).astype(np.int32)
        labels = np.full((2, width), -100, np.int32)
        for r, t in enumerate(real):
            labels[r, :t] = ids[r, :t]
        return {"input_ids": ids, "labels": labels}

    batches = [mk(13, (13, 5)), mk(30, (30, 22)), mk(64, (60, 10)),
               mk(20, (20, 11)), mk(7, (7, 3)), mk(55, (55, 54))]
    before = trace_counts().get("train_step", 0)
    tr.initialize()
    hist = tr.train(iter(batches), steps=len(batches))
    compiles = trace_counts().get("train_step", 0) - before
    assert compiles <= 3, compiles          # <= n_buckets, the audit
    # widths {13,7}->16, {30,20}->32, {64,55}->64: all three buckets hit
    assert compiles == 3
    st = tr.bucketer.stats
    assert st.batches == len(batches)
    # the raw batches here are exact-width (loader already trimmed), so
    # bucketing trades a little pad for the bounded compile count; the
    # win to assert is vs PAD-TO-MAX, which those 3 compiles replace
    assert st.bucket_tokens < len(batches) * 2 * 64
    assert st.real_tokens == 290
    # a second epoch through the same ladder stays compile-free
    tr.train(iter([mk(14, (14, 2)), mk(61, (61, 61))]), steps=2)
    assert trace_counts().get("train_step", 0) - before == 3
    tr.close()


# ---------------------------------------------------------------------------
# trace_summary shape-plane section
# ---------------------------------------------------------------------------

def test_trace_summary_shape_plane_section(tmp_path, capsys):
    from hetu_tpu.tools.trace_summary import main

    path = str(tmp_path / "t.jsonl")
    recs = [
        {"kind": "span", "name": "step", "ts_s": 0.0, "dur_s": 1.0,
         "tid": 1, "depth": 0, "attrs": {}},
        {"kind": "metrics_snapshot", "metrics": {
            "data_real_tokens_total": 9000.0,
            "data_padding_tokens_total": 1000.0,
            "data_raw_tokens_total": 40000.0,
            'data_bucket_hits_total{bucket="32"}': 12.0,
            'data_bucket_hits_total{bucket="64"}': 3.0,
            'data_bucket_compiles_total{bucket="32"}': 1.0,
            'step_traces_total{what="train_step"}': 2.0,
            "serving_cp_prefill_requests_total": 2.0,
            "serving_cp_prefill_tokens_total": 180.0,
            'serving_requests_total{outcome="completed"}': 10.0}},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "== shape plane ==" in out
    assert "pad fraction" in out and "10.0% after bucketing" in out
    assert "bucket 32" in out and "80%" in out
    assert "cp-prefill lane" in out and "180" in out
    assert "n_buckets audit" in out


# ---------------------------------------------------------------------------
# CP-prefill serving lane (compile-heavy: slow tier)
# ---------------------------------------------------------------------------

def _greedy_ref(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.mark.slow
def test_cp_lane_serves_long_prompt_greedy_parity(gpt):
    """Acceptance: a prompt with P + max_tokens beyond one slot's
    max_len is SERVED through the CP lane with greedy tokens identical
    to one-shot generate; serving_step stays at 1 compile across the
    mixed long/short churn and the lane stays within its bucket
    ladder's executable budget."""
    from hetu_tpu.engine.train_step import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    tc0 = trace_counts()      # process-global: count from here
    eng = ServingEngine(model, params, slots=2, max_len=32,
                        prefill_chunk=16, long_max_len=96)
    rng = np.random.default_rng(0)
    sp = SamplingParams(max_tokens=8)
    long1 = rng.integers(1, cfg.vocab_size, (40,)).tolist()
    long2 = rng.integers(1, cfg.vocab_size, (70,)).tolist()
    short = rng.integers(1, cfg.vocab_size, (10,)).tolist()
    outs = eng.generate_many([long1, short, long2], sp)
    assert outs[0] == _greedy_ref(model, params, long1, 8)
    assert outs[1] == _greedy_ref(model, params, short, 8)
    assert outs[2] == _greedy_ref(model, params, long2, 8)
    tc = trace_counts()
    assert tc["serving_step"] - tc0.get("serving_step", 0) == 1, tc
    assert tc["serving_cp_prefill"] - tc0.get("serving_cp_prefill", 0) \
        <= len(eng._cp_buckets.sizes)
    # more churn: same buckets, zero new compiles anywhere
    before = dict(tc)
    outs2 = eng.generate_many([long2, long1], sp)
    assert outs2[0] == _greedy_ref(model, params, long2, 8)
    assert trace_counts() == before
    # KV placement is exact, not just argmax-identical: the arena rows
    # the lane scattered equal the dense prefill's cache rows
    from hetu_tpu.models import generation as g
    req = eng.submit(long1, SamplingParams(max_tokens=30))
    eng.step()
    slot, blk = req.slot, eng.pool.block_size
    bt = eng._bt[slot].copy()
    caches = g.init_kv_caches(model, 1, 96, jnp.float32)
    _, caches = g.decode(model, params, jnp.asarray([long1], jnp.int32),
                         jnp.arange(len(long1))[None, :], caches)
    k_ref = np.asarray(caches[0])[:, 0, :len(long1)]
    k_ref = k_ref.reshape(k_ref.shape[:2] + (-1,))   # arena merges (hkv, d)
    k_arena = np.asarray(eng.pool.caches[0])
    idx = np.arange(len(long1))
    np.testing.assert_allclose(
        k_arena[:, bt[idx // blk], idx % blk], k_ref, atol=2e-5)
    while eng.has_work():
        eng.step()


@pytest.mark.slow
def test_cp_lane_under_cp2_mesh_matches_single_device(gpt):
    """The lane's prefill really runs the cp-sharded ring: under a
    Strategy(cp=2) plan (zigzag layout, host permute) the served greedy
    tokens still match single-device one-shot generate."""
    from hetu_tpu.engine import make_plan
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    plan = make_plan(model, optim.adamw(1e-3), Strategy(cp=2))
    assert plan.strategy.effective_cp_layout == "zigzag"
    eng = ServingEngine(model, params, slots=2, max_len=32,
                        prefill_chunk=16, long_max_len=96, plan=plan)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, (50,)).tolist()
    out = eng.generate_many([prompt], SamplingParams(max_tokens=6))
    assert out[0] == _greedy_ref(model, params, prompt, 6)


@pytest.mark.slow
def test_cp_lane_int8_pool(gpt):
    """The lane's KV scatter quantizes into the int8 paged layout:
    serving a long prompt from the quantized lane matches one-shot
    int8-cache generation (the same bar as the existing int8 pool
    acceptance test)."""
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, (40,)).tolist()
    sp = SamplingParams(max_tokens=6)
    q = ServingEngine(model, params, slots=2, max_len=32,
                      long_max_len=96, cache_dtype=jnp.int8)
    assert q.pool.quantized
    ref = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=6, cache_dtype=jnp.int8)
    want = np.asarray(ref)[0, len(prompt):].tolist()
    assert q.generate_many([prompt], sp) == [want]
