"""Control-plane latency tests: StepCache compile-count regressions, AOT
pre-compilation, prefetch overlap, the cross-topology device_put fast
path, and grad-accumulator buffer reuse (ISSUE 2).

The compile-count tests assert on ``engine.train_step.trace_counts()`` —
a counter bumped INSIDE the jitted step body, so it increments exactly
when jax re-traces (and therefore recompiles); warm executables never
re-enter the Python body.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import optim, telemetry
from hetu_tpu.engine import (
    StepCache, build_grad_accum_steps, init_state, make_plan,
    trace_counts,
)
from hetu_tpu.engine.trainer import Trainer, TrainerConfig
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy

CFG = GPTConfig.tiny()


def _batches(n, seed=0, b=4, s=16):
    for i in range(n):
        ids = jax.random.randint(jax.random.key(seed + i), (b, s + 1), 0,
                                 CFG.vocab_size)
        yield {"input_ids": np.asarray(ids[:, :-1]),
               "labels": np.asarray(ids[:, 1:])}


def _cfg(**kw):
    return TrainerConfig(log_every=0, precision="fp32", **kw)


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable(True)
    yield telemetry
    telemetry.enable(False)
    telemetry.reset()


# -- compile-count regression (acceptance criterion) ------------------------
def test_switch_back_zero_recompiles():
    """A→B→A on a 2-device CPU mesh: the return switch performs ZERO
    re-traces/recompiles (StepCache hit + the entry's live jit
    executable) — asserted via both the cache counters and the in-body
    trace counter."""
    cache = StepCache()
    t = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3), Strategy(dp=2),
                _cfg(total_steps=1), step_cache=cache)
    t.train(_batches(1))
    t.set_strategy(Strategy(tp=2))                     # B: compiles
    t.train(_batches(1, seed=1))
    traces_before = trace_counts().get("train_step", 0)
    misses_before = cache.misses
    t.set_strategy(Strategy(dp=2))                     # return leg
    assert cache.misses == misses_before               # pure cache hit
    assert cache.hits >= 1
    t.train(_batches(1, seed=2))                       # warm executable
    assert trace_counts().get("train_step", 0) == traces_before
    assert len(cache) == 2


def test_step_cache_disabled_rebuilds(telem):
    """config.step_cache=False is the A/B baseline: every set_strategy
    rebuilds, so the return leg gets a NEW entry (and the compile ledger
    a third slice)."""
    t = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3), Strategy(dp=2),
                _cfg(total_steps=1, step_cache=False),
                step_cache=StepCache())
    entry_a = t._step_fn
    t.train(_batches(1))
    t.set_strategy(Strategy(tp=2))
    t.set_strategy(Strategy(dp=2))
    assert t._step_fn is not entry_a                   # rebuilt
    assert len(t.cache) == 0                           # never populated
    # every switch landed in the cumulative compile counter
    assert telem.get_registry().counter(
        "compile_seconds_total").value() > 0


def test_plan_pool_identity_and_eval_preserved():
    """The cached entry carries plan AND eval_fn; switching back restores
    the identical objects (ExecGraphPlan-pool semantics via StepCache)."""
    t = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3), Strategy(dp=2),
                _cfg(total_steps=1), step_cache=StepCache())
    plan_a, step_a, eval_a = t.plan, t._step_fn, t._eval_fn
    assert eval_a is not None
    t.set_strategy(Strategy(tp=2))
    assert t.plan is not plan_a
    t.set_strategy(Strategy(dp=2))
    assert t.plan is plan_a and t._step_fn is step_a \
        and t._eval_fn is eval_a


# -- AOT pre-compilation ----------------------------------------------------
def test_precompile_aot_switch_is_trace_free():
    """Background AOT (engine.precompile): after precompiling strategy B
    for the run's batch shape, set_strategy(B) plus the first step add
    ZERO foreground traces — the switch dispatches the ahead-of-time
    executable."""
    cache = StepCache()
    t = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3), Strategy(dp=2),
                _cfg(total_steps=1), step_cache=cache)
    t.train(_batches(1))
    handle = t.precompile([Strategy(dp=4)], batch_shape=(4, 16),
                          block=True)
    res = handle.results
    assert len(res) == 1 and res[0].ok and res[0].aot, res
    traces = dict(trace_counts())
    t.set_strategy(Strategy(dp=4))
    m = t.train_step(next(_batches(1, seed=3)))
    assert np.isfinite(float(jax.device_get(m["loss"])))
    assert dict(trace_counts()) == traces    # no foreground re-trace
    assert cache.hits >= 1                   # switch found the warm entry


def test_precompile_handles_bad_candidate():
    """One infeasible candidate must not abort the rest of the queue."""
    from hetu_tpu.engine import precompile_strategies
    model = GPTLMHeadModel(CFG)
    opt = optim.adamw(1e-3)
    cache = StepCache()
    handle = precompile_strategies(
        model, opt,
        [Strategy(dp=16),                  # 16 devices, mesh has 8
         Strategy(dp=2)],
        cache=cache, background=False)
    res = handle.results
    assert [r.ok for r in res] == [False, True]
    assert res[0].error
    assert len(cache) == 1


def test_persistent_cache_wiring(tmp_path, monkeypatch):
    """enable_persistent_compilation_cache: with JAX_COMPILATION_CACHE_DIR
    set nothing is set in code; without it the cache goes to the given
    dir, by default the one fixed directory inside the checkout."""
    import os
    from hetu_tpu.engine import enable_persistent_compilation_cache
    from hetu_tpu.engine.precompile import COMPILE_CACHE_DIR
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "env"))
        assert enable_persistent_compilation_cache(str(tmp_path / "xc")) \
            == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == old
        assert not os.path.exists(tmp_path / "xc")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_persistent_compilation_cache(str(tmp_path / "xc"))
        assert path == str(tmp_path / "xc")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.isdir(path)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# -- prefetch overlap -------------------------------------------------------
def test_prefetch_batches_arrive_preplaced():
    """While the consumer is busy (step N), the producer stages batch
    N+1 on device: the next fetch finds it ready (no stall) and already
    carrying the plan's sharding."""
    import time
    from hetu_tpu.data.prefetch import DevicePrefetcher
    plan = make_plan(GPTLMHeadModel(CFG), optim.adamw(1e-3),
                     Strategy(dp=2))
    pf = DevicePrefetcher(_batches(4), plan.shard_batch, buffer_size=2)
    with pf:
        first = next(pf)               # may block: pipeline still filling
        time.sleep(0.5)                # "step N computes" — producer runs
        second = next(pf)
        stats = pf.stats()
        assert stats["ready_hits"] >= 1, stats
        for b in (first, second):
            ids = b["input_ids"]
            assert isinstance(ids, jax.Array)
            assert ids.sharding.spec == plan.strategy.data_spec(2)
            # committed to the mesh, not a single-device default
            assert len(ids.sharding.device_set) == 2


def test_prefetch_set_place_restages_staged_batches():
    """Hot switch mid-stream: set_place() re-points placement; batches
    staged under the OLD plan are re-placed from their host form on
    fetch — correct sharding, nothing dropped."""
    import time
    from hetu_tpu.data.prefetch import DevicePrefetcher
    model, opt = GPTLMHeadModel(CFG), optim.adamw(1e-3)
    plan_a = make_plan(model, opt, Strategy(dp=2))
    plan_b = make_plan(model, opt, Strategy(dp=4))
    src = list(_batches(4, b=8))
    pf = DevicePrefetcher(iter(src), plan_a.shard_batch, buffer_size=2)
    with pf:
        _ = next(pf)
        time.sleep(0.5)                      # let the queue fill under A
        pf.set_place(plan_b.shard_batch)     # the Trainer's hot switch
        got = [next(pf) for _ in range(3)]
        assert pf.stats()["restaged"] >= 1
        for b in got:
            assert b["input_ids"].sharding.spec == \
                plan_b.strategy.data_spec(2)
        # nothing dropped and order preserved
        for b, s in zip(got, src[1:]):
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(b["input_ids"])),
                s["input_ids"])


def test_trainer_switch_repoints_live_prefetcher():
    """Trainer.train + mid-run set_strategy: the registered prefetcher is
    re-pointed so post-switch steps consume batches placed under the new
    plan (no stale-sharding retrace storm)."""
    t = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3), Strategy(dp=2),
                _cfg(total_steps=2, prefetch=2), step_cache=StepCache())
    t.train(_batches(2))
    assert t._live_prefetcher is None        # unregistered after train()
    t.set_strategy(Strategy(dp=4))
    t.train(_batches(2, seed=7, b=8), steps=2)
    assert int(jax.device_get(t.state.step)) == 4


# -- cross-topology fast path -----------------------------------------------
def test_cross_topology_fastpath_equivalent_shardings(telem):
    """Shrink onto a different device set with the SAME layout: every
    leaf's destination shard regions equal the source's, so the switch
    goes through jax.device_put (no numpy reassembly) — counted by the
    fast-path counter — and values survive bit-exactly."""
    from hetu_tpu.parallel.switch import switch_strategy
    model, opt = GPTLMHeadModel(CFG), optim.adamw(1e-3)
    plan_src = make_plan(model, opt, Strategy(dp=2, tp=2),
                         devices=jax.devices()[:4])
    state = init_state(model, opt, plan_src, jax.random.key(0))
    plan_dst = make_plan(model, opt, Strategy(dp=2, tp=2),
                         devices=jax.devices()[4:])
    moved = switch_strategy(state, plan_dst)
    reg = telemetry.get_registry()
    fast = reg.counter("switch_fastpath_leaves_total").value()
    slow = reg.counter("switch_reassembled_leaves_total").value()
    assert fast == len([l for l in jax.tree.leaves(state)
                        if isinstance(l, jax.Array)])
    assert slow == 0
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(moved)):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)))
    assert {d.id for d in
            jax.tree.leaves(moved)[1].sharding.device_set} <= {4, 5, 6, 7}


def test_cross_topology_mixed_fast_and_reassembled(telem):
    """tp4→tp2 across device sets: tp-sharded leaves need genuine
    re-slicing (reassembly path) while replicated leaves ride the fast
    path — and the result still matches exactly."""
    from hetu_tpu.parallel.switch import switch_strategy
    model, opt = GPTLMHeadModel(CFG), optim.adamw(1e-3)
    plan_src = make_plan(model, opt, Strategy(tp=4),
                         devices=jax.devices()[:4])
    state = init_state(model, opt, plan_src, jax.random.key(0))
    plan_dst = make_plan(model, opt, Strategy(dp=2, tp=2),
                         devices=jax.devices()[4:])
    moved = switch_strategy(state, plan_dst)
    reg = telemetry.get_registry()
    assert reg.counter("switch_fastpath_leaves_total").value() > 0
    assert reg.counter("switch_reassembled_leaves_total").value() > 0
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(moved)):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b)))


# -- grad-accumulator buffer reuse ------------------------------------------
def test_init_acc_like_recycles_buffer():
    """donate_acc=False + init_acc(like=prev): the previous accumulator
    is donated into an in-place zero-fill instead of allocating a fresh
    fp32 buffer every update — and two recycled updates match the
    default (fresh-alloc) flow exactly."""
    model, opt = GPTLMHeadModel(CFG), optim.adamw(1e-3)
    plan = make_plan(model, opt, Strategy(dp=2))
    batches = list(_batches(2))

    def run(donate_acc):
        state = init_state(model, opt, plan, jax.random.key(1),
                           dtype=jnp.float32)
        init_acc, grad_step, apply_step = build_grad_accum_steps(
            model, opt, plan, donate_acc=donate_acc)
        acc = init_acc()
        losses = []
        for upd in range(2):
            acc, loss = grad_step(state, acc, plan.shard_batch(
                batches[upd]))
            losses.append(float(loss))
            state, _ = apply_step(state, acc, 1.0)
            if upd == 0:
                prev = acc
                acc = init_acc(like=acc) if not donate_acc \
                    else init_acc()
                if not donate_acc:
                    # the recycled buffer is CONSUMED by the zero-fill
                    # (XLA:CPU ignores donation, so the jax-level delete
                    # only happens where aliasing is supported)
                    if jax.default_backend() != "cpu":
                        assert all(l.is_deleted()
                                   for l in jax.tree.leaves(prev))
                    assert all(
                        float(jnp.abs(l).max()) == 0.0
                        for l in jax.tree.leaves(acc))
        return losses, state

    losses_reuse, state_reuse = run(donate_acc=False)
    losses_fresh, state_fresh = run(donate_acc=True)
    np.testing.assert_allclose(losses_reuse, losses_fresh, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(state_reuse.params),
                    jax.tree.leaves(state_fresh.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# -- goodput A/B (acceptance criterion) -------------------------------------
def test_cached_run_reduces_compile_share():
    """Same A→B→A script, cache on vs off, judged on the RETURN leg's
    goodput ledger (the final train segment): cache-disabled re-traces
    its first step (compile share > 0, diluted goodput); cached
    dispatches the warm executable (compile share exactly 0) — exactly
    the reduction trace_summary shows as reclaimed goodput."""

    def run(step_cache_on):
        t = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3),
                    Strategy(dp=2),
                    _cfg(total_steps=1, step_cache=step_cache_on),
                    step_cache=StepCache())
        t.train(_batches(1))
        t.set_strategy(Strategy(tp=2))
        t.train(_batches(1, seed=1))
        t.set_strategy(Strategy(dp=2))     # the leg under test
        t.train(_batches(1, seed=2))
        rep = t.goodput.report()           # final segment's ledger
        return rep.components.get("compile", 0.0), rep.goodput

    off_compile, off_goodput = run(step_cache_on=False)
    on_compile, on_goodput = run(step_cache_on=True)
    assert off_compile > 0.0, "cold return leg must ledger a compile"
    assert on_compile == 0.0, "warm return leg must not compile at all"
    assert on_goodput > off_goodput
