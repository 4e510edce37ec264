"""Data-plane overlap (ISSUE 3): decomposed collective matmuls, delayed
grad sync, double-buffered pipeline comms, and the byte/sync ledger.

Parity discipline: overlap modes must be numerically TRANSPARENT. At
degree-2 meshes every reduction is a two-term sum (fp addition is
commutative, so reduction order cannot change the bits) and the ring
matmuls never split a contraction dim — losses are asserted
bitwise-identical to overlap-off there. Higher degrees re-associate
multi-term sums, so those cases assert tight allclose instead.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hetu_tpu import optim, telemetry
from hetu_tpu.engine.train_step import (
    build_grad_accum_steps, build_train_step, init_state, make_plan,
)
from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from hetu_tpu.nn.parallel import ColumnParallelLinear, RowParallelLinear
from hetu_tpu.parallel import overlap as ov
from hetu_tpu.parallel.sharding import (
    ActivationSharding, param_partition_specs, shard_params,
)
from hetu_tpu.parallel.strategy import Strategy


@pytest.fixture(autouse=True)
def _clean_ledger():
    ov.reset_comm_stats()
    yield
    ov.reset_comm_stats()


CFG = GPTConfig.tiny()
B, S = 8, 32


def _batch(key=1):
    ids = jax.random.randint(jax.random.key(key), (B, S + 1), 0,
                             CFG.vocab_size)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _train_losses(model, strategy, steps=3):
    opt = optim.adamw(1e-3)
    plan = make_plan(model, opt, strategy)
    step = build_train_step(model, opt, plan, donate=False)
    state = init_state(model, opt, plan, jax.random.key(0))
    sb = plan.shard_batch(_batch())
    out = []
    for _ in range(steps):
        state, m = step(state, sb)
        out.append(float(jax.device_get(m["loss"])))
    return out


# -- ring collective matmuls -------------------------------------------------

def _tp_ctx(strategy, **kw):
    mesh = strategy.build_mesh()
    return mesh, ActivationSharding(mesh, batch="dp", seq=None, tp="tp",
                                    **kw)


def test_ring_matmul_layer_smoke(rng):
    """Quick-tier smoke of the decomposed AG→matmul / matmul→RS pair:
    bitwise parity against the GSPMD path at tp=2 plus byte accounting.
    (The full train-step matrix is slow-tier.)"""
    st = Strategy(dp=2, tp=2, sp=True)
    mesh, ctx_off = _tp_ctx(st, sp=True, tp_overlap="off")
    _, ctx_on = _tp_ctx(st, sp=True, tp_overlap="ring")
    col = ColumnParallelLinear(16, 32, bias=True)
    row = RowParallelLinear(32, 16, bias=True)
    pc = col.init(rng, dtype=jnp.float32)
    pr = row.init(jax.random.key(7), dtype=jnp.float32)
    rules = st.axis_rules()
    pc_s = shard_params(pc, mesh, param_partition_specs(col, rules,
                                                        mesh=mesh))
    pr_s = shard_params(pr, mesh, param_partition_specs(row, rules,
                                                        mesh=mesh))
    x = jax.random.normal(jax.random.key(2), (4, 8, 16), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None, None)))

    def fwd(ctx):
        @jax.jit
        def f(pc, pr, x):
            with ctx:
                return row(pr, col(pc, x))
        return np.asarray(f(pc_s, pr_s, xs))

    ref = fwd(ctx_off)
    got = fwd(ctx_on)
    np.testing.assert_array_equal(ref, got)
    stats = ov.comm_stats()
    assert stats["bytes_by_kind"].get("tp_ring_all_gather", 0) > 0
    assert stats["bytes_by_kind"].get("tp_ring_reduce_scatter", 0) > 0
    # both ring kinds are overlapping paths
    assert stats["overlap_ratio"] == 1.0
    # divisible dims: the ring must have engaged, never the dense
    # fallback (tp_ring_fallback_total audits silent degradation)
    assert stats["tp_ring_fallbacks"] == 0


def test_ring_column_requires_sp(rng):
    """Without Megatron-SP the column matmul has no all-gather to hide:
    overlap must fall through to the dense path (no AG bytes recorded);
    the row ring still decomposes its all-reduce, bitwise at tp=2."""
    st = Strategy(dp=2, tp=2)
    mesh, ctx_off = _tp_ctx(st, tp_overlap="off")
    _, ctx_on = _tp_ctx(st, tp_overlap="ring")
    col = ColumnParallelLinear(16, 32, bias=False)
    row = RowParallelLinear(32, 16, bias=False)
    pc = col.init(rng, dtype=jnp.float32)
    pr = row.init(jax.random.key(7), dtype=jnp.float32)
    rules = st.axis_rules()
    pc_s = shard_params(pc, mesh, param_partition_specs(col, rules,
                                                        mesh=mesh))
    pr_s = shard_params(pr, mesh, param_partition_specs(row, rules,
                                                        mesh=mesh))
    x = jax.random.normal(jax.random.key(2), (4, 8, 16), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None, None)))

    def fwd(ctx):
        @jax.jit
        def f(pc, pr, x):
            with ctx:
                return row(pr, col(pc, x))
        return np.asarray(f(pc_s, pr_s, xs))

    ref = fwd(ctx_off)
    got = fwd(ctx_on)
    np.testing.assert_array_equal(ref, got)
    stats = ov.comm_stats()
    assert "tp_ring_all_gather" not in stats["bytes_by_kind"]
    assert stats["bytes_by_kind"].get("tp_ring_reduce_scatter", 0) > 0
    # sp off is a legitimate fall-through (nothing to hide), NOT a
    # divisibility fallback — the counter must stay 0
    assert stats["tp_ring_fallbacks"] == 0


@pytest.mark.slow
def test_tp_ring_train_parity_bitwise():
    """ACCEPTANCE: overlap-on vs overlap-off losses bitwise-identical
    on the 8-device mesh (dp=2 × tp=2: every cross-device reduction is
    a two-term sum) over real optimizer-coupled train steps.

    Horizon note: the ring's weight grad splits the seq contraction
    (chunk matmuls summed pairwise vs the fused matmul's internal
    accumulation), so weights drift ~1 ulp/step; losses stay bitwise
    for the first ~5 steps on this backend and ≤1e-7 apart long-run
    (docs/PERFORMANCE.md). Three steps is inside the exact window."""
    model = GPTLMHeadModel(CFG)
    off = _train_losses(model, Strategy(dp=2, tp=2, sp=True))
    on = _train_losses(model, Strategy(dp=2, tp=2, sp=True,
                                       tp_overlap="ring"))
    assert off == on, f"ring overlap changed numerics: {off} vs {on}"
    stats = ov.comm_stats()
    assert stats["bytes_by_kind"].get("tp_ring_all_gather", 0) > 0


@pytest.mark.slow
def test_tp_ring_train_parity_tp4():
    """tp=4 re-associates the ring's partial sums vs GSPMD's all-reduce
    — allclose, not bitwise, is the correct contract there."""
    model = GPTLMHeadModel(CFG)
    off = _train_losses(model, Strategy(dp=2, tp=4, sp=True))
    on = _train_losses(model, Strategy(dp=2, tp=4, sp=True,
                                       tp_overlap="ring"))
    np.testing.assert_allclose(off, on, rtol=1e-5, atol=1e-6)


# -- delayed gradient synchronization ---------------------------------------

def _accum_updates(model, strategy, *, delay, schedule=(2, 4)):
    """Run len(schedule) optimizer updates, update i accumulating
    schedule[i] microbatches (same microbatch SHAPE throughout — the
    sync-per-update invariant must hold for any count without
    recompiles). Returns (per-microbatch losses, ledger stats)."""
    opt = optim.adamw(1e-3)
    plan = make_plan(model, opt, strategy)
    init_acc, grad_step, apply_step = build_grad_accum_steps(
        model, opt, plan, delay_grad_sync=delay)
    state = init_state(model, opt, plan, jax.random.key(0))
    losses = []
    mb = 4
    for n_accum in schedule:
        acc = init_acc()
        for i in range(n_accum):
            ids = jax.random.randint(
                jax.random.key(100 + i), (mb, S + 1), 0, CFG.vocab_size)
            sb = plan.shard_batch({"input_ids": ids[:, :-1],
                                   "labels": ids[:, 1:]})
            acc, loss = grad_step(state, acc, sb, i)
            losses.append(float(jax.device_get(loss)))
        state, _ = apply_step(state, acc, float(n_accum))
    return losses, ov.comm_stats()


def test_delayed_grad_sync_one_reduction_per_update():
    """ACCEPTANCE: delayed sync issues exactly ONE DP gradient
    reduction per optimizer update regardless of accum_steps (2 then 4
    microbatches → 2 syncs for 2 updates), where eager pays one per
    microbatch (6 syncs). Asserted via the telemetry counter AND the
    module ledger; per-microbatch losses must agree across modes."""
    telemetry.reset()
    telemetry.enable(True)
    try:
        model = GPTLMHeadModel(CFG)
        le, stats_e = _accum_updates(model, Strategy(dp=2), delay=False)
        assert stats_e["dp_syncs"] == 6          # 2 + 4 microbatches
        assert stats_e["optimizer_updates"] == 2
        ov.reset_comm_stats()
        ld, stats_d = _accum_updates(model, Strategy(dp=2), delay=True)
        assert stats_d["dp_syncs"] == 2          # one per update
        assert stats_d["optimizer_updates"] == 2
        assert stats_d["dp_sync_per_step"] == 1.0
        reg = telemetry.get_registry()
        assert reg.counter("dp_grad_syncs_total").value() == 8  # 6 + 2
        assert reg.counter("optimizer_updates_total").value() == 4
        # dp=2: every cross-group reduction is a two-term sum — the
        # reorder (sync-per-microbatch vs one deferred sum) cannot
        # change the bits of the per-microbatch losses
        np.testing.assert_allclose(le, ld, rtol=0, atol=1e-6)
        # O(accum) traffic reduction shows in the byte ledger too
        assert stats_d["bytes_by_kind"]["dp_grad_sync"] * 3 == \
            stats_e["bytes_by_kind"]["dp_grad_sync"]
    finally:
        telemetry.reset()
        telemetry.enable(False)


def test_delayed_grad_sync_rejects_fsdp():
    # (ep > 1 no longer rejects — the dp×ep group generalization in
    # build_local_grad_fn covers it; see tests/test_moe_plane.py)
    model = GPTLMHeadModel(CFG)
    opt = optim.adamw(1e-3)
    plan = make_plan(model, opt, Strategy(dp=2, fsdp=True))
    with pytest.raises(ValueError, match="fsdp"):
        build_grad_accum_steps(model, opt, plan, delay_grad_sync=True)


@pytest.mark.slow
def test_delayed_grad_sync_update_parity_with_zero():
    """Delayed sync composes with ZeRO: the single deferred reduction
    feeds the dp-sharded optimizer states; updated-state training
    curves match eager to fp tolerance."""
    model = GPTLMHeadModel(CFG)
    le, _ = _accum_updates(model, Strategy(dp=2, tp=2, zero=True),
                           delay=False, schedule=(2, 2))
    ov.reset_comm_stats()
    ld, _ = _accum_updates(model, Strategy(dp=2, tp=2, zero=True),
                           delay=True, schedule=(2, 2))
    np.testing.assert_allclose(le, ld, rtol=0, atol=5e-6)


# -- double-buffered pipeline comms ------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("pp,nm", [(2, 2), (4, 4)])
def test_pp_double_buffer_parity_bitwise(pp, nm):
    """ACCEPTANCE: the double-buffered schedule runs the same block
    computes on the same microbatch data (only shifted in time), so
    losses are bitwise-identical to the baseline scan pipeline."""
    import dataclasses
    cfg = dataclasses.replace(CFG, num_layers=pp)   # 1 layer per stage
    model = GPTLMHeadModel(cfg)
    off = _train_losses(model, Strategy(pp=pp, num_microbatches=nm))
    on = _train_losses(model, Strategy(pp=pp, num_microbatches=nm,
                                       pp_overlap=True))
    assert off == on, f"pp double-buffer changed numerics: {off} vs {on}"
    stats = ov.comm_stats()
    assert stats["bytes_by_kind"].get("pp_ppermute", 0) > 0


# -- ledger / flags / satellites ---------------------------------------------

def test_comm_ledger_and_overlap_ratio():
    ov.record_comm_bytes("tp_allreduce", 100)
    ov.record_comm_bytes("tp_ring_all_gather", 300, overlapped=True)
    stats = ov.comm_stats()
    assert stats["bytes_total"] == 400
    assert stats["overlap_ratio"] == 0.75
    ov.record_dp_sync(2, grad_bytes=50)
    ov.record_optimizer_update()
    stats = ov.comm_stats()
    assert stats["dp_syncs"] == 2 and stats["optimizer_updates"] == 1
    assert stats["bytes_by_kind"]["dp_grad_sync"] == 100


def test_default_trainer_leaves_xla_flags_alone(monkeypatch):
    """Constructing a default Trainer puts nothing into XLA_FLAGS, even
    when no platform is pinned: jaxlib aborts the process on a flag its
    parser does not know, so nothing is appended behind the user."""
    from hetu_tpu.engine.trainer import Trainer
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    before = os.environ.get("XLA_FLAGS", "")
    Trainer(GPTLMHeadModel(GPTConfig.tiny()), optim.adamw(1e-3),
            Strategy())
    assert os.environ.get("XLA_FLAGS", "") == before


def test_strategy_overlap_fields_roundtrip():
    s = Strategy(dp=2, tp=2, sp=True, tp_overlap="ring", pp_overlap=True)
    s2 = Strategy.from_json(s.to_json())
    assert s2.tp_overlap == "ring" and s2.pp_overlap is True
    with pytest.raises(ValueError, match="tp_overlap"):
        Strategy(tp_overlap="pipelined").validate()


def test_state_bytes_counts_only_jax_arrays():
    from hetu_tpu.parallel.switch import _state_bytes
    dev = jnp.ones((4, 4), jnp.float32)            # 64 bytes
    host = np.ones((1024,), np.float32)            # numpy mirror: ignored
    assert _state_bytes({"a": dev, "b": host, "c": 3}) == dev.nbytes


def test_rerank_by_measured_prefers_observed():
    from hetu_tpu.tools.galvatron.cost_model import CostBreakdown
    from hetu_tpu.tools.galvatron.search import (
        Candidate, load_measured_step_times, rerank_by_measured,
    )

    def cand(strategy, t):
        return Candidate(strategy, CostBreakdown(
            step_time=t, compute=t, tp_comm=0.0, cp_comm=0.0,
            dp_comm=0.0, pp_bubble_factor=1.0, mem_per_device=1.0))

    fast_a = cand(Strategy(dp=8), 0.010)             # analytic winner
    slow_a = cand(Strategy(dp=4, tp=2), 0.020)
    unmeasured = cand(Strategy(dp=2, tp=4), 0.030)
    # reality disagrees: the analytic winner measured 3x slower
    measured = {Strategy(dp=8).to_json(): 0.060,
                Strategy(dp=4, tp=2).to_json(): 0.015}
    ranked = rerank_by_measured([fast_a, slow_a, unmeasured], measured)
    assert ranked[0].strategy == Strategy(dp=4, tp=2)
    assert ranked[0].measured_step_time == 0.015
    # the unmeasured candidate is scaled by the observed/analytic ratio
    # (median 3x → 0.09s) and lands last, after the measured loser
    assert [c.strategy for c in ranked] == [
        Strategy(dp=4, tp=2), Strategy(dp=8), Strategy(dp=2, tp=4)]
    # empty measurements: identity
    assert [c.strategy for c in
            rerank_by_measured([fast_a, slow_a], {})] == \
        [Strategy(dp=8), Strategy(dp=4, tp=2)]


def test_load_measured_step_times(tmp_path):
    from hetu_tpu.tools.galvatron.search import load_measured_step_times
    p = tmp_path / "telemetry.jsonl"
    s = Strategy(dp=2, tp=2)
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "bench_result", "value": 1}) + "\n")
        f.write("not json\n")
        f.write(json.dumps({"kind": "measured_step",
                            "strategy": s.to_json(),
                            "step_time_s": 0.5}) + "\n")
        # later record for the same strategy wins
        f.write(json.dumps({"kind": "measured_step",
                            "strategy": s.to_json(),
                            "step_time_s": 0.25}) + "\n")
    out = load_measured_step_times(str(p))
    assert out == {s.to_json(): 0.25}
    assert load_measured_step_times(str(tmp_path / "missing.jsonl")) == {}


def test_trainer_aggregate_cadence_and_measured_record(tmp_path):
    """Satellite: cluster_aggregate on the Trainer cadence (local
    reduction in single-process runs — same record schema the
    multi-host path produces) + the measured_step record the planner
    re-rank consumes, both landing in telemetry.jsonl."""
    from hetu_tpu.engine.trainer import Trainer, TrainerConfig
    telemetry.reset()
    cfg = TrainerConfig(total_steps=4, log_every=2, telemetry=True,
                        trace_dir=str(tmp_path), aggregate_every=2,
                        prefetch=0)
    trainer = Trainer(GPTLMHeadModel(CFG), optim.adamw(1e-3),
                      Strategy(), config=cfg)
    try:
        ids = jax.random.randint(jax.random.key(3), (4, 4, S + 1), 0,
                                 CFG.vocab_size)
        batches = [{"input_ids": ids[i, :, :-1],
                    "labels": ids[i, :, 1:]} for i in range(4)]
        trainer.train(batches)
        with open(os.path.join(str(tmp_path), "telemetry.jsonl")) as f:
            recs = [json.loads(l) for l in f if l.strip()]
        aggs = [r for r in recs if r.get("kind") == "cluster_aggregate"]
        assert [a["step"] for a in aggs] == [2, 4]
        assert all(a["ranks"] == 1 for a in aggs)
        # the aggregate carries reduced series from this rank's registry
        assert all(isinstance(a["metrics"], dict) and a["metrics"]
                   for a in aggs)
        meas = [r for r in recs if r.get("kind") == "measured_step"]
        assert len(meas) == 1
        assert meas[0]["strategy"] == trainer.strategy.to_json()
        assert meas[0]["step_time_s"] > 0
    finally:
        trainer.close()
        telemetry.reset()
        telemetry.enable(False)
