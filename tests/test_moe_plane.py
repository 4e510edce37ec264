"""Expert plane (ISSUE 9): chunked a2a/FFN overlap, ep-aware delayed
grad sync, expert-priced planning, MoE serving decode, and expert-plane
telemetry.

Parity discipline mirrors test_overlap/test_memory_plane: the chunked
a2a decomposition moves the SAME bits through the same per-row
arithmetic (capacity slices are disjoint), so serialized-vs-chunked
asserts bitwise; the ep-aware delayed sync re-associates group means
(and estimates the load-balance aux per group, GShard-style), so it
asserts tight allclose with the aux coefficient zeroed and loose
allclose with it on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hetu_tpu import optim, telemetry
from hetu_tpu.engine import memory as mem
from hetu_tpu.engine.train_step import (
    build_grad_accum_steps, build_train_step, init_state, make_plan,
    trace_counts,
)
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.nn.moe import MoEMLP, hierarchical_all_to_all
from hetu_tpu.parallel import overlap as ov
from hetu_tpu.parallel.sharding import (
    ActivationSharding, param_partition_specs, shard_params,
)
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.tools.galvatron import ModelDims, TPUTopology, search_uniform
from hetu_tpu.tools.galvatron.cost_model import estimate


@pytest.fixture(autouse=True)
def _clean_ledgers():
    ov.reset_comm_stats()
    yield
    ov.reset_comm_stats()


# -- hierarchical a2a unit (multi-slice factored ep axis) --------------------

def test_hierarchical_all_to_all_reference_permutation():
    """The two-stage exchange must implement EXACTLY the flat a2a
    permutation out[r][s] = in[s][r] (destination-major blocks with
    rank r = outer·I + inner) — previously only exercised end-to-end
    through the MoE layer, never against the raw permutation."""
    from hetu_tpu.core.mesh import make_mesh
    mesh = make_mesh({"ep_out": 2, "ep_in": 2})
    ranks = 4
    # x[r, s, :]: rank r's block destined for rank s, tagged r*10+s
    x = (jnp.arange(ranks)[:, None] * 10
         + jnp.arange(ranks)[None, :]).astype(jnp.float32)
    x = jnp.broadcast_to(x[:, :, None], (ranks, ranks, 3))

    from jax import shard_map

    def body(buf):
        return hierarchical_all_to_all(buf[0], "ep_out", "ep_in")[None]

    fn = shard_map(body, mesh=mesh,
                   in_specs=P(("ep_out", "ep_in")),
                   out_specs=P(("ep_out", "ep_in")), check_vma=False)
    out = np.asarray(fn(x))
    expect = np.asarray(x).transpose(1, 0, 2)   # out[r][s] = in[s][r]
    np.testing.assert_array_equal(out, expect)


# -- chunked a2a/FFN overlap -------------------------------------------------

def _moe_layer_outputs(moe, params, x, strat, ep_overlap, ep_chunks=2):
    mesh = strat.build_mesh()
    sp = shard_params(params, mesh, param_partition_specs(
        moe, strat.axis_rules(), mesh))
    act = ActivationSharding(mesh, batch=("dp", "ep"), seq="cp", tp="tp",
                             ep_overlap=ep_overlap, ep_chunks=ep_chunks)

    @jax.jit
    def f(p, x):
        with act:
            return moe(p, x)

    xs = jax.device_put(x, NamedSharding(mesh, strat.data_spec(3)))
    out, aux = f(sp, xs)
    return np.asarray(out), float(aux)


def test_chunked_overlap_bitwise_and_ledger():
    """ACCEPTANCE: ep_overlap="chunk" is bitwise-identical to the
    serialized EP dispatch at degree 2+ chunks (disjoint capacity
    slices, same per-row arithmetic) and the comm ledger shows ep_a2a
    bytes with a nonzero overlapped fraction."""
    moe = MoEMLP(8, 16, num_experts=8, k=2, capacity_factor=2.0)
    params = moe.init(jax.random.key(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(3), (8, 4, 8))

    for strat in (Strategy(dp=2, ep=4), Strategy(dp=2, ep=2)):
        ov.reset_comm_stats()
        ref, aux_ref = _moe_layer_outputs(moe, params, x, strat, "off")
        st = ov.comm_stats()
        assert st["bytes_by_kind"]["ep_a2a"] > 0
        assert st["bytes_overlapped_by_kind"].get("ep_a2a", 0) == 0

        for chunks in (2, 3):
            ov.reset_comm_stats()
            out, aux = _moe_layer_outputs(moe, params, x, strat,
                                          "chunk", chunks)
            np.testing.assert_array_equal(ref, out)
            assert aux == aux_ref
            st = ov.comm_stats()
            assert st["bytes_by_kind"]["ep_a2a"] > 0
            assert st["bytes_overlapped_by_kind"]["ep_a2a"] == \
                st["bytes_by_kind"]["ep_a2a"]
            assert st["overlap_ratio"] > 0


def _gpt_moe_losses(model, strategy, raw, steps=3):
    opt = optim.adamw(1e-3)
    plan = make_plan(model, opt, strategy)
    state = init_state(model, opt, plan, jax.random.key(0),
                       dtype=jnp.float32)
    step = build_train_step(model, opt, plan, donate=False)
    batch = plan.shard_batch(raw)
    out = []
    for _ in range(steps):
        state, m = step(state, batch)
        out.append(float(m["loss"]))
    return out


@pytest.mark.slow
def test_chunked_overlap_model_composes_remat():
    """Chunked EP overlap in the full GPT-MoE train step under dp×ep
    at degree 2: bitwise-identical losses end-to-end (the _pin_buffer
    barriers keep XLA from re-associating the dispatch/combine
    contractions across the capacity slices). At wider FFN gemms
    (tiny_moe's 64×256) the CPU backend's fast-math K-loop
    vectorization picks a different reduction blocking for the halved
    row count — a backend artifact, not a chunking re-association (the
    pre-activation tensors stay bitwise-equal; TPU MXU accumulation is
    shape-independent) — so that config, with and without remat,
    asserts the two-term-sum fp tolerance instead."""
    ids = jax.random.randint(jax.random.key(2), (8, 17), 0, 256)
    raw = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    # narrow FFN: bitwise through 3 optimizer steps
    cfg = GPTConfig(vocab_size=256, max_positions=128, hidden_size=32,
                    num_layers=2, num_heads=4, num_experts=4,
                    moe_capacity_factor=4.0)
    model = GPTLMHeadModel(cfg)
    serialized = _gpt_moe_losses(model, Strategy(dp=2, ep=2), raw)
    chunked = _gpt_moe_losses(
        model, Strategy(dp=2, ep=2, ep_overlap="chunk"), raw)
    np.testing.assert_allclose(serialized, chunked, rtol=0, atol=0)

    # tiny_moe width, with and without full remat: fp tolerance
    cfg = GPTConfig.tiny_moe(num_experts=4, moe_capacity_factor=4.0)
    model = GPTLMHeadModel(cfg)
    for extra in ({}, {"remat": "full"}):
        serialized = _gpt_moe_losses(model, Strategy(dp=2, ep=2,
                                                     **extra), raw)
        chunked = _gpt_moe_losses(
            model, Strategy(dp=2, ep=2, ep_overlap="chunk", **extra),
            raw)
        np.testing.assert_allclose(serialized, chunked, rtol=0,
                                   atol=1e-6)


@pytest.mark.slow
def test_chunked_overlap_model_composes_tp():
    """Chunked EP overlap composed with tp sharding: bitwise parity
    with the serialized EP path."""
    cfg = GPTConfig.tiny_moe(num_experts=4, moe_capacity_factor=4.0)
    model = GPTLMHeadModel(cfg)
    ids = jax.random.randint(jax.random.key(2), (8, 17), 0,
                             cfg.vocab_size)
    raw = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    base = dict(dp=2, ep=2, tp=2)
    serialized = _gpt_moe_losses(model, Strategy(**base), raw)
    chunked = _gpt_moe_losses(
        model, Strategy(**base, ep_overlap="chunk"), raw)
    np.testing.assert_allclose(serialized, chunked, rtol=0, atol=0)


# -- ep-aware delayed grad sync ----------------------------------------------

def _moe_run(model, strategy, raw, steps=2):
    opt = optim.adamw(1e-3)
    plan = make_plan(model, opt, strategy)
    state = init_state(model, opt, plan, jax.random.key(0),
                       dtype=jnp.float32)
    step = build_train_step(model, opt, plan, donate=False)
    batch = plan.shard_batch(raw)
    losses = []
    for _ in range(steps):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses, jax.device_get(state.params)


@pytest.mark.slow
def test_ep_delayed_sync_counter_parity_and_grads():
    """ACCEPTANCE: delay_grad_sync=True with ep>1 no longer raises;
    the dp×ep-group scan issues exactly ONE reduction per optimizer
    update (eager = nm) and training matches eager. With the aux
    coefficient zeroed the paths are allclose to fp noise; with it on
    they stay close (the delayed path estimates the load-balance aux
    per group, GShard-style, vs eager's global-batch estimate)."""
    ids = jax.random.randint(jax.random.key(1), (8, 17), 0, 256)
    raw = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    cfg0 = GPTConfig.tiny_moe(num_experts=4, moe_capacity_factor=8.0,
                              moe_aux_coef=0.0)
    model0 = GPTLMHeadModel(cfg0)
    le, pe = _moe_run(model0, Strategy(dp=2, ep=2, num_microbatches=2),
                      raw)
    se = ov.comm_stats()
    assert se["dp_sync_per_step"] == 2.0    # nm per update
    ov.reset_comm_stats()
    ld, pd = _moe_run(model0, Strategy(dp=2, ep=2, num_microbatches=2,
                                       delay_grad_sync=True), raw)
    sd = ov.comm_stats()
    assert sd["dp_sync_per_step"] == 1.0    # ONE per update
    np.testing.assert_allclose(le, ld, rtol=0, atol=2e-5)
    for a, b in zip(jax.tree.leaves(pe), jax.tree.leaves(pd)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)

    # default aux coefficient: per-group estimator keeps the curves
    # close but not identical
    cfg1 = GPTConfig.tiny_moe(num_experts=4, moe_capacity_factor=8.0)
    model1 = GPTLMHeadModel(cfg1)
    le1, _ = _moe_run(model1, Strategy(dp=2, ep=2, num_microbatches=2),
                      raw)
    ld1, _ = _moe_run(model1, Strategy(dp=2, ep=2, num_microbatches=2,
                                       delay_grad_sync=True), raw)
    np.testing.assert_allclose(le1, ld1, rtol=5e-3, atol=5e-3)


@pytest.mark.slow
def test_ep_delayed_sync_split_phase():
    """The split-phase twin (build_grad_accum_steps) shares
    build_local_grad_fn: with ep>1 it no longer raises, counts one
    sync per apply, and the updated params match eager accumulation."""
    cfg = GPTConfig.tiny_moe(num_experts=4, moe_capacity_factor=8.0,
                             moe_aux_coef=0.0)
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    ids = jax.random.randint(jax.random.key(5), (8, 17), 0,
                             cfg.vocab_size)
    raw = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    def accum(delay):
        ov.reset_comm_stats()
        plan = make_plan(model, opt, Strategy(dp=2, ep=2))
        state = init_state(model, opt, plan, jax.random.key(0),
                           dtype=jnp.float32)
        init_acc, grad_step, apply_step = build_grad_accum_steps(
            model, opt, plan, delay_grad_sync=delay)
        batch = plan.shard_batch(raw)
        acc = init_acc()
        for i in range(2):
            acc, loss = grad_step(state, acc, batch, accum_index=i)
        state, m = apply_step(state, acc, 2)
        return (float(loss), jax.device_get(state.params),
                ov.comm_stats())

    l_e, p_e, s_e = accum(False)
    assert s_e["dp_syncs"] == 2             # one per grad_step
    l_d, p_d, s_d = accum(True)
    assert s_d["dp_syncs"] == 1             # one per UPDATE
    assert s_d["optimizer_updates"] == 1
    np.testing.assert_allclose(l_e, l_d, rtol=0, atol=2e-5)
    for a, b in zip(jax.tree.leaves(p_e), jax.tree.leaves(p_d)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_strategy_ep_flags_validate_and_roundtrip():
    # ep>1 + delay_grad_sync is now a VALID strategy (the ISSUE 9 lift)
    s = Strategy(dp=2, ep=2, num_microbatches=2, delay_grad_sync=True,
                 ep_overlap="chunk", ep_chunks=4).validate()
    assert Strategy.from_json(s.to_json()) == s
    with pytest.raises(ValueError, match="ep_overlap"):
        Strategy(ep_overlap="ring").validate()
    with pytest.raises(ValueError, match="ep_chunks"):
        Strategy(ep_chunks=0).validate()
    with pytest.raises(ValueError, match="fsdp"):
        Strategy(dp=2, fsdp=True, delay_grad_sync=True).validate()


# -- expert-priced planning --------------------------------------------------

def _moe_dims(**kw):
    base = dict(num_layers=4, hidden=256, intermediate=1024,
                num_heads=8, num_kv_heads=8, vocab=8192, seq_len=512,
                global_batch=32, num_experts=8, moe_top_k=2)
    base.update(kw)
    return ModelDims(**base)


def test_ledger_prices_expert_params_by_ep():
    """Expert params divide by ep; dense params must NOT (the old
    formula divided the whole model by ep, under-pricing dense weights
    exactly when ranking ep against tp/fsdp)."""
    dims = _moe_dims()
    expert_total = dims.num_layers * dims.layer_expert_params()
    dense_total = dims.total_params() - expert_total
    assert expert_total > 0 and dense_total > 0

    bd1 = mem.estimate_breakdown(dims, Strategy(dp=1, ep=1))
    bd4 = mem.estimate_breakdown(dims, Strategy(dp=1, ep=4))
    # weights bf16: params_bytes = 2 * p_shard
    np.testing.assert_allclose(
        bd1.params_bytes, 2.0 * (dense_total + expert_total))
    np.testing.assert_allclose(
        bd4.params_bytes, 2.0 * (dense_total + expert_total / 4))
    # dense model of identical shape: no ep division at all
    ddims = _moe_dims(num_experts=0)
    bdd = mem.estimate_breakdown(ddims, Strategy(dp=1, ep=1))
    assert bdd.params_bytes < bd1.params_bytes


def test_ledger_prices_capacity_buffers():
    """The fp32 dispatch/combine capacity buffers add activation bytes
    proportional to capacity_factor·k — visible to derive_remat_mask
    through act_bytes."""
    lo = mem.estimate_breakdown(
        _moe_dims(moe_capacity_factor=1.0), Strategy(dp=1, ep=4))
    hi = mem.estimate_breakdown(
        _moe_dims(moe_capacity_factor=2.0), Strategy(dp=1, ep=4))
    assert hi.act_bytes > lo.act_bytes
    # at the SAME token split (dp=4 vs ep=4 both divide the batch by
    # 4), the MoE layer's dispatch buffers show up on top of the dense
    # residual stream
    moe4 = mem.estimate_breakdown(_moe_dims(), Strategy(dp=4))
    dense4 = mem.estimate_breakdown(
        _moe_dims(num_experts=0), Strategy(dp=4))
    assert moe4.act_bytes > dense4.act_bytes


def test_cost_model_prices_ep_a2a():
    """estimate() carries an ep_comm term for MoE strategies (2 fwd +
    2 bwd a2as of the capacity buffers) so search_uniform ranks ep
    against tp honestly; dense strategies and ep=1 pay zero."""
    dims = _moe_dims()
    topo = TPUTopology(num_devices=8)
    c_ep = estimate(dims, Strategy(dp=2, ep=4), topo)
    assert c_ep.ep_comm > 0
    assert c_ep.step_time > estimate(
        dims, Strategy(dp=2, ep=4), TPUTopology(
            num_devices=8, ici_bw=9e15)).step_time
    c1 = estimate(dims, Strategy(dp=8), topo)
    assert c1.ep_comm == 0.0
    cands = search_uniform(dims, topo)
    assert cands, "search must return feasible candidates"
    eps = {c.strategy.ep for c in cands}
    assert {1}.issubset(eps) and any(e > 1 for e in eps), eps


# -- MoE decode path (serving / generation) ----------------------------------

def test_moe_decode_matches_dense_combine():
    """MoEMLP.decode (per-row top-k through gathered expert weights)
    computes the same Σ_j w_j·expert_j(x) as the dense oracle."""
    for gated in (False, True):
        moe = MoEMLP(8, 16, num_experts=4, k=2, gated=gated)
        params = moe.init(jax.random.key(0), dtype=jnp.float32)
        x = jax.random.normal(jax.random.key(2), (2, 5, 8))
        ref, _ = moe(params, x)                 # dense oracle
        out = moe.decode(params, x)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


def test_moe_decode_rejects_batch_coupled_gate():
    """BalanceGate routes over the WHOLE co-batched row set (Sinkhorn
    column marginals), so a serving step packing rows from unrelated
    requests could never match one-shot generate — decode must refuse
    it loudly instead of silently produce arrival-order-dependent
    tokens."""
    moe = MoEMLP(8, 16, num_experts=4, gate_type="balance")
    params = moe.init(jax.random.key(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 3, 8))
    with pytest.raises(NotImplementedError, match="per-token gate"):
        moe.decode(params, x)


@pytest.mark.slow
def test_moe_serving_matches_one_shot_generate():
    """ACCEPTANCE: a GPT-MoE model serves through ServingEngine with
    greedy outputs token-identical to one-shot generate, and exactly
    one serving_step compile across admit/evict churn (slots <
    requests forces slot recycling)."""
    from hetu_tpu.models.generation import generate
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg = GPTConfig.tiny_moe(num_experts=4)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (int(n),)).tolist()
               for n in (5, 11, 3, 9)]
    MT = 6
    refs = []
    for p in prompts:
        out = generate(model, params, jnp.asarray([p], jnp.int32),
                       max_new_tokens=MT)
        refs.append(np.asarray(out)[0, len(p):].tolist())

    eng = ServingEngine(model, params, slots=2, max_len=32,
                        prefill_chunk=8)
    before = trace_counts().get("serving_step", 0)
    res = eng.generate_many(prompts, SamplingParams(max_tokens=MT))
    assert trace_counts().get("serving_step", 0) == before + 1
    assert res == refs


# -- expert-plane telemetry --------------------------------------------------

def test_expert_plane_telemetry_counters():
    """The per-expert load gauges / dropped-token counter / aux and
    overflow histograms fire from BOTH execution modes: plain forward
    (primal callback) and a differentiated layer scan (the custom_vjp
    probe routes emission through the backward — jax 0.4.37 drops
    effects inside differentiated scan bodies)."""
    telemetry.reset()
    telemetry.enable(True)
    try:
        E = 4
        moe = MoEMLP(8, 16, num_experts=E, k=1, capacity_factor=0.25)
        params = moe.init(jax.random.key(0), dtype=jnp.float32)
        x = jax.random.normal(jax.random.key(4), (4, 8, 8))
        strat = Strategy(dp=1, ep=4)
        mesh = strat.build_mesh()
        sp = shard_params(params, mesh, param_partition_specs(
            moe, strat.axis_rules(), mesh))
        act = ActivationSharding(mesh, batch=("dp", "ep"), seq="cp",
                                 tp="tp")

        @jax.jit
        def fwd(p, x):
            with act:
                out, aux = moe(p, x)
            return out.sum()

        fwd(sp, jax.device_put(x, NamedSharding(
            mesh, strat.data_spec(3))))
        jax.effects_barrier()
        reg = telemetry.get_registry()
        dropped_fwd = reg.counter("moe_dropped_tokens_total").value()
        assert dropped_fwd > 0          # capacity 0.25 must drop
        gauge = reg.gauge("moe_expert_tokens")
        loads = [gauge.value(expert=str(e)) for e in range(E)]
        assert sum(loads) == 4 * 8      # every (token, choice) routed
        assert reg.histogram("moe_overflow_fraction").summary()["count"] \
            == 1
        assert reg.histogram("moe_aux_loss").summary()["count"] == 1

        # differentiated scan (the train-step shape): emission must
        # still fire, exactly once per layer call
        def loss(p):
            def body(h, _):
                out, aux = moe(p, h)
                return out, aux
            h, auxs = jax.lax.scan(body, x, None, length=2)
            return h.sum() + auxs.sum()

        jax.jit(jax.value_and_grad(loss))(params)
        jax.effects_barrier()
        assert reg.histogram("moe_aux_loss").summary()["count"] == 3
        assert reg.counter("moe_dropped_tokens_total").value() \
            == dropped_fwd              # dense oracle path: no drops
    finally:
        telemetry.reset()
        telemetry.enable(False)


def test_trace_summary_expert_plane_section(tmp_path):
    """The expert-plane section renders from a telemetry JSONL
    snapshot (load + imbalance, drops, a2a overlap split)."""
    import json

    from hetu_tpu.tools.trace_summary import expert_plane_summary
    snap = {
        'moe_expert_tokens{expert="0"}': 10.0,
        'moe_expert_tokens{expert="1"}': 30.0,
        "moe_dropped_tokens_total": 5.0,
        "moe_overflow_fraction": {"count": 2, "p50": 0.1, "p99": 0.2},
        "moe_aux_loss": {"count": 2, "p50": 1.0, "p99": 1.1},
        'comm_bytes_total{kind="ep_a2a"}': 1000.0,
        'comm_overlapped_bytes_total{kind="ep_a2a"}': 750.0,
    }
    records = [{"kind": "metrics_snapshot", "metrics": snap}]
    lines = expert_plane_summary(records)
    text = "\n".join(lines)
    assert "max/mean 1.50" in text
    assert "5 (token, choice) slots" in text
    assert "75% on the chunked-overlap path" in text
    # and the section is wired into summarize()
    path = tmp_path / "telemetry.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    from hetu_tpu.tools.trace_summary import summarize
    assert "== expert plane ==" in summarize(str(path))
