"""Auto-parallel search tests (reference: ``tools/Galvatron`` —
``csrc/dp_core.cpp`` DP over layers × strategies × memory)."""

import numpy as np
import pytest

from hetu_tpu.models import GPTConfig, LlamaConfig
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.tools.galvatron import (
    ModelDims, TPUTopology, estimate, search_layerwise, search_uniform,
    solve_layer_dp,
)
from hetu_tpu.tools.galvatron.dp_core import _build_lib


def test_native_dp_core_compiles():
    assert _build_lib() is not None, "g++ build of dp_core.cpp failed"


def test_dp_core_native_matches_python():
    rng = np.random.default_rng(0)
    for _ in range(5):
        L, S, M = 6, 4, 40
        t = rng.uniform(0.1, 1.0, (L, S))
        m = rng.integers(1, 8, (L, S)).astype(np.int64)
        sw = rng.uniform(0, 0.05, (S, S))
        np.fill_diagonal(sw, 0.0)
        tn, cn = solve_layer_dp(t, m, M, sw)
        tp_, cp_ = solve_layer_dp(t, m, M, sw, force_python=True)
        np.testing.assert_allclose(tn, tp_, rtol=1e-9)
        # same total cost even if tie-broken differently
        def total(c):
            out = sum(t[l, c[l]] for l in range(L))
            out += sum(sw[c[l - 1], c[l]] for l in range(1, L))
            return out
        np.testing.assert_allclose(total(cn), total(cp_), rtol=1e-9)


def test_dp_core_respects_budget_and_infeasible():
    t = np.array([[1.0, 10.0]] * 3)
    m = np.array([[5, 1]] * 3, np.int64)
    # budget 3: must pick the slow/small strategy everywhere
    total, choice = solve_layer_dp(t, m, 3)
    assert list(choice) == [1, 1, 1]
    # budget 15: fast/large everywhere
    total, choice = solve_layer_dp(t, m, 15)
    assert list(choice) == [0, 0, 0]
    total, choice = solve_layer_dp(t, m, 2)
    assert choice is None and total == float("inf")


def _dims_7b(batch=64, seq=4096):
    return ModelDims.from_config(LlamaConfig.llama_7b(), seq_len=seq,
                                 global_batch=batch)


def test_search_small_model_prefers_dp():
    dims = ModelDims.from_config(GPTConfig.small(), seq_len=1024,
                                 global_batch=64)
    topo = TPUTopology(num_devices=8)
    cands = search_uniform(dims, topo)
    assert cands, "no feasible strategy for GPT-2 small on 8 chips"
    best = cands[0].strategy
    # GPT-2 small fits everywhere: pure DP (no model sharding) must win
    assert best.tp == 1 and best.pp == 1, cands[0]
    assert best.dp == 8


def test_search_7b_respects_memory():
    dims = _dims_7b()
    topo = TPUTopology(num_devices=8, hbm_bytes=32e9)  # constrained HBM
    cands = search_uniform(dims, topo)
    assert cands
    best = cands[0]
    assert best.cost.mem_per_device <= 32e9
    # 7B @ 32GB with Adam cannot be pure dp without zero/fsdp sharding
    s = best.strategy
    assert s.tp * s.pp > 1 or s.zero, best


def test_search_strategies_valid_and_ranked():
    dims = _dims_7b(batch=128)
    topo = TPUTopology(num_devices=16)
    cands = search_uniform(dims, topo)
    times = [c.cost.step_time for c in cands]
    assert times == sorted(times)
    for c in cands[:10]:
        c.strategy.validate(16)
        # emitted strategies roundtrip through the planner JSON interface
        assert Strategy.from_json(c.strategy.to_json()) == c.strategy


def test_more_devices_not_slower():
    dims = _dims_7b()
    t8 = search_uniform(dims, TPUTopology(num_devices=8))[0].cost.step_time
    t32 = search_uniform(dims,
                         TPUTopology(num_devices=32))[0].cost.step_time
    assert t32 < t8


def test_layerwise_dp_search():
    dims = _dims_7b()
    topo = TPUTopology(num_devices=8)
    cands = [Strategy(dp=8, zero=True, remat="full"),
             Strategy(dp=8, zero=True),
             Strategy(dp=2, tp=4, remat="full")]
    total, per_layer = search_layerwise(dims, topo, cands)
    assert per_layer is not None and len(per_layer) == dims.num_layers
    assert np.isfinite(total)


def test_long_context_prefers_cp_or_remat():
    """32k context on small HBM must engage cp and/or aggressive remat
    (BASELINE config 5 regime)."""
    dims = ModelDims.from_config(LlamaConfig.llama_13b(), seq_len=32768,
                                 global_batch=16)
    # HBM sized so the full-activation plan cannot fit: the search must
    # engage cp and/or remat (the cost model now charges remat compute,
    # so it is no longer a free default)
    topo = TPUTopology(num_devices=16, hbm_bytes=48e9)
    cands = search_uniform(dims, topo)
    assert cands, "32k-context Llama-13B has no feasible strategy"
    s = cands[0].strategy
    # some activation-memory measure must engage: cp, remat, or
    # pipeline+microbatch splitting — plain full-activation dp*tp
    # cannot fit this regime
    assert s.cp > 1 or s.remat != "none" \
        or (s.pp > 1 and s.num_microbatches > 1), cands[0]
    assert cands[0].cost.mem_per_device <= topo.hbm_bytes


def test_calibration_pipeline_cpu():
    """Calibration machinery end-to-end on CPU (tiny): fit efficiency,
    measure two strategies, ranking report well-formed."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu import optim
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.tools.galvatron.calibrate import (
        calibrate_topology, measure_strategies, predicted_times,
        validate_ranking,
    )
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    dims = ModelDims.from_config(cfg, seq_len=64, global_batch=4)
    topo = TPUTopology(num_devices=1, peak_flops=1e12)
    params = model.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (4, 64), 0, cfg.vocab_size)
    cal = calibrate_topology(model, params,
                             {"input_ids": ids, "labels": ids}, topo, dims)
    assert 0.02 <= cal.mxu_efficiency <= 0.95
    sts = [Strategy(), Strategy(remat="full")]
    measured = measure_strategies(model, optim.adamw(1e-3), sts, (4, 64),
                                  cfg.vocab_size, steps=2, warmup=1)
    assert all(t > 0 for t in measured)
    pred = predicted_times(dims, sts, cal)
    assert pred[1] > pred[0]  # remat costs compute in the model now
    rep = validate_ranking(measured, pred)
    assert set(rep) >= {"spearman_rho", "ranking_correct"}


def test_microbatch_memory_accounting():
    """Per-microbatch memory fields: more microbatches shrink the live
    activation term; the scan pipeline without remat keeps nm+pp-1
    microbatches live."""
    dims = _dims_7b()
    topo = TPUTopology(num_devices=8)
    c1 = estimate(dims, Strategy(dp=8, num_microbatches=1), topo)
    c4 = estimate(dims, Strategy(dp=8, num_microbatches=4), topo)
    assert c4.mem_act_per_microbatch < c1.mem_act_per_microbatch
    assert c1.mem_params > 0 and c1.mem_opt > 0
    pp = estimate(dims, Strategy(dp=2, pp=4, num_microbatches=4), topo)
    rem = estimate(dims, Strategy(dp=2, pp=4, num_microbatches=4,
                                  remat="full"), topo)
    # nm+pp-1 live microbatches without remat vs 1 with remat
    assert pp.mem_per_device - pp.mem_params - pp.mem_opt \
        > 3 * (rem.mem_per_device - rem.mem_params - rem.mem_opt)


def test_topology_calibrated_loads_measured_json(tmp_path):
    """TPUTopology.calibrated() is profile-first (VERDICT r3 item 4):
    measured parameters win over spec-sheet defaults, overrides win over
    both, and a measured calibration must keep search_uniform's ranking
    consistent with the recorded step times."""
    import json
    from hetu_tpu.tools.galvatron.cost_model import TPUTopology

    p = str(tmp_path / "calibration.json")
    with open(p, "w") as f:
        json.dump({"peak_flops": 197e12, "mxu_efficiency": 0.61,
                   "hbm_bytes": 16e9,
                   "measured_ms": [100.0, 120.0, 150.0],
                   "predicted_ms": [90.0, 115.0, 160.0]}, f)
    topo = TPUTopology.calibrated(8, path=p)
    assert topo.mxu_efficiency == 0.61
    assert topo.peak_flops == 197e12
    assert topo.num_devices == 8
    # explicit override beats the file
    topo2 = TPUTopology.calibrated(8, path=p, mxu_efficiency=0.5)
    assert topo2.mxu_efficiency == 0.5
    # missing file → spec defaults
    topo3 = TPUTopology.calibrated(4, path=str(tmp_path / "nope.json"))
    assert topo3.mxu_efficiency == 0.5 and topo3.num_devices == 4

    # ranked-order agreement between the file's measured/predicted pairs
    from hetu_tpu.tools.galvatron.calibrate import validate_ranking
    with open(p) as f:
        cal = json.load(f)
    r = validate_ranking(cal["measured_ms"], cal["predicted_ms"])
    assert r["ranking_correct"]


def test_v5e_peaks_agree():
    """Two tables state the chip's published figures: the package's
    (``cost_model.DEVICE_SPECS``, peak and memory size) and the
    benchmark's own (``benchmark/peaks.py``, peak and memory
    bandwidth); neither imports the other. They name the same device
    kinds and give the figure both hold, the bf16 peak, alike."""
    from benchmark.peaks import PEAKS
    from hetu_tpu.tools.galvatron.cost_model import DEVICE_SPECS

    assert set(DEVICE_SPECS) == set(PEAKS)
    for kind, spec in DEVICE_SPECS.items():
        assert spec["peak_flops"] == PEAKS[kind]["bf16_flops_per_s"], kind
    v5e = DEVICE_SPECS["TPU v5 lite"]
    assert (v5e["peak_flops"], v5e["hbm_bytes"]) == (197e12, 16e9)
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_search_uniform_rank_agrees_with_recorded_calibration():
    """When a real measured calibration exists (TPU window ran), the
    cost model must rank at least one measured strategy pair the same
    way the hardware did — the VERDICT item-4 done-criterion. Skips
    until the window fires."""
    import json
    import os
    from hetu_tpu.tools.galvatron.cost_model import (
        CALIBRATION_PATH, ModelDims, TPUTopology, estimate,
    )
    from hetu_tpu.parallel.strategy import Strategy

    if not os.path.exists(CALIBRATION_PATH):
        pytest.skip("no measured calibration yet (needs a TPU window)")
    with open(CALIBRATION_PATH) as f:
        cal = json.load(f)
    measured = cal["measured_ms"]
    strategies = [Strategy.from_json(s) for s in cal["strategies"]]
    topo = TPUTopology.calibrated(1)
    from hetu_tpu.models import GPTConfig
    dims = ModelDims.from_config(GPTConfig.small(), seq_len=1024,
                                 global_batch=8)
    est = [estimate(dims, s, topo).step_time for s in strategies]
    # at least one ordered pair must agree between model and hardware
    agree = sum(
        1 for i in range(len(est)) for j in range(len(est))
        if i != j and (est[i] < est[j]) == (measured[i] < measured[j]))
    assert agree >= 2, (est, measured)


def test_memory_model_agrees_with_compiler_truth():
    """The search's memory model, calibrated against XLA's own memory
    analysis (workloads/mem_calibrate.py — AOT, no window needed): the
    per-remat scales must load, the calibrated estimates must bracket
    the measured AOT peaks (0.4x..4x — the raw analytic model was
    5-17x OFF before calibration), and the scan-flush liveness must
    order none > selective > full at fixed shape (the pre-r4 model
    gated liveness on remat and inverted this)."""
    import json
    import os

    from hetu_tpu.tools.galvatron.cost_model import (
        MEM_CALIBRATION_PATH, ModelDims, TPUTopology, estimate,
    )

    if not os.path.exists(MEM_CALIBRATION_PATH):
        pytest.skip("no mem calibration artifact (run mem_calibrate.py)")
    with open(MEM_CALIBRATION_PATH) as f:
        cal = json.load(f)
    topo = TPUTopology.calibrated(
        8, peak_flops=197e12, hbm_bytes=int(15.75 * 2 ** 30))
    assert topo.mem_scale > 1.0       # the analytic model underestimates
    assert dict(topo.mem_scale_remat)  # per-remat refinements loaded

    cfg = GPTConfig(vocab_size=50257, max_positions=1024,
                    hidden_size=768, num_layers=12, num_heads=12)
    by_name = {
        "dp2pp4_none_b8": Strategy(dp=2, pp=4, remat="none",
                                   num_microbatches=8),
        "dp2pp4_sel": Strategy(dp=2, pp=4, remat="selective",
                               num_microbatches=8),
        "dp2pp4_full": Strategy(dp=2, pp=4, remat="full",
                                num_microbatches=8),
        "dp8_sel": Strategy(dp=8, remat="selective"),
        "dp2pp2tp2_sel": Strategy(dp=2, pp=2, tp=2, remat="selective",
                                  num_microbatches=2),
    }
    checked = 0
    for row in cal["rows"]:
        if "error" in row or row["name"] not in by_name:
            continue
        dims = ModelDims.from_config(cfg, seq_len=1024,
                                     global_batch=row["batch"])
        est = estimate(dims, by_name[row["name"]], topo).mem_per_device
        meas = row["aot_peak_bytes"]
        assert 0.4 * meas <= est <= 4.0 * meas, (row["name"], est, meas)
        checked += 1
    assert checked >= 3

    # scan-flush liveness is schedule-bound, not remat-gated
    dims16 = ModelDims.from_config(cfg, seq_len=1024, global_batch=16)
    mems = [estimate(dims16, Strategy(dp=2, pp=4, remat=r,
                                      num_microbatches=8),
                     topo).mem_per_device
            for r in ("none", "selective", "full")]
    assert mems[0] > mems[1] > mems[2]
