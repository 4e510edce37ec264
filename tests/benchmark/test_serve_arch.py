"""CPU rehearsal of the ``serve_arch`` runner kind
(``benchmark/runners/serve_arch.py``): a model and its reference found
by name (``benchmark/archs/cohere2_moe.py``) end to end at a tiny size
through a manifest, a configuration and a mix of their own (new files
HERE only), with and without ``--trace``, and the arithmetic of the
per-layer metrics the kind brings."""

import json
import os
import sys
import time
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops_cohere2_moe, harness  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402

CELL = "command-a-plus-ep8.mixed-backlog"
MANIFEST = os.path.join(HERE, "manifest_arch.json")
COUNTED = {"moe_local_imbalance.backlogs", "kv_window_dead_pct.mixed",
           "engine_host_ms.backlogs", "setup_compile_s",
           "engine_iter_ms.backlogs", "kv_used_peak_pct"}


def _run(*, trace):
    import jax
    return harness.run_cell(
        harness.load_manifest(MANIFEST), ROOT, "tiny.mixed",
        seed=2**31 + 26, seconds=1.5, trace=trace, devices=jax.devices(),
        on_chip=False, t_process=time.perf_counter())


@pytest.mark.parametrize("trace", [False, True])
def test_serve_arch_cell_end_to_end_at_tiny_size(trace):
    out = _run(trace=trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = out["info"]["reference"]
    # prompts up to 24 and outputs up to 24 against a window of 8: the
    # comparison reaches beyond the window, and the two longest
    # finished requests are among the compared ones
    assert ref["compared_positions"] > 0
    assert ref["compared_beyond_window"] > 0
    assert ref["max_logit_gap"] <= 1e-3     # float32 on both sides
    assert out["info"]["moe_in_window"]["moe_local_calls_total"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes or kernels are left out, the counted ones are there
        assert set(line["metrics"]) == COUNTED
        assert line["metrics"]["moe_local_imbalance.backlogs"]["value"] >= 1
        assert 0 < line["metrics"]["kv_used_peak_pct"]["value"] <= 100
        assert line["metrics"]["engine_iter_ms.backlogs"]["value"] > 0
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


#: beside what every backlog cell needs: the folded entries this
#: cell's program feeds, and the entries of its own (bodies no other
#: cell shares)
MIXED = {
    **mc.KV_PEAK, **mc.ENGINE_ITER,
    **mc.of(["engine_host_ms"], ".backlogs", "ms", mc.STEP),
    **mc.of(["step_moe_experts_ms", "step_moe_shared_ms",
             "step_moe_route_ms"], ".backlogs", "ms", mc.MOE),
    "moe_local_imbalance.backlogs": ("x", mc.MOE, mc.TOKENS),
    "moe_experts_roofline_pct.mixed": ("%", mc.MOE, mc.TOKENS),
    "paged_decode_roofline_pct.mixed": ("%", mc.KERNELS, mc.TOKENS),
    "kv_window_dead_pct.mixed": ("%", mc.KV, mc.TOKENS)}


@mc.cell_needs
def the_mixed_cell(m):
    cell, _ = mc.cell_of(
        m, CELL, config="command-a-plus-ep8",
        traffic="mixed-len-backlog-8k",
        reduced=["num_hidden_layers", "num_experts", "vocab_size"])
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch"
    assert mix["arrivals"] == {"process": "backlog", "count": 600}
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"],
            mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == \
        (2048, 0.9, 128, 7168)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"],
            mix["output_len"]["min"], mix["output_len"]["max"]) == \
        (192, 0.6, 32, 768)
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, MIXED, mirrored_in=MANIFEST)
    mc.stand_together(m, ["moe_experts_roofline_pct.mixed",
                          "paged_decode_roofline_pct.mixed"])


def test_benchmark_json_names_what_the_mixed_cell_needs():
    """BENCHMARK.json's cell finds its files by name and every metric
    it needs lists it, with a reader whose constants agree."""
    the_mixed_cell(mc.real())


def test_published_widths_are_in_the_configuration():
    with open(os.path.join(
            ROOT, "benchmark/configs/command-a-plus-ep8.json")) as f:
        c = json.load(f)
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["intermediate_size"], c["num_experts_per_tok"],
            c["num_shared_experts"], c["sliding_window"],
            c["rope_theta"]) == (4096, 128, 8, 128, 4096, 8, 4, 4096,
                                 50000)
    assert c["published"]["num_experts"] == 128       # the router
    assert set(c["reduced"]) == {"num_hidden_layers", "num_experts",
                                 "vocab_size"}
    # the cut keeps to the guide's floors: a whole period, >= 8
    # experts, >= an eighth of the vocabulary
    assert c["layer_types"][:c["num_hidden_layers"]] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert c["num_experts"] >= 8
    assert c["vocab_size"] * 8 >= c["published"]["vocab_size"]
    # parameters held here, in bf16, against the chip's 16.9 GB
    from benchmark.runners.serve_arch import load_arch
    import jax
    model = load_arch(c["arch"]).build(c)
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    assert abs(n - 4.733e9) < 2e6


def _run_of(config, records, peaks=None):
    return types.SimpleNamespace(config=config, records=records,
                                 peaks=peaks, trace=None)


def test_new_metrics_arithmetic():
    cfg = {"hidden_size": 4096, "intermediate_size": 4096,
           "num_hidden_layers": 4, "num_attention_heads": 128,
           "num_key_value_heads": 8, "head_dim": 128,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    # one expert is 3 x 4096 x 4096 bf16 = 100.7 MB; 6 x 4096^2
    # operations per local (token, choice) pair
    assert flops_cohere2_moe.expert_bytes(cfg) == 100663296
    call = flops_cohere2_moe.moe_experts_call(cfg, assignments=48,
                                              experts_touched=15)
    assert call["bytes"] == 15 * 100663296
    assert call["flops"] == 6 * 4096 * 4096 * 48
    # decode is bound by reading the weights: 1.51 GB / 819 GB/s
    from benchmark import flops
    need = flops.roofline_seconds(call["flops"], call["bytes"],
                                  peaks_for("TPU v5 lite"))
    assert need == pytest.approx(15 * 100663296 / 819e9)
    # paged decode: 3 window layers read the capped pages, 1 full
    # layer all of them; K and V rows of 1024 bf16
    p = flops_cohere2_moe.paged_decode_call(cfg, pages_full=400,
                                            pages_window=260,
                                            block_size=64)
    tokens = (3 * 260 + 400) / 4 * 64
    assert p["bytes"] == 2 * tokens * 1024 * 2
    assert p["flops"] == 4 * tokens * 128 * 128
    # imbalance: busiest over mean
    imb = harness.find_reader(ROOT, m, "moe_local_imbalance.backlogs")
    assert imb.read(_run_of(cfg, {"moe": {"per_expert": [10, 30, 20,
                                                        20]}})) == 1.5
    assert imb.read(_run_of(cfg, {})) is None
    # dead share: 3 of 4 layers have a window
    dead = harness.find_reader(ROOT, m, "kv_window_dead_pct.mixed")
    got = dead.read(_run_of(cfg, {"window_dead_blocks": [100, 300],
                                  "kv_blocks_in_use": [1000, 1000]}))
    assert got == pytest.approx(100 * 0.75 * 400 / 2000)
    assert dead.read(_run_of(cfg, {})) is None
    # the iteration: the window over the counter's increase
    it = harness.find_reader(ROOT, m, "engine_iter_ms.backlogs")
    assert it.read(_run_of(cfg, {"engine_iterations": 190,
                                 "engine_iterations_s": 45.0})) == \
        pytest.approx(45e3 / 190)
    assert it.read(_run_of(cfg, {})) is None
    # readers of device scopes return nothing without a device plane
    for name in ("step_moe_experts_ms.backlogs",
                 "step_moe_route_ms.backlogs",
                 "step_moe_shared_ms.backlogs",
                 "moe_experts_roofline_pct.mixed",
                 "paged_decode_roofline_pct.mixed"):
        run = _run_of(cfg, {"live_pages": [1], "live_pages_window": [1],
                            "block_size": 64, "moe": {
                                "moe_local_calls_total": 1,
                                "moe_local_assignments_total": 1,
                                "moe_local_experts_touched_total": 1}},
                      peaks=peaks_for("TPU v5 lite"))
        run.cell = {"name": "none"}
        assert harness.find_reader(ROOT, m, name).read(run) is None
