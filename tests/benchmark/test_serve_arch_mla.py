"""CPU rehearsal of the ``serve_arch_ties`` runner kind on ``arch: mla_moe``
(``benchmark/archs/mla_moe.py``): the latent-attention model and its
plain reference end to end at a tiny size through a manifest, a
configuration and a mix of their own (new files HERE only), with and
without ``--trace``; what ``BENCHMARK.json`` says of the cell; and the
arithmetic of ``benchmark/flops_mla_moe.py``."""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_mla_moe, harness  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_mla.json")
CELL = "kimi-vl-a3b-pp4.longdoc-backlog"
COUNTED = {"moe_local_imbalance.backlogs", "engine_host_ms.backlogs",
           "setup_compile_s", "kv_used_peak_pct"}


@pytest.mark.parametrize("trace", [False, True])
def test_serve_arch_mla_cell_end_to_end_at_tiny_size(trace):
    out = tiny_run.run_cell(MANIFEST, "tiny.longdoc", seed=2**31 + 30,
                            trace=trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = out["info"]["reference"]
    assert ref["compared_positions"] > 0
    assert ref["compared_beyond_window"] == 0        # no window
    assert ref["max_logit_gap"] <= 1e-3     # float32 on both sides
    assert ref["near_ties_over_logit_tol"] == 0 and ref["limits"]
    assert len(ref["compared_prompt_lens"]) == 8
    assert out["info"]["moe_in_window"]["moe_local_calls_total"] > 0
    # one leaf of 48-wide float32 rows: 4 layers x 49 blocks x 4
    assert out["info"]["arena_bytes"] == 4 * 49 * 4 * 48 * 4
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes or kernels are left out, the counted ones are there
        assert set(line["metrics"]) == COUNTED
        assert line["metrics"]["moe_local_imbalance.backlogs"]["value"] >= 1
        assert 0 < line["metrics"]["kv_used_peak_pct"]["value"] <= 100
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


#: beside what every backlog cell needs: the folded entries this
#: cell's program feeds, and the one of its own
LONGDOC = {
    **mc.KV_PEAK,
    **mc.of(["step_sample_ms", "engine_host_ms"], ".backlogs", "ms",
            mc.STEP),
    **mc.of(["step_moe_experts_ms", "step_moe_shared_ms",
             "step_moe_route_ms"], ".backlogs", "ms", mc.MOE),
    "moe_local_imbalance.backlogs": ("x", mc.MOE, mc.TOKENS),
    "moe_experts_roofline_pct.backlogs": ("%", mc.MOE, mc.TOKENS),
    "mla_decode_roofline_pct.backlogs": ("%", mc.KERNELS, mc.TOKENS),
    "step_mla_absorb_ms.longdoc": (
        "ms", "latent attention (nn/parallel.py)", mc.TOKENS)}


@mc.cell_needs
def the_longdoc_cell(m):
    cell, _ = mc.cell_of(m, CELL, config="kimi-vl-a3b-pp4",
                         traffic="longdoc-backlog-16k",
                         reduced=["num_hidden_layers"])
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_ties" and mix["schedule_seed"] == 30
    assert mix["arrivals"] == {"process": "backlog", "count": 600}
    assert (mix["ramp_s"], mix["drain_s"]) == (30, 0)
    assert [mix["prompt_len"][k] for k in
            ("dist", "median", "sigma", "min", "max")] == \
        ["lognormal", 6144, 0.7, 1024, 14336]
    assert [mix["output_len"][k] for k in
            ("dist", "median", "sigma", "min", "max")] == \
        ["lognormal", 256, 0.6, 32, 1024]
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, LONGDOC, mirrored_in=MANIFEST)


def test_benchmark_json_names_what_the_longdoc_cell_needs():
    the_longdoc_cell(mc.real())


def test_published_widths_are_in_the_kimi_configuration():
    with open(os.path.join(
            ROOT, "benchmark/configs/kimi-vl-a3b-pp4.json")) as f:
        c = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl") \
            else open(os.devnull) as f:
        rows = [json.loads(x) for x in f if "Kimi-VL-A3B-Instruct" in x]
    for row in rows:                # every key of the catalog's config
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v or k in c["reduced"], k
    assert (c["hidden_size"], c["num_attention_heads"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["kv_lora_rank"], c["q_lora_rank"], c["intermediate_size"],
            c["moe_intermediate_size"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["n_shared_experts"],
            c["routed_scaling_factor"], c["first_k_dense_replace"],
            c["rope_theta"], c["vocab_size"], c["tie_word_embeddings"]) \
        == (2048, 16, 128, 64, 128, 512, None, 11264, 1408, 64, 6, 2,
            2.446, 1, 800000, 163840, False)
    assert c["reduced"] == ["num_hidden_layers"]
    assert (c["num_hidden_layers"],
            c["published"]["num_hidden_layers"]) == (7, 27)
    assert c["stored_row"] == c["n_embd"] >= 576
    assert c["num_experts"] == c["n_routed_experts"]
    s = c["serve"]
    assert s["max_len"] == 16384 and s["max_len"] % s["block_size"] == 0
    # parameters held here, in bf16, and the arena, against the chip's
    # 16.91 GB: at least 80 % allocated
    from benchmark.runners.serve_arch import load_arch
    import jax
    arch = load_arch(c["arch"])
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(arch.build(c).init, jax.random.key(0))))
    assert abs(n - 4.263e9) < 1e6
    arena = c["num_hidden_layers"] * s["kv_blocks"] * s["block_size"] \
        * arch.arena_row_elements(c) * 2
    assert 0.80 <= (2 * n + arena) / 16.91e9 <= 0.86


def test_flops_mla_moe_arithmetic():
    cfg = {"hidden_size": 2048, "moe_intermediate_size": 1408,
           "kv_lora_rank": 512, "qk_rope_head_dim": 64,
           "num_attention_heads": 16}
    peaks = peaks_for("TPU v5 lite")
    assert flops_mla_moe.latent_row(cfg) == 576
    # one expert is 3 x 2048 x 1408 bf16 = 17.3 MB
    assert flops_mla_moe.expert_bytes(cfg) == 17301504
    call = flops_mla_moe.moe_experts_call(cfg, assignments=384,
                                          experts_touched=64)
    assert call["bytes"] == 64 * 17301504
    assert call["flops"] == 6 * 2048 * 1408 * 384
    # 64 decode rows: bound by reading the 64 experts, 1.107 GB
    assert flops.roofline_seconds(call["flops"], call["bytes"], peaks) \
        == pytest.approx(64 * 17301504 / 819e9)
    # decode attention: 7,000 pages of 64 tokens, a row read ONCE
    p = flops_mla_moe.mla_decode_call(cfg, pages=7000, block_size=64)
    assert p["bytes"] == 7000 * 64 * 576 * 2
    assert p["flops"] == 7000 * 64 * 16 * (2 * 576 + 2 * 512)
    # 34,816 operations on 1,152 bytes: 30 a byte, under the chip's
    # 240 — the call is bound by the bytes
    assert flops.roofline_seconds(p["flops"], p["bytes"], peaks) \
        == pytest.approx(p["bytes"] / 819e9)
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    run = types.SimpleNamespace(config=cfg, peaks=peaks, trace=None,
                                cell={"name": "none"}, records={
        "live_pages": [7000], "block_size": 64, "moe": {
            "per_expert": [10, 30, 20, 20],
            "moe_local_calls_total": 1, "moe_local_assignments_total": 1,
            "moe_local_experts_touched_total": 1}})
    imb = harness.find_reader(ROOT, m, "moe_local_imbalance.backlogs")
    assert imb.read(run) == 1.5
    # readers of device scopes return nothing without a device plane,
    # and the latent roofline nothing on a configuration without MLA
    for name in ("step_mla_absorb_ms.longdoc", "step_moe_experts_ms.backlogs",
                 "step_moe_route_ms.backlogs", "step_moe_shared_ms.backlogs",
                 "step_decode_ms.backlogs", "step_prefill_ms.backlogs",
                 "step_sample_ms.backlogs",
                 "moe_experts_roofline_pct.backlogs",
                 "mla_decode_roofline_pct.backlogs"):
        assert harness.find_reader(ROOT, m, name).read(run) is None
