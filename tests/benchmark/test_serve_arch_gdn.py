"""CPU rehearsal of ``arch: qwen3_next`` (``benchmark/archs/qwen3_next.py``)
under the ``serve_arch_ssm`` runner (``serve_arch_ties``' comparison of
the emitted tokens, then the reading of the slot's state): the model and
its plain token-recurrence reference end to end at a tiny size through a
manifest, a configuration and a mix of their own (new files HERE only),
with and without ``--trace``; each planted control refused THROUGH the
harness; every ``.gdn`` reader and the folded entries the cell lists —
what ``BENCHMARK.json`` says of the cell by NAME, so that the next cell
can be appended behind it; the configuration against the catalog's row;
the arithmetic of ``benchmark/flops_qwen3_next.py`` against a hand count
at the published widths and of ``benchmark/gdn.py`` on synthetic
records."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_mla_moe, harness  # noqa: E402
from benchmark import flops_qwen3_next as fq  # noqa: E402
from benchmark import gdn as readers  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402
from benchmark.runners import serve_arch  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_gdn.json")
CELL = "qwen3-next-80b-a3b-ep8.rag-32k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
GDN = "Gated DeltaNet (nn/parallel.py, ops/kda.py)"
#: the cell's own entries, which stand together in this order ...
OWN = {
    **mc.of(["step_gdn_conv_ms", "step_gdn_scan_ms", "step_gdn_update_ms",
             "step_state_copies_ms"], ".gdn", "ms", GDN),
    "step_gated_attn_ms.gdn": ("ms", "gated attention (nn/parallel.py)",
                               mc.TOKENS),
    **mc.of(["gdn_scan_roofline_pct", "gdn_update_roofline_pct",
             "paged_decode_roofline_pct"], ".gdn", "%", mc.KERNELS)}
#: ... and, beside what every backlog cell needs, the folded entries
#: its program feeds: an arena, the sampler, an ``ExpertShareMoE``
#: beside a shared expert whose widths ``flops_mla_moe`` reads by the
#: same keys
FOLDED = {
    **mc.KV_PEAK, **mc.ENGINE_ITER,
    **mc.of(["step_sample_ms", "engine_host_ms"], ".backlogs", "ms",
            mc.STEP),
    **mc.of(["step_kv_arena_ms"], ".backlogs", "ms", mc.KV),
    **mc.of(["step_moe_experts_ms", "step_moe_shared_ms",
             "step_moe_route_ms"], ".backlogs", "ms", mc.MOE),
    "moe_local_imbalance.backlogs": ("x", mc.MOE, mc.TOKENS),
    "moe_experts_roofline_pct.backlogs": ("%", mc.MOE, mc.TOKENS)}


def _config():
    with open(os.path.join(
            ROOT, "benchmark/configs/qwen3-next-80b-a3b-ep8.json")) as f:
        return json.load(f)


def _run(trace=False):
    return tiny_run.run_cell(MANIFEST, "tiny.rag", seed=2**31 + 59,
                             trace=trace)


@pytest.mark.parametrize("trace", [False, True])
def test_gdn_cell_end_to_end_at_tiny_size(trace):
    out = _run(trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    info = out["info"]
    ref = info["reference"]
    arch = serve_arch.load_arch("qwen3_next")
    # every position is a routing near-tie by ROUTE_TOL, judged by the
    # share over LOGIT_TOL; float32 both sides: a flip or two at most
    assert ref["compared_positions"] == ref["route_near_ties"] > 0
    assert ref["near_ties_over_share"] <= arch.NEAR_TIE_OVER_MAX
    assert ref["limits"] == {
        "LOGIT_TOL": arch.LOGIT_TOL, "ROUTE_TOL": 1.0,
        "NEAR_TIE_OVER_MAX": arch.NEAR_TIE_OVER_MAX, "ROUTE_SHARE_MAX": 1.0}
    assert len(ref["compared_prompt_lens"]) == 8
    # the slot's state where the last chunk and the last decoded token
    # leave it, against the recurrence's: float32 on both sides
    assert ref["state_tolerance"] == arch.STATE_TOL["float32"]
    assert 0 < ref["state_gap"] <= 2e-5
    assert len(ref["state_readings"]) == 1
    # k and v over 2 attention layers of 2 x 32, the states of 6 Gated
    # DeltaNet layers x 4 slots x 8 heads x 16 x 16 and their tails of
    # 3 x 256, float32
    assert info["arena_bytes"] == 2 * 2 * 65 * 4 * 64 * 4 \
        + 6 * 4 * (8 * 16 * 16 + 3 * 256) * 4
    assert info["arena_blocks"] == 65 and info["slots"] == 4
    # a quarter of the router's experts held: some pairs land here
    assert info["moe_in_window"]["moe_local_calls_total"] > 0
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        assert set(line["metrics"]) >= {
            "setup_compile_s", "engine_iter_ms.backlogs",
            "kv_used_peak_pct", "moe_local_imbalance.backlogs"}
        assert not any("roofline" in k or k.startswith("step_")
                       and "lag" not in k for k in line["metrics"])
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


#: Five of the reference's ten controls go through a whole run each
#: (half a minute a run): one of each kind of refusal. The others move
#: the reference in ``tests/test_qwen3_next.py`` and are refused on the
#: chip (``workloads/qwen3_next_controls.py``; ``no_shared_gate`` there
#: alone: at this size a shared expert twice as loud moves a logit by
#: 0.15 at the most and the tokens by less)
CONTROLS = {"operands": "float8_e4m3fn", "state_dtype": "bfloat16",
            "tile_key_heads": True, "plain_gain": True,
            "sigmoid_router": True}
#: controls that do not touch the FIRST Gated DeltaNet layer's state
#: (the attention layers', the experts': the tokens refuse them) ...
NOT_IN_THE_STATE = ("sigmoid_router",)
#: ... and those the tokens may miss and the state's reading does not:
#: the state a precision below, and gains of ``w`` for ``1 + w``, under
#: which the reference's logits are nearly flat (every token within 0.14
#: of the top) while its states lie 50 norms away
STATE_ALONE = ("state_dtype", "plain_gain")


@pytest.mark.parametrize("control", CONTROLS)
def test_a_planted_control_is_refused_through_the_harness(control):
    """The computations the limits must refuse (``reference.CONTROL``),
    each planted in the reference's seat of a whole
    ``harness.run_cell``: the program's tokens and state are then NOT
    that computation's, and the run comes out ``correct: false`` by
    ``archs/qwen3_next.py``'s limits — by the tokens' share over
    ``LOGIT_TOL``, by the state's gap, or both. (The STATE one
    precision below its stated float32 is what the tokens may miss and
    the state's reading does not.)"""
    import jax.numpy as jnp
    from benchmark.reference import qwen3_next as reference
    planted = CONTROLS[control]
    reference.CONTROL[control] = getattr(jnp, planted) \
        if isinstance(planted, str) else planted
    try:
        out = _run()
    finally:
        reference.CONTROL.clear()
    assert out["line"]["correct"] is False and out["line"]["failed"] == 0
    why, ref = " ".join(out["why_incorrect"]), out["info"]["reference"]
    if control in NOT_IN_THE_STATE:
        assert ref["state_gap"] <= ref["state_tolerance"]
    else:
        assert "the slot's state lies" in why
        assert ref["state_gap"] > ref["state_tolerance"]
    if control not in STATE_ALONE:
        assert "routing near-ties lie more than" in why
        assert ref["near_ties_over_share"] > ref["limits"][
            "NEAR_TIE_OVER_MAX"]


def test_every_gdn_reader_is_the_manifests_and_reads_nothing_off_chip():
    m = mc.real()
    for name in OWN:
        harness.find_reader(ROOT, m, name)
    mc.silent_without_a_device(
        m, [*OWN, "step_sample_ms.backlogs", "step_decode_ms.backlogs",
            "step_prefill_ms.backlogs", "step_moe_experts_ms.backlogs",
            "moe_experts_roofline_pct.backlogs"], _config())


@mc.cell_needs
def the_gdn_cell(m):
    cell, config = mc.cell_of(
        m, CELL, config="qwen3-next-80b-a3b-ep8",
        traffic="rag-fixed-32k-backlog",
        reduced=["num_hidden_layers", "num_experts", "vocab_size"])
    assert len(config["why"]) <= 200
    assert config["file"] == "benchmark/configs/qwen3-next-80b-a3b-ep8.json"
    assert config["source"] == _config()["source"]
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_ssm" and mix["schedule_seed"] == 59
    assert mix["arrivals"] == {"process": "backlog", "count": 240}
    assert mix["drain_s"] == 0 and mix["ramp_s"] >= 40
    assert mix["prompt_len"] == {"dist": "fixed", "value": 32768,
                                 "min": 32768, "max": 32768}
    assert mix["output_len"]["value"] == 256
    assert mix["reference_requests"] in (1, 2)
    assert mix["reference_longest"] == 0
    serve = _config()["serve"]
    assert serve["slots"] == 18
    # the lane stays full: slots x chunks >= chunks + outputs
    chunks = 32768 // serve["prefill_chunk"]
    assert serve["slots"] * chunks >= chunks + mix["output_len"]["value"]
    assert serve["max_len"] >= 32768 + 256
    assert serve["kv_blocks"] >= serve["slots"] * (
        serve["max_len"] // serve["block_size"])
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, FOLDED, mirrored_in=MANIFEST)
    mc.needs(m, CELL, OWN, mirrored_in=MANIFEST, sources=("device_trace",))
    mc.stand_together(m, list(OWN))


def test_benchmark_json_names_what_the_gdn_cell_needs():
    """By name, not by place: a later PR appends behind these."""
    the_gdn_cell(mc.real())


def test_the_rehearsals_manifest_is_the_real_files_subset():
    """What ``manifest_gdn.json`` rehearses on the CPU is what
    ``BENCHMARK.json`` reads on the chip: every per-layer entry there
    is an entry here, to the letter but for the cell it lists."""
    m = mc.real()
    mine = harness.load_manifest(MANIFEST)
    assert {x["name"] for x in mine["per_layer"]} >= set(OWN) | set(FOLDED)
    for x in mine["per_layer"]:
        assert x["workloads"] == ["tiny.rag"]
        assert dict(mc.entry(m, x["name"]), workloads=None) == \
            dict(x, workloads=None)
        assert mc.lists(m, x["name"], CELL)


def test_published_widths_are_in_the_qwen3_next_configuration():
    c = _config()
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(x) for x in f
                    if '"Qwen3-Next-80B-A3B-Instruct"' in x]
    for row in rows:    # every key of the catalog's config but the cuts
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in c["reduced"]:
                assert c[k] == v, k
            else:
                assert c["published"][k] == v, k
    assert c["reduced"] == ["num_hidden_layers", "num_experts",
                            "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (12, 64, 18992)
    # the guide's floors: whole periods, >= 8 experts, an eighth of the
    # vocabulary
    assert c["num_hidden_layers"] % c["full_attention_interval"] == 0
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["num_experts"] * 8 == c["published"]["num_experts"] == 512
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["partial_rotary_factor"],
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"], c["moe_intermediate_size"],
            c["shared_expert_intermediate_size"],
            c["num_experts_per_tok"]) == (
        2048, 256, 16, 2, 0.25, 16, 32, 128, 128, 4, 512, 512, 10)
    d = c["deployment"]
    assert d["expert_share"] == 3 and "32 chips" in d["layout"]
    for key in ("layout", "this_chip", "left_out", "what_differs"):
        assert d[key]
    s = c["serve"]
    assert (s["max_len"], s["prefill_chunk"], s["block_size"],
            s["slots"]) == (33024, 2048, 64, 18)
    # the arithmetic of ``sizes``, recounted
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048 + 32 + 32 + 128
    att = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048
    layer = ffn + 2 * 2048 + 64 * 3 * 2048 * 512
    chip = 9 * (gdn + layer) + 3 * (att + layer) + 2 * 18992 * 2048 + 2048
    assert f"{chip:,} parameters" in c["sizes"]["this_chip"]
    assert 2.92e9 < chip < 2.94e9
    assert fq.state_bytes(c) == 32 * 128 * 128 * 4
    assert fq.tail_bytes(c) == 3 * 8192 * 4
    slot = 9 * (fq.state_bytes(c) + fq.tail_bytes(c))
    assert f"{slot / 1e6:.2f} MB" in c["sizes"]["state"]
    arena = 3 * s["kv_blocks"] * 64 * 512 * 2 * 2
    assert c["n_embd"] == 512 == serve_arch.load_arch(
        "qwen3_next").arena_row_elements(c)
    # weights + caches against the chip's 16.91 GB: about three fifths
    held = 2 * chip + arena + 18 * slot
    assert 0.55 <= held / 16.91e9 <= 0.62
    for key in ("no_mtp", "zero_centered_norms", "qk_norm_gain",
                "gdn_drawn_constants", "a_range", "dt_range", "gdn_layout",
                "short_conv", "rope", "routing", "weights", "num_experts",
                "n_embd", "rope_table"):
        assert key in c["assumed"], key


def test_flops_qwen3_next_against_a_hand_count_at_the_published_widths():
    c = _config()
    peaks = peaks_for("TPU v5 lite")
    assert fq.gdn_layers(c) == 9 and fq.attention_layers(c) == 3
    assert fq.conv_channels(c) == 8192
    scan = fq.gdn_scan_call(c, 2048)
    # a token: 16 key heads' two triangular products (2 x 64 x 128
    # each), 32 value heads' solve, P U and three state products
    assert scan["flops"] == 2048 * (16 * 2 * 64 * 128
                                    + 32 * (3 * 64 * 128 + 6 * 128 * 128))
    # q, k (16 x 128) and v (32 x 128) in bf16, o in float32, one g and
    # one beta a value head; a run's state in and out
    assert scan["bytes"] == 2048 * (8192 * 2 + 4096 * 4 + 2 * 32 * 4) \
        + 2 * 2097152
    # Gated DeltaNet's own work is LESS than the per-channel rule's at
    # the same value heads (Ling's count: 32 key heads, a decay a
    # channel)
    from benchmark import flops_kda_mla_moe as fk
    ling = fk.kda_scan_call({"num_attention_heads": 32, "head_dim": 128},
                            2048)
    assert scan["flops"] < ling["flops"] and scan["bytes"] < ling["bytes"]
    upd = fq.gdn_update_call(c, 16)
    assert upd["bytes"] == 16 * 2 * 2097152
    assert upd["flops"] == 16 * 32 * 7 * 128 * 128
    assert flops.roofline_seconds(upd["flops"], upd["bytes"], peaks) \
        == pytest.approx(upd["bytes"] / 819e9)
    # the decode rows' read: 16 rows at 33k tokens, k and v of 2 x 256
    dec = fq.paged_decode_call(c, 16 * 516, 64)
    assert dec["bytes"] == 2 * 16 * 516 * 64 * 512 * 2
    assert dec["flops"] == 4 * 16 * 516 * 64 * 16 * 256
    assert 1.2e-3 < dec["bytes"] / 819e9 < 1.4e-3
    # the experts: ``flops_mla_moe`` counts this configuration's by its
    # own keys (hidden 2048, width 512): 6.3 MB an expert
    assert flops_mla_moe.expert_bytes(c) == 3 * 2048 * 512 * 2
    assert flops_mla_moe.moe_experts_call(c, 2560, 64)["flops"] == \
        6.0 * 2048 * 512 * 2560


def test_copies_of_the_slot_leaves_and_the_rooflines(monkeypatch):
    from benchmark import longctx, program_trace
    from hetu_tpu.telemetry.device_scopes import classify
    c = _config()
    scopes = {("serving_step", 0): {
        "fusion.3": classify(
            "jit(step)/hetu.prefill_lane/hetu.gdn_scan/c"),
        "fusion.4": classify(
            "jit(step)/hetu.decode_lane/hetu.gdn_update/c"),
        "fusion.5": classify(
            "jit(step)/hetu.decode_lane/hetu.gated_attn/hetu.paged_attn/c"),
        "fusion.6": classify("jit(step)/hetu.prefill_lane/hetu.gated_attn/c"),
        "copy.7": classify("jit(step)/while/body/x"),
        "copy.10": classify(
            "jit(step)/hetu.prefill_lane/hetu.gdn_conv/gather")}}
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    monkeypatch.setattr(program_trace, "read", lambda run: {
        "host": {"steps_in_slice": 4}})
    text = "%{} = {}[{}]{{4,3,2,1,0}} copy({}[{}] %p)"

    def op(name, dtype, dims):
        return text.format(name, dtype, dims, dtype, dims)
    ops = {"copy.5": (0.040, op("copy.5", "f32", "9,18,32,128,128")),
           "copy.7": (0.020, op("copy.7", "f32", "18,3,8192")),
           # the gathered tails of a pack's runs, inside the scope
           "copy.10": (1.0, op("copy.10", "f32", "18,3,8192")),
           # one slot's state; bf16; no copy
           "copy.9": (1.0, op("copy.9", "f32", "32,128,128")),
           "copy.11": (1.0, op("copy.11", "bf16", "9,18,32,128,128")),
           "fusion.3": (0.8, op("fusion.3", "f32", "9,18,32,128,128")),
           "fusion.4": (0.2, op("fusion.4", "f32", "18,4096")),
           "fusion.5": (0.1, op("fusion.5", "bf16", "18,4096")),
           "fusion.6": (0.3, op("fusion.6", "bf16", "2048,4096"))}
    run = types.SimpleNamespace(config=c, peaks=peaks_for("TPU v5 lite"),
                                records={}, trace={
        "n_devices": 1, "op_seconds": {k: v[0] for k, v in ops.items()},
        "op_text": {k: v[1] for k, v in ops.items()}})
    assert readers.state_copies_ms_per_step(run) == \
        pytest.approx(1e3 * 0.060 / 4)
    m = harness.load_manifest(MANIFEST)
    # the whole mixer: the scope ANYWHERE in the path
    assert harness.find_reader(ROOT, m, "step_gated_attn_ms.gdn").read(run) \
        == pytest.approx(1e3 * 0.4 / 4)
    # in place: the scopes are there and no copy is
    run.trace["op_seconds"] = {"fusion.3": 0.8, "fusion.4": 0.2}
    assert readers.state_copies_ms_per_step(run) == 0.0
    # a program without the scopes (the parent commit) reads nothing
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: {
        ("serving_step", 0): {"copy.7": classify("jit(step)/x")}})
    assert readers.state_copies_ms_per_step(run) is None
    assert readers.ms_per_step(run, "hetu.gdn_scan") is None
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    # the rooflines: what an iteration needs over what it took
    monkeypatch.setattr(longctx, "window_units", lambda run: {
        "decode": 16.0, "prefill": 2048.0})
    got = harness.find_reader(ROOT, m, "gdn_scan_roofline_pct.gdn").read(run)
    call = fq.gdn_scan_call(c, 2048)
    need = 9 * flops.roofline_seconds(call["flops"], call["bytes"],
                                      run.peaks)
    assert got == pytest.approx(100 * need / (0.8 / 4)) and 0 < got < 100
    got = harness.find_reader(
        ROOT, m, "gdn_update_roofline_pct.gdn").read(run)
    need = 9 * fq.gdn_update_call(c, 16)["bytes"] / 819e9
    assert got == pytest.approx(100 * need / (0.2 / 4)) and 0 < got < 100
    assert harness.find_reader(ROOT, m, "step_gdn_scan_ms.gdn").read(run) \
        == pytest.approx(1e3 * 0.8 / 4)
    # the decode call: the kernel's seconds a call under the lane
    monkeypatch.setattr(program_trace, "kernel_seconds_per_call",
                        lambda run, kernel: {
                            "hetu.decode_lane>hetu.paged_attn": 2e-3}[kernel])
    run.records = {"live_pages": [16 * 516] * 3, "block_size": 64}
    got = harness.find_reader(
        ROOT, m, "paged_decode_roofline_pct.gdn").read(run)
    need = fq.paged_decode_call(c, 16 * 516, 64)["bytes"] / 819e9
    assert got == pytest.approx(100 * need / 2e-3) and 0 < got < 100
    # another configuration's run reads nothing
    other = types.SimpleNamespace(config={"model_type": "gpt2"},
                                  peaks=run.peaks, trace=run.trace,
                                  records=run.records)
    assert readers.roofline_pct(other, "hetu.gdn_scan", None) is None
    assert readers.ms_per_step(other, "hetu.gdn_scan") is None
    assert readers.state_copies_ms_per_step(other) is None
    assert readers.paged_decode_roofline_pct(other) is None


def _tiny():
    import jax
    with open(os.path.join(HERE, "configs/qwen3-next-tiny.json")) as f:
        config = json.load(f)
    arch = serve_arch.load_arch("qwen3_next")
    model = arch.build(config)
    return arch, config, jax.jit(model.init)(jax.random.key(3))


def test_the_references_states_are_the_recurrences_at_those_positions():
    """``hidden_states(stops=)``: the state it keeps after position
    ``p`` of a row is the state at the END of the row cut behind ``p``
    (the recurrence is causal), for every Gated DeltaNet layer; the
    margins are a held expert's distance from the cut, finite and
    small."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import qwen3_next as reference
    arch, config, params = _tiny()
    ids = np.random.default_rng(0).integers(1, 127, 30, dtype=np.int32)
    h, low, kept = reference.hidden_states(
        params, ids, config, with_margins=True, stops=jnp.asarray([11, 29]))
    assert kept.shape == (6, 2, 8, 16, 16) and low.shape == (30,)
    np.testing.assert_array_equal(
        h, reference.hidden_states(params, ids, config))
    for j, p in enumerate((11, 29)):
        _, end = reference.hidden_states(params, ids[:p + 1], config,
                                         stops=jnp.asarray([p]))
        np.testing.assert_allclose(kept[:, j], end[:, 0], rtol=1e-4,
                                   atol=1e-5)
    assert float(jnp.abs(kept[:, 0] - kept[:, 1]).max()) > 1e-3
    lg, margin, states = arch.reference_rows(config, params, ids, 11, 19)
    np.testing.assert_allclose(states, kept, rtol=1e-4, atol=1e-5)
    assert lg.shape == (19, 128)
    assert bool((margin < arch.ROUTE_TOL).all())


def test_state_gap_judges_the_first_layers_slowest_heads():
    """``archs/qwen3_next.py::state_gap``: the norm of the difference
    over the norm, over ``SLOW_SHARE`` of a layer's value heads by
    horizon; the FIRST Gated DeltaNet layer's is what ``STATE_TOL``
    judges — another layer's, or the fast heads', is reported and not
    judged."""
    import numpy as np
    from benchmark.reference import qwen3_next as reference
    arch, config, params = _tiny()
    H = np.asarray(reference.horizons(params, config))
    assert H.shape == (6, 8) and H.min() > 0
    want = np.random.default_rng(1).normal(size=(6, 2, 8, 16, 16))
    assert arch.state_gap(config, params, want, want)["gap"] == 0.0
    slow = H >= np.quantile(H, 1 - arch.SLOW_SHARE, axis=1)[:, None]
    assert slow[0].sum() == 2                   # a quarter of eight heads
    got = want.copy()
    got[0, 1] += 0.02 * want[0, 1] * slow[0][:, None, None]
    g = arch.state_gap(config, params, got, want)
    assert g["gap"] == pytest.approx(0.02) == g["first_layer_after_decode"]
    assert g["first_layer_after_prompt"] == 0.0
    assert g["gap"] > arch.state_tol(_config()) > arch.state_tol(config)
    got = want.copy()
    got[0] += 0.5 * want[0] * ~slow[0][None, :, None, None]   # fast heads
    got[5] *= 1.5                                             # another layer
    g = arch.state_gap(config, params, got, want)
    assert g["gap"] == 0.0
    assert g["slowest_by_layer"][5] == pytest.approx(0.5)
    assert g["whole_by_layer"][0] > 0.4
    # the program's leaf is the one of five axes
    leaf = np.arange(6 * 4 * 8 * 16 * 16, dtype=np.float32) \
        .reshape(6, 4, 8, 16, 16)
    other = np.zeros((2, 65, 4, 64), np.float32)
    np.testing.assert_array_equal(
        arch.program_states((other, other, leaf, np.zeros((6, 4, 3, 256))),
                            2), leaf[:, 2])
