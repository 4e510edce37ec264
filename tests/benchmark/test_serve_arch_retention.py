"""CPU rehearsal of ``arch: brumby`` (``benchmark/archs/brumby.py``)
under the ``serve_arch_state`` runner: the model and its plain quadratic
reference end to end at a tiny size through a manifest, a configuration
and a mix of their own (new files HERE only), with and without
``--trace``; each planted control refused THROUGH the harness; what
``BENCHMARK.json`` says of the cell — by NAME, so that the next cell can
be appended behind it; the configuration against the catalog's row; the
arithmetic of ``benchmark/flops_brumby.py`` against a hand count at the
published widths and of ``benchmark/retention.py`` on synthetic
records."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_brumby as fb, harness  # noqa: E402
from benchmark import retention as readers  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_retention.json")
CELL = "brumby-14b-pp4.repo-16k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
POWER = "power retention (nn/parallel.py, ops/retention_pallas.py)"
#: the cell's own entries, which stand together in this order ...
OWN = {
    **mc.of(["step_retention_scan_ms", "step_retention_update_ms",
             "step_state_copies_ms"], ".retention", "ms", POWER),
    **mc.of(["retention_scan_roofline_pct",
             "retention_update_roofline_pct"], ".retention", "%",
            mc.KERNELS),
    "state_slots_live_pct.retention": ("%", mc.KV, mc.TOKENS)}
#: ... and, beside what every backlog cell needs, the folded entries
#: its program feeds (no arena: no share of blocks)
FOLDED = {**mc.ENGINE_ITER,
          **mc.of(["step_sample_ms"], ".backlogs", "ms", mc.STEP)}


def _config():
    with open(os.path.join(
            ROOT, "benchmark/configs/brumby-14b-pp4.json")) as f:
        return json.load(f)


def _run(trace=False):
    return tiny_run.run_cell(MANIFEST, "tiny.repo", seed=2**31 + 51,
                             trace=trace)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_arch_state_cell_end_to_end_at_tiny_size(trace):
    out = _run(trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    info = out["info"]
    ref = info["reference"]
    assert ref["compared_positions"] > 0 and ref["route_near_ties"] == 0
    assert ref["max_logit_gap"] <= 1e-3          # float32 both sides
    assert ref["logit_tolerance"] == 0.4
    assert len(ref["compared_prompt_lens"]) == 8
    # no arena: the state of 3 layers x 4 slots, float32, and nothing else
    assert info["arena_bytes"] == 3 * 4 * 2 * 9 * 24 * 16 * 4
    assert info["arena_blocks"] == 0 and info["attn_kernel"] == "none"
    assert 0 < info["slots_live_mean"] <= 4
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes are left out, the counted ones are there
        assert set(line["metrics"]) >= {
            "setup_compile_s", "engine_iter_ms.backlogs",
            "state_slots_live_pct.retention"}
        assert not any("roofline" in k or k.startswith("step_")
                       and "lag" not in k for k in line["metrics"])
        live = line["metrics"]["state_slots_live_pct.retention"]["value"]
        assert 0 < live <= 100
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


CONTROLS = {"operands": None, "no_gate": True, "reset_every": 8,
            "diag_only": True}


@pytest.mark.parametrize("control", CONTROLS)
def test_a_planted_control_is_refused_through_the_harness(control):
    """The four computations the limit must refuse (``reference.
    CONTROL``), each planted in the reference's seat of a whole
    ``harness.run_cell``: the program's tokens are then NOT that
    computation's, and the run comes out ``correct: false`` by
    ``archs/brumby.py``'s ``LOGIT_TOL``. (The tiny configuration draws
    its weights at 0.4, where a token drawn at random lies 6.0 below
    the top as in the published cell (6.3), and its gates at 0.8 ..
    0.999; the state is reset at the tiny chunk, 8.)"""
    import jax.numpy as jnp
    from benchmark.reference import brumby as reference
    reference.CONTROL.update(
        {"operands": jnp.float8_e4m3fn} if control == "operands"
        else {control: CONTROLS[control]})
    try:
        out = _run()
    finally:
        reference.CONTROL.clear()
    assert out["line"]["correct"] is False and out["line"]["failed"] == 0
    assert "below the float32 reference's top logit" in \
        " ".join(out["why_incorrect"])
    assert out["info"]["reference"]["max_logit_gap"] > 0.4


def test_the_runner_asks_of_the_step_what_can_be_said_of_it(monkeypatch):
    """``serve_arch``'s "the paged kernel ran" is taken out, every other
    audit of its stays, and this kind's own are added."""
    from benchmark.runners import serve_arch, serve_arch_state
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry.metrics import MetricRegistry

    def fake(kernel, why):
        return lambda ctx, check: {
            "correct": not why, "why_incorrect": list(why),
            "attempted": 1, "failed": 0,
            "records": {"window": (0.0, 1.0), "kv_blocks": 0,
                        "block_size": 64},
            "info": {"attn_kernel": kernel, "slots": 4}}
    ctx = types.SimpleNamespace(on_chip=False, trace=False)
    reg = MetricRegistry()
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    rows = reg.counter("retention_rows_total", "test")
    monkeypatch.setattr(serve_arch, "run", fake(
        "none", ["decode ran 'none', not the paged kernel", "other"]))
    out = serve_arch_state.run(ctx)
    assert out["why_incorrect"] == [
        "other", "the retention kernels advanced no prefill row",
        "the retention kernels advanced no decode row"]
    rows.inc(5, lane="prefill")
    rows.inc(5, lane="decode")
    monkeypatch.setattr(serve_arch, "run", fake(
        "none", ["decode ran 'none', not the paged kernel"]))
    out = serve_arch_state.run(ctx)
    assert out["correct"] and not out["why_incorrect"]
    # the slots as the unit the cache is handed out in
    rec = out["records"]
    assert (rec["kv_blocks"], rec["block_size"], rec["arena_blocks"],
            rec["slots"]) == (4, 1, 0, 4)
    monkeypatch.setattr(serve_arch, "run", fake("paged", []))
    out = serve_arch_state.run(ctx)
    assert not out["correct"] and "keeps token" in out["why_incorrect"][0]


@mc.cell_needs
def the_retention_cell(m):
    cell, config = mc.cell_of(m, CELL, config="brumby-14b-pp4",
                              traffic="repo-fixed-16k-backlog",
                              reduced=["num_hidden_layers"])
    assert config["file"] == "benchmark/configs/brumby-14b-pp4.json"
    assert config["source"] == _config()["source"]
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_state" and mix["schedule_seed"] == 51
    assert mix["arrivals"] == {"process": "backlog", "count": 320}
    assert mix["drain_s"] == 0 and mix["ramp_s"] >= 40
    assert mix["prompt_len"] == {"dist": "fixed", "value": 16384,
                                 "min": 16384, "max": 16384}
    slots = _config()["serve"]["slots"]
    assert 12 <= slots <= 14
    assert mix["output_len"]["value"] == 8 * (slots - 2)
    assert (mix["reference_requests"], mix["reference_longest"]) == (2, 0)
    # the lane stays full: slots x chunks >= chunks + outputs
    chunks = 16384 // _config()["serve"]["prefill_chunk"]
    assert slots * chunks >= chunks + mix["output_len"]["value"]
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, FOLDED, mirrored_in=MANIFEST)
    mc.needs(m, CELL, OWN, mirrored_in=MANIFEST,
             sources=("device_trace", "host_clock"))
    mc.stand_together(m, list(OWN))


def test_benchmark_json_names_what_the_retention_cell_needs():
    """By name, not by place: a later PR appends behind these."""
    the_retention_cell(mc.real())


@mc.cell_needs
def the_retention_cell_lists_nothing_its_program_lacks(m):
    """An engine without an arena and a model without experts or latent
    rows: no share of blocks, no arena time, no expert or latent
    reader lists the cell — a folded entry is lent to the cells whose
    program has the scope or counter, not to all."""
    for name in ("kv_used_peak_pct", "step_kv_arena_ms.backlogs",
                 "step_moe_experts_ms.backlogs",
                 "step_moe_shared_ms.backlogs", "step_moe_route_ms.backlogs",
                 "moe_local_imbalance.backlogs",
                 "moe_experts_roofline_pct.backlogs",
                 "mla_decode_roofline_pct.backlogs"):
        assert not mc.lists(m, name, CELL), name


def test_the_retention_cell_is_listed_by_nothing_its_program_lacks():
    the_retention_cell_lists_nothing_its_program_lacks(mc.real())


def test_published_widths_are_in_the_brumby_configuration():
    c = _config()
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(x) for x in f if '"Brumby-14B-Base"' in x]
    for row in rows:                # every key of the catalog's config
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v or k in c["reduced"], k
            assert c["published"].get(k, v) == v, k
    assert c["reduced"] == ["num_hidden_layers"]
    assert (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"], c["rope_theta"],
            c["max_position_embeddings"], c["tie_word_embeddings"]) == (
        5120, 17408, 40, 8, 128, 151936, 1000000, 32768, False)
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) \
        == (10, 40)
    s, z = c["serve"], c["sizes"]
    assert (s["max_len"], s["kv_blocks"], s["prefill_chunk"]) == \
        (16512, 0, 2048)
    assert s["max_len"] >= 16384 + 96
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408 + 5120 * 8
    assert z["layer_parameters"] == layer == 330342400
    assert z["held_parameters"] == 10 * layer + 2 * 151936 * 5120
    assert z["held_bytes_bf16"] == 2 * z["held_parameters"] == 9718497280
    assert c["published"]["parameters"] == 40 * layer + 2 * 151936 * 5120
    # the model's state, and the program's layout of it
    assert z["model_state_bytes_a_layer_and_slot"] == fb.state_bytes(c) \
        == 8 * 8256 * 129 * 4
    assert z["state_bytes_a_layer_and_slot"] == 8 * 65 * 136 * 128 * 4
    assert z["state_bytes_a_layer_and_slot"] < 1.07 * fb.state_bytes(c)
    assert c["n_embd"] * 4 == z["state_bytes_a_layer_and_slot"]
    assert z["state_bytes"] == s["slots"] * z["state_bytes_a_slot"]
    assert z["cache_bytes_a_token"] == 0
    # weights + state against the chip's 16.91 GB
    assert 0.85 <= (z["held_bytes_bf16"] + z["state_bytes"]) / 16.91e9 \
        <= 0.90
    for key in ("degree", "gate", "normaliser", "retention_eps",
                "power_scale", "rope", "qk_norm", "gate_means",
                "gate_means_why"):
        assert key in c["assumed"], key


def test_flops_brumby_against_a_hand_count_at_the_published_widths():
    c = _config()
    peaks = peaks_for("TPU v5 lite")
    assert fb.features(c) == 8256
    assert fb.state_bytes(c) == 34080768
    scan = fb.retention_scan_call(c, 2048)
    # a token: 48 heads' 2 x 8,256 x 129 (the read a query head, the
    # write a kv head) = 102.2 M, and 40 heads' 256 x 257 of the
    # quadratic form
    assert scan["flops"] == 2048 * (48 * 2 * 8256 * 129
                                    + 40 * 256 * 257)
    assert abs(48 * 2 * 8256 * 129 - 102.2e6) < 1e5
    assert scan["bytes"] == 2048 * (56 * 128 * 2 + 8 * 4 + 40 * 128 * 4) \
        + 2 * 34080768
    # the scan is bound by its operations: 1.1 ms a layer at the peak
    assert flops.roofline_seconds(scan["flops"], scan["bytes"], peaks) \
        == pytest.approx(scan["flops"] / 197e12)
    assert 1.0e-3 < scan["flops"] / 197e12 < 1.2e-3
    upd = fb.retention_update_call(c, 12)
    assert upd["bytes"] == 2 * 12 * 34080768
    assert upd["flops"] == 12 * (3 * 8 + 2 * 40) * 8256 * 129
    # the update is bound by its bytes: 1.0 ms a layer at 12 rows
    assert flops.roofline_seconds(upd["flops"], upd["bytes"], peaks) \
        == pytest.approx(upd["bytes"] / 819e9)
    run = types.SimpleNamespace(config=c, peaks=peaks, trace=None,
                                cell={"name": "none"}, records={})
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    mc.silent_without_a_device(
        m, [*FOLDED, *OWN, "step_decode_ms.backlogs",
            "step_prefill_ms.backlogs"], c)


def test_copies_of_the_state_leaf_and_the_live_slots(monkeypatch):
    from benchmark import longctx, program_trace
    from hetu_tpu.telemetry.device_scopes import classify
    c = _config()
    scopes = {("serving_step", 0): {
        "fusion.3": classify(
            "jit(step)/hetu.prefill_lane/hetu.retention_scan/c"),
        "fusion.4": classify(
            "jit(step)/hetu.decode_lane/hetu.retention_update/c"),
        "copy.7": classify("jit(step)/while/body/x")}}
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    monkeypatch.setattr(program_trace, "read", lambda run: {
        "host": {"steps_in_slice": 4}})
    text = "%{} = {}[{}]{{5,4,3,2,1,0}} copy({}[{}] %p)"

    def op(name, dtype, dims):
        return text.format(name, dtype, dims, dtype, dims)
    ops = {"copy.5": (0.040, op("copy.5", "f32", "10,14,8,65,136,128")),
           "copy.7": (0.020, op("copy.7", "f32", "14,8,65,136,128")),
           # one slot's state; bf16; no copy
           "copy.9": (1.0, op("copy.9", "f32", "8,65,136,128")),
           "copy.11": (1.0, op("copy.11", "bf16", "10,14,8,65,136,128")),
           "fusion.3": (0.8, op("fusion.3", "f32", "10,14,8,65,136,128")),
           "fusion.4": (0.2, op("fusion.4", "f32", "14,40,128"))}
    run = types.SimpleNamespace(config=c, peaks=peaks_for("TPU v5 lite"),
                                records={}, trace={
        "n_devices": 1, "op_seconds": {k: v[0] for k, v in ops.items()},
        "op_text": {k: v[1] for k, v in ops.items()}})
    assert readers.state_copies_ms_per_step(run) == \
        pytest.approx(1e3 * 0.060 / 4)
    # in place: the scopes are there and no copy is
    run.trace["op_seconds"] = {"fusion.3": 0.8, "fusion.4": 0.2}
    assert readers.state_copies_ms_per_step(run) == 0.0
    # a program without the scopes (an older commit) reads nothing
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: {
        ("serving_step", 0): {"copy.7": classify("jit(step)/x")}})
    assert readers.state_copies_ms_per_step(run) is None
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    # the rooflines: what an iteration needs over what it took
    monkeypatch.setattr(longctx, "window_units", lambda run: {
        "decode": 12.0, "prefill": 2048.0})
    got = readers.roofline_pct(
        run, "hetu.retention_scan",
        lambda cfg, u: {k: 10 * v for k, v in fb.retention_scan_call(
            cfg, u["prefill"]).items()})
    need = 10 * fb.retention_scan_call(c, 2048)["flops"] / 197e12
    assert got == pytest.approx(100 * need / (0.8 / 4))
    got = readers.roofline_pct(
        run, "hetu.retention_update",
        lambda cfg, u: {k: 10 * v for k, v in fb.retention_update_call(
            cfg, u["decode"]).items()})
    need = 10 * 2 * 12 * 34080768 / 819e9
    assert got == pytest.approx(100 * need / (0.2 / 4)) and got < 100
    # another configuration's run reads nothing
    other = types.SimpleNamespace(config={"model_type": "gpt2"},
                                  peaks=run.peaks, trace=run.trace,
                                  records={})
    assert readers.roofline_pct(other, "hetu.retention_scan", None) is None
    # the live slots: the mean of the window's samples over the slots
    run.records = {"slots_live": [14, 13, 14, 14], "slots": 14}
    assert readers.slots_live_pct(run) == pytest.approx(100 * 55 / 56)
    run.records = {}
    assert readers.slots_live_pct(run) is None
