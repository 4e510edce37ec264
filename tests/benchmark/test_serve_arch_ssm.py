"""CPU rehearsal of ``arch: jamba`` (``benchmark/archs/jamba.py``) under
the ``serve_arch_ssm`` runner (``serve_arch_ties`` plus the reading of
the slot's state): the model and its plain token-recurrence reference
end to end at a tiny size through a manifest, a configuration and a mix
of their own (new files HERE only), with and without ``--trace``; each
planted control refused THROUGH the harness — the state kept in
``bfloat16``, ONE precision below the stated float32, among them;
every ``.ssm`` reader on the cell, and the four accepted readers of the
iteration and its lanes that list it; what ``BENCHMARK.json`` says of the cell —
by NAME, so that the next cell can be appended behind it; the
configuration against the catalog's row; the arithmetic of
``benchmark/flops_jamba.py`` against a hand count at the published
widths and of ``benchmark/ssm.py`` on synthetic records."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_jamba as fj, harness  # noqa: E402
from benchmark import ssm as readers  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402
from benchmark.runners import serve_arch  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_ssm.json")
CELL = "jamba2-3b.doc-32k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SCAN = "selective scan (nn/parallel.py, ops/selective_scan_pallas.py)"
#: the cell's own entries, which stand together in this order (since
#: PR 58 in ``BENCHMARK.json`` itself: the list has room again) ...
OWN = {
    **mc.of(["step_ssm_conv_ms", "step_ssm_scan_ms", "step_ssm_update_ms",
             "step_state_copies_ms"], ".ssm", "ms", SCAN),
    "step_mqa_attn_ms.ssm": ("ms", mc.KERNELS, mc.TOKENS),
    **mc.of(["ssm_scan_roofline_pct", "ssm_update_roofline_pct"], ".ssm",
            "%", mc.KERNELS)}
#: ... and, beside what every backlog cell needs, the folded entries
#: its program feeds: the iteration and the sampler (generic readers:
#: ``program_trace.device_ms_per_step`` / ``readers.engine_iter_ms``)
FOLDED = {**mc.KV_PEAK, **mc.ENGINE_ITER,
          **mc.of(["step_sample_ms"], ".backlogs", "ms", mc.STEP)}


def _config():
    with open(os.path.join(ROOT, "benchmark/configs/jamba2-3b.json")) as f:
        return json.load(f)


def _run(trace=False):
    return tiny_run.run_cell(MANIFEST, "tiny.doc", seed=2**31 + 55,
                             trace=trace)


@pytest.mark.parametrize("trace", [False, True])
def test_ssm_cell_end_to_end_at_tiny_size(trace):
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
    assert ref["limits"] == {"LOGIT_TOL": 0.4, "ROUTE_TOL": 0.0,
                             "NEAR_TIE_OVER_MAX": 0.0,
                             "ROUTE_SHARE_MAX": 0.0}
    assert len(ref["compared_prompt_lens"]) == 8
    # the slot's state where the last chunk and the last decoded token
    # leave it, against the recurrence's: float32 on both sides
    arch = serve_arch.load_arch("jamba")
    assert ref["state_tolerance"] == arch.STATE_TOL["float32"] == 3e-4
    assert 0 < ref["state_gap"] <= 2e-5
    assert len(ref["state_readings"]) == 1
    # the arena's two leaves over ONE attention layer and the two slot
    # leaves over 13 Mamba layers x 4 slots, float32
    assert info["arena_bytes"] == 2 * 65 * 4 * 16 * 4 \
        + 13 * 4 * 128 * (4 + 4) * 4
    assert info["arena_blocks"] == 65 and info["slots"] == 4
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes are left out, the counted ones are there
        assert set(line["metrics"]) >= {
            "setup_compile_s", "engine_iter_ms.backlogs",
            "kv_used_peak_pct"}
        assert not any("roofline" in k or k.startswith("step_")
                       and "lag" not in k for k in line["metrics"])
        assert 0 < line["metrics"]["kv_used_peak_pct"]["value"] <= 100
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


CONTROLS = {"operands": "float8_e4m3fn", "state_dtype": "bfloat16",
            "reset_every": 8, "drop_tail_every": 8, "no_inner_norms": True,
            "no_skip": True}


@pytest.mark.parametrize("control", CONTROLS)
def test_a_planted_control_is_refused_through_the_harness(control):
    """The computations the limits must refuse (``reference.CONTROL``),
    each planted in the reference's seat of a whole
    ``harness.run_cell``: the program's tokens and state are then NOT
    that computation's, and the run comes out ``correct: false`` by
    ``archs/jamba.py``'s limits. (The tiny configuration draws its
    weights at 0.3, where a token drawn at random lies 6.0 below the
    top (the published cell: 4.3), and its steps in [0.01, 1]; the
    state is reset and the tail dropped at the tiny chunk, 8; the
    operands are one precision below the cell's stated ``bfloat16``,
    the STATE one below its stated ``float32``: that one the tokens do
    not resolve, here or on the chip, and the state's reading does.)"""
    import jax.numpy as jnp
    from benchmark.reference import jamba as reference
    arch = serve_arch.load_arch("jamba")
    planted = CONTROLS[control]
    reference.CONTROL[control] = getattr(jnp, planted) \
        if isinstance(planted, str) else planted
    try:
        out = _run()
    finally:
        reference.CONTROL.clear()
    assert out["line"]["correct"] is False and out["line"]["failed"] == 0
    why, ref = " ".join(out["why_incorrect"]), out["info"]["reference"]
    if control != "no_skip":            # ``D x`` is not in the state
        assert "the slot's state lies" in why
        assert ref["state_gap"] > ref["state_tolerance"] == 3e-4
    if control != "state_dtype":        # ... which the tokens may miss
        assert "below the float32 reference's top logit" in why
        assert ref["max_logit_gap"] > arch.LOGIT_TOL


def test_every_ssm_reader_is_the_manifests_and_reads_nothing_off_chip():
    m = mc.real()
    for name in OWN:
        mod = harness.find_reader(ROOT, m, name)
        if "roofline" in name:
            assert "no VECTOR peak" in mod.__doc__ or "update" in name
    mc.silent_without_a_device(
        m, [*OWN, "step_sample_ms.backlogs", "step_decode_ms.backlogs",
            "step_prefill_ms.backlogs"], _config())


@mc.cell_needs
def the_ssm_cell(m):
    cell, config = mc.cell_of(m, CELL, config="jamba2-3b",
                              traffic="doc-fixed-32k-backlog", reduced=[])
    assert len(config["why"]) <= 200
    assert config["file"] == "benchmark/configs/jamba2-3b.json"
    assert config["source"] == _config()["source"]
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_ssm" and mix["schedule_seed"] == 55
    assert mix["arrivals"] == {"process": "backlog", "count": 240}
    assert mix["drain_s"] == 0 and mix["ramp_s"] in (40, 50)
    assert mix["prompt_len"] == {"dist": "fixed", "value": 32768,
                                 "min": 32768, "max": 32768}
    assert mix["output_len"]["value"] == 256
    assert (mix["reference_requests"], mix["reference_longest"]) == (2, 0)
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


def test_benchmark_json_names_what_the_ssm_cell_needs():
    """By name, not by place: a later PR appends behind these. The
    seven ``.ssm`` readers are entries of ``BENCHMARK.json`` since PR
    58 (PR 55 had to leave them as files: the list was full)."""
    the_ssm_cell(mc.real())


def test_the_rehearsals_manifest_is_the_real_files_subset():
    """What ``manifest_ssm.json`` rehearses on the CPU is what
    ``BENCHMARK.json`` reads on the chip: every per-layer entry there
    is an entry here, to the letter but for the cell it lists."""
    m = mc.real()
    mine = harness.load_manifest(MANIFEST)
    assert {x["name"] for x in mine["per_layer"]} >= set(OWN) | set(FOLDED)
    for x in mine["per_layer"]:
        assert x["workloads"] == ["tiny.doc"]
        assert dict(mc.entry(m, x["name"]), workloads=None) == \
            dict(x, workloads=None)
        assert mc.lists(m, x["name"], CELL)


def test_published_widths_are_in_the_jamba_configuration():
    c = _config()
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(x) for x in f if '"AI21-Jamba2-3B"' in x]
    for row in rows:                # every key of the catalog's config
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v, k
    assert c["reduced"] == []
    assert (c["num_hidden_layers"], c["hidden_size"],
            c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["vocab_size"],
            c["tie_word_embeddings"], c["mamba_d_state"],
            c["mamba_d_conv"], c["mamba_dt_rank"], c["mamba_expand"],
            c["attn_layer_period"], c["attn_layer_offset"],
            c["num_experts"], c["max_position_embeddings"]) == (
        28, 2560, 8192, 20, 1, 65536, True, 16, 4, 160, 2, 14, 7, 1,
        262144)
    s, z = c["serve"], c["sizes"]
    assert (s["max_len"], s["prefill_chunk"], s["block_size"],
            s["slots"]) == (33024, 2048, 64, 18)
    mamba = 2560 * 10240 + (5120 * 4 + 5120) + 5120 * 192 \
        + (160 * 5120 + 5120) + 5120 * 16 + 5120 + 5120 * 2560 + 192 \
        + 3 * 2560 * 8192 + 2 * 2560
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128 + 3 * 2560 * 8192 + 2 * 2560
    assert z["mamba_layer_parameters"] == mamba == 104161472
    assert z["attention_layer_parameters"] == attn == 76682240
    assert z["parameters"] == 26 * mamba + 2 * attn + 65536 * 2560 + 2560 \
        == 3029337472
    assert z["bytes_bf16"] == 2 * z["parameters"]
    # the model's state, which is also the program's layout of it
    assert z["state_bytes_a_layer_and_slot"] == fj.state_bytes(c) == 327680
    assert z["model_tail_bytes_a_layer_and_slot"] == fj.tail_bytes(c) \
        == 61440
    assert z["tail_bytes_a_layer_and_slot"] == 81920    # four rows held
    assert z["model_slot_bytes"] == 26 * 389120 == 10117120
    assert z["slot_bytes"] == 26 * 409600 == 10649600
    assert z["cache_bytes_a_token"] == 1024
    assert z["gqa8_every_layer_cache_bytes_a_token"] == 114688
    assert z["arena_bytes"] == 18 * 33024 * 1024
    assert c["n_embd"] == 128
    # weights + caches against the chip's 16.91 GB: about two fifths
    held = z["bytes_bf16"] + z["arena_bytes"] + z["slot_leaves_bytes"]
    assert 0.35 <= held / 16.91e9 <= 0.45
    for key in ("layer_order", "head_dim", "positions", "inner_norms",
                "A_log", "dt_range", "dt_bias", "conv", "D", "weights",
                "num_experts", "n_embd"):
        assert key in c["assumed"], key


def test_flops_jamba_against_a_hand_count_at_the_published_widths():
    c = _config()
    peaks = peaks_for("TPU v5 lite")
    assert fj.channels(c) == 5120 and fj.mamba_layers(c) == 26
    scan = fj.selective_scan_call(c, 2048)
    # a token: 5120 x 16 pairs x 7 operations and dt x a channel
    assert scan["flops"] == 2048 * 5120 * (7 * 16 + 1)
    # x, dt in and y out a token (61,440 B) + B, C (128 B); A; a run's
    # state in and out
    assert scan["bytes"] == 2048 * (3 * 5120 + 32) * 4 + 327680 \
        + 2 * 327680
    # against the matrix peak the operations read a twelfth of the
    # bytes' time: the share is bound by the bytes, 0.15 ms a layer
    assert flops.roofline_seconds(scan["flops"], scan["bytes"], peaks) \
        == pytest.approx(scan["bytes"] / 819e9)
    assert 1.5e-4 < scan["bytes"] / 819e9 < 1.6e-4
    assert scan["flops"] / 197e12 < scan["bytes"] / 819e9 / 10
    upd = fj.selective_update_call(c, 16)
    assert upd["bytes"] == 16 * (2 * 327680 + 61568) + 327680
    assert upd["flops"] == 16 * 5120 * 113
    assert flops.roofline_seconds(upd["flops"], upd["bytes"], peaks) \
        == pytest.approx(upd["bytes"] / 819e9)


def test_copies_of_the_slot_leaves_and_the_rooflines(monkeypatch):
    from benchmark import longctx, program_trace
    from hetu_tpu.telemetry.device_scopes import classify
    c = _config()
    scopes = {("serving_step", 0): {
        "fusion.3": classify(
            "jit(step)/hetu.prefill_lane/hetu.ssm_scan/c"),
        "fusion.4": classify(
            "jit(step)/hetu.decode_lane/hetu.ssm_update/c"),
        "copy.7": classify("jit(step)/while/body/x"),
        "copy.10": classify(
            "jit(step)/hetu.prefill_lane/hetu.ssm_conv/gather")}}
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    monkeypatch.setattr(program_trace, "read", lambda run: {
        "host": {"steps_in_slice": 4}})
    text = "%{} = {}[{}]{{4,3,2,1,0}} copy({}[{}] %p)"

    def op(name, dtype, dims):
        return text.format(name, dtype, dims, dtype, dims)
    ops = {"copy.5": (0.040, op("copy.5", "f32", "26,18,16,40,128")),
           "copy.7": (0.020, op("copy.7", "f32", "18,4,5120")),
           "copy.8": (0.004, op("copy.8", "f32", "26,18,3,5120")),
           # the gathered tails of a pack's runs, inside the scope
           "copy.10": (1.0, op("copy.10", "f32", "18,4,5120")),
           # one slot's state; bf16; no copy
           "copy.9": (1.0, op("copy.9", "f32", "16,40,128")),
           "copy.11": (1.0, op("copy.11", "bf16", "26,18,16,40,128")),
           "fusion.3": (0.8, op("fusion.3", "f32", "26,18,16,40,128")),
           "fusion.4": (0.2, op("fusion.4", "f32", "18,5120"))}
    run = types.SimpleNamespace(config=c, peaks=peaks_for("TPU v5 lite"),
                                records={}, trace={
        "n_devices": 1, "op_seconds": {k: v[0] for k, v in ops.items()},
        "op_text": {k: v[1] for k, v in ops.items()}})
    assert readers.state_copies_ms_per_step(run) == \
        pytest.approx(1e3 * 0.064 / 4)
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
        "decode": 16.0, "prefill": 2048.0})
    m = harness.load_manifest(MANIFEST)
    got = harness.find_reader(ROOT, m, "ssm_scan_roofline_pct.ssm").read(run)
    need = 26 * fj.selective_scan_call(c, 2048)["bytes"] / 819e9
    assert got == pytest.approx(100 * need / (0.8 / 4)) and got < 100
    got = harness.find_reader(
        ROOT, m, "ssm_update_roofline_pct.ssm").read(run)
    need = 26 * fj.selective_update_call(c, 16)["bytes"] / 819e9
    assert got == pytest.approx(100 * need / (0.2 / 4)) and got < 100
    assert harness.find_reader(ROOT, m, "step_ssm_scan_ms.ssm").read(run) \
        == pytest.approx(1e3 * 0.8 / 4)
    # another configuration's run reads nothing
    other = types.SimpleNamespace(config={"model_type": "gpt2"},
                                  peaks=run.peaks, trace=run.trace,
                                  records={})
    assert readers.roofline_pct(other, "hetu.ssm_scan", None) is None
    assert readers.ms_per_step(other, "hetu.ssm_scan") is None
    assert readers.state_copies_ms_per_step(other) is None


def _tiny():
    import jax
    with open(os.path.join(HERE, "configs/jamba-tiny.json")) as f:
        config = json.load(f)
    arch = serve_arch.load_arch("jamba")
    model = arch.build(config)
    return arch, config, jax.jit(model.init)(jax.random.key(3))


def test_the_references_states_are_the_recurrences_at_those_positions():
    """``hidden_states(stops=)``: the state it keeps after position
    ``p`` of a row is the state at the END of the row cut behind ``p``
    (the recurrence is causal), for every Mamba layer; without
    ``stops`` the hidden states come alone, as before."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import jamba as reference
    arch, config, params = _tiny()
    ids = np.random.default_rng(0).integers(1, 127, 30, dtype=np.int32)
    h, kept = reference.hidden_states(params, ids, config,
                                      stops=jnp.asarray([11, 29]))
    assert kept.shape == (13, 2, 4, 128)
    np.testing.assert_array_equal(
        h, reference.hidden_states(params, ids, config))
    for j, p in enumerate((11, 29)):
        _, end = reference.hidden_states(params, ids[:p + 1], config,
                                         stops=jnp.asarray([p]))
        np.testing.assert_allclose(kept[:, j], end[:, 0], rtol=1e-4,
                                   atol=1e-4)
    assert float(jnp.abs(kept[:, 0] - kept[:, 1]).max()) > 1e-3
    lg, margin, states = arch.reference_rows(config, params, ids, 11, 19)
    np.testing.assert_allclose(states, kept, rtol=1e-4, atol=1e-4)
    assert lg.shape == (19, 128) and bool(jnp.isinf(margin).all())


def test_state_gap_judges_the_first_layers_slowest_pairs():
    """``archs/jamba.py::state_gap``: the norm of the difference over
    the norm, over ``SLOW_SHARE`` of a layer's pairs by horizon; the
    FIRST Mamba layer's is what ``STATE_TOL`` judges — another layer's,
    or the fast pairs', is reported and not judged (their reading is
    the operands' rounding, PERF.md section 6, PR 55)."""
    import numpy as np
    from benchmark.reference import jamba as reference
    arch, config, params = _tiny()
    H = np.asarray(reference.horizons(params, config))
    assert H.shape == (13, 4, 128) and H.min() > 0
    # the slowest pair: the smallest step at the first state, A = -1
    assert H.max() <= 1.0 / config["assumed"]["dt_range"][0] * 1.0001
    want = np.random.default_rng(1).normal(size=(13, 2, 4, 128))
    assert arch.state_gap(config, params, want, want)["gap"] == 0.0
    slow = H >= np.quantile(H.reshape(13, -1), 1 - arch.SLOW_SHARE,
                            axis=1)[:, None, None]
    assert abs(slow[0].mean() - arch.SLOW_SHARE) < 0.01
    got = want.copy()
    got[0, 1] += 0.02 * want[0, 1] * slow[0]    # the first layer, slow
    g = arch.state_gap(config, params, got, want)
    assert g["gap"] == pytest.approx(0.02) == g["first_layer_after_decode"]
    assert g["first_layer_after_prompt"] == 0.0
    assert g["gap"] > arch.state_tol(_config()) == 0.012 \
        > arch.state_tol(config) == 3e-4
    got = want.copy()
    got[0] += 0.5 * want[0] * ~slow[0][None]    # its fast pairs
    got[5] *= 1.5                               # another layer
    g = arch.state_gap(config, params, got, want)
    assert g["gap"] == 0.0
    assert g["slowest_by_layer"][5] == pytest.approx(0.5)
    assert g["whole_by_layer"][0] > 0.4
    # the program's leaf: channels in rows of 128 lanes
    leaf = np.arange(13 * 4 * 4 * 128, dtype=np.float32) \
        .reshape(13, 4, 4, 1, 128)
    other = np.zeros((2, 65, 4, 16), np.float32)
    np.testing.assert_array_equal(
        arch.program_states((other, other, leaf, np.zeros((13, 4, 4, 128))),
                            2), leaf[:, 2].reshape(13, 4, 128))
