"""CPU rehearsal of ``benchmark/program_trace.py`` and the per-layer
metrics that read the program's scopes, spans and compile events.

A manifest of its own (``manifest_program.json``: the tiny cells plus
the new metrics) runs through the unedited harness. On the CPU there is
no device plane: the host-span metrics are positive, the device-scope
ones are left out, and the information line parses. The device side's
arithmetic is held to synthetic inputs.
"""

import json
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, program_trace  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_program.json")
HOST_METRICS = {
    "tiny.pretrain": {"data_stall_ms"},
    "tiny.chat": {"engine_host_ms.chat", "setup_compile_s"},
    "tiny.backlog": {"engine_host_ms.backlogs", "setup_compile_s"},
}
DEVICE_METRICS = {
    "tiny.pretrain": {"flash_fwd_roofline_pct", "flash_bwd_roofline_pct",
                      "train_fwd_ms", "train_bwd_ms", "train_opt_ms"},
    "tiny.chat": {"paged_decode_roofline_pct.chat", "step_decode_ms.chat",
                  "step_prefill_ms.chat", "step_kv_arena_ms.chat",
                  "step_sample_ms.chat"},
    "tiny.backlog": {"paged_decode_roofline_pct.backlog",
                     "step_decode_ms.backlogs", "step_prefill_ms.backlogs",
                     "step_kv_arena_ms.backlogs"},
}


@pytest.mark.parametrize("workload", sorted(HOST_METRICS))
def test_host_span_metrics_on_the_cpu_rehearsal(workload, capsys):
    import jax
    out = harness.run_cell(
        harness.load_manifest(MANIFEST), ROOT, workload, seed=2**31 + 7,
        seconds=1.5, trace=True, devices=jax.devices(), on_chip=False,
        t_process=time.perf_counter())
    assert not out["why_incorrect"]
    got = out["line"]["metrics"]
    for name in HOST_METRICS[workload]:
        assert got[name]["value"] > 0, name
    # no device plane on the CPU: what reads a scope is left out
    assert not DEVICE_METRICS[workload] & set(got)
    # ONE information line, and it parses
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"program_trace"')]
    assert len(lines) == 1
    info = lines[0]["program_trace"]
    assert set(info) >= {"device_by_scope", "host_spans", "idle_by_span",
                         "unscoped_top", "instruction_scopes"}
    step = "train/step" if workload == "tiny.pretrain" else "serve/step"
    spans = info["host_spans"]
    assert spans[step]["n"] >= 1 and info["steps_in_slice"] > 0
    assert spans[step]["self_s"] <= spans[step]["total_s"]
    kids = ("train/next_batch", "train/dispatch", "train/loss_fetch") \
        if workload == "tiny.pretrain" else \
        ("serve/admit", "serve/pack", "serve/dispatch",
         "serve/device_wait", "serve/commit", "serve/pump")
    for k in kids:
        assert spans[k]["n"] >= spans[step]["n"], k
    if workload != "tiny.pretrain":
        stages = info["compile_before_window"]["seconds"]
        assert stages["trace"] > 0 and stages["compile"] > 0


#: the per-layer entries PR 24 brought (the program's scopes, spans and
#: compile events), under the names they have now: metric -> (unit,
#: layer, the end-to-end metric it moves); each lists every cell of the
#: rehearsal's kind that reports that metric
TRAIN_STEP = "train step (engine/train_step.py)"
PR24 = {
    "flash_fwd_roofline_pct": ("%", mc.KERNELS, "train_tokens_per_s"),
    "flash_bwd_roofline_pct": ("%", mc.KERNELS, "train_tokens_per_s"),
    "train_fwd_ms": ("ms", TRAIN_STEP, "train_tokens_per_s"),
    "train_bwd_ms": ("ms", TRAIN_STEP, "train_tokens_per_s"),
    "train_opt_ms": ("ms", TRAIN_STEP, "train_tokens_per_s"),
    "data_stall_ms": ("ms", "input pipeline (hetu_tpu/data)",
                      "train_tokens_per_s"),
    "paged_decode_roofline_pct.chat": ("%", mc.KERNELS, "gap_p95_ms"),
    "paged_decode_roofline_pct.backlog": ("%", mc.KERNELS, mc.TOKENS),
    **{f"{n}.chat": ("ms", layer, "gap_p95_ms")
       for n, layer in (("step_decode_ms", mc.STEP),
                        ("step_prefill_ms", mc.STEP),
                        ("step_sample_ms", mc.STEP),
                        ("step_kv_arena_ms", mc.KV),
                        ("engine_host_ms", mc.STEP))},
    **{f"{n}.backlogs": ("ms", layer, mc.TOKENS)
       for n, layer in (("step_decode_ms", mc.STEP),
                        ("step_prefill_ms", mc.STEP),
                        ("step_kv_arena_ms", mc.KV),
                        ("engine_host_ms", mc.STEP))},
    "setup_compile_s": ("s", mc.COMPILE, mc.SETUP)}
CELLS = {"train_tokens_per_s": "gpt2-small.pretrain",
         "gap_p95_ms": "gpt2-small.chat", mc.TOKENS: "gpt2-large.backlog",
         mc.SETUP: "gpt2-small.chat"}


@mc.cell_needs
def the_program_trace_entries(m):
    """Every per-layer entry PR 24 brought has a reader whose constants
    agree, lists the GPT-2 cell that reports the metric it moves, and is
    mirrored in the rehearsal's manifest. (It held them to a COUNT of
    ``program_span`` / ``program_counter`` labels until PR 58: later
    readers of the program then had to say ``host_clock`` or
    ``device_trace``; a label is now each entry's own to state.)"""
    for name, spec in PR24.items():
        mc.needs(m, CELLS[spec[2]], {name: spec}, mirrored_in=MANIFEST)
        assert mc.entry(m, name)["source"] in (
            "program_span", "program_counter", "device_trace")


def test_the_program_trace_entries_match_their_readers():
    the_program_trace_entries(mc.real())


def _run(records, trace, config=None):
    return types.SimpleNamespace(
        records=records, trace=trace, config=config or {"n_embd": 8},
        cell={"name": "synthetic"}, peaks=None)


def _scopes(monkeypatch, table):
    from hetu_tpu.telemetry import device_scopes
    monkeypatch.setattr(
        program_trace, "_registered_scopes",
        lambda: {k: {n: device_scopes.classify(op)
                     for n, op in v.items()} for k, v in table.items()})


def test_serve_buckets_on_synthetic_names(monkeypatch):
    lane = "jit(step)/cond/branch_1_fun/hetu.decode_lane/"
    _scopes(monkeypatch, {("serving_step", None): {
        "closed_call.35": lane + "while/body/hetu.paged_attn/k",
        "closed_call.33": "jit(step)/hetu.prefill_lane/while/body/"
                          "hetu.paged_attn/k",
        "fusion.1": lane + "while/body/dot_general",
        "scatter.2": lane + "while/body/hetu.kv_arena/scatter",
        "sort.12": lane + "hetu.sample/sort",
        "dynamic-slice_fusion.4": lane + "while/body/dynamic_slice",
        "slice_fusion.9": lane + "while/body/slice",
        "copy.283": "",
        "fusion.7": "jit(step)/hetu.prefill_lane/dot_general"}})
    text = {
        "closed_call.35": "%closed_call.35 = bf16[4,1,2,4]{3} "
                          "custom-call(%a), custom_call_target="
                          "\"tpu_custom_call\"",
        "dynamic-slice_fusion.4": "%dynamic-slice_fusion.4 = "
                                  "bf16[1,10,4,8]{3,2,1,0} fusion(%a)",
        "slice_fusion.9": "%slice_fusion.9 = f32[4,8]{1,0} fusion(%a)",
        "copy.283": "%copy.283 = bf16[3,10,4,8]{3,2,1,0:T(8,128)} "
                    "copy(%b)"}
    secs = {"closed_call.35": 0.30, "closed_call.33": 0.10,
            "fusion.1": 0.20, "scatter.2": 0.05, "sort.12": 0.04,
            "dynamic-slice_fusion.4": 0.06, "slice_fusion.9": 0.01,
            "copy.283": 0.08, "fusion.7": 0.07, "convert.1": 0.02}
    run = _run({"kv_blocks": 10, "block_size": 4},
               {"n_devices": 1, "busy_s": 0.9, "op_seconds": secs,
                "op_calls": {k: 12 for k in secs}, "op_text": text})
    dev = program_trace._device(run)
    b = dev["buckets"]
    assert b["decode"] == pytest.approx(0.30 + 0.20 + 0.01)
    assert b["prefill"] == pytest.approx(0.10 + 0.07)
    # the scope, plus what moves at least one layer's leaf of the arena
    assert b["kv_arena"] == pytest.approx(0.05 + 0.06 + 0.08)
    assert set(dev["arena_moves"]) == {"dynamic-slice_fusion.4",
                                       "copy.283"}
    assert b["sample"] == pytest.approx(0.04)
    assert b["unscoped"] == pytest.approx(0.02)
    assert sum(b.values()) == pytest.approx(sum(secs.values()))
    assert dev["unscoped_top"] == [("convert.1", 0.02)]
    assert dev["kernel_s"]["hetu.decode_lane>hetu.paged_attn"] == 0.30
    assert dev["kernel_calls"] == {
        "hetu.decode_lane>hetu.paged_attn": 12}
    assert dev["instruction_scopes"]["closed_call.35"] == \
        "hetu.paged_attn"
    # the share of device time whose instruction a registered step has
    assert dev["named_share"] == pytest.approx(1 - 0.02 / 0.93)


def test_train_buckets_and_shared_names(monkeypatch):
    fwd = "jit(step)/jvp(hetu.loss)/while/body/"
    bwd = "jit(step)/transpose(jvp(hetu.loss))/while/body/"
    _scopes(monkeypatch, {
        ("train_step", "a"): {
            "closed_call.5": fwd + "closed_call/hetu.flash_fwd/k",
            "checkpoint.20": bwd + "hetu.flash_bwd/dq",
            "checkpoint.21": "checkpoint/hetu.flash_bwd/dkv",
            "fusion.740": fwd + "dot_general",
            "fusion.9": bwd + "checkpoint/rematted_computation/tanh",
            "fusion.3": "jit(step)/hetu.opt/mul",
            "fusion.8": fwd + "add"},
        ("train_step", "b"): {"fusion.8": "jit(step)/hetu.opt/add"}})
    call = "%x = bf16[2]{0} custom-call(%a), custom_call_target=" \
           "\"tpu_custom_call\""
    secs = {"closed_call.5": 0.036, "checkpoint.20": 0.040,
            "checkpoint.21": 0.031, "fusion.740": 0.100,
            "fusion.9": 0.020, "fusion.3": 0.030, "fusion.8": 0.010}
    run = _run({}, {"n_devices": 1, "busy_s": 0.27, "op_seconds": secs,
                    "op_calls": {"closed_call.5": 12, "checkpoint.20": 12,
                                 "checkpoint.21": 12, "fusion.740": 12,
                                 "fusion.9": 12, "fusion.3": 1,
                                 "fusion.8": 1},
                    "op_text": {k: call for k in
                                ("closed_call.5", "checkpoint.20",
                                 "checkpoint.21")}})
    dev = program_trace._device(run)
    b = dev["buckets"]
    assert b["fwd"] == pytest.approx(0.036 + 0.100)
    assert b["bwd"] == pytest.approx(0.040 + 0.031 + 0.020)
    assert b["opt"] == pytest.approx(0.030)
    assert dev["recompute_s"] == pytest.approx(0.020)
    # a name two registered steps share is left unscoped, and said
    assert dev["shared_names"] == ["fusion.8"]
    assert b["unscoped"] == pytest.approx(0.010)
    run._program_trace = {"device": dev, "host": None, "compile": None}
    # one forward call a layer; dq and dk/dv summed for the backward
    assert program_trace.kernel_seconds_per_call(
        run, "hetu.flash_fwd") == pytest.approx(0.003)
    assert program_trace.kernel_seconds_per_call(
        run, "hetu.flash_bwd", kernels_per_call=2) == \
        pytest.approx(0.071 / 12)
    assert program_trace.device_ms_per_step(run, "fwd") is None


def test_roofline_readers_on_synthetic_seconds(monkeypatch):
    """The share is need/took: the v5e's peaks, the runner's records."""
    from benchmark.peaks import peaks_for
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "gpt2-small.json")))
    run = _run({"step_pairs_per_row": [400000.0], "batch_rows": 32,
                "seq_len": 1024, "live_pages": [2000, 2200],
                "block_size": 16}, {"n_devices": 1}, cfg)
    run.peaks = peaks_for("TPU v5 lite")
    took = {"hetu.flash_fwd": 3.0e-3, "hetu.flash_bwd": 5.9e-3,
            "hetu.decode_lane>hetu.paged_attn": 3.6e-3}
    monkeypatch.setattr(
        program_trace, "kernel_seconds_per_call",
        lambda run, k, kernels_per_call=1: took[k])
    fwd = harness.find_reader(ROOT, m, "flash_fwd_roofline_pct").read(run)
    bwd = harness.find_reader(ROOT, m, "flash_bwd_roofline_pct").read(run)
    # forward: 2 x 2 x 768 x 4e5 x 32 = 3.93e10 operations = 0.200 ms
    # at peak, but Q, K, V, O in bf16 = 4 x 2 x 32 x 1024 x 768 bytes =
    # 0.246 ms of HBM: the larger one is the roofline (both passes)
    tensor = 2.0 * 32 * 1024 * 768
    assert 3.93216e10 / 197e12 < 4 * tensor / 819e9
    assert fwd == pytest.approx(100 * (4 * tensor / 819e9) / 3.0e-3)
    assert bwd == pytest.approx(100 * (8 * tensor / 819e9) / 5.9e-3)
    dec = harness.find_reader(
        ROOT, m, "paged_decode_roofline_pct.chat").read(run)
    need = 2.0 * 2100 * 16 * 768 * 2 / 819e9
    assert dec == pytest.approx(100 * need / 3.6e-3)
    assert 0 < fwd < 100 and 0 < bwd < 100 and 0 < dec < 100
    run.peaks = None                      # the CPU: no peaks, no share
    assert harness.find_reader(
        ROOT, m, "flash_fwd_roofline_pct").read(run) is None


def test_result_shape_of_an_instruction():
    f = program_trace._result_elements
    assert f("%copy.2 = bf16[12,9473,16,768]{3,2,1,0:T(8,128)(2,1)} "
             "copy(%p)") == 12 * 9473 * 16 * 768
    assert f("%f = (f32[4,8]{1,0}, bf16[2,3,5]{2,1,0}) fusion(%a, %b)") \
        == 32
    assert f("%c = s32[] constant(3)") == 1


def test_idle_time_goes_to_the_innermost_span_by_overlap():
    import numpy as np
    from benchmark import trace as trace_mod
    ev = lambda s, d: types.SimpleNamespace(start_ns=s, duration_ns=d)
    # the device runs 10-40 and 60-90 of a window 0-100; idle: 0-10,
    # 40-60, 90-100
    device = types.SimpleNamespace(
        name=trace_mod.DEVICE_PLANE + "0",
        lines=[types.SimpleNamespace(name=trace_mod.OPS_LINE,
                                     events=[ev(10, 30), ev(60, 30)])])
    names = ["serve/step", "serve/pack", "serve/device_wait",
             "serve/step", "serve/pack"]
    starts = np.array([5.0, 5.0, 12.0, 45.0, 50.0])
    ends = np.array([44.0, 9.0, 43.0, 95.0, 58.0])
    other = (["stall"], np.array([0.0]), np.array([100.0]))
    got = program_trace._idle_by_span([device], (0.0, 100.0),
                                      [other, (names, starts, ends)])
    ns = 1e-9
    assert got["serve/pack"] == pytest.approx((4 + 8) * ns)
    # 9-10 and 43-44 of the first step, 45-50, 58-60 and 90-95 of the
    # second are the steps' own
    assert got["serve/step"] == pytest.approx((1 + 1 + 5 + 2 + 5) * ns)
    assert got["serve/device_wait"] == pytest.approx(3 * ns)
    assert got["(no hetu span)"] == pytest.approx(1 * ns)   # 44-45
    # 0-5 and 95-100: no step span was recorded there
    assert got["(outside the recorded steps)"] == pytest.approx(10 * ns)
    assert sum(got.values()) == pytest.approx(40 * ns)
    assert "stall" not in got                # another thread's span
