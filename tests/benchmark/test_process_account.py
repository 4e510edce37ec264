"""CPU rehearsal of ``benchmark/process_account.py`` and the eleven
per-layer metrics that read the run's account of its PROCESS (ISSUE 53).

The helper's arithmetic is held to synthetic compile events, counter
tracks and ``gc/collect`` events; a manifest of its own
(``manifest_process.json``: the tiny cells under names of their own,
plus the new metrics) runs through the unedited harness.
"""

import gc
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

from benchmark import harness, iteration_account as ia  # noqa: E402
from benchmark import process_account as pa  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_process.json")
SPLIT = ("window_compile_s", "host_other_cpu_ms", "gc_pause_ms",
         "process_threads_peak", "idle_host_phases_ms")
ALL = ["setup_cold_compile_s"] + [f"{n}{s}" for n in SPLIT
                                  for s in (".chat", ".backlogs")]
UNITS = {"setup_cold_compile_s": "s", "window_compile_s": "s",
         "process_threads_peak": "count"}
EPOCH = 1000.0


def _cev(stage, seconds, t, fun="jit(step)", cache=None,
         thread="MainThread"):
    return types.SimpleNamespace(stage=stage, seconds=seconds, t=t,
                                 fun_name=fun, cache=cache, thread=thread)


def test_compile_account_on_synthetic_events():
    evs = [_cev("trace", 2.0, 5.0), _cev("lower", 1.0, 6.0),
           _cev("compile", 40.0, 50.0, cache="miss"),
           _cev("compile", 3.0, 55.0, fun="jit(init)", cache="hit"),
           _cev("compile", 0.5, 56.0, fun="jit(small)", cache="uncached"),
           _cev("compile", 0.25, 57.0, fun="jit(off)", cache="off"),
           _cev("cache", 2.5, 55.0, fun="jit(init)"),
           # inside the window, on a thread of its own
           _cev("trace", 0.5, 101.0, fun="jit(rows)", thread="compile-1"),
           _cev("compile", 4.0, 110.0, fun="jit(rows)", cache="miss",
                thread="compile-1"),
           # after it
           _cev("compile", 9.0, 150.0, fun="jit(ref)", cache="miss")]
    acc = pa.compile_account(evs, (100.0, 145.0))
    assert acc["cold_s"] == pytest.approx(40.75)
    assert acc["before_window_by_cache"] == {
        "miss": {"n": 1, "s": 40.0}, "hit": {"n": 1, "s": 3.0},
        "uncached": {"n": 1, "s": 0.5}, "off": {"n": 1, "s": 0.25}}
    assert acc["cold_top"][0] == ["jit(step)", "compile", "miss",
                                  "MainThread", 40.0]
    assert acc["window_s"] == pytest.approx(4.5)
    assert acc["window_events"] == 2
    assert acc["window_top"][0][:4] == ["jit(rows)", "compile", "miss",
                                        "compile-1"]
    # a warm side: every compile a hit
    warm = [_cev("compile", 3.0, 55.0, cache="hit")]
    assert pa.compile_account(warm, (100.0, 145.0))["cold_s"] == 0.0
    # an older commit's events say nothing of the cache
    old = [types.SimpleNamespace(stage="compile", seconds=1.0, t=5.0,
                                 fun_name="f")]
    assert pa.compile_account(old, (100.0, 145.0)) is None


def _it(n, *, t0=1.0, period=0.02, cpu=0.004):
    return {"start": EPOCH + t0 + period * np.arange(n),
            "cpu_s": np.full(n, cpu)}


def _samples(name, every, n, value, *, t0=1.0, period=0.02):
    """A sample at the end of every ``every``-th iteration."""
    return [(name, t0 + period * (i + 0.9), value(i))
            for i in range(every - 1, n, every)]


def _gc_ev(ts, dur, *, gen=2, tid=7, under="serve/pack"):
    return types.SimpleNamespace(
        name="gc/collect", ts_s=ts, dur_s=dur, tid=tid,
        attrs={"generation": gen, "under": under, "thread": "loop"})


def test_window_account_on_synthetic_tracks():
    n, every = 200, 10
    # the process burns 9 ms an iteration, the loop 4 of them
    samples = _samples(pa.CPU, every, n, lambda i: 50.0 + 0.009 * (i + 1))
    samples += _samples(pa.THREADS, every, n, lambda i: 40 + (i == 99) * 7)
    # generation 0 from the start, generation 2 born mid-window
    samples += _samples('gc_pause_seconds_total{generation="0"}', every,
                        n, lambda i: 0.0001 * (i + 1))
    samples += [s for s in _samples(
        'gc_pause_seconds_total{generation="2"}', every, n,
        lambda i: 0.03 * ((i + 1) // 100)) if s[1] > 2.9]
    samples += _samples(pa.RSS, every, n, lambda i: 3e9 + 1e6 * (i + 1))
    tracks = pa.series(samples, EPOCH)
    window = (EPOCH + 1.0, EPOCH + 1.0 + n * 0.02)
    gcs = [_gc_ev(1.5, 0.001, gen=0), _gc_ev(2.99, 0.03),
           _gc_ev(3.5, 0.002, gen=1, tid=9, under="")]
    acc = pa.window_account(tracks, _it(n), gcs, EPOCH, window, None,
                            loop_tids={7})
    # between the first and the last sample: iterations 10..199
    assert acc["iterations"] == 190
    assert acc["process_cpu_s"] == pytest.approx(190 * 0.009)
    assert acc["loop_cpu_s"] == pytest.approx(190 * 0.004)
    assert acc["host_other_cpu_ms"] == pytest.approx(5.0)
    # generation 2's series did not exist at the first sample: 0 there
    assert acc["gc_pause_ms"] == pytest.approx(
        1e3 * (190 * 0.0001 + 0.03 * 2) / 190)
    g = acc["gc_events"]
    assert g["n"] == 3 and g["by_generation"] == {"0": 1, "2": 1, "1": 1}
    assert g["loop_seconds"] == pytest.approx(0.031)
    assert g["longest_ms"] == pytest.approx(30.0)
    assert list(g["under_s"]) == ["serve/pack", "(no span)"]
    assert acc["threads_peak"] == 47
    assert acc["rss_peak_bytes"] == 3e9 + 1e6 * n
    assert acc["cpu_s_per_s"] == []              # under SERIES_S long
    # the profiled slice cuts the window in two; each piece brackets
    # its own samples
    slice_ = (EPOCH + 2.0, EPOCH + 3.0)
    cut = pa.window_account(tracks, _it(n), gcs, EPOCH, window, slice_,
                            loop_tids={7})
    assert cut["iterations"] == 40 + 90
    assert cut["host_other_cpu_ms"] == pytest.approx(5.0)
    assert cut["gc_events"]["n"] == 2            # the one at 2.99 is cut
    # too few iterations between two samples, or no track: nothing
    assert pa.window_account(tracks, _it(n), gcs, EPOCH,
                             (EPOCH + 1.0, EPOCH + 1.5), None) is None
    assert pa.window_account({}, _it(n), gcs, EPOCH, window, None) is None
    assert pa.window_account(tracks, None, gcs, EPOCH, window,
                             None) is None


def test_rates_through_a_long_window():
    samples = [(pa.CPU, float(t), 2.0 * t) for t in range(0, 21)]
    tracks = pa.series(samples, EPOCH)
    it = {"start": EPOCH + 0.05 * np.arange(400), "cpu_s": np.full(400, .01)}
    acc = pa.window_account(tracks, it, [], EPOCH,
                            (EPOCH + 2.0, EPOCH + 18.0), None)
    assert acc["cpu_s_per_s"] == [2.0, 2.0, 2.0]
    assert acc["threads_peak"] is None and acc["rss_peak_bytes"] is None


def test_idle_account_takes_the_loops_host_phases():
    host = {"spans": {"serve/step": {"n": 50}},
            "idle_by_span": {"serve/dispatch": 0.2, "serve/device_wait": 0.1,
                             "serve/pack": 0.05, "serve/admit": 0.02,
                             "gc/collect": 0.01, "serve/step": 0.005,
                             "(no hetu span)": 0.015,
                             "(outside the recorded steps)": 0.4}}
    acc = pa.idle_account(host)
    assert acc["steps_recorded"] == 50
    assert set(acc["idle_s_by_phase"]) == {
        "serve/pack", "serve/admit", "gc/collect", "serve/step",
        "(no hetu span)"}
    assert acc["idle_host_phases_ms"] == pytest.approx(1e3 * 0.1 / 50)
    assert pa.idle_account(None) is None
    assert pa.idle_account({"spans": {}, "idle_by_span": {"a": 1.0}}) is None
    assert pa.idle_account({"spans": {"serve/step": {"n": 3}},
                            "idle_by_span": None}) is None


@mc.cell_needs
def the_eleven_process_entries(m):
    """The eleven entries ISSUE 53 brought: readers whose constants
    agree, each listing the cells that report the metric it moves (a
    cell appended to that metric is appended here), all mirrored in the
    rehearsal's manifest, standing together."""
    e2e = {x["name"]: x for x in m["end_to_end"]}
    mc.stand_together(m, ALL)
    for name in ALL:
        x = mc.entry(m, name)
        base = name.rsplit(".", 1)[0] if "." in name else name
        unit, layer = mc.PROCESS_ACCOUNT.get(base, ("s", mc.COMPILE))
        assert unit == UNITS.get(base, "ms")
        if name.endswith(".chat"):
            moves, cells = "gap_p95_ms", e2e["gap_p95_ms"]["workloads"]
        elif name.endswith(".backlogs"):
            moves, cells = mc.TOKENS, e2e[mc.TOKENS]["workloads"]
        else:
            moves = mc.SETUP
            cells = mc.entry(m, "setup_compile_s")["workloads"]
        assert x["workloads"] == cells
        mc.needs(m, cells[0], {name: (unit, layer, moves)},
                 mirrored_in=MANIFEST)
        assert x["better"] == "lower"
        # the manifest's label; the docstring says the true source
        assert x["source"] == "host_clock"
        assert "Source, truly" in harness.find_reader(
            ROOT, m, name).__doc__
    others = {x["layer"] for x in m["per_layer"] if x["name"] not in ALL}
    assert mc.entry(m, "setup_cold_compile_s")["layer"] in others
    assert mc.entry(m, "idle_host_phases_ms.chat")["layer"] in others


def test_the_eleven_process_entries_match_their_readers():
    the_eleven_process_entries(mc.real())


@mc.cell_needs
def a_split_quantity_is_one_reader_under_two_names(m):
    """An entry moves ONE end-to-end metric, so a quantity the chat
    cell and the backlog cells both report is two entries: ``.chat``
    and ``.backlogs`` — the same body to the byte, the same unit,
    ``better`` and layer, each cell under exactly one of them."""
    names = {x["name"] for x in m["per_layer"]}
    split = sorted(n[:-len(".chat")] for n in names
                   if n.endswith(".chat") and
                   n[:-len(".chat")] + ".backlogs" in names)
    assert set(SPLIT) <= set(split) and len(split) >= 17
    for base in split:
        a, b = mc.entry(m, base + ".chat"), mc.entry(m, base + ".backlogs")
        assert (a["unit"], a["better"], a["layer"]) == \
            (b["unit"], b["better"], b["layer"]), base
        assert (a["moves"], b["moves"]) == ("gap_p95_ms", mc.TOKENS)
        assert not set(a["workloads"]) & set(b["workloads"])
        assert mc.reader_body(m, a["name"]) == \
            mc.reader_body(m, b["name"]), base


def test_a_split_quantity_is_one_reader_under_two_names():
    a_split_quantity_is_one_reader_under_two_names(mc.real())


def _rehearse(workload, seconds, capsys):
    import jax
    from hetu_tpu import telemetry
    telemetry.reset()
    try:
        out = harness.run_cell(
            harness.load_manifest(MANIFEST), ROOT, workload,
            seed=2**31 + 53, seconds=seconds, trace=True,
            devices=jax.devices(), on_chip=False,
            t_process=time.perf_counter())
    finally:
        telemetry.enable(False)
        telemetry.reset()
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"process_account"')]
    assert len(lines) == 1                       # ONE information line
    return out["line"]["metrics"], lines[0]["process_account"]


def test_short_rehearsal_window_leaves_the_window_metrics_out(capsys):
    """A window the slice covers whole holds no two samples outside it:
    the readers of the counted window return ``None``, nothing raises;
    what is read before the window and in the slice is there."""
    got, info = _rehearse("process.chat", 1.5, capsys)
    assert info["window"] is None
    for name in ("host_other_cpu_ms.chat", "gc_pause_ms.chat",
                 "process_threads_peak.chat"):
        assert name not in got
    assert got["setup_cold_compile_s"]["value"] > 0      # no cache here
    assert got["window_compile_s.chat"]["value"] >= 0
    assert info["compile"]["before_window_by_cache"]
    assert got["engine_host_ms.chat"]["value"] > 0


def test_rehearsal_prints_the_process_account_with_every_key(capsys):
    """5.5 s of the tiny backlog: the first 1.5 s lie outside the
    padded slice and hold samples (one every 32 iterations), so every
    new metric of the cell is on the line but the one that needs a
    device plane (the test below hands its arithmetic one)."""
    got, info = _rehearse("process.backlog", 5.5, capsys)
    mine = ["setup_cold_compile_s"] + [n + ".backlogs" for n in SPLIT[:-1]]
    assert "idle_host_phases_ms.backlogs" not in got and \
        info["idle"] is None
    for name in mine:
        assert name in got, name
        assert got[name]["value"] >= 0, name
    assert got["process_threads_peak.backlogs"]["unit"] == "count"
    assert got["process_threads_peak.backlogs"]["value"] >= 3
    assert set(info) == {"compile", "window", "idle"}
    w = info["window"]
    assert set(w) == {"iterations", "process_cpu_s", "loop_cpu_s",
                      "host_other_cpu_ms", "gc_pause_ms", "gc_events",
                      "threads_peak", "rss_peak_bytes", "cpu_s_per_s"}
    assert w["rss_peak_bytes"] > 0
    assert w["iterations"] >= pa.MIN_STEPS
    assert w["process_cpu_s"] > 0 and w["loop_cpu_s"] > 0
    assert set(info["compile"]) == {
        "cold_s", "before_window_by_cache", "cold_top", "window_s",
        "window_events", "window_top"}
    assert info["compile"]["cold_top"][0][3]          # a thread's name
    # the wire's threads are part of the process: the two clocks tick
    # in 10 ms, so only the order of magnitude is held here
    assert got["host_other_cpu_ms.backlogs"]["value"] < 1e3


def test_a_forced_collection_in_the_loop_is_in_the_trace_and_idle_by_span(
        tmp_path):
    """A generation-2 collection in the loop thread of the tiny engine
    appears as ``hetu:gc/collect`` in the host plane of a profiler
    trace and as a key of ``idle_by_span``."""
    import jax
    from benchmark import program_trace, trace as trace_mod
    from benchmark.runners.serve import gpt_config
    from hetu_tpu import telemetry
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.serving import SamplingParams, ServingEngine

    with open(os.path.join(HERE, "configs", "gpt2-tiny.json")) as f:
        config = json.load(f)
    cfg = gpt_config(config)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(53))
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, max_len=64, prefill_chunk=16,
                            slots=2, block_size=8)
        rng = np.random.default_rng(53)
        for _ in range(2):
            eng.submit(rng.integers(1, cfg.vocab_size, 12,
                                    dtype=np.int32),
                       SamplingParams(max_tokens=24))
        for _ in range(3):
            eng.step()                           # compiled, warm
        real = eng._pump_stream_subs

        def pump_and_collect():
            gc.collect()                         # under serve/pump
            return real()

        eng._pump_stream_subs = pump_and_collect
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                for _ in range(6):
                    eng.step()
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.enable(False)
        telemetry.reset()
    path = trace_mod.newest_xplane(str(tmp_path))
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    host = [ev for pl in planes if pl.name == trace_mod.HOST_PLANE
            for ln in pl.lines for ev in ln.events
            if ev.name == "hetu:gc/collect"]
    assert len(host) >= 6
    stats = dict(host[0].stats)
    assert stats["under"] == "serve/pump" and int(stats["generation"]) == 2
    # the loop thread's segments name it the innermost span; on the CPU
    # there is no device plane, so hand the attribution one whose only
    # operation ends before the steps: all of the slice after it is idle
    lines, window = [], None
    for pl in planes:
        if pl.name != trace_mod.HOST_PLANE:
            continue
        for ln in pl.lines:
            evs = [ev for ev in ln.events if ev.duration_ns > 0]
            for ev in evs:
                if ev.name == trace_mod.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
            mine = [ev for ev in evs if ev.name.startswith("hetu:")]
            if mine:
                lines.append((
                    [ev.name[5:] for ev in mine],
                    np.array([ev.start_ns for ev in mine], np.float64),
                    np.array([ev.start_ns + ev.duration_ns
                              for ev in mine], np.float64)))
    op = types.SimpleNamespace(name="fusion", start_ns=window[0],
                               duration_ns=1000)
    dev = types.SimpleNamespace(
        name=trace_mod.DEVICE_PLANE + "0",
        lines=[types.SimpleNamespace(name=trace_mod.OPS_LINE,
                                     events=[op])])
    idle = program_trace._idle_by_span(planes + [dev], window, lines)
    assert idle["gc/collect"] > 0
    acc = pa.idle_account({"spans": {"serve/step": {"n": 6}},
                           "idle_by_span": idle})
    assert acc["idle_s_by_phase"]["gc/collect"] == idle["gc/collect"]
