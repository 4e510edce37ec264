"""CPU rehearsal of ``benchmark/iteration_account.py`` and the per-layer
metrics that read the serving loop's own account (ISSUE 35).

The helper's arithmetic is held to synthetic events (window clipping,
the profiled slice, dropped events, an older commit's events, the tail)
and a synthetic pairing of device programs with the ``iter`` of the
spans around them; a manifest of its own (``manifest_account.json``:
the tiny cells under names of their own, so that their trace directories
are no other test file's, plus the new metrics) runs through the
unedited harness.
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

from benchmark import harness, iteration_account as ia  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_account.json")
WINDOW_METRICS = ("engine_host_cpu_ms", "engine_host_offcpu_ms",
                  "host_dispatch_ms", "wire_cpu_ms")
LAG_METRICS = ("step_launch_lag_ms", "step_fetch_lag_ms")
CHAT_ONLY = ("iter_p95_ms.chat", "iter_tail_host_pct.chat")
ALL = [f"{n}{s}" for n in WINDOW_METRICS + LAG_METRICS
       for s in (".chat", ".backlogs")] + list(CHAT_ONLY)
EPOCH = 1000.0


def _ev(name, ts, dur, *, tid=7, depth=1, cat="span", **attrs):
    return types.SimpleNamespace(name=name, ts_s=ts, dur_s=dur, tid=tid,
                                 depth=depth, cat=cat, attrs=attrs)


def _step(i, ts, wall, *, wait, cpu, wait_cpu=0.001, lock=0.0005,
          frames=4, dispatch=0.002, account=True):
    """One iteration: admit, dispatch, device_wait, pump back to back
    from the step's start, the rest of it under no child."""
    attrs = dict(iter=i, active=3, prefill_tokens=0)
    if account:
        attrs.update(cpu_s=cpu, wait_cpu_s=wait_cpu, lock_wait_s=lock,
                     admitted=1, frames=frames, since_prev_s=0.001)
    kids, t = [], ts
    for name, d in (("serve/admit", 0.001), ("serve/dispatch", dispatch),
                    ("serve/device_wait", wait), ("serve/pump", 0.0005)):
        kids.append(_ev(name, t, d, depth=2, iter=i))
        t += d
    return [_ev("serve/step", ts, wall, **attrs)] + kids


def _steady(n=40, *, period=0.02, wall=0.010, **kw):
    """``n`` iterations of ``wall`` seconds, one every ``period``."""
    evs = []
    for i in range(n):
        evs += _step(i + 1, 1.0 + i * period, wall, wait=0.004,
                     cpu=0.004, **kw)
    return evs


def test_window_account_on_synthetic_events():
    evs = _steady(40)
    # two slow iterations, host-bound: the tail
    for e in evs:
        if e.name == "serve/step" and e.attrs["iter"] in (10, 30):
            e.dur_s = 0.018
    # a step cut by the window's start, one cut by its end, a span of
    # another thread and a nested span: none of them counts
    evs += _step(0, 0.985, 0.010, wait=0.004, cpu=0.004)
    evs += _step(41, 1.0 + 40 * 0.02, 0.010, wait=0.004, cpu=0.004)
    evs.append(_ev("serve/pack", 1.0005, 0.5, tid=8, depth=2))
    # a step that returned before the fused step (an auxiliary job
    # alone) carries no account: it is no iteration, nor are its spans
    evs += _step(99, 1.0 + 5 * 0.02 + 0.011, 0.008, wait=0.004, cpu=0.0,
                 account=False)
    evs.append(_ev("serve/inner", 1.0001, 0.0002, depth=3))
    # the wire: two drainers and a submit end in the window, one after
    evs += [_ev("stream/drain", 1.0, 0.5, tid=20, depth=0, cat="wire",
                cpu_s=0.030, frames=50, req=1),
            _ev("stream/drain", 1.1, 0.5, tid=21, depth=0, cat="wire",
                cpu_s=0.020, frames=30, req=2),
            _ev("server/submit", 1.2, 0.001, tid=22, depth=0,
                cat="wire", cpu_s=0.001, lock_wait_s=0.0004),
            _ev("stream/drain", 1.5, 0.9, tid=23, depth=0, cat="wire",
                cpu_s=9.0, frames=999, req=3)]
    lo, hi = EPOCH + 0.99, EPOCH + 1.0 + 40 * 0.02 + 0.005
    acc = ia.window_account(evs, EPOCH, (lo, hi))
    assert acc["iterations"] == 40
    assert acc["step_ms"] == pytest.approx((38 * 10 + 2 * 18) / 40)
    assert acc["step"]["p50"] == pytest.approx(10.0)
    assert acc["step"]["p95"] == pytest.approx(10.0)
    kids = acc["children_ms"]
    assert set(kids) == {"serve/admit", "serve/dispatch",
                         "serve/device_wait", "serve/pump"}
    assert kids["serve/dispatch"] == pytest.approx(2.0)
    assert acc["children"]["serve/dispatch"]["mean"] == \
        pytest.approx(2.0)
    # host wall = step - device_wait; CPU = cpu_s - wait_cpu_s
    assert acc["host_wall_ms"] == pytest.approx(acc["step_ms"] - 4.0)
    assert acc["host_cpu_ms"] == pytest.approx(3.0)
    assert acc["host_cpu"]["mean"] == pytest.approx(3.0)
    assert acc["host_offcpu_ms"] == pytest.approx(
        acc["host_wall_ms"] - 3.0)
    assert acc["host_cpu_ms"] + acc["host_offcpu_ms"] == \
        pytest.approx(acc["host_wall_ms"])
    assert acc["lock_wait_ms"] == pytest.approx(0.5)
    assert acc["frames"] == 4 and acc["admitted"] == 1
    covered = 40 * 7.5
    assert acc["coverage"] == pytest.approx(covered / (40 * acc["step_ms"]))
    un = acc["uncovered_ms"]
    assert un["head"] == pytest.approx(0.0, abs=1e-9)
    assert un["between"] == pytest.approx(0.0, abs=1e-9)
    assert un["tail"] == pytest.approx(acc["step_ms"] - 7.5)
    # the slowest 5 % = the two slow iterations: 14 of their 18 ms are
    # the host's
    tail = acc["tail"]
    assert tail["iterations"] == 2
    assert tail["step_ms"] == pytest.approx(18.0)
    assert tail["host_pct"] == pytest.approx(100 * 14 / 18)
    assert tail["host_offcpu_ms"] == pytest.approx(11.0)
    # the wire events that END in the window, over its iterations
    assert acc["wire_events"] == 3 and acc["wire_frames"] == 80
    assert acc["wire_cpu_s"] == pytest.approx(0.051)
    assert acc["wire_cpu_ms"] == pytest.approx(51.0 / 40)
    assert acc["submit_lock_wait_s"] == pytest.approx(0.0004)
    assert acc["in_slice"] is None
    assert acc["step_ms_series"] == [pytest.approx(acc["step_ms"])]
    json.dumps(acc)


def test_the_profiled_slice_is_counted_apart():
    """Iterations that overlap the slice are slow (the profiler's
    Python tracer): the account leaves them out and prints them."""
    evs = _steady(400, period=0.02)                  # 8 s
    lo = EPOCH + 1.0
    slice_ = (lo + 2.0 - ia.SLICE_PAD_S, lo + 5.0 + ia.SLICE_PAD_S)
    n_in = 0
    for e in evs:
        if e.name == "serve/step" and \
                e.ts_s + e.dur_s + EPOCH > slice_[0] and \
                e.ts_s + EPOCH < slice_[1]:
            e.dur_s, n_in = 0.015, n_in + 1
    acc = ia.window_account(evs, EPOCH, (lo, lo + 8.0), slice_=slice_)
    assert acc["iterations"] == 400 - n_in and 195 <= n_in <= 205
    assert acc["step_ms"] == pytest.approx(10.0)
    assert acc["in_slice"]["iterations"] == n_in
    assert acc["in_slice"]["step_ms"] == pytest.approx(15.0)
    assert acc["in_slice"]["host_offcpu_ms"] == pytest.approx(8.0)
    # 0-5 s: 1.5 s at 10 ms, then 15; 5-8 s: 0.5 s at 15 ms, then 10
    assert acc["step_ms_series"] == [pytest.approx(x, abs=0.1)
                                     for x in (13.5, 10.83)]
    run = types.SimpleNamespace(trace={}, seconds=45.0,
                                records={"window": (lo, lo + 45.0)})
    assert ia.profiled_slice(run) == pytest.approx(slice_)
    # stop_trace wrote the file 12 s into the window: the host was the
    # profiler's until then; a file written before the slice's end (a
    # stale clock) moves nothing
    assert ia.profiled_slice(run, written=lo + 12.0) == pytest.approx(
        (slice_[0], lo + 12.0 + ia.SLICE_PAD_S))
    assert ia.profiled_slice(run, written=lo + 1.0) == \
        pytest.approx(slice_)
    run.trace = None
    assert ia.profiled_slice(run) is None
    # a window too short for the delay is traced from its start
    run = types.SimpleNamespace(trace={}, seconds=1.5,
                                records={"window": (lo, lo + 1.5)})
    assert ia.profiled_slice(run) == pytest.approx(
        (lo - ia.SLICE_PAD_S, lo + 1.5 + ia.SLICE_PAD_S))


@pytest.mark.parametrize("why", ["dropped", "older_commit", "too_few",
                                 "no_steps"])
def test_no_account_where_the_record_cannot_carry_one(why):
    lo, hi = EPOCH + 1.0, EPOCH + 2.0
    if why == "dropped":
        got = ia.window_account(_steady(40), EPOCH, (lo, hi), dropped=3)
    elif why == "older_commit":           # spans, but no attributes
        got = ia.window_account(_steady(40, account=False), EPOCH,
                                (lo, hi))
    elif why == "too_few":
        got = ia.window_account(_steady(ia.MIN_STEPS - 1), EPOCH,
                                (lo, hi))
    else:
        got = ia.window_account([], EPOCH, (lo, hi))
    assert got is None


def test_lags_pair_programs_with_the_iterations_spans():
    """Iteration N's program is the longest one that starts between
    ``serve/dispatch``'s start and ``serve/device_wait``'s end."""
    spans, modules = {}, []
    for i in range(1, 8):
        t0 = i * 100_000_000                       # ns
        launch, busy, fetch = 2_000_000 + i * 1000, 40_000_000, 3_000_000
        spans[i] = {"dispatch": (t0, t0 + 5_000_000),
                    "wait": (t0 + 5_000_000,
                             t0 + launch + busy + fetch)}
        modules.append((t0 + launch, t0 + launch + busy))
        # a short auxiliary program of the same iteration
        modules.append((t0 + 100_000, t0 + 400_000))
    spans[8] = {"dispatch": (900_000_000, 905_000_000)}  # cut by the edge
    spans[9] = {"dispatch": (2_000_000_000, 2_005_000_000),
                "wait": (2_005_000_000, 2_050_000_000)}  # no program
    got = ia.pair_lags(spans, modules)
    assert got["pairs"] == 7
    assert got["launch_ms"] == pytest.approx(2.004)
    assert got["fetch_ms"] == pytest.approx(3.0)
    assert got["module_ms"] == pytest.approx(40.0)
    assert got["dispatch_to_fetched_ms"] == pytest.approx(
        got["launch_ms"] + got["module_ms"] + got["fetch_ms"])
    assert ia.pair_lags(spans, []) is None
    assert ia.pair_lags({8: spans[8]}, modules) is None


def _xplane(monkeypatch, planes):
    import jax.profiler
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda p: types.SimpleNamespace(planes=planes)))


def test_lags_from_a_synthetic_xplane(monkeypatch):
    ev = lambda n, s, d, **st: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d, stats=list(st.items()))
    host = types.SimpleNamespace(name=ia.trace_mod.HOST_PLANE, lines=[
        types.SimpleNamespace(name="loop", events=[
            ev("hetu:serve/step", 0, 60, iter=1),
            ev("hetu:serve/dispatch", 10, 10, iter=1),
            ev("hetu:serve/device_wait", 20, 40, iter=1),
            ev("hetu:serve/dispatch", 110, 10, iter=2),
            ev("hetu:serve/device_wait", 120, 30, iter=2),
            ev("hetu:serve/dispatch", 210, 10),      # an older commit's
            ev("$engine.py:1 f", 0, 300)])])
    dev = types.SimpleNamespace(name=ia.trace_mod.DEVICE_PLANE + "0", lines=[
        types.SimpleNamespace(name=ia.trace_mod.OPS_LINE,
                              events=[ev("fusion.1", 15, 30)]),
        types.SimpleNamespace(name=ia.MODULES_LINE, events=[
            ev("jit_step(1)", 14, 36), ev("jit_step(1)", 113, 30),
            ev("jit_step(1)", 215, 30)])])
    _xplane(monkeypatch, [host, dev])
    got = ia._lags("x.pb")
    assert got["pairs"] == 2
    # launch 4 and 3 ns, fetch 10 and 7 ns: nearest-rank medians
    assert got["launch_ms"] == pytest.approx(3e-6)
    assert got["fetch_ms"] == pytest.approx(7e-6)
    assert got["modules"] == [("jit_step(1)", 3)]
    # the CPU rehearsal: a host plane and no device plane
    _xplane(monkeypatch, [host])
    assert ia._lags("x.pb") is None
    assert ia._lags(None) is None                     # no --trace


@mc.cell_needs
def the_fourteen_account_entries(m):
    """The 14 entries ISSUE 35 brought: two an account metric (one
    lists the cells that report ``gap_p95_ms``, one every cell that
    reports ``serve_tokens_per_s``), readers whose constants agree,
    all mirrored in the rehearsal's manifest, standing together."""
    e2e = {x["name"]: x for x in m["end_to_end"]}
    mc.stand_together(m, ALL)
    for name in ALL:
        x = mc.entry(m, name)
        base = name.rsplit(".", 1)[0]
        unit, layer = mc.ACCOUNT.get(base, (
            "%" if base == "iter_tail_host_pct" else "ms", mc.STEP))
        moves = "gap_p95_ms" if name.endswith(".chat") else mc.TOKENS
        assert x["workloads"] == e2e[moves]["workloads"]
        mc.needs(m, x["workloads"][0], {name: (unit, layer, moves)},
                 mirrored_in=MANIFEST)
        assert x["better"] == "lower"
        # the manifest's label; the docstring says the true source
        assert x["source"] == ("device_trace" if base in LAG_METRICS
                               else "host_clock")
        assert "Source, truly" in harness.find_reader(
            ROOT, m, name).__doc__
    others = {x["layer"] for x in m["per_layer"] if x["name"] not in ALL}
    assert {mc.entry(m, n)["layer"] for n in ALL} <= others


def test_the_fourteen_account_entries_match_their_readers():
    the_fourteen_account_entries(mc.real())


def _rehearse(workload, seconds, capsys):
    import jax
    from hetu_tpu import telemetry
    telemetry.reset()
    try:
        out = harness.run_cell(
            harness.load_manifest(MANIFEST), ROOT, workload,
            seed=2**31 + 35, seconds=seconds, trace=True,
            devices=jax.devices(), on_chip=False,
            t_process=time.perf_counter())
    finally:
        telemetry.enable(False)
        telemetry.reset()
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"iteration_account"')]
    assert len(lines) == 1                       # ONE information line
    return out["line"]["metrics"], lines[0]["iteration_account"]


@pytest.mark.parametrize("workload", ["account.chat", "account.backlog"])
def test_short_rehearsal_window_leaves_the_metrics_out(workload, capsys):
    """A window the slice covers whole has no iteration outside it:
    every new reader returns ``None`` and nothing raises."""
    got, info = _rehearse(workload, 1.5, capsys)
    assert not set(ALL) & set(got)
    name = "engine_host_ms." + ("chat" if workload.endswith(".chat")
                                else "backlogs")
    assert got[name]["value"] > 0
    assert info["window"] is None and info["lags"] is None
    assert info["dropped"] == 0 < info["events"]


def test_rehearsal_reports_the_account_outside_the_slice(capsys):
    """5.5 s of the tiny backlog: the first 1.5 s lie outside the
    padded slice, so the whole-window metrics are there; there is no
    device plane on the CPU, so the lags are not."""
    got, info = _rehearse("account.backlog", 5.5, capsys)
    for name in WINDOW_METRICS:
        assert got[name + ".backlogs"]["value"] >= 0, name
    assert got["host_dispatch_ms.backlogs"]["value"] > 0
    assert not [n for n in got if n.rsplit(".", 1)[0] in LAG_METRICS]
    w = info["window"]
    assert w["iterations"] >= ia.MIN_STEPS and info["lags"] is None
    assert w["host_cpu_ms"] + w["host_offcpu_ms"] == \
        pytest.approx(w["host_wall_ms"])
    assert w["in_slice"]["iterations"] >= 1
    a, b = info["profiled_s"]
    assert a == pytest.approx(2.0 - ia.SLICE_PAD_S) and \
        b >= 5.0 + ia.SLICE_PAD_S
    assert w["wire_events"] >= 1 and w["frames"] > 0
    assert set(w["children_ms"]) >= {
        "serve/admit", "serve/pack", "serve/dispatch",
        "serve/device_wait", "serve/commit", "serve/pump",
        "serve/account"}
    assert len(w["step_ms_series"]) == 2
