"""Production observability (ISSUE 6): flight recorder + crash
handlers, hang watchdog, SLO/anomaly engine, per-request serving
traces, HEALTHZ/METRICS verbs, obs_report CLI, metrics-docs lint.

Everything here is host-side (no XLA compiles): the watchdog hang is an
injected stalled fake step, SLO timelines are synthetic with a fake
clock, and the serving-path integration pieces that do compile live in
``tests/test_serving.py`` (module-shared jit cache).
"""

import json
import os
import signal
import sys
import threading
import time

import pytest

from hetu_tpu import telemetry
from hetu_tpu.telemetry import MetricRegistry, SLOEngine
from hetu_tpu.telemetry.flight import (
    FlightRecorder, HangWatchdog, atomic_write_text,
    _reset_crash_handlers_for_tests, install_crash_handlers,
)


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable(True)
    yield telemetry
    telemetry.enable(False)
    telemetry.reset()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_and_dump_parseable(tmp_path):
    fr = FlightRecorder(capacity=8, rank=3)
    for i in range(20):
        fr.record("step", step=i)
    assert len(fr) == 8
    path = fr.dump(str(tmp_path / "flight_3.jsonl"), reason="manual",
                   stacks=True)
    recs = [json.loads(ln) for ln in open(path)]
    header = recs[0]
    assert header["kind"] == "flight_header"
    assert header["reason"] == "manual" and header["rank"] == 3
    assert header["events_total"] == 20
    assert header["events_dropped"] == 12
    events = [r for r in recs if r["kind"] == "flight_event"]
    assert [e["event"] for e in events] == ["step"] * 8
    # the ring keeps the LAST events, seq strictly increasing
    assert [e["step"] for e in events] == list(range(12, 20))
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    # stacks record is parseable and includes this (the main) thread
    stacks = [r for r in recs if r["kind"] == "thread_stacks"]
    assert len(stacks) == 1
    assert any("test_flight_ring_bounded" in "".join(frames)
               for frames in stacks[0]["stacks"].values())
    # atomic write leaves no temp litter
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []


def test_atomic_write_failure_preserves_previous(tmp_path, monkeypatch):
    """SATELLITE: a die-mid-export never leaves a truncated artifact —
    the previous complete file survives and no temp litter remains."""
    path = str(tmp_path / "artifact.json")
    atomic_write_text(path, '{"ok": 1}')

    class Boom(Exception):
        pass

    def bad_replace(a, b):
        raise Boom()

    monkeypatch.setattr(os, "replace", bad_replace)
    with pytest.raises(Boom):
        atomic_write_text(path, '{"new": 2}')
    monkeypatch.undo()
    assert json.load(open(path)) == {"ok": 1}
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
    # export_dir routes through the same helper for both artifacts
    tr = telemetry.Tracer()
    with tr.span("x"):
        pass
    reg = MetricRegistry()
    reg.counter("c_total").inc()
    out = telemetry.export_dir(str(tmp_path / "exp"), tracer=tr,
                               registry=reg)
    assert json.load(open(out["trace"]))["traceEvents"]
    assert [f for f in os.listdir(tmp_path / "exp") if ".tmp." in f] == []


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crash_handlers_dump_on_excepthook_and_sigterm(tmp_path):
    fr = FlightRecorder(capacity=16, rank=0)
    fr.record("step", step=1)
    _reset_crash_handlers_for_tests()
    prev_hook = sys.excepthook
    prev_thook = threading.excepthook
    prev_term = signal.getsignal(signal.SIGTERM)
    try:
        install_crash_handlers(str(tmp_path), recorder=fr)
        # re-install is a no-op (idempotent), not a handler chain bomb
        install_crash_handlers(str(tmp_path), recorder=fr)
        # crash path: invoke the installed excepthook directly
        try:
            raise ValueError("boom")
        except ValueError:
            sys.excepthook(*sys.exc_info())
        path = str(tmp_path / f"flight_0.{os.getpid()}.jsonl")
        recs = [json.loads(ln) for ln in open(path)]
        assert recs[0]["reason"] == "crash"
        assert any(r.get("event") == "crash"
                   and r.get("error") == "ValueError"
                   for r in recs)
        assert any(r["kind"] == "thread_stacks" for r in recs)
        # SIGTERM path: the installed handler dumps then exits
        handler = signal.getsignal(signal.SIGTERM)
        assert callable(handler) and handler is not prev_term
        with pytest.raises(SystemExit):
            handler(signal.SIGTERM, None)
        recs = [json.loads(ln) for ln in open(path)]
        assert recs[0]["reason"] == "sigterm"
        assert any(r.get("event") == "sigterm" for r in recs)
        # the atexit hook must NOT os.replace a failure dump with a
        # stacks-free reason="atexit" file (the forensics survive exit)
        from hetu_tpu.telemetry.flight import _dump_at_exit
        _dump_at_exit(fr)
        recs = [json.loads(ln) for ln in open(path)]
        assert recs[0]["reason"] == "sigterm"
        # ...but on a plain exit (no prior dump) it does write one
        fr2 = FlightRecorder(capacity=4, rank=7)
        fr2.dump_dir = str(tmp_path)
        fr2.record("step", step=1)
        _dump_at_exit(fr2)
        recs = [json.loads(ln) for ln in open(tmp_path / f"flight_7.{os.getpid()}.jsonl")]
        assert recs[0]["reason"] == "atexit"
        # a DAEMON-thread crash (serving loop, prefetcher) dumps too —
        # sys.excepthook never fires for those
        th = threading.Thread(target=lambda: 1 / 0, name="boom-thread")
        th.start()
        th.join()
        recs = [json.loads(ln) for ln in open(path)]
        assert recs[0]["reason"] == "thread_crash"
        assert any(r.get("event") == "crash"
                   and r.get("error") == "ZeroDivisionError"
                   and r.get("thread") == "boom-thread" for r in recs)
    finally:
        sys.excepthook = prev_hook
        threading.excepthook = prev_thook
        signal.signal(signal.SIGTERM, prev_term)
        _reset_crash_handlers_for_tests()


def test_sigterm_handler_preserves_sig_ign(tmp_path):
    """A process that deliberately ignores SIGTERM keeps ignoring it:
    the handler dumps the postmortem but does not convert the ignored
    signal into an exit."""
    fr = FlightRecorder(capacity=8, rank=5)
    fr.record("step", step=1)
    _reset_crash_handlers_for_tests()
    prev_hook = sys.excepthook
    prev_thook = threading.excepthook
    prev_term = signal.getsignal(signal.SIGTERM)
    try:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        install_crash_handlers(str(tmp_path), recorder=fr)
        handler = signal.getsignal(signal.SIGTERM)
        handler(signal.SIGTERM, None)        # no SystemExit
        recs = [json.loads(ln)
                for ln in open(tmp_path / f"flight_5.{os.getpid()}.jsonl")]
        assert recs[0]["reason"] == "sigterm"
    finally:
        sys.excepthook = prev_hook
        threading.excepthook = prev_thook
        signal.signal(signal.SIGTERM, prev_term)
        _reset_crash_handlers_for_tests()


# ---------------------------------------------------------------------------
# hang watchdog
# ---------------------------------------------------------------------------

def test_watchdog_trips_on_injected_hang(tmp_path, telem):
    """ACCEPTANCE: a stalled fake step trips the watchdog, which dumps a
    parseable flight record WITH thread stacks; a healthy cadence trips
    nothing."""
    fr = FlightRecorder(capacity=64, rank=0)
    reg = telem.get_registry()
    tripped = []
    wd = HangWatchdog(name="train", factor=4.0, min_timeout_s=0.1,
                      poll_s=0.02, dump_dir=str(tmp_path), recorder=fr,
                      registry=reg, on_trip=tripped.append)
    wd.start()
    try:
        # healthy phase: fake steps beating every ~5 ms
        for i in range(20):
            fr.record("step", step=i)
            wd.beat()
            time.sleep(0.005)
        time.sleep(0.06)            # under the 0.1 s floor: no trip
        assert wd.trips == 0 and not tripped
        # the injected hang: the fake step stalls, beats stop
        deadline = time.monotonic() + 5.0
        while wd.trips == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert wd.trips == 1, "watchdog did not trip on the stall"
        assert tripped and "no beat for" in tripped[0]
        assert reg.counter("watchdog_trips_total").value(
            name="train") == 1
        # one trip per hang: the latch holds while the stall continues
        time.sleep(0.3)
        assert wd.trips == 1
        # the dump: parseable, reason=watchdog, stacks present
        path = str(tmp_path / f"flight_0.{os.getpid()}.jsonl")
        recs = [json.loads(ln) for ln in open(path)]
        assert recs[0]["reason"] == "watchdog"
        assert recs[0]["watchdog"] == "train"
        assert recs[0]["stalled_s"] > 0
        assert any(r.get("event") == "watchdog_trip" for r in recs)
        stacks = [r for r in recs if r["kind"] == "thread_stacks"]
        assert stacks and len(stacks[0]["stacks"]) >= 2  # main + monitor
        # faulthandler sidecar exists and names a thread
        side = open(str(tmp_path / f"flight_0.{os.getpid()}.stacks")).read()
        assert "Thread" in side or "thread" in side
        # recovery: a beat clears the latch; a new stall trips again
        wd.beat()
        assert wd.trips == 1
    finally:
        wd.stop()


def test_watchdog_timeout_tracks_rolling_median(tmp_path):
    t = [0.0]
    wd = HangWatchdog(name="x", factor=4.0, min_timeout_s=0.5,
                      dump_dir=str(tmp_path),
                      recorder=FlightRecorder(capacity=8, rank=0),
                      registry=MetricRegistry(),
                      clock=lambda: t[0])
    assert wd.timeout_s() == 0.5              # no beats yet: the floor
    for _ in range(10):
        t[0] += 1.0
        wd.beat()
    assert wd.timeout_s() == pytest.approx(4.0)   # 4 x median(1s)
    # check() with a fresh beat: quiet; 5s of silence: trip
    assert wd.check() is None
    t[0] += 5.0
    stalled = wd.check()
    assert stalled == pytest.approx(5.0)
    assert wd.trips == 1
    assert wd.check() is None                 # latched until next beat


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------

def test_burn_rate_alert_on_injected_ttft_regression_histogram(telem):
    """ACCEPTANCE: a TTFT regression injected into SYNTHETIC histogram
    data fires the burn-rate alert (registry-pull path: the rule samples
    the live p99 on every evaluate)."""
    t = [0.0]
    reg = telem.get_registry()
    h = reg.histogram("serving_ttft_seconds")
    eng = SLOEngine(reg, clock=lambda: t[0])
    eng.add_burn_rate("ttft_slo", "serving_ttft_seconds",
                      objective=0.2, field="p99", budget=0.25,
                      windows=((10.0, 2.0), (60.0, 1.0)),
                      min_samples=3)
    # healthy baseline: p99 well under the objective
    for _ in range(50):
        h.observe(0.05)
    for _ in range(12):
        t[0] += 2.0
        assert eng.evaluate() == []
    assert not eng.status()["alerting"]
    # injected regression: TTFT jumps 10x, p99 crosses the objective
    for _ in range(200):
        h.observe(0.5)
    alerts = []
    for _ in range(40):
        t[0] += 2.0
        alerts += eng.evaluate()
        if alerts:
            break
    assert alerts, "burn-rate alert did not fire on the regression"
    a = alerts[0]
    assert a.rule == "ttft_slo" and a.kind == "burn_rate"
    assert a.value > 0.2
    assert eng.status()["alerting"]
    assert reg.counter("slo_alerts_total").value(rule="ttft_slo") == 1
    assert reg.gauge("slo_alerting").value(rule="ttft_slo") == 1.0
    # edge-triggered: staying breached does not re-fire
    t[0] += 2.0
    assert eng.evaluate() == []
    assert reg.counter("slo_alerts_total").value(rule="ttft_slo") == 1


def test_burn_rate_needs_every_window_breached():
    """Multi-window semantics: a short blip breaches the fast window but
    not the slow one — no alert (that is the point of the long window)."""
    t = [0.0]
    eng = SLOEngine(MetricRegistry(), clock=lambda: t[0])
    eng.add_burn_rate("r", "lat", objective=0.1, budget=0.5,
                      windows=((2.0, 1.5), (50.0, 1.5)), min_samples=2)
    # long healthy history...
    for _ in range(20):
        t[0] += 2.0
        eng.observe("lat", 0.01)
    # ...then a 2-sample blip: fast window 100% bad (burn 2.0 > 1.5)
    # but the slow window is 2/22 bad (burn ~0.18 < 1.5) — no alert
    for _ in range(2):
        t[0] += 1.0
        eng.observe("lat", 1.0)
    assert eng.evaluate() == []
    r = eng.status()["rules"][0]
    assert not r["alerting"] and r["kind"] == "burn_rate"


def test_regression_detector_loss_spike_and_step_time(telem):
    t = [0.0]
    reg = telem.get_registry()
    eng = SLOEngine(reg, clock=lambda: t[0])
    # recent_s under the 4 s observation spacing: the "recent window"
    # is exactly the newest point, so one spike is enough to fire
    eng.add_regression("loss_spike", "loss", factor=2.0,
                       baseline_s=100.0, recent_s=2.0,
                       min_baseline=5, min_recent=1)
    for _ in range(20):                      # flat baseline at 1.0
        t[0] += 4.0
        eng.observe("loss", 1.0)
        assert eng.evaluate() == []
    t[0] += 4.0
    eng.observe("loss", 3.5)                 # the spike: 3.5x baseline
    alerts = eng.evaluate()
    assert len(alerts) == 1
    a = alerts[0]
    assert a.rule == "loss_spike" and a.kind == "regression"
    assert a.value == pytest.approx(3.5)
    assert "3.50x" in a.message
    rec = a.to_record()
    assert rec["kind"] == "slo_alert" and rec["rule"] == "loss_spike"
    # recovery clears the alerting gauge
    for _ in range(4):
        t[0] += 4.0
        eng.observe("loss", 1.0)
    eng.evaluate()
    assert reg.gauge("slo_alerting").value(rule="loss_spike") == 0.0
    # alerts reached the flight recorder (always-on black box)
    assert any(e["event"] == "slo_alert"
               for e in telemetry.get_flight_recorder().events())


def test_health_degrades_even_with_telemetry_switch_off(tmp_path):
    """The black-box guarantee: with the telemetry master switch OFF
    (registry writes all no-op), a watchdog trip and a live SLO
    engine's alerting state still degrade HEALTHZ — a hang must never
    report 'ok' just because opt-in observability was left off."""
    telemetry.enable(False)
    telemetry.reset()
    try:
        t = [0.0]
        wd = HangWatchdog(name="train", factor=4.0, min_timeout_s=0.5,
                          dump_dir=str(tmp_path),
                          recorder=FlightRecorder(capacity=8, rank=0),
                          clock=lambda: t[0])
        wd.beat()
        t[0] += 10.0
        assert wd.check() is not None        # tripped
        # the disabled registry swallowed the counter...
        assert telemetry.get_registry().snapshot() == {}
        # ...but health still sees the trip via the always-on ledger
        h = telemetry.health_status()
        assert h["status"] == "degraded" and h["watchdog_trips"] == 1
        # same for a live SLO engine's rule state (no registry writes)
        eng = SLOEngine(None, clock=lambda: t[0])
        eng.add_regression("loss_spike", "loss", factor=2.0,
                           baseline_s=100.0, recent_s=2.0,
                           min_baseline=3, min_recent=1)
        for _ in range(5):
            t[0] += 4.0
            eng.observe("loss", 1.0)
            eng.evaluate()
        t[0] += 4.0
        eng.observe("loss", 9.0)
        eng.evaluate()
        h = telemetry.health_status(slo=eng)
        assert "loss_spike" in h["slo"]["alerting_rules"]
    finally:
        telemetry.reset()


def test_watchdog_pause_suspends_checks_across_blocking_ops(tmp_path):
    """pause() covers legitimately long blocking work (checkpoint
    drain, eval) without tripping or poisoning the rolling median."""
    t = [0.0]
    wd = HangWatchdog(name="x", factor=4.0, min_timeout_s=1.0,
                      dump_dir=str(tmp_path),
                      recorder=FlightRecorder(capacity=8, rank=0),
                      registry=MetricRegistry(),
                      clock=lambda: t[0])
    for _ in range(8):
        t[0] += 1.0
        wd.beat()
    wd.pause()
    t[0] += 500.0                     # a long checkpoint drain
    assert wd.check() is None and wd.trips == 0
    wd.resume()
    t[0] += 1.0
    wd.beat()
    # the 500 s pause never entered the median: threshold is still
    # interval-scale, and a real stall after resume still trips
    assert wd.timeout_s() == pytest.approx(4.0)
    t[0] += 50.0
    assert wd.check() is not None and wd.trips == 1


def test_health_status_degrades_on_trips_and_alerts(telem):
    reg = telem.get_registry()
    assert telemetry.health_status(reg)["status"] == "ok"
    reg.counter("watchdog_trips_total").inc(name="train")
    h = telemetry.health_status(reg)
    assert h["status"] == "degraded" and h["watchdog_trips"] == 1
    reg.gauge("slo_alerting").set(1.0, rule="ttft_slo")
    h = telemetry.health_status(reg)
    assert h["slo"]["alerting_rules"] == ["ttft_slo"]


# ---------------------------------------------------------------------------
# prometheus exposition correctness
# ---------------------------------------------------------------------------

def test_prometheus_escapes_labels_and_string_quantiles():
    reg = MetricRegistry()
    reg.counter("c_total", 'help with \\ and\nnewline').inc(
        2, path='a\\b"c\nd')
    h = reg.histogram("lat_seconds")
    for v in (1.0, 2.0, 3.0):
        h.observe(v, stage="p\"q")
    text = reg.to_prometheus()
    # label escaping: backslash, quote, newline (exposition format)
    assert 'c_total{path="a\\\\b\\"c\\nd"} 2.0' in text
    # HELP escapes backslash + newline
    assert "# HELP c_total help with \\\\ and\\nnewline" in text
    # quantile labels are strings, escaped label rides along
    assert 'lat_seconds{quantile="0.5",stage="p\\"q"} 2.0' in text
    assert 'lat_seconds{quantile="0.99",stage="p\\"q"}' in text
    assert 'lat_seconds_count{stage="p\\"q"} 3' in text
    assert 'lat_seconds_sum{stage="p\\"q"} 6.0' in text
    # the in-memory snapshot keys keep the raw (unescaped) form
    assert 'c_total{path="a\\b"c\nd"}' in reg.snapshot()


# ---------------------------------------------------------------------------
# live endpoints: HEALTHZ / METRICS over the coordinator
# ---------------------------------------------------------------------------

def test_healthz_and_metrics_verbs_roundtrip(telem):
    import socket

    from hetu_tpu.rpc.client import CoordinatorClient
    from hetu_tpu.rpc.py_server import PyCoordinatorServer

    reg = telem.get_registry()
    reg.counter("steps_total", "steps run").inc(7)
    reg.histogram("serving_ttft_seconds").observe(0.01)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = PyCoordinatorServer(port)
    srv.start()
    srv.wait_ready()
    try:
        cli = CoordinatorClient(port)
        h = cli.healthz()
        assert h["status"] == "ok"
        assert h["watchdog_trips"] == 0
        assert h["slo"]["alerting_rules"] == []
        assert "serving" not in h            # no engine attached
        text = cli.metrics_text()
        assert "# TYPE steps_total counter" in text
        assert "steps_total 7.0" in text
        assert 'serving_ttft_seconds{quantile="0.99"}' in text
        # degraded state propagates
        reg.counter("watchdog_trips_total").inc(name="serving")
        assert cli.healthz()["status"] == "degraded"
        cli.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# tools: obs_report CLI + metrics-docs lint + trace_summary health
# ---------------------------------------------------------------------------

def test_obs_report_renders_flight_and_slo(tmp_path, capsys):
    from hetu_tpu.tools.obs_report import main
    fr = FlightRecorder(capacity=32, rank=0)
    for i in range(4):
        fr.record("step", step=i)
    fr.record("watchdog_trip", name="train", stalled_s=9.1)
    fr.dump(str(tmp_path / "flight_0.jsonl"), reason="watchdog",
            stacks=True, extra={"watchdog": "train", "stalled_s": 9.1})
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        f.write(json.dumps({
            "kind": "slo_alert", "rule": "ttft_slo",
            "alert_kind": "burn_rate", "series": "serving_ttft_seconds",
            "value": 0.9, "threshold": 0.2, "message": "budget burning",
            "ts_unix": 1.0, "windows": {}}) + "\n")
        f.write(json.dumps({
            "kind": "metrics_snapshot",
            "metrics": {"watchdog_trips_total{name=\"train\"}": 1.0,
                        "slo_alerts_total{rule=\"ttft_slo\"}": 1.0,
                        "slo_alerting{rule=\"ttft_slo\"}": 1.0}}) + "\n")
    assert main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "== flight record" in out
    assert "reason watchdog" in out
    assert "tripped after 9.1s" in out
    assert "watchdog_trip" in out and "step=" in out
    assert "thread stacks" in out
    assert "== SLO verdicts" in out
    assert "ttft_slo" in out and "STILL ALERTING" in out
    assert "watchdog trips   1" in out
    # missing path is a clean error, not a traceback
    assert main([str(tmp_path / "nope.jsonl")]) == 2


def test_check_metrics_docs_lint_is_clean():
    """CI gate: every literal metric name registered under hetu_tpu/
    appears in docs/OBSERVABILITY.md (the operator contract)."""
    from hetu_tpu.tools.check_metrics_docs import (
        missing_from_docs, registered_metric_names,
    )
    names = registered_metric_names()
    # sanity: the scan actually sees the well-known metrics (incl.
    # multi-line registration sites)
    for expect in ("serving_ttft_seconds", "watchdog_trips_total",
                   "slo_alerts_total", "step_cache_hits_total"):
        assert expect in names, f"scanner lost {expect}"
    missing = missing_from_docs()
    assert not missing, (
        "metrics registered in code but undocumented in "
        f"docs/OBSERVABILITY.md: {sorted(missing)} — add a row to the "
        "'What is emitted where' table")


def test_trace_summary_health_section(tmp_path, capsys):
    from hetu_tpu.tools.trace_summary import main
    path = str(tmp_path / "t.jsonl")
    recs = [
        {"kind": "span", "name": "step", "ts_s": 0.0, "dur_s": 1.0,
         "tid": 1, "depth": 0, "attrs": {}},
        {"kind": "slo_alert", "rule": "loss_spike",
         "alert_kind": "regression", "series": "loss", "value": 9.0,
         "threshold": 2.0, "message": "loss 9.0 is 4.5x baseline",
         "ts_unix": 5.0, "windows": {}},
        {"kind": "metrics_snapshot",
         "metrics": {"watchdog_trips_total{name=\"train\"}": 2.0,
                     "slo_alerts_total{rule=\"loss_spike\"}": 1.0,
                     "slo_alerting{rule=\"loss_spike\"}": 0.0}},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "== health ==" in out
    assert "watchdog trips" in out and "HUNG" in out
    assert "loss_spike" in out and "4.5x baseline" in out


def test_trace_summary_recovery_plane_section(tmp_path, capsys):
    from hetu_tpu.tools.trace_summary import main
    path = str(tmp_path / "t.jsonl")
    hist = {"count": 2, "sum": 6.0, "min": 2.8, "max": 3.2,
            "p50": 3.0, "p90": 3.2, "p99": 3.2}
    rec_hist = {"count": 2, "sum": 0.3, "min": 0.1, "max": 0.2,
                "p50": 0.15, "p90": 0.2, "p99": 0.2}
    recs = [
        {"kind": "goodput", "wall_s": 40.0,
         "components": {"compute": 30.0, "checkpoint": 1.5,
                        "recovery": 0.3}, "tokens": 1000, "steps": 12},
        {"kind": "metrics_snapshot", "metrics": {
            'chaos_kills_total{target="w7"}': 1.0,
            'chaos_kills_total{target="w3"}': 1.0,
            'elastic_recoveries_total{mode="live"}': 2.0,
            "elastic_detect_seconds": hist,
            'elastic_recovery_seconds{mode="live"}': rec_hist,
            'heartbeat_send_failures_total{worker="w1"}': 3.0,
            "checkpoint_snapshot_seconds": {
                "count": 12, "sum": 0.12, "min": 0.005, "max": 0.02,
                "p50": 0.01, "p90": 0.02, "p99": 0.02},
            'checkpoint_delta_bytes_total{kind="written"}': 1.5e6,
            'checkpoint_delta_bytes_total{kind="reused"}': 8.5e6}},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert main([path]) == 0
    out = capsys.readouterr().out
    assert "== recovery plane ==" in out
    assert "2 injected" in out and "w7: 1" in out
    assert "recoveries" in out and "live: 2" in out
    assert "detection" in out and "p50 3.00s" in out
    assert "recovery (live)" in out
    assert "3 sends failed" in out
    assert "ckpt snapshot" in out and "10ms step-blocking" in out
    assert "85% saved" in out
    assert "cadence cost" in out and "4.5%" in out


# ---------------------------------------------------------------------------
# serving-engine hang: the injected stalled fake step (no compiles —
# the fused fn is monkeypatched, so this stays quick-tier)
# ---------------------------------------------------------------------------

def test_serving_loop_watchdog_trips_on_stalled_step(telem, tmp_path):
    import numpy as np

    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    import jax
    import jax.numpy as jnp
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    eng = ServingEngine(model, params, slots=2, max_len=32,
                        prefill_chunk=8, watchdog=True,
                        watchdog_factor=4.0,
                        # the floor sits well above any scheduling
                        # stall of a loaded host (six xdist workers):
                        # the healthy phase must not be able to trip
                        watchdog_min_timeout_s=2.0)
    eng.watchdog.poll_s = 0.02
    eng.watchdog.dump_dir = str(tmp_path)   # keep dumps out of the cwd
    hang = threading.Event()

    def fake_fn(params, caches, ctl, pf, bt, cow, spec, wq, lora):
        if hang.is_set():
            time.sleep(4.0)          # the stalled fake step (2x floor)
        # the 9-operand/5-result contract (ISSUE 17 sampled verify
        # lane + ISSUE 20 adapter arena + ISSUE 38): the arena, the
        # pos/last_tok/key carries, and ONE packed vector of what the
        # host reads (the engine's ``_results`` layout): no committed
        # or first token, one commit a slot, the key state as it was;
        # no layer stats from a GPT-2 block
        out = np.zeros(eng._results.size, np.int32)
        res = eng._results.unpack_host(out)         # views of `out`
        res["ncommit"][:] = 1
        res["key"][:] = np.asarray(ctl["key"])
        return (caches, ctl["pos"], ctl["last_tok"], ctl["key"],
                jnp.asarray(out))

    eng._fn = fake_fn
    eng.start(idle_sleep_s=0.001)
    try:
        # healthy churn: requests flow, loop beats, no trip
        eng.generate_many([[1, 2, 3]], SamplingParams(max_tokens=2))
        time.sleep(0.1)
        assert eng.watchdog.trips == 0
        # inject the hang and give it work to stall on
        hang.set()
        eng.submit([4, 5, 6], SamplingParams(max_tokens=2))
        deadline = time.monotonic() + 5.0
        while eng.watchdog.trips == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.watchdog.trips >= 1, \
            "serving watchdog did not trip on the stalled step"
        assert telem.get_registry().counter(
            "watchdog_trips_total").value(name="serving") >= 1
        # the postmortem exists and records the serving lifecycle
        recs = [json.loads(ln)
                for ln in open(tmp_path / f"flight_0.{os.getpid()}.jsonl")]
        assert recs[0]["reason"] == "watchdog"
        evs = {r.get("event") for r in recs}
        assert "serving_submit" in evs and "watchdog_trip" in evs
        assert any(r["kind"] == "thread_stacks" for r in recs)
        hang.clear()
    finally:
        hang.clear()
        eng.stop()
