"""Fleet-wide observability (ISSUE 16): cross-process request tracing,
RPC wire instrumentation, and the federated metrics/health plane.

Quick tier is HOST-SIDE only (stub engines behind real line-protocol
sockets — no compiles): traceparent encode/parse/propagation, the
NTP-style clock-offset handshake against a deliberately skewed server
clock, Prometheus federation merge correctness (label collision +
escaping + fleet totals), FLEETMETRICS / fleet-HEALTHZ end to end,
DUMPOBS bundles, the fleet_trace merge math on synthetic skewed
bundles, fleet_top rendering, flight-dump identity, and the
weight-push / chaos-kill trace-stamp correlation. The real
multi-process P/D-split merged-trace acceptance test is slow-marked
(two jax engine processes)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.rpc.client import CoordinatorClient
from hetu_tpu.rpc.py_server import PyCoordinatorServer
from hetu_tpu.serving.fleet import RemoteEngineProxy
from hetu_tpu.serving.router import Router, WeightPublisher
from hetu_tpu.serving.scheduler import Request, SamplingParams
from hetu_tpu.telemetry.federation import (
    FLEET_REPLICA, merge_prometheus, parse_prometheus,
)
from hetu_tpu.telemetry.tracecontext import (
    TRACEPARENT_VERBS, current_traceparent, make_traceparent,
    parse_traceparent, use_trace,
)
from hetu_tpu.tools import fleet_trace


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable(True)
    yield telemetry
    telemetry.enable(False)
    telemetry.reset()


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKERS = os.path.join(_REPO, "tests", "workers")


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _StubEngine:
    """Host-side echo engine (test_fleet idiom): completes a request
    with ``prompt[:max_tokens]``; adopts wire trace context the way the
    real engine does; swappable so the publisher path runs."""

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s
        self.weight_version = 0
        self._plan = None                    # materialize_params path
        self._next = 0
        self._lock = threading.Lock()
        self.requests: list[Request] = []
        self._requests_by_id: dict[int, Request] = {}  # RPC poll map
        self._thread = None                  # ReplicaHandle.loop_died

        class _Sched:
            depth = 0
            occupancy = 0.0
        self.scheduler = _Sched()

    @property
    def load(self):
        return sum(1 for r in self.requests if not r.done.is_set())

    def has_work(self):
        return self.load > 0

    def submit(self, prompt, sampling=None, *, resume=None,
               handoff=False, traceparent=None):
        sampling = sampling or SamplingParams()
        with self._lock:
            req = Request(id=self._next,
                          prompt=np.asarray(prompt, np.int32).ravel(),
                          sampling=sampling, submit_s=time.monotonic())
            self._next += 1
            self.requests.append(req)
        if traceparent:
            tid, _span = telemetry.parse_traceparent(traceparent)
            if tid:
                req.trace_id = tid
                req.traceparent = traceparent

        def finish():
            if self.delay_s:
                time.sleep(self.delay_s)
            req.tokens = [int(t) for t in
                          req.prompt[:sampling.max_tokens]]
            req.status = "done"
            req.first_token_s = time.monotonic()
            req.done.set()

        threading.Thread(target=finish, daemon=True).start()
        return req

    def result(self, req, timeout=None):
        if not req.done.wait(timeout):
            return None
        return req.result()

    def cancel_queued(self, ids=None):
        return []

    def evict_request(self, req, *, lock_timeout_s=None):
        return None

    def swap_params(self, params, *, version=None):
        self.weight_version = int(version or self.weight_version + 1)
        return {"version": self.weight_version, "flushed_blocks": 0}

    def start(self):
        pass

    def stop(self):
        pass


def _serve_stub(stub):
    port = _free_port()
    srv = PyCoordinatorServer(port, serving=stub)
    srv.start()
    srv.wait_ready()
    return srv, port


# -- traceparent primitives ---------------------------------------------------


def test_traceparent_roundtrip_and_junk():
    tp = make_traceparent("ab12cd34ef56")
    tid, span = parse_traceparent(tp)
    assert tid == "ab12cd34ef56" and len(span) == 8
    # explicit span id round-trips
    assert parse_traceparent(make_traceparent("ab12cd34ef56",
                                              "00aa11bb")) \
        == ("ab12cd34ef56", "00aa11bb")
    # junk degrades to (None, None), never raises
    for junk in ("", "nope", "xyz-123", "ab12-", "-ab12",
                 "ab12cd34ef56", None, "g" * 12 + "-" + "h" * 8):
        assert parse_traceparent(junk) == (None, None)


def test_use_trace_is_cross_thread_and_nested():
    """The active trace is process-global (a chaos soak thread must see
    the publisher thread's push), nests, and tolerates None."""
    assert current_traceparent() is None
    tp1, tp2 = make_traceparent("a" * 12), make_traceparent("b" * 12)
    with use_trace(tp1):
        assert current_traceparent() == tp1
        seen = {}

        def other_thread():
            seen["tp"] = current_traceparent()
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
        assert seen["tp"] == tp1
        with use_trace(tp2):
            assert current_traceparent() == tp2
        with use_trace(None):                # no-op
            assert current_traceparent() == tp1
    assert current_traceparent() is None


def test_traceparent_verbs_exist_and_docs_lint_passes():
    """Every traceparent-carrying verb is a real serving verb, and the
    doc lint (metric names + verb table rows) passes — the satellite
    that keeps docs/OBSERVABILITY.md honest."""
    from hetu_tpu.serving.server import SERVING_COMMANDS
    from hetu_tpu.tools.check_metrics_docs import (
        missing_from_docs, missing_traceparent_verbs,
    )
    assert set(TRACEPARENT_VERBS) <= set(SERVING_COMMANDS)
    assert {"DUMPOBS", "FLEETMETRICS"} <= set(SERVING_COMMANDS)
    assert missing_from_docs() == {}
    assert missing_traceparent_verbs() == []


# -- propagation over the wire ------------------------------------------------


def test_submit_traceparent_propagates_over_stub_socket(telem):
    """SUBMIT carries the traceparent; the engine across the socket
    adopts the trace id — its local spans/flight events join the
    upstream trace."""
    stub = _StubEngine()
    srv, port = _serve_stub(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        tp = make_traceparent("feedfacecafe")
        doc = cli.serving_submit_info([1, 2, 3], max_tokens=2,
                                      traceparent=tp)
        assert doc["trace_id"] == "feedfacecafe"
        assert stub.requests[0].trace_id == "feedfacecafe"
        assert stub.requests[0].traceparent == tp
        cli.close()
    finally:
        srv.stop()


def test_router_dispatch_mints_hop_span_under_one_trace(telem):
    """Router.submit adopts an upstream traceparent; each dispatch hop
    mints a FRESH span id under the SAME trace id, and the replica
    across the wire adopts it."""
    stub = _StubEngine()
    srv, port = _serve_stub(stub)
    router = Router(poll_s=0.01)
    try:
        router.register("s0", RemoteEngineProxy(port, poll_s=0.02))
        up_tp = make_traceparent("0123456789ab")
        rreq = router.submit([5, 6, 7], SamplingParams(max_tokens=2),
                             traceparent=up_tp)
        assert rreq.done.wait(10.0)
        assert rreq.trace_id == "0123456789ab"
        req = stub.requests[0]
        assert req.trace_id == "0123456789ab"
        # a fresh span id per hop: the replica saw a traceparent under
        # the same trace, but not the upstream caller's span id
        tid, span = parse_traceparent(req.traceparent)
        assert tid == "0123456789ab"
        assert req.traceparent != up_tp
    finally:
        router.stop()
        srv.stop()


# -- clock-offset handshake ---------------------------------------------------


def test_clock_offset_measured_against_skewed_server(telem, monkeypatch):
    """ESTATUS stamps the server's wall clock; the proxy computes the
    NTP-style offset. Skew the SERVER side's clock by +5 s and the
    measured offset must land on it."""
    import hetu_tpu.serving.server as server_mod
    real_time = time

    class _Skewed:
        def __getattr__(self, name):
            return getattr(real_time, name)

        @staticmethod
        def time():
            return real_time.time() + 5.0

    stub = _StubEngine()
    srv, port = _serve_stub(stub)
    monkeypatch.setattr(server_mod, "time", _Skewed())
    try:
        proxy = RemoteEngineProxy(port, poll_s=60.0)
        assert proxy._poll_once()
        assert 4.5 < proxy.clock_offset_s < 5.5
        g = telemetry.get_registry().gauge(
            "fleet_clock_skew_seconds", "")
        assert 4.5 < g.value(replica=f":{port}") < 5.5
        proxy.stop()
    finally:
        srv.stop()


def test_clock_offset_math_with_fake_timestamps():
    """The offset formula itself: server stamp minus RTT midpoint."""
    t0, t1 = 100.0, 100.2                    # 200 ms round trip
    srv_ts = 150.1                           # server is +50 s, mid-RTT
    off = float(srv_ts) - 0.5 * (t0 + t1)
    assert abs(off - 50.0) < 1e-9


# -- federation merge ---------------------------------------------------------


def test_merge_prometheus_labels_escaping_and_fleet_totals():
    r0 = ('# HELP reqs_total requests\n'
          '# TYPE reqs_total counter\n'
          'reqs_total{route="a"} 3\n'
          'reqs_total{route="b"} 1\n'
          '# TYPE occupancy gauge\n'
          'occupancy 0.5\n'
          'untyped_mystery 7\n')
    r1 = ('# HELP reqs_total requests\n'
          '# TYPE reqs_total counter\n'
          'reqs_total{route="a"} 4\n'
          # a pre-existing replica label must survive as orig_replica,
          # not silently collide with the federation label
          'weird_total{replica="inner"} 2\n'
          'occupancy 0.25\n')
    merged = merge_prometheus({'e"vil\\name': r0, "r1": r1})
    meta, samples = parse_prometheus(merged)
    by = {}
    for name, labels, value in samples:
        by[(name, tuple(sorted(labels.items())))] = value
    # the evil replica name round-trips through escaping
    assert by[("reqs_total", (("replica", 'e"vil\\name'),
                              ("route", "a")))] == 3
    assert by[("reqs_total", (("replica", "r1"),
                              ("route", "a")))] == 4
    # fleet totals sum across replicas, grouped by original labels
    assert by[("reqs_total", (("replica", FLEET_REPLICA),
                              ("route", "a")))] == 7
    assert by[("reqs_total", (("replica", FLEET_REPLICA),
                              ("route", "b")))] == 1
    assert by[("occupancy", (("replica", FLEET_REPLICA),))] == 0.75
    # untyped non-_total series must NOT invent a fleet total
    assert ("untyped_mystery",
            (("replica", FLEET_REPLICA),)) not in by
    # label collision: inner replica label preserved
    assert by[("weird_total", (("orig_replica", "inner"),
                               ("replica", "r1")))] == 2
    # HELP/TYPE once per family despite two contributors
    assert merged.count("# TYPE reqs_total counter") == 1


def test_merge_prometheus_quantiles_never_aggregate():
    text = ('# TYPE lat_ms summary\n'
            'lat_ms{quantile="0.5"} 2.0\n'
            'lat_ms_count 10\n'
            'lat_ms_sum 25.0\n')
    merged = merge_prometheus({"r0": text, "r1": text})
    _meta, samples = parse_prometheus(merged)
    fleet = [(n, l, v) for n, l, v in samples
             if l.get("replica") == FLEET_REPLICA]
    names = {n for n, _l, _v in fleet}
    # count/sum aggregate; the quantile series must not
    assert "lat_ms_count" in names and "lat_ms_sum" in names
    assert not any(l.get("quantile") for _n, l, _v in fleet)
    by = {n: v for n, _l, v in fleet}
    assert by["lat_ms_count"] == 20 and by["lat_ms_sum"] == 50.0


def test_health_rollup_names_degraded_replicas():
    from hetu_tpu.telemetry.federation import health_rollup
    ok = health_rollup({"a": {"status": "ok"}, "b": {"status": "ok"}})
    assert ok["status"] == "ok" and ok["degraded"] == []
    bad = health_rollup({"a": {"status": "ok"},
                         "b": {"status": "degraded"},
                         "c": {"status": "unreachable"}})
    assert bad["status"] == "degraded"
    assert bad["degraded"] == ["b", "c"]
    assert bad["replicas_ok"] == 1 and bad["replicas_total"] == 3
    assert health_rollup({})["status"] == "degraded"


def test_fleetmetrics_and_fleet_healthz_end_to_end(telem):
    """TENTPOLE acceptance (quick half): a Router front door over two
    remote stub replicas serves one federated Prometheus page and a
    fleet HEALTHZ rollup that NAMES the degraded replica — validated
    over real sockets."""
    s0, p0 = _serve_stub(_StubEngine())
    s1, p1 = _serve_stub(_StubEngine())
    router = Router(poll_s=0.01, scrape_every_s=0.05)
    fport = _free_port()
    front = PyCoordinatorServer(fport, serving=router)
    front.start()
    front.wait_ready()
    try:
        router.register("s0", RemoteEngineProxy(p0, poll_s=0.02))
        router.register("s1", RemoteEngineProxy(p1, poll_s=0.02))
        telem.get_registry().counter("fedtest_total", "probe").inc(5)
        cli = CoordinatorClient(fport, timeout=5.0)
        # the monitor may have scraped between the two register()
        # calls: a round cached then names s0 alone until it is older
        # than scrape_every_s (and a slow older round can land after a
        # newer one) — wait for a round that saw both
        def settled(fetch, seen):
            deadline = time.monotonic() + 5.0
            got = fetch()
            while not seen(got) and time.monotonic() < deadline:
                time.sleep(0.06)             # past scrape_every_s
                got = fetch()
            return got

        text = settled(cli.fleet_metrics_text,
                       lambda t: 'replica="s1"' in t)
        assert 'replica="s0"' in text and 'replica="s1"' in text
        assert f'replica="{FLEET_REPLICA}"' in text
        assert 'replica="_local"' in text
        hz = settled(cli.healthz,
                     lambda h: "s1" in h["fleet"]["replicas"])
        fleet = hz["fleet"]
        assert set(fleet["replicas"]) == {"s0", "s1"}
        assert fleet["replicas_total"] == 2
        assert fleet["status"] == "ok" and fleet["degraded"] == []
        # scrape outcome ledger recorded rounds for both replicas
        snap = telem.get_registry().snapshot()
        assert snap.get(
            'fleet_scrapes_total{outcome="ok",replica="s0"}', 0) >= 1
        # a draining replica degrades the rollup BY NAME
        cli.fleet_drain("s0")
        time.sleep(0.1)                      # past scrape_every_s
        fleet = cli.healthz()["fleet"]
        assert fleet["status"] == "degraded"
        assert "s0" in fleet["degraded"]
        cli.fleet_resume("s0")
        cli.close()
    finally:
        router.stop()
        front.stop()
        s0.stop()
        s1.stop()


# -- DUMPOBS + fleet_trace merge ----------------------------------------------


def test_dumpobs_bundle_over_wire(telem):
    stub = _StubEngine()
    srv, port = _serve_stub(stub)
    try:
        telem.get_tracer().complete("probe_span", 0.001)
        telem.get_flight_recorder().record("probe_event", x=1)
        cli = CoordinatorClient(port, timeout=5.0)
        b = cli.dump_obs()
        assert b["pid"] == os.getpid()
        assert b["epoch_unix"] > 0
        names = {ev.get("name")
                 for ev in b["chrome"]["traceEvents"]}
        assert "probe_span" in names
        assert any(ev["event"] == "probe_event" for ev in b["flight"])
        cli.close()
    finally:
        srv.stop()


def _bundle(name, epoch_unix, *, trace_id=None, spans=(), flight=(),
            pid=1000):
    """A synthetic DUMPOBS bundle: ``spans`` = (name, ts_us, dur_us)
    on the request track for ``trace_id``."""
    evs = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "hetu_tpu"}}]
    if trace_id:
        evs.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": 77, "args": {"name": f"req {trace_id}"}})
    for sname, ts, dur in spans:
        evs.append({"name": sname, "ph": "X", "cat": "request",
                    "ts": ts, "dur": dur, "pid": pid, "tid": 77,
                    "args": {}})
    return {"replica": name, "pid": pid, "epoch_unix": epoch_unix,
            "chrome": {"traceEvents": evs}, "flight": list(flight)}


def test_fleet_trace_merge_aligns_skewed_clocks_into_one_track():
    """Two processes, the second with a +50 s wall clock: the merged
    request track must order spans by REAL time (offset-corrected),
    not by raw clocks, and hold them on ONE unified track."""
    tid = "abc123abc123"
    router_b = _bundle("router", 1000.0, trace_id=tid,
                       spans=[("dispatch", 1_000.0, 500.0)])
    # replica wall clock is +50 s; its decode truly started 0.2 s
    # after the router's epoch
    replica_b = _bundle("r0", 1050.2, trace_id=tid, pid=2000,
                        spans=[("decode", 0.0, 10_000.0)],
                        flight=[{"kind": "flight_event", "seq": 1,
                                 "ts_unix": 1050.25, "tid": 9,
                                 "event": "serving_finish",
                                 "trace": tid}])
    merged = fleet_trace.merge_bundles(
        [router_b, replica_b], offsets={"r0": 50.0}, master="router")
    track = fleet_trace.request_track(merged, tid)
    assert fleet_trace.span_order(merged, tid) == ["dispatch", "decode"]
    by_name = {ev["name"]: ev for ev in track}
    assert abs(by_name["decode"]["ts"] - 200_000.0) < 1.0
    # the mirrored flight instant sits on the same unified track
    finish = [ev for ev in track if ev["name"] == "serving_finish"]
    assert len(finish) == 1 and abs(finish[0]["ts"] - 250_000.0) < 1.0
    # one REQUESTS track for the trace_id across both processes
    req_meta = [ev for ev in merged["traceEvents"]
                if ev.get("ph") == "M"
                and ev.get("pid") == fleet_trace.REQ_PID
                and ev.get("name") == "thread_name"]
    assert len(req_meta) == 1
    assert req_meta[0]["args"]["name"] == f"req {tid}"
    # without the offset, decode would land 50 s out — sanity-check the
    # correction actually happened
    raw = fleet_trace.merge_bundles([router_b, replica_b],
                                    master="router")
    assert fleet_trace.request_track(raw, tid)[-1]["ts"] > 10_000_000


def test_fleet_trace_cli_merges_files(tmp_path):
    tid = "c0ffee000001"
    b0 = _bundle("router", 500.0, trace_id=tid,
                 spans=[("dispatch", 10.0, 5.0)])
    b1 = _bundle("r0", 500.1, trace_id=tid, pid=2000,
                 spans=[("decode", 0.0, 100.0)])
    p0, p1 = tmp_path / "router.json", tmp_path / "r0.json"
    p0.write_text(json.dumps(b0))
    p1.write_text(json.dumps(b1))
    out = tmp_path / "merged.json"
    rc = fleet_trace.main([str(p0), str(p1), "--master", "router",
                           "--out", str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert fleet_trace.span_order(merged, tid) == ["dispatch", "decode"]


# -- fleet_top ----------------------------------------------------------------


_CANNED_FLEETMETRICS = '\n'.join([
    '# TYPE router_replica_load gauge',
    'router_replica_load{orig_replica="r0",replica="_local"} 3',
    'router_replica_load{orig_replica="r1",replica="_local"} 1',
    'fleet_replica_beat_age_seconds{orig_replica="r1",'
    'replica="_local"} 0.25',
    'fleet_clock_skew_seconds{orig_replica="r1",replica="_local"}'
    ' 0.012',
    'serving_queue_depth{replica="r0"} 2',
    'serving_slot_occupancy{replica="r0"} 0.5',
    'rpc_client_verb_ms{quantile="0.5",replica="_local",'
    'verb="SUBMIT"} 0.42',
    'rpc_client_verb_ms_count{replica="_local",verb="SUBMIT"} 12',
    'rpc_client_verb_ms{quantile="0.5",replica="_local",'
    'verb="RESULT"} 0.15',
    'rpc_client_verb_ms_count{replica="_local",verb="RESULT"} 90',
]) + '\n'


def test_fleet_top_renders_canned_snapshot(tmp_path, capsys):
    from hetu_tpu.tools import fleet_top
    out = fleet_top.render(_CANNED_FLEETMETRICS)
    assert "r0" in out and "r1" in out
    assert "2" in out                        # r0 queue depth
    assert "RESULT" in out and "SUBMIT" in out
    # RESULT is hotter (90 calls) — listed first
    assert out.index("RESULT") < out.index("SUBMIT")
    # --once --snapshot renders and exits 0
    snap = tmp_path / "fleet.prom"
    snap.write_text(_CANNED_FLEETMETRICS)
    rc = fleet_top.main(["--snapshot", str(snap), "--once"])
    assert rc == 0
    assert "r0" in capsys.readouterr().out


def test_fleet_top_tolerates_empty_page():
    from hetu_tpu.tools import fleet_top
    out = fleet_top.render("")
    assert "0 replicas" in out


# -- flight identity + obs_report ---------------------------------------------


def test_flight_dump_identity_and_pid_suffix(tmp_path):
    from hetu_tpu.telemetry.flight import FlightRecorder
    rec = FlightRecorder(capacity=16, rank=0)
    rec.set_identity(replica="r7", role="prefill")
    path = rec.default_path(dir=str(tmp_path))
    assert os.path.basename(path) == f"flight_0.{os.getpid()}.jsonl"
    rec.record("x", a=1)
    rec.dump(path)
    header = json.loads(open(path).readline())
    assert header["replica"] == "r7" and header["role"] == "prefill"


def test_obs_report_fleet_overview_groups_processes(tmp_path):
    from hetu_tpu.tools import obs_report
    from hetu_tpu.telemetry.flight import FlightRecorder
    for name, role, pid in (("pre", "prefill", 111),
                            ("dec", "decode", 222)):
        rec = FlightRecorder(capacity=8, rank=0)
        rec.set_identity(replica=name, role=role)
        rec.record("step", i=1)
        # distinct pids in the NAME (the collision fix) — fake them,
        # one process writes both in this test
        rec.dump(str(tmp_path / f"flight_0.{pid}.jsonl"))
    text = obs_report.report(str(tmp_path))
    assert "fleet overview (2 processes)" in text
    assert "pre" in text and "decode" in text
    # per-dump headers carry the identity too
    assert "replica pre (prefill)" in text


# -- trace-stamped weight pushes + chaos kills --------------------------------


def test_weight_push_and_chaos_kill_share_one_trace(telem):
    """SATELLITE: a publish mints a push trace; a chaos kill landing
    mid-push (from ANOTHER thread) stamps the same trace, and the
    merged timeline puts both on one track."""
    from hetu_tpu.engine.chaos import ChaosMonkey
    stub = _StubEngine()
    router = Router(poll_s=0.01)
    seen = {}
    try:
        router.register("s0", stub)
        monkey = ChaosMonkey({"noop": lambda: None})
        pub = WeightPublisher(router, drain_timeout_s=5.0)

        real_swap = stub.swap_params

        def swap_with_kill(params, *, version=None):
            # the soak thread's view: the kill must observe the
            # publisher thread's active trace
            def kill():
                monkey.kill("noop")
                seen["tp"] = current_traceparent()
            t = threading.Thread(target=kill)
            t.start()
            t.join()
            return real_swap(params, version=version)

        stub.swap_params = swap_with_kill
        report = pub.publish({"w": np.zeros(2, np.float32)})
        assert "trace" in report
        push_tid, _span = parse_traceparent(report["trace"])
        assert push_tid
        assert seen["tp"] == report["trace"]
        events = telem.get_flight_recorder().events()
        pushes = [e for e in events if e["event"] == "weight_push"]
        kills = [e for e in events if e["event"] == "chaos_kill"]
        assert pushes and pushes[-1]["trace"] == report["trace"]
        assert kills and kills[-1]["trace"] == report["trace"]
        assert monkey.kills[-1]["trace"] == report["trace"]
        # merged timeline: both events mirror onto the push's track
        bundle = {"replica": "router", "pid": os.getpid(),
                  "epoch_unix": telem.get_flight_recorder().epoch_unix,
                  "chrome": telem.get_tracer().to_chrome(),
                  "flight": events}
        merged = fleet_trace.merge_bundles([bundle])
        track = fleet_trace.request_track(merged, push_tid)
        names = [ev["name"] for ev in track]
        assert "weight_push" in names and "chaos_kill" in names
    finally:
        router.stop()


def test_chaos_kill_without_active_trace_is_unstamped(telem):
    from hetu_tpu.engine.chaos import ChaosMonkey
    monkey = ChaosMonkey({"noop": lambda: None})
    monkey.kill("noop")
    kills = [e for e in telem.get_flight_recorder().events()
             if e["event"] == "chaos_kill"]
    assert kills and "trace" not in kills[-1]


# -- RPC wire instrumentation -------------------------------------------------


def test_rpc_verb_instrumentation_both_ends(telem):
    """Client and server histograms/byte counters land per verb; the
    dir labels (tx/rx vs in/out) keep both ends separable in one
    registry."""
    stub = _StubEngine()
    srv, port = _serve_stub(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        cli.serving_submit_info([1, 2, 3], max_tokens=2)
        cli.ping()
        cli.close()
        snap = telem.get_registry().snapshot()
        c = snap['rpc_client_verb_ms{verb="SUBMIT"}']
        s = snap['rpc_server_verb_ms{verb="SUBMIT"}']
        assert c["count"] >= 1 and s["count"] >= 1
        # the client measures the full round trip; the server only its
        # handling slice of the SAME call
        assert snap['rpc_payload_bytes_total{dir="tx",verb="SUBMIT"}'] \
            > 0
        assert snap['rpc_payload_bytes_total{dir="in",verb="SUBMIT"}'] \
            > 0
    finally:
        srv.stop()


def test_result_empty_polls_counted(telem):
    stub = _StubEngine(delay_s=0.3)
    srv, port = _serve_stub(stub)
    router = Router(poll_s=0.01)
    try:
        router.register("s0", RemoteEngineProxy(port, poll_s=0.01))
        rreq = router.submit([4, 4, 4], SamplingParams(max_tokens=2))
        assert rreq.done.wait(10.0)
        snap = telem.get_registry().snapshot()
        assert snap.get("router_result_poll_empty_total", 0) >= 1
    finally:
        router.stop()
        srv.stop()


# -- slow: the real multi-process merged trace --------------------------------


@pytest.mark.slow
def test_pd_split_fleet_request_merges_into_one_ordered_trace(tmp_path):
    """TENTPOLE acceptance (slow half): a P/D-split request through a
    real two-process fleet produces ONE merged Perfetto trace whose
    request track orders router dispatch → prefill → KV handoff →
    decode on the master clock."""
    from hetu_tpu.rpc.launcher import launch_serving_fleet
    telemetry.reset()
    telemetry.enable(True)
    fleet = launch_serving_fleet(
        n_replicas=2, names=["pre", "dec"],
        roles={"pre": "prefill", "dec": "decode"},
        remote=True, engine_spec="fleet_engine:build_engine",
        env={"PYTHONPATH": f"{_REPO}:{_WORKERS}",
             "HETU_TELEMETRY": "1"},
        beat_timeout_s=10.0, poll_s=0.005, spawn_timeout_s=180.0)
    try:
        rreq = fleet.router.submit(
            [5, 6, 7, 8, 9, 10], SamplingParams(max_tokens=4))
        assert rreq.done.wait(120.0), "fleet request never finished"
        assert rreq.status == "done"
        tid = rreq.trace_id
        # collect: DUMPOBS from each engine process + the router's own
        bundles = [{
            "replica": "router", "pid": os.getpid(),
            "epoch_unix": telemetry.get_tracer().epoch_unix,
            "chrome": telemetry.get_tracer().to_chrome(),
            "flight": telemetry.get_flight_recorder().events(),
        }]
        offsets = {"router": 0.0}
        for name in ("pre", "dec"):
            h = fleet.router._replicas[name]
            bundles.append(h.engine.dump_obs())
            offsets[name] = h.status()["clock_offset_s"]
        merged = fleet_trace.merge_bundles(bundles, offsets=offsets,
                                           master="router")
        out = tmp_path / "fleet_trace.json"
        out.write_text(json.dumps(merged))
        order = fleet_trace.span_order(merged, tid)
        assert "dispatch" in order, order
        assert "prefill_chunk" in order, order
        assert "kv_handoff" in order, order
        assert "decode" in order, order
        # the P/D phases appear in causal order on the merged clock
        assert order.index("dispatch") \
            < order.index("prefill_chunk") \
            < order.index("kv_handoff") \
            < order.index("decode"), order
        # spans start monotonically (request_track sorts by ts; every
        # ts must be finite and non-negative after alignment)
        track = fleet_trace.request_track(merged, tid)
        ts = [ev["ts"] for ev in track]
        assert all(t >= 0.0 for t in ts)
        assert ts == sorted(ts)
        # fragments really came from three processes
        replicas = {ev["args"].get("replica") for ev in track
                    if ev.get("ph") == "X"}
        assert {"router", "pre", "dec"} <= replicas
    finally:
        fleet.stop()
        telemetry.enable(False)
        telemetry.reset()
