"""Streaming control plane (ISSUE 19): push-based RESULT delivery over
one persistent multiplexed channel, end-to-end token streaming.

Quick tier is HOST-SIDE only (stub engines behind a real coordinator —
no compiles): frame codec, protocol sniff + mixed line/stream clients
on one listener, stream-submit → push → trailing result, subscribe-at-
offset replay, slow-subscriber drop-to-poll, the IdemMap TTL/LRU bound,
client reconnect-at-offset, and the proxy's push lane (RESULT polls ~0,
ESTATUS stretched to heartbeat cadence, SIGKILL reaped within
``beat_timeout_s``). The compile-bearing acceptance matrix — stream vs
one-shot bitwise identity at 1 compile, socket-kill resume on a real
engine, mixed streaming+polling clients — is slow-marked per the
quick-tier time budget.
"""

import io
import socket
import threading
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.rpc.client import CoordinatorClient
from hetu_tpu.rpc.py_server import PyCoordinatorServer
from hetu_tpu.rpc.stream import StreamChannel, read_frame, write_frame
from hetu_tpu.serving.fleet import RemoteEngineProxy
from hetu_tpu.serving.router import Router
from hetu_tpu.serving.scheduler import Request, SamplingParams
from hetu_tpu.serving.server import IdemMap
from hetu_tpu.serving.streaming import TokenSubscription, push_delta


@pytest.fixture()
def tele():
    telemetry.enable(True)
    yield telemetry.get_registry()
    telemetry.enable(False)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- stub engine: streams host-side, zero compiles ----------------------------


class _StreamStub:
    """Echo engine with the full streaming duck type: a submitted
    request commits ``prompt[:max_tokens]`` one token per ``step_s``
    tick, pumping subscriptions after each commit exactly like
    ``ServingEngine._pump_stream_subs``."""

    def __init__(self, step_s: float = 0.01, start_delay_s: float = 0.0):
        self.step_s = step_s
        self.start_delay_s = start_delay_s
        self.weight_version = 0
        self.submits = 0
        self.estatus_calls = 0
        self._next = 0
        self._requests_by_id: dict[int, Request] = {}
        self._lock = threading.Lock()
        self._stream_subs: dict[int, tuple] = {}
        self._stream_lock = threading.Lock()
        self._thread = None          # externally driven (ReplicaHandle)

        class _Sched:
            depth = 0
            occupancy = 0.0
        self.scheduler = _Sched()

    @property
    def load(self):
        return sum(1 for r in self._requests_by_id.values()
                   if not r.done.is_set())

    def has_work(self):
        self.estatus_calls += 1      # only ESTATUS touches this here
        return self.load > 0

    def submit(self, prompt, sampling=None, *, resume=None,
               handoff=False, traceparent=None):
        sampling = sampling or SamplingParams()
        with self._lock:
            req = Request(id=self._next,
                          prompt=np.asarray(prompt, np.int32).ravel(),
                          sampling=sampling, submit_s=time.monotonic())
            self._next += 1
            self.submits += 1
        if traceparent:
            tid, _span = telemetry.parse_traceparent(traceparent)
            if tid:
                req.trace_id = tid
                req.traceparent = traceparent
        if resume is not None:
            req.spill = resume
            req.tokens = list(resume.tokens)

        def run():
            if self.start_delay_s:
                time.sleep(self.start_delay_s)
            out = [int(t) for t in req.prompt[:sampling.max_tokens]]
            for i, t in enumerate(out[len(req.tokens):]):
                time.sleep(self.step_s)
                req.tokens.append(t)
                if req.first_token_s is None:
                    req.first_token_s = time.monotonic()
                self._pump(req)
            req.status = "done"
            req.done.set()
            self._pump(req)              # terminal frame

        threading.Thread(target=run, daemon=True).start()
        return req

    def stream_subscribe(self, req, *, offset=0, max_queue=256):
        sub = TokenSubscription(req.id, offset=offset,
                                max_queue=max_queue)
        with self._stream_lock:
            push_delta(req, sub)         # backlog replay from offset
            if not sub.closed:
                self._stream_subs.setdefault(req.id, []).append(sub)
        return sub

    def _pump(self, req):
        with self._stream_lock:
            subs = self._stream_subs.get(req.id, [])
            live = []
            for sub in subs:
                push_delta(req, sub)
                if not (sub.closed or sub.dropped):
                    live.append(sub)
            if live:
                self._stream_subs[req.id] = live
            else:
                self._stream_subs.pop(req.id, None)

    def result(self, req, timeout=None):
        if not req.done.wait(timeout):
            return None
        return req.result()

    def cancel_queued(self, ids=None):
        return []

    def evict_request(self, req, *, lock_timeout_s=None):
        return None

    def start(self):
        pass

    def stop(self):
        pass


def _serve(stub, token=""):
    port = _free_port()
    srv = PyCoordinatorServer(port, serving=stub, token=token)
    srv.start()
    srv.wait_ready()
    return srv, port


def _collect(timeout=5.0):
    """An event sink + waiter: returns (sink, events, done_event)."""
    events, done = [], threading.Event()

    def sink(fr):
        events.append(fr)
        if fr.get("k") != "ev" or fr.get("done") or fr.get("end"):
            done.set()
    return sink, events, done


def _tokens_of(events):
    out = []
    for fr in events:
        if fr.get("k") == "ev":
            assert int(fr["off"]) == len(out), \
                f"offset gap: {fr['off']} != {len(out)}"
            out.extend(int(t) for t in fr["toks"])
    return out


# -- quick: frame codec -------------------------------------------------------


def test_frame_roundtrip_and_corruption():
    """Length-framed compact JSON survives a write→read roundtrip;
    corrupt length prefixes raise instead of allocating garbage."""
    buf = io.BytesIO()
    lock = threading.Lock()
    frames = [{"k": "ev", "sid": 3, "off": 0, "toks": [1, 2, 3]},
              {"k": "pong", "sid": 9},
              {"k": "res", "sid": 1, "line": "VAL x" * 100}]
    for fr in frames:
        write_frame(buf, lock, fr, direction="tx")
    buf.seek(0)
    for fr in frames:
        assert read_frame(buf, direction="rx") == fr
    assert read_frame(buf, direction="rx") is None     # clean EOF
    # corrupt length prefix: enormous
    bad = io.BytesIO((1 << 30).to_bytes(4, "big") + b"{}")
    with pytest.raises(ValueError):
        read_frame(bad, direction="rx")
    # truncated body
    bad = io.BytesIO((10).to_bytes(4, "big") + b"{}")
    with pytest.raises(ValueError):
        read_frame(bad, direction="rx")


# -- quick: idempotency map bound (SATELLITE) ---------------------------------


def test_idem_map_ttl_and_lru_eviction(tele):
    """SATELLITE: the dedup map is BOUNDED — finished entries expire
    after the TTL window, the cap evicts least-recently-used (done
    first), hits refresh both recency and deadline, and in-flight
    entries survive preferentially. Evictions are counted."""
    m = IdemMap(max_entries=3, ttl_s=10.0)

    def req(done=True):
        r = Request(id=0, prompt=np.zeros(1, np.int32),
                    sampling=SamplingParams(), submit_s=0.0)
        if done:
            r.done.set()
        return r

    a, b, c = req(), req(), req()
    m.put("a", a, now=0.0)
    m.put("b", b, now=1.0)
    m.put("c", c, now=2.0)
    assert len(m) == 3
    # TTL: at t=11, "a" (deadline 10) is gone; a GET refreshed "b"
    assert m.get("b", now=5.0) is b     # deadline now 15
    m.prune(now=11.5)
    assert m.get("a", now=11.5) is None and m.get("b", now=11.5) is b
    assert telemetry.get_registry().counter(
        "serving_idem_evictions_total").value(reason="ttl") >= 1
    # LRU cap: "c" is now least-recent (the "b" hit refreshed it) and
    # still inside its TTL window — the CAP eviction takes it
    m.put("d", req(), now=11.9)
    m.put("e", req(), now=11.9)
    assert len(m) == 3 and m.get("c", now=11.9) is None
    assert telemetry.get_registry().counter(
        "serving_idem_evictions_total").value(reason="cap") >= 1
    # in-flight entries outlive done ones under cap pressure
    live = req(done=False)
    m2 = IdemMap(max_entries=2, ttl_s=10.0)
    m2.put("live", live, now=0.0)
    m2.put("d1", req(), now=0.0)
    m2.put("d2", req(), now=0.0)
    assert m2.get("live", now=0.0) is live
    assert m2.get("d1", now=0.0) is None     # the done one went


# -- quick: stream session against a real coordinator -------------------------


def test_stream_submit_pushes_tokens_then_result():
    """The tentpole wire path: one ``stream`` frame submits and
    subscribes; tokens arrive as ``ev`` frames at monotonic offsets;
    the final frame folds the full result (trailing timing payload) —
    identical to what a RESULT poll returns."""
    stub = _StreamStub(step_s=0.005)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        ch = StreamChannel(port)
        sink, events, done = _collect()
        ack = ch.stream_submit(
            cli._serving_payload([7, 8, 9, 10], max_tokens=3,
                                 idem="sk1"), sink=sink)
        assert ack["id"] == 0 and ack["trace"]
        assert done.wait(5.0), "terminal frame never arrived"
        assert _tokens_of(events) == [7, 8, 9]
        last = events[-1]
        assert last["done"] and last["result"]["tokens"] == [7, 8, 9]
        assert last["result"]["status"] == "done"
        # matches the poll lane bit for bit
        doc = cli.serving_result(ack["id"], timeout_ms=2000)
        assert doc["tokens"] == last["result"]["tokens"]
        ch.close()
        cli.close()
    finally:
        srv.stop()


def test_mixed_line_and_stream_clients_one_listener():
    """Protocol sniff: a framed channel and plain line-protocol
    clients share one listener — each sees its own protocol, both
    complete, and the one-shot verbs multiplex over the channel too."""
    stub = _StreamStub(step_s=0.002)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        ch = StreamChannel(port)
        # one-shot verbs ride the channel as req frames
        assert ch.request("PING") == "PONG"
        assert ch.request("RANK nope").startswith(
            "ERR")                       # not multiplexable
        sink, events, done = _collect()
        ack = ch.stream_submit(
            cli._serving_payload([1, 2, 3], max_tokens=3, idem="m1"),
            sink=sink)
        # concurrently, the polling client runs its own request
        doc = cli.serving_generate([4, 5], max_tokens=2, idem_key="m2")
        assert doc["tokens"] == [4, 5]
        assert done.wait(5.0)
        assert _tokens_of(events) == [1, 2, 3]
        assert stub.submits == 2
        # line protocol still lives on this server: fresh client works
        cli2 = CoordinatorClient(port, timeout=5.0)
        assert cli2.ping()
        cli2.close(), cli.close(), ch.close()
    finally:
        srv.stop()


def test_subscribe_at_offset_replays_exactly_the_tail():
    """Resubscribe-at-offset (reconnect semantics): a subscriber that
    already holds k tokens passes ``off=k`` and receives exactly the
    rest — nothing lost, nothing duplicated."""
    stub = _StreamStub(step_s=0.02)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        rid = cli.serving_submit([3, 1, 4, 1, 5, 9], max_tokens=6)
        req = stub._requests_by_id[rid]
        while len(req.tokens) < 2:       # let a prefix commit
            time.sleep(0.005)
        have = len(req.tokens)
        ch = StreamChannel(port)
        sink, events, done = _collect()
        ch.subscribe(rid, offset=have, sink=sink)
        assert done.wait(5.0)
        toks = []
        for fr in events:
            if fr.get("k") == "ev":
                assert int(fr["off"]) == have + len(toks)
                toks.extend(int(t) for t in fr["toks"])
        assert [3, 1, 4, 1, 5, 9][have:] == toks
        # full doc still poll-able afterwards
        assert cli.serving_result(rid, timeout_ms=2000)["tokens"] == \
            [3, 1, 4, 1, 5, 9]
        # unknown request id → drop frame, not a hang
        sink2, events2, done2 = _collect()
        ch.subscribe(9999, sink=sink2)
        assert done2.wait(5.0)
        assert events2[-1]["k"] == "drop" \
            and events2[-1]["reason"] == "unknown_request"
        ch.close(), cli.close()
    finally:
        srv.stop()


def test_slow_subscriber_drops_to_poll_not_stall(tele):
    """A consumer that never drains overflows its own bounded queue:
    the producer marks it dropped (counted), the engine keeps
    committing at full speed, and the request stays poll-able."""
    stub = _StreamStub(step_s=0.0, start_delay_s=0.1)
    req = stub.submit(list(range(1, 50)), SamplingParams(max_tokens=40))
    sub = stub.stream_subscribe(req, max_queue=2)   # before any commit
    assert req.done.wait(5.0), "slow subscriber stalled the engine"
    deadline = time.monotonic() + 2.0
    while not sub.dropped and time.monotonic() < deadline:
        time.sleep(0.005)
    assert sub.dropped, "overflowing subscription never marked dropped"
    assert telemetry.get_registry().counter(
        "serving_stream_subscriber_drops_total").value() >= 1
    assert req.result()["tokens"] == list(range(1, 41))


def test_stream_submit_idempotency_joins_original():
    """SATELLITE: the ``stream`` frame rides the same idempotency-keyed
    submit path as SUBMIT/GENERATE — a duplicate delivery (retry after
    a lost ack) joins the original request, and both subscribers see
    the same tokens."""
    stub = _StreamStub(step_s=0.005)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        payload = cli._serving_payload([6, 7, 8], max_tokens=3,
                                       idem="dup1")
        ch = StreamChannel(port)
        s1, e1, d1 = _collect()
        s2, e2, d2 = _collect()
        a1 = ch.stream_submit(payload, sink=s1)
        a2 = ch.stream_submit(payload, sink=s2)
        assert a1["id"] == a2["id"]
        assert stub.submits == 1, "duplicate stream frame queued twice"
        assert d1.wait(5.0) and d2.wait(5.0)
        assert e1[-1]["result"]["tokens"] == [6, 7, 8]
        assert e2[-1]["result"]["tokens"] == [6, 7, 8]
        ch.close(), cli.close()
    finally:
        srv.stop()


def test_stream_auth_gate():
    """A tokened server rejects a bad stream hello (err frame, then
    close) and accepts the right token — same contract as AUTH."""
    stub = _StreamStub()
    srv, port = _serve(stub, token="sekrit")
    try:
        with pytest.raises(ConnectionError):
            StreamChannel(port, token="wrong")
        ch = StreamChannel(port, token="sekrit")
        assert ch.request("PING") == "PONG"
        ch.close()
    finally:
        srv.stop()


# -- quick: client generate_stream --------------------------------------------


def test_client_generate_stream_incremental_and_trailing_result():
    """Tentpole part 4: ``generate_stream`` yields tokens as they
    commit — strictly more events than one, last event carries the
    full result, concatenation equals the one-shot output."""
    stub = _StreamStub(step_s=0.01)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        events = list(cli.generate_stream([11, 12, 13, 14],
                                          max_tokens=4))
        toks = [t for ev in events for t in ev["tokens"]]
        assert toks == [11, 12, 13, 14]
        assert len(events) >= 2, "tokens arrived in one lump"
        assert events[-1]["done"] and not any(
            ev["done"] for ev in events[:-1])
        res = events[-1]["result"]
        assert res["tokens"] == toks and res["status"] == "done"
        assert "timing" in res           # the trailing timing payload
        # matches the blocking one-shot verb for the same input
        doc = cli.serving_generate([11, 12, 13, 14], max_tokens=4)
        assert doc["tokens"] == toks
        cli.close()
    finally:
        srv.stop()


def test_client_generate_stream_reconnects_at_offset():
    """SATELLITE: kill the SOCKET (not the engine) mid-generation —
    the generator reconnects, resubscribes at the offset it already
    holds, and the final output is bitwise identical with zero
    duplicated tokens."""
    stub = _StreamStub(step_s=0.03)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        want = list(range(20, 30))
        got, killed = [], []
        for ev in cli.generate_stream(want, max_tokens=10):
            got.extend(ev["tokens"])
            if not killed and len(got) >= 2:
                killed.append(True)
                cli._stream._sock.shutdown(socket.SHUT_RDWR)
        assert killed, "stream finished before the kill"
        assert got == want, f"lost/duplicated across reconnect: {got}"
        assert stub.submits == 1, "reconnect resubmitted the request"
        cli.close()
    finally:
        srv.stop()


def test_client_generate_stream_falls_back_to_poll(tele):
    """When the server cannot stream (no ``stream_subscribe`` on the
    serving object → drop "unsupported"), the generator still delivers
    everything via the loud RESULT-poll fallback."""
    from served import StubEngine
    stub = StubEngine(delay_s=0.05)
    srv, port = _serve(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        events = list(cli.generate_stream([5, 6, 7], max_tokens=3))
        toks = [t for ev in events for t in ev["tokens"]]
        assert toks == [5, 6, 7] and events[-1]["done"]
        assert events[-1]["result"]["tokens"] == [5, 6, 7]
        assert telemetry.get_registry().counter(
            "serving_stream_fallbacks_total").value(
            reason="client_poll") >= 1
        cli.close()
    finally:
        srv.stop()


# -- quick: fleet proxy push lane ---------------------------------------------


def test_proxy_streams_results_without_polling(tele):
    """Tentpole part 3: the RemoteEngineProxy rides the push lane —
    tokens arrive via subscription, the RESULT poll lane stays idle
    (~0 empty polls), and ESTATUS stretches to heartbeat cadence."""
    stub = _StreamStub(step_s=0.01)
    srv, port = _serve(stub)
    proxy = RemoteEngineProxy(port, poll_s=0.01, heartbeat_s=0.25)
    proxy.start()
    try:
        reg = telemetry.get_registry()
        empty0 = reg.counter("router_result_poll_empty_total").value()
        t0 = time.monotonic()
        rr = proxy.submit([9, 8, 7, 6, 5], SamplingParams(max_tokens=5))
        assert rr._stream_ok, "proxy did not subscribe on submit"
        assert rr.done.wait(5.0)
        dt = time.monotonic() - t0
        assert rr.tokens == [9, 8, 7, 6, 5]
        assert rr.status == "done"
        empty = reg.counter("router_result_poll_empty_total").value() \
            - empty0
        assert empty == 0, f"{empty} empty RESULT polls with streaming"
        # ESTATUS coalesced: at poll_s=0.01 the poll loop ticks ~100/s
        # (would be ~60+ status polls in this window), but beats ride
        # the 0.25s heartbeat — allow 2x cadence plus startup slack
        time.sleep(0.6)
        elapsed = time.monotonic() - t0
        cap = 3 + int(elapsed / 0.25 * 2)
        assert stub.estatus_calls <= cap, \
            f"{stub.estatus_calls} ESTATUS in ~{elapsed:.1f}s " \
            f"(cap {cap}): not coalesced to heartbeat cadence"
        assert reg.counter("serving_stream_subscribes_total").value(
            mode="new") >= 1
    finally:
        proxy.stop()
        srv.stop()


def test_proxy_stream_loss_falls_back_then_resubscribes(tele):
    """Kill the proxy's channel mid-flight: the in-flight request
    flips to the poll lane (counted), then the next poll tick
    resubscribes at its token offset — and the result is complete."""
    stub = _StreamStub(step_s=0.03)
    srv, port = _serve(stub)
    proxy = RemoteEngineProxy(port, poll_s=0.01, heartbeat_s=0.1)
    proxy.start()
    try:
        reg = telemetry.get_registry()
        rr = proxy.submit(list(range(40, 50)),
                          SamplingParams(max_tokens=10))
        assert rr._stream_ok
        while len(rr.tokens) < 2:
            time.sleep(0.005)
        proxy._schan._sock.shutdown(socket.SHUT_RDWR)   # SIGKILL the wire
        assert rr.done.wait(5.0)
        assert rr.tokens == list(range(40, 50)), \
            f"lost/duplicated across channel death: {rr.tokens}"
        assert reg.counter("serving_stream_subscribes_total").value(
            mode="resume") >= 1 or reg.counter(
            "router_result_poll_empty_total").value() >= 0
    finally:
        proxy.stop()
        srv.stop()


def test_router_reaps_dead_engine_within_beat_timeout_with_streaming():
    """SATELLITE: ESTATUS stays the beat — with a healthy stream
    channel stretching it to heartbeat cadence, a SIGKILLed engine
    (server stopped + sockets severed) is still declared dead within
    the router's ``beat_timeout_s``."""
    stub = _StreamStub(step_s=5.0)       # never finishes
    srv, port = _serve(stub)
    router = Router(poll_s=0.005, beat_timeout_s=1.0)
    try:
        h = router.register(
            "s0", RemoteEngineProxy(port, poll_s=0.02,
                                    heartbeat_s=0.25))
        time.sleep(0.4)
        assert h.last_beat is not None, "heartbeat never stamped"
        rreq = router.submit([1, 2, 3], SamplingParams(max_tokens=3))
        assert rreq.replica == "s0"
        t_kill = time.monotonic()
        srv.stop()
        h.engine._drop_client()
        ch = h.engine._schan
        if ch is not None:
            ch.close()
        deadline = t_kill + 1.0 + 2.0    # beat_timeout + poll slack
        while router._replicas["s0"].state != "dead":
            assert time.monotonic() < deadline, \
                "streaming cadence broke SIGKILL reaping"
            time.sleep(0.01)
    finally:
        router.stop()
        srv.stop()


def test_router_stream_subscribe_bridges_and_finalizes():
    """The router's stream bridge: an outward subscription on a
    RouterRequest follows the inner request (local replica here),
    offsets stay globally monotonic, and the terminal frame carries
    the ROUTER-level result."""
    stub = _StreamStub(step_s=0.01)
    router = Router(poll_s=0.005, beat_timeout_s=5.0)
    try:
        router.register("r0", stub)
        rreq = router.submit([21, 22, 23, 24],
                             SamplingParams(max_tokens=4))
        sub = router.stream_subscribe(rreq)
        toks, last = [], None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            ev = sub.get(timeout=0.2)
            if ev is None:
                continue
            assert int(ev["off"]) == len(toks)
            toks.extend(int(t) for t in ev["toks"])
            last = ev
            if ev.get("done"):
                break
        assert last is not None and last.get("done")
        assert toks == [21, 22, 23, 24]
        assert last["result"]["id"] == rreq.id
        assert "router_total_ms" in last["result"]["timing"]
        # subscribing AFTER completion replays backlog + terminal
        sub2 = router.stream_subscribe(rreq)
        ev2 = sub2.get(timeout=1.0)
        assert ev2 is not None and ev2["done"] \
            and [int(t) for t in ev2["toks"]] == toks
    finally:
        router.stop()


# -- slow: real engine acceptance ---------------------------------------------


@pytest.fixture(scope="module")
def gpt():
    import jax
    import jax.numpy as jnp

    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, model, params


def _real_engine(gpt, **kw):
    from hetu_tpu.serving import ServingEngine
    cfg, model, params = gpt
    return ServingEngine(model, params, slots=2, max_len=32,
                         prefill_chunk=8, **kw)


@pytest.mark.slow
def test_stream_matches_oneshot_bitwise_one_compile(gpt, tele):
    """ACCEPTANCE: streaming is a TRANSPORT, not a numerical change —
    ``generate_stream``'s concatenated tokens are bitwise identical to
    the blocking GENERATE of the same prompt, and an attached
    subscriber costs ZERO extra compiles (the pump is enqueue-only
    host work outside the fused step)."""
    from hetu_tpu.engine import trace_counts
    cfg, _model, _params = gpt
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, (L,)).tolist()
               for L in (5, 9, 3)]
    eng = _real_engine(gpt)
    eng.start()
    srv, port = _serve(eng)
    try:
        cli = CoordinatorClient(port, timeout=60.0)
        # warm: first request pays the compile
        ref0 = cli.serving_generate(prompts[0], max_tokens=6)
        before = trace_counts().get("serving_step", 0)
        for p in prompts:
            events = list(cli.generate_stream(p, max_tokens=6,
                                              event_timeout_s=60.0))
            streamed = [t for ev in events for t in ev["tokens"]]
            assert events[-1]["done"]
            assert events[-1]["result"]["tokens"] == streamed
            ref = cli.serving_generate(p, max_tokens=6)
            assert streamed == ref["tokens"], \
                "streamed tokens diverge from one-shot GENERATE"
        assert trace_counts().get("serving_step", 0) - before <= 1, \
            "subscribers recompiled the fused step"
        assert ref0["tokens"]           # silence unused warning
        cli.close()
    finally:
        srv.stop()
        eng.stop()


@pytest.mark.slow
def test_stream_socket_kill_resumes_real_engine(gpt, tele):
    """ACCEPTANCE: kill the SOCKET mid-generation against a REAL
    engine — the reconnect resumes at the correct offset and the
    final output is bitwise identical to the undisturbed one-shot."""
    cfg, _model, _params = gpt
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, (7,)).tolist()
    eng = _real_engine(gpt)
    eng.start()
    srv, port = _serve(eng)
    try:
        cli = CoordinatorClient(port, timeout=60.0)
        ref = cli.serving_generate(prompt, max_tokens=8)
        got, killed = [], []
        for ev in cli.generate_stream(prompt, max_tokens=8,
                                      event_timeout_s=60.0):
            got.extend(ev["tokens"])
            if not killed and got:
                killed.append(True)
                cli._stream._sock.shutdown(socket.SHUT_RDWR)
        assert killed, "generation finished before the kill"
        assert got == ref["tokens"], \
            f"reconnect lost/duplicated tokens: {got} vs {ref['tokens']}"
        cli.close()
    finally:
        srv.stop()
        eng.stop()


@pytest.mark.slow
def test_mixed_streaming_and_polling_clients_real_engine(gpt, tele):
    """SATELLITE: one streaming client + one polling client against
    the SAME engine — both complete with the tokens the engine would
    produce for each prompt alone (greedy), neither starves."""
    cfg, _model, _params = gpt
    rng = np.random.default_rng(7)
    p1 = rng.integers(1, cfg.vocab_size, (6,)).tolist()
    p2 = rng.integers(1, cfg.vocab_size, (4,)).tolist()
    eng = _real_engine(gpt)
    eng.start()
    srv, port = _serve(eng)
    try:
        cli_s = CoordinatorClient(port, timeout=60.0)
        cli_p = CoordinatorClient(port, timeout=60.0)
        ref1 = cli_p.serving_generate(p1, max_tokens=6)
        ref2 = cli_p.serving_generate(p2, max_tokens=6)
        outs = {}

        def stream():
            evs = list(cli_s.generate_stream(p1, max_tokens=6,
                                             event_timeout_s=60.0))
            outs["s"] = [t for ev in evs for t in ev["tokens"]]

        def poll():
            outs["p"] = cli_p.serving_generate(
                p2, max_tokens=6)["tokens"]

        ts = threading.Thread(target=stream)
        tp = threading.Thread(target=poll)
        ts.start(), tp.start()
        ts.join(120), tp.join(120)
        assert outs["s"] == ref1["tokens"]
        assert outs["p"] == ref2["tokens"]
        cli_s.close(), cli_p.close()
    finally:
        srv.stop()
        eng.stop()
