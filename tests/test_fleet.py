"""Multi-process fleet plane (ISSUE 15): remote replicas over the
coordinator, prefill/decode disaggregation, KV/weight wire transport.

Quick tier is HOST-SIDE only (stub engines behind a real line-protocol
coordinator — no compiles): RemoteReplicaHandle lifecycle (register →
heartbeat-stale → dead → requeue), KV-block wire-format bitwise
roundtrip, SUBMIT/GENERATE idempotency dedup, verb-table sync, and the
publisher transport guards. The compile-bearing acceptance matrix —
multi-process greedy parity + SIGKILL survival + rolling ``dist_ckpt``
weight push, P/D-split parity (colocated-identical tokens, decode-side
1-compile audit), and the chaos soak lane — is slow-marked per the
quick-tier time budget.
"""

import os
import threading
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.rpc.client import CoordinatorClient
from hetu_tpu.rpc.py_server import PyCoordinatorServer
from hetu_tpu.serving.fleet import (
    RemoteEngineProxy, RemoteReplicaHandle, spill_from_wire,
    spill_to_wire,
)
from hetu_tpu.serving.kv_pool import SpillEntry
from hetu_tpu.serving.router import Router
from hetu_tpu.serving.scheduler import SamplingParams
from served import StubEngine

@pytest.fixture()
def tele():
    """Counters only record while telemetry is on (test_chaos idiom)."""
    telemetry.enable(True)
    yield telemetry.get_registry()
    telemetry.enable(False)


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKERS = os.path.join(_REPO, "tests", "workers")
_FLEET_ENV = {"PYTHONPATH": f"{_REPO}:{_WORKERS}"}
_SPEC = "fleet_engine:build_engine"


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _serve_stub(stub):
    port = _free_port()
    srv = PyCoordinatorServer(port, serving=stub)
    srv.start()
    srv.wait_ready()
    return srv, port


# -- quick: wire format -------------------------------------------------------


def test_spill_wire_roundtrip_bitwise():
    """SATELLITE: serialize → deserialize reproduces every KV page and
    table field bit for bit — fp32 pages and the int8+fp32-scale arena
    layout both travel losslessly."""
    rng = np.random.default_rng(0)
    layouts = [
        (rng.standard_normal((2, 3, 4, 2, 5)).astype(np.float32),),
        (rng.integers(-128, 128, (2, 3, 4, 2, 5)).astype(np.int8),
         rng.standard_normal((2, 3, 4, 2, 1)).astype(np.float32),
         rng.integers(-128, 128, (2, 3, 4, 2, 5)).astype(np.int8),
         rng.standard_normal((2, 3, 4, 2, 1)).astype(np.float32)),
    ]
    for data in layouts:
        entry = SpillEntry(req_id=7, data=data, n_blocks=3,
                           block_size=4, pos=11, last_tok=42,
                           tokens=[42, 3], weight_version=2)
        # through REAL json — the line protocol's representation
        import json
        back = spill_from_wire(json.loads(json.dumps(
            spill_to_wire(entry))))
        assert back.req_id == 7 and back.n_blocks == 3
        assert back.block_size == 4 and back.pos == 11
        assert back.last_tok == 42 and back.tokens == [42, 3]
        assert back.weight_version == 2
        assert len(back.data) == len(data)
        for a, b in zip(data, back.data):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert (a == b).all(), "wire roundtrip not bitwise"


def test_serving_verb_tables_in_sync():
    """py_server mirrors SERVING_COMMANDS (it must stay importable
    without jax, so it cannot import the real table)."""
    from hetu_tpu.rpc.py_server import _SERVING_VERBS
    from hetu_tpu.serving.server import SERVING_COMMANDS
    assert set(_SERVING_VERBS) == set(SERVING_COMMANDS)


# -- quick: idempotency keys --------------------------------------------------


def test_submit_idempotency_dedups_duplicate_delivery():
    """SATELLITE: two SUBMIT deliveries with one key = ONE queued
    request, same id returned — the retry-after-response-timeout
    scenario, replayed deliberately."""
    stub = StubEngine()
    srv, port = _serve_stub(stub)
    try:
        cli = CoordinatorClient(port, timeout=5.0)
        a = cli.serving_submit_info([1, 2, 3], idem_key="k1",
                                    max_tokens=2)
        b = cli.serving_submit_info([1, 2, 3], idem_key="k1",
                                    max_tokens=2)
        assert a["id"] == b["id"]
        assert stub.submits == 1, "duplicate delivery queued twice"
        # distinct keys are distinct requests
        c = cli.serving_submit_info([1, 2, 3], idem_key="k2",
                                    max_tokens=2)
        assert c["id"] != a["id"] and stub.submits == 2
        # the deduped request still completes normally
        r = cli.serving_result(a["id"], timeout_ms=5000)
        assert r is not None and r["tokens"] == [1, 2]
        cli.close()
    finally:
        srv.stop()


def test_generate_idempotency_joins_original():
    stub = StubEngine(delay_s=0.05)
    srv, port = _serve_stub(stub)
    try:
        cli1 = CoordinatorClient(port, timeout=10.0)
        cli2 = CoordinatorClient(port, timeout=10.0)
        outs = {}

        def gen(name, cli):
            outs[name] = cli.serving_generate([5, 6, 7], idem_key="g1",
                                              max_tokens=3)

        t1 = threading.Thread(target=gen, args=("a", cli1))
        t2 = threading.Thread(target=gen, args=("b", cli2))
        t1.start(), t2.start()
        t1.join(10), t2.join(10)
        assert outs["a"]["tokens"] == outs["b"]["tokens"] == [5, 6, 7]
        assert outs["a"]["id"] == outs["b"]["id"]
        assert stub.submits == 1, "duplicate GENERATE generated twice"
        cli1.close(), cli2.close()
    finally:
        srv.stop()


def test_trace_summary_fleet_plane_section(tmp_path):
    """SATELLITE: trace_summary renders the fleet-plane section —
    dispatch spread, remote-requeue slice, P/D handoffs with KV
    blocks, weight pushes by transport, beat staleness — from the last
    metrics snapshot."""
    import json

    from hetu_tpu.tools.trace_summary import summarize
    snap = {
        'router_requests_total{replica="r0"}': 8.0,
        'router_requests_total{replica="r1"}': 6.0,
        "router_requeues_total": 3.0,
        "fleet_remote_requeues_total": 2.0,
        "router_resumed_requeues_total": 1.0,
        "fleet_pd_handoffs_total": 5.0,
        "fleet_kv_stream_blocks_total": 10.0,
        "weight_pushes_total": 2.0,
        'weight_push_bytes_total{transport="dist_ckpt"}': 5e5,
        "router_replicas_live": 2.0,
        'fleet_replica_beat_age_seconds{replica="r1"}': 0.02,
        'serving_idem_dedup_total{verb="SUBMIT"}': 4.0,
    }
    p = tmp_path / "telemetry.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "metrics_snapshot",
                            "metrics": snap}) + "\n")
    out = summarize(str(p))
    assert "== fleet plane ==" in out
    assert "14 (r0:8 / r1:6)" in out
    assert "3 (2 remote, 1 KV-resumed)" in out
    assert "5 requests, 10 KV blocks streamed" in out
    assert "dist_ckpt:0.5MB" in out
    assert "4 duplicate deliveries suppressed" in out
    assert "stalest remote beat: r1 20ms" in out


# -- quick: remote replica lifecycle ------------------------------------------


def test_remote_handle_lifecycle_stale_dead_requeue(tele):
    """SATELLITE: register → serve → heartbeat-stale → dead → the
    in-flight request requeues onto a live peer and completes exactly
    once. Stub engines, real sockets, no compiles."""
    slow = StubEngine(delay_s=30.0)         # never finishes in time
    fast = StubEngine()
    srv_slow, port_slow = _serve_stub(slow)
    srv_fast, port_fast = _serve_stub(fast)
    router = Router(poll_s=0.005, beat_timeout_s=0.3)
    try:
        h = router.register(
            "s0", RemoteEngineProxy(port_slow, poll_s=0.02))
        assert isinstance(h, RemoteReplicaHandle)
        assert h.status()["remote"] is True
        # liveness comes from polls, not a loop thread
        assert not h.loop_alive() and not h.loop_died()
        time.sleep(0.1)
        assert h.last_beat is not None
        rreq = router.submit([9, 8, 7, 6], SamplingParams(max_tokens=3))
        assert rreq.status == "dispatched" and rreq.replica == "s0"
        # the "process" dies: its coordinator stops answering → beats
        # stop → the router's staleness check declares it dead.
        # (ThreadingTCPServer handler threads outlive stop(), so also
        # drop the proxy's live socket — a real SIGKILL severs both.)
        srv_slow.stop()
        h.engine._drop_client()
        deadline = time.monotonic() + 10
        while router._replicas["s0"].state != "dead":
            assert time.monotonic() < deadline, "staleness never fired"
            time.sleep(0.02)
        # the request parked pending (no live peer yet), then a fresh
        # replica registers and absorbs it
        router.register("s1", RemoteEngineProxy(port_fast, poll_s=0.02))
        assert rreq.done.wait(10.0), "request lost across the death"
        assert rreq.status == "done" and rreq.replica == "s1"
        assert rreq.tokens == [9, 8, 7]
        assert router.requeues_total >= 1
        snap = telemetry.get_registry().snapshot()
        assert snap.get("fleet_remote_requeues_total", 0) >= 1
    finally:
        router.stop()
        srv_fast.stop()
        srv_slow.stop()


def test_publisher_transport_guards():
    """reshard transport refuses remote replicas loudly; dist_ckpt
    demands a ckpt_dir; unknown transports rejected at construction."""
    from hetu_tpu.serving.router import WeightPublisher
    router = Router(poll_s=0.01)
    with pytest.raises(ValueError, match="transport"):
        WeightPublisher(router, transport="carrier_pigeon")
    with pytest.raises(ValueError, match="ckpt_dir"):
        WeightPublisher(router, transport="dist_ckpt")
    stub = StubEngine()
    srv, port = _serve_stub(stub)
    try:
        router.register("s0", RemoteEngineProxy(port, poll_s=0.02))
        pub = WeightPublisher(router)        # reshard (default)
        with pytest.raises(RuntimeError, match="dist_ckpt"):
            pub.publish({"w": np.zeros(2, np.float32)})
    finally:
        router.stop()
        srv.stop()


# -- slow: the compile-bearing acceptance matrix ------------------------------


@pytest.fixture(scope="module")
def gpt():
    import jax
    import jax.numpy as jnp

    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params0 = model.init(jax.random.key(0), dtype=jnp.float32)
    params1 = model.init(jax.random.key(7), dtype=jnp.float32)
    return cfg, model, params0, params1


def _ref(model, params, prompt, max_tokens=4):
    import jax.numpy as jnp

    from hetu_tpu.models import generate
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=max_tokens, max_len=32)
    return np.asarray(out[0, len(prompt):]).tolist()


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (L,)).tolist()
            for L in lens]


@pytest.mark.slow
def test_multiprocess_fleet_parity_kill_and_dist_ckpt_push(gpt, tmp_path, tele):
    """ACCEPTANCE: ≥2 engine PROCESSES behind one Router serve a mixed
    workload greedy-token-identical to single-engine generate, complete
    a rolling dist_ckpt weight push under live traffic with capacity
    floor ≥ 1 and version-tagged continuity, and survive a SIGKILL of
    one replica with zero lost/duplicated requests."""
    from hetu_tpu.rpc.launcher import launch_serving_fleet
    from hetu_tpu.serving import WeightPublisher
    cfg, model, params0, params1 = gpt
    fleet = launch_serving_fleet(
        n_replicas=2, remote=True, engine_spec=_SPEC, env=_FLEET_ENV,
        log_dir=str(tmp_path / "logs"), beat_timeout_s=3.0,
        poll_s=0.005)
    router = fleet.router
    try:
        prompts = _prompts(cfg, [5, 11, 3, 8, 6, 9], seed=0)
        sp = SamplingParams(max_tokens=4)
        want0 = [_ref(model, params0, p) for p in prompts]
        assert router.generate_many(prompts, sp) == want0
        st = router.fleet_status()
        assert st["live"] == 2
        assert all(r["dispatched"] > 0 for r in st["replicas"].values())

        # rolling dist_ckpt push under a live trickle
        pub = WeightPublisher(router, transport="dist_ckpt",
                              ckpt_dir=str(tmp_path / "push"))
        floor, stop = [], threading.Event()

        def sampler():
            while not stop.is_set():
                floor.append(router.fleet_status()["live"])
                time.sleep(0.002)

        trickle = []

        def submitter():
            while not stop.is_set():
                trickle.append(router.submit(prompts[0], sp))
                time.sleep(0.01)

        threads = [threading.Thread(target=sampler, daemon=True),
                   threading.Thread(target=submitter, daemon=True)]
        for t in threads:
            t.start()
        try:
            rep = pub.publish(params1)
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert min(floor) >= 1, "capacity floor broken"
        for r in trickle:
            assert r.done.wait(120.0)
            assert r.status == "done"
            # one request, one version — never spliced across the swap
            assert r.tokens in (want0[0],
                                _ref(model, params1, prompts[0]))
        want1 = [_ref(model, params1, p) for p in prompts]
        assert router.generate_many(prompts, sp) == want1, \
            "post-push tokens are not the new weights'"
        time.sleep(0.3)                  # proxies poll the new version
        assert router.fleet_status()["weight_versions"] \
            == [rep["version"]]

        # cross-process drain under live decodes: queued requests move
        # via CANCELQ, mid-decode ones spill their KV via EVICT and
        # resume on the peer — all over the wire, nothing lost, tokens
        # identical to the undisturbed run
        long_sp = SamplingParams(max_tokens=20)
        long_want = [_ref(model, params1, p, 20) for p in prompts[:4]]
        long_reqs = [router.submit(p, long_sp) for p in prompts[:4]]
        time.sleep(0.15)             # let some admit and start decoding
        router.drain("r0", preempt=True)
        router.resume("r0")
        for r, want in zip(long_reqs, long_want):
            assert r.done.wait(120.0), f"request #{r.id} lost in drain"
            assert r.status == "done" and list(r.tokens) == want

        # SIGKILL one replica mid-stream: zero lost/duplicated
        reqs = [router.submit(p, sp) for p in prompts * 2]
        victim = next((n for n, h in router._replicas.items()
                       if h.inflight), "r0")
        fleet.kill_replica_process(victim)
        for r in reqs:
            assert r.done.wait(120.0), f"request #{r.id} lost"
        assert [r.status for r in reqs] == ["done"] * len(reqs)
        assert [list(r.tokens) for r in reqs] == want1 * 2
        assert router.fleet_status()["replicas"][victim]["state"] \
            == "dead"
        snap = telemetry.get_registry().snapshot()
        assert snap.get("fleet_remote_requeues_total", 0) >= 1
    finally:
        fleet.stop()


@pytest.mark.slow
def test_pd_split_parity_and_one_compile(gpt, tele):
    """ACCEPTANCE (P/D, in-process): a prefill-tier replica streams KV
    to a decode-tier replica; emitted tokens are identical to the
    colocated path and the decode replica's fused step stays at ONE
    compile across the handoff churn."""
    from hetu_tpu.engine.train_step import trace_counts
    from hetu_tpu.serving import ServingEngine
    cfg, model, params0, _ = gpt
    router = Router(poll_s=0.001)
    router.register("pre", ServingEngine(model, params0, slots=2,
                                         max_len=32, prefill_chunk=8),
                    role="prefill")
    router.register("dec", ServingEngine(model, params0, slots=2,
                                         max_len=32, prefill_chunk=8),
                    role="decode")
    try:
        sp = SamplingParams(max_tokens=4)
        prompts = _prompts(cfg, [5, 11, 3], seed=3)
        want = [_ref(model, params0, p) for p in prompts]
        assert router.generate_many(prompts, sp) == want
        compiles = trace_counts().get("serving_step", 0)
        # churn: more handoffs, mixed lengths + arrival orders
        more = _prompts(cfg, [7, 4, 9, 6, 3, 8], seed=4)
        assert router.generate_many(more, sp) \
            == [_ref(model, params0, p) for p in more]
        assert router.generate_many(list(reversed(prompts)), sp) \
            == list(reversed(want))
        assert trace_counts().get("serving_step", 0) == compiles, \
            "P/D handoff churn recompiled a fused step"
        st = router.fleet_status()
        # every request prefilled on the prefill tier AND decoded on
        # the decode tier
        n = len(prompts) * 2 + len(more)
        assert st["replicas"]["pre"]["dispatched"] == n
        assert st["replicas"]["dec"]["dispatched"] == n
        snap = telemetry.get_registry().snapshot()
        assert snap.get("fleet_pd_handoffs_total", 0) >= n
        assert snap.get("fleet_kv_stream_blocks_total", 0) >= n
    finally:
        router.stop()


@pytest.mark.slow
def test_pd_split_remote_streams_kv_over_the_wire(gpt, tmp_path, tele):
    """ACCEPTANCE (P/D, multi-process): prefill and decode tiers in
    SEPARATE processes — the KV blocks travel the coordinator wire
    format and the decoded tokens still match one-shot generate."""
    from hetu_tpu.rpc.launcher import launch_serving_fleet
    cfg, model, params0, _ = gpt
    fleet = launch_serving_fleet(
        n_replicas=2, remote=True, names=["pre", "dec"],
        roles={"pre": "prefill", "dec": "decode"},
        engine_spec=_SPEC, env=_FLEET_ENV,
        log_dir=str(tmp_path / "logs"), beat_timeout_s=5.0,
        poll_s=0.005)
    router = fleet.router
    try:
        prompts = _prompts(cfg, [5, 11, 3, 8], seed=2)
        sp = SamplingParams(max_tokens=4)
        assert router.generate_many(prompts, sp) \
            == [_ref(model, params0, p) for p in prompts]
        st = router.fleet_status()
        assert st["replicas"]["pre"]["dispatched"] == len(prompts)
        assert st["replicas"]["dec"]["dispatched"] == len(prompts)
        snap = telemetry.get_registry().snapshot()
        assert snap.get("fleet_kv_stream_blocks_total", 0) \
            >= len(prompts)
    finally:
        fleet.stop()


@pytest.mark.slow
def test_fleet_chaos_soak_periodic_kills(gpt, tmp_path):
    """SATELLITE (ROADMAP PR 12 residual, extended by ISSUE 18):
    ``ChaosMonkey.start`` periodically SIGKILLs replicas of a live
    multi-process fleet — WITH decode-KV buddy replication enabled —
    while a request stream runs: zero lost, zero duplicated, every
    token correct, and any request recovered from a buddy's replica
    set reports ``resumed`` in its RESULT timing (proof it resumed
    mid-decode instead of replaying the prompt). One replica is never
    targeted, so capacity survives."""
    from hetu_tpu.engine.chaos import ChaosMonkey
    from hetu_tpu.rpc.launcher import launch_serving_fleet
    cfg, model, params0, _ = gpt
    fleet = launch_serving_fleet(
        n_replicas=3, remote=True, engine_spec=_SPEC, env=_FLEET_ENV,
        log_dir=str(tmp_path / "logs"), beat_timeout_s=2.0,
        poll_s=0.005, replicate_kv=True, replicate_cadence_s=0.01)
    router = fleet.router
    try:
        sp = SamplingParams(max_tokens=4)
        long_sp = SamplingParams(max_tokens=12)   # long decodes give
        #                        the kills something to land mid-decode
        prompts = _prompts(cfg, [5, 9, 3, 7, 6, 4], seed=5)
        want = [_ref(model, params0, p) for p in prompts]
        want_long = [_ref(model, params0, p, 12) for p in prompts]
        router.generate_many(prompts[:3], sp)      # warm the compiles
        rec0 = telemetry.get_registry().snapshot().get(
            "fleet_kv_recoveries_total", 0)
        monkey = ChaosMonkey(
            {n: (lambda n=n: fleet.kill_replica_process(n))
             for n in ("r1", "r2")},               # r0 always survives
            period_s=1.0, max_kills=2, seed=0)
        reqs = []
        monkey.start()
        try:
            deadline = time.monotonic() + 6.0
            i = 0
            while time.monotonic() < deadline:
                idx = i % len(prompts)
                is_long = i % 3 == 0
                reqs.append((idx, is_long, router.submit(
                    prompts[idx], long_sp if is_long else sp)))
                i += 1
                time.sleep(0.05)
        finally:
            monkey.stop()
        resumed = 0
        for idx, is_long, r in reqs:
            assert r.done.wait(120.0), f"request #{r.id} lost in soak"
            assert r.status == "done"
            assert list(r.tokens) == \
                (want_long if is_long else want)[idx], \
                "soak corrupted tokens"
            resumed += bool(r.result()["timing"].get("resumed"))
        assert len(monkey.kills) >= 1, "soak never killed anything"
        dead = [n for n, h in router._replicas.items()
                if h.state == "dead"]
        assert set(dead) <= {"r1", "r2"} and dead, dead
        # ISSUE 18: every buddy-KV recovery the router performed must
        # surface as a resumed=true RESULT — the wire carries the proof
        recoveries = telemetry.get_registry().snapshot().get(
            "fleet_kv_recoveries_total", 0) - rec0
        assert resumed >= recoveries, (resumed, recoveries)
    finally:
        fleet.stop()
