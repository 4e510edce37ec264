"""Fleet-global KV plane (ISSUE 18): shared prefix directory,
decode-KV replication, tiered spill.

Quick tier is HOST-SIDE only (numpy + stub engines behind a real
line-protocol coordinator — no compiles): the HostSpillArena
device→host→peer tier chain (LRU demotion, look-through pop/get,
oversized pass-through), KVReplicaStore shipment assembly (bitwise) +
tombstones + LRU cap, the spill wire format's PRNG key-state
roundtrip, FleetPrefixDirectory longest-match lookup and atomic
staleness flush, the stale-version wire pull REFUSAL (the
falls-back-to-prefill contract), the KVREPL/KVFETCH/KVBUDDY verbs
end to end over a socket, and the adaptive RESULT-poll backoff.

The compile-bearing acceptance matrix — cross-engine export/import
token identity with a zero-prefill cached span, router directory pull,
and buddy recovery from a wedged-then-killed replica — is slow-marked
per the quick-tier time budget.
"""

import json
import threading
import time

import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.rpc.py_server import PyCoordinatorServer
from hetu_tpu.serving.fleet import (
    KVReplicaStore, RemoteEngineProxy, array_to_wire, spill_from_wire,
    spill_to_wire,
)
from hetu_tpu.serving.kv_pool import HostSpillArena, SpillEntry
from hetu_tpu.serving.router import FleetPrefixDirectory, Router
from hetu_tpu.serving.scheduler import Request, SamplingParams


@pytest.fixture()
def tele():
    """Counters only record while telemetry is on (test_chaos idiom)."""
    telemetry.enable(True)
    yield telemetry.get_registry()
    telemetry.enable(False)


_BS = 4                               # toy arena block size


def _entry(req_id, nb, *, seed=0, wv=0, key_state=None, tokens=None):
    """A host-side SpillEntry with one (L=2, nb, bs, 2, 3) leaf."""
    rng = np.random.default_rng(seed)
    data = (rng.standard_normal((2, nb, _BS, 2, 3)).astype(np.float32),)
    return SpillEntry(req_id=req_id, data=data, n_blocks=nb,
                      block_size=_BS, pos=nb * _BS, last_tok=1,
                      tokens=tokens if tokens is not None
                      else list(range(nb * _BS)),
                      weight_version=wv, key_state=key_state)


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- quick: tiered spill store ------------------------------------------------


def test_spill_arena_tier_chain_demotes_lru():
    """TENTPOLE (tier chain): a full host tier demotes its
    least-recently-spilled entries whole into the peer tier; pop/get
    look through, a promoted entry leaves the peer ledgered."""
    peer = HostSpillArena()                      # unbounded backing tier
    host = HostSpillArena(max_blocks=4, peer=peer)
    host.put(_entry(1, 2, seed=1))
    host.put(_entry(2, 2, seed=2))
    assert host.tier_counts() == {"host": 4, "peer": 0}
    host.put(_entry(3, 2, seed=3))               # demotes 1 (the LRU)
    assert host.tier_counts() == {"host": 4, "peer": 2}
    assert host.demoted_total == 2
    assert 1 in host and len(host) == 3          # look-through contains
    assert host.get(1) is not None and host.get(1).req_id == 1
    got = host.pop(1)                            # promotes back up
    assert got is not None and got.req_id == 1 and got.n_blocks == 2
    assert host.promoted_total == 2
    assert host.tier_counts() == {"host": 4, "peer": 0}
    # bitwise: demotion and promotion never touch the pages
    ref = _entry(1, 2, seed=1)
    assert (got.data[0] == ref.data[0]).all()


def test_spill_arena_oversized_passthrough_and_refusal():
    """An entry wider than the whole host tier passes straight through
    to the peer; without a peer the same put is refused (the caller's
    eviction degrades to a replay, never a crash)."""
    peer = HostSpillArena()
    host = HostSpillArena(max_blocks=4, peer=peer)
    host.put(_entry(9, 6, seed=9))               # 6 > 4: pass-through
    assert host.tier_counts() == {"host": 0, "peer": 6}
    assert host.demoted_total == 6
    assert host.pop(9).req_id == 9
    lone = HostSpillArena(max_blocks=2)
    lone.put(_entry(1, 2))
    assert not lone.can_fit(1)
    with pytest.raises(ValueError):
        lone.put(_entry(2, 1))


# -- quick: buddy replica store ----------------------------------------------


def _shipment(full, start, n, *, pos, tid="t1", last_tok=17):
    """One replication wire doc covering blocks [start, start+n)."""
    return {"trace_id": tid, "origin": "e0", "req_id": 5,
            "weight_version": 0, "block_size": _BS, "pos": pos,
            "last_tok": last_tok, "tokens": [1, 2], "key_state": None,
            "traceparent": None, "start": start,
            "data": [array_to_wire(full[:, start:start + n])]}


def test_kv_replica_store_assembles_bitwise_and_drops():
    """Shipments accumulate per trace; fetch assembles the full block
    range bit for bit, refuses while coverage is partial, and a
    tombstone evicts the finished trace."""
    rng = np.random.default_rng(3)
    full = rng.standard_normal((2, 3, _BS, 2, 3)).astype(np.float32)
    store = KVReplicaStore()
    store.put(_shipment(full, 0, 2, pos=2 * _BS))
    assert "t1" in store and store.blocks_held == 2
    got = store.fetch("t1")
    assert got is not None and got.n_blocks == 2
    store.put(_shipment(full, 2, 1, pos=2 * _BS + 1))
    got = store.fetch("t1")
    assert got.n_blocks == 3 and got.pos == 2 * _BS + 1
    assert got.last_tok == 17 and got.tokens == [1, 2]
    assert (got.data[0] == full).all(), "replica set not bitwise"
    # partial coverage (block 0 missing) = not resumable yet
    store.put(_shipment(full, 2, 1, pos=2 * _BS + 1, tid="t2"))
    assert store.fetch("t2") is None
    store.put({"drop": "t1"})
    assert "t1" not in store and store.fetch("t1") is None


def test_kv_replica_store_lru_cap_refreshes_on_put():
    rng = np.random.default_rng(4)
    full = rng.standard_normal((2, 1, _BS, 2, 3)).astype(np.float32)
    store = KVReplicaStore(max_traces=2)
    store.put(_shipment(full, 0, 1, pos=_BS, tid="a"))
    store.put(_shipment(full, 0, 1, pos=_BS, tid="b"))
    store.put(_shipment(full, 0, 1, pos=_BS, tid="a"))   # refresh a
    store.put(_shipment(full, 0, 1, pos=_BS, tid="c"))   # evicts b
    assert "a" in store and "c" in store and "b" not in store


# -- quick: wire format -------------------------------------------------------


def test_spill_wire_roundtrips_key_state_and_traceparent():
    """SATELLITE: the commit-stream PRNG key state and the originating
    trace context survive the wire bit for bit — a sampled buddy
    resume must restart its key stream exactly where it stopped."""
    ks = np.arange(4, dtype=np.uint32) * 7
    entry = _entry(7, 2, key_state=ks)
    entry.traceparent = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    back = spill_from_wire(json.loads(json.dumps(
        spill_to_wire(entry))))
    assert back.key_state is not None
    assert back.key_state.dtype == np.uint32
    assert (back.key_state == ks).all()
    assert back.traceparent == entry.traceparent
    # absent key state stays absent (greedy requests ship none)
    back2 = spill_from_wire(json.loads(json.dumps(
        spill_to_wire(_entry(8, 1)))))
    assert back2.key_state is None


# -- quick: fleet prefix directory -------------------------------------------


def test_prefix_directory_longest_match_and_flush():
    """TENTPOLE (directory): one publish records every whole-block
    boundary; lookup returns the LONGEST known span; flush_stale
    atomically invalidates by replica (death) and by version (weight
    push) — the directory can never route a stale pull."""
    d = FleetPrefixDirectory()
    toks = list(range(100, 140))                 # 40 toks, bs 16 → 2 blk
    assert d.publish("r0", toks, block_size=16, weight_version=1) == 2
    assert d.published_total == 2 and len(d) == 2
    assert d.lookup(toks) == ("r0", 2, 16)
    # a prompt sharing only the first block still finds its span
    assert d.lookup(toks[:16] + [999] * 24) == ("r0", 1, 16)
    assert d.lookup([1, 2, 3]) is None
    assert d.lookup(toks[:15]) is None           # sub-block: no entry
    # r1 re-publishes the 1-block boundary; longest-first still
    # prefers r0's 2-block span for the full prompt
    d.publish("r1", toks[:16], block_size=16, weight_version=1)
    assert d.lookup(toks) == ("r0", 2, 16)
    # version flush: a weight push invalidates only the older entries
    d.publish("r1", list(range(200, 232)), block_size=16,
              weight_version=0)
    assert d.flush_stale(below_version=1) == 2
    assert d.lookup(list(range(200, 232))) is None
    assert d.lookup(toks) == ("r0", 2, 16)
    # replica death drops exactly its entries, the 1-block key (now
    # owned by r1) survives and serves the shorter span
    assert d.drop_replica("r0") == 1
    assert d.lookup(toks) == ("r1", 1, 16)
    assert d.flushed_total == 3


def test_prefix_directory_fifo_cap():
    d = FleetPrefixDirectory(max_entries=2)
    d.publish("r0", list(range(16)), block_size=16, weight_version=0)
    d.publish("r0", list(range(50, 66)), block_size=16,
              weight_version=0)
    d.publish("r0", list(range(80, 96)), block_size=16,
              weight_version=0)
    assert len(d) == 2
    assert d.lookup(list(range(16))) is None     # FIFO-evicted
    assert d.lookup(list(range(80, 96))) is not None


# -- quick: stub KV engine behind a real coordinator -------------------------


class _FakePool:
    def __init__(self):
        self.block_size = _BS
        self.caches = (np.zeros((2, 8, _BS, 2, 3), np.float32),)


class _StubKVEngine:
    """Speaks the fleet-KV verbs host-side: export builds a real
    SpillEntry, import applies the REAL ``compatible_with`` gate, and
    the buddy/replica-store surfaces are live."""

    def __init__(self, weight_version=0):
        self.weight_version = weight_version
        self.pool = _FakePool()
        self.kv_replica_store = KVReplicaStore()
        self.imported = []
        self.buddy_cfg = None
        self.load = 0

        class _Sched:
            depth = 0
            occupancy = 0.0
        self.scheduler = _Sched()

    def has_work(self):
        return False

    def export_prefix(self, tokens, **kw):
        nb = len(tokens) // _BS
        if nb <= 0:
            return None
        return _entry(-1, nb, wv=self.weight_version,
                      tokens=[int(t) for t in tokens[:nb * _BS]])

    def import_prefix(self, entry, **kw):
        if not entry.compatible_with(self.pool, self.weight_version):
            return False
        self.imported.append(entry)
        return True

    def configure_replication(self, sink, *, origin="",
                              cadence_s=0.02):
        self.buddy_cfg = (sink, origin, cadence_s)


def _serve(stub):
    port = _free_port()
    srv = PyCoordinatorServer(port, serving=stub)
    srv.start()
    srv.wait_ready()
    return srv, port


def test_stale_version_wire_pull_refused_falls_back():
    """SATELLITE (bugfix by construction): a KVEXPORT/KVIMPORT pull
    whose entry was written under a superseded weight version is
    REFUSED at the importing engine — nothing is mapped, so the caller
    falls back to a plain prefill instead of splicing two models'
    states. A version-matched pull on the same wire lands."""
    owner = _StubKVEngine(weight_version=0)
    puller = _StubKVEngine(weight_version=1)     # already swapped ahead
    srv_o, port_o = _serve(owner)
    srv_p, port_p = _serve(puller)
    try:
        po = RemoteEngineProxy(port_o)
        pp = RemoteEngineProxy(port_p)
        entry = po.export_prefix(list(range(9)))
        assert entry is not None and entry.n_blocks == 2
        assert entry.weight_version == 0
        assert entry.tokens == list(range(8))    # whole blocks only
        # stale: refused over the wire, and NOTHING was mapped — the
        # router's fallback (plain prefill) stays correct
        assert pp.import_prefix(entry) is False
        assert puller.imported == []
        # matched versions: the same wire path lands the pull
        owner.weight_version = 1
        entry2 = po.export_prefix(list(range(9)))
        assert pp.import_prefix(entry2) is True
        assert len(puller.imported) == 1
        got = puller.imported[0]
        assert (got.data[0] == entry2.data[0]).all()
    finally:
        srv_o.stop()
        srv_p.stop()


def test_kv_repl_fetch_buddy_verbs_over_wire():
    """KVREPL delivers a shipment into the remote buddy's store,
    KVFETCH assembles it back bitwise, KVBUDDY (un)wires the origin's
    replication stream."""
    stub = _StubKVEngine()
    srv, port = _serve(stub)
    try:
        proxy = RemoteEngineProxy(port)
        rng = np.random.default_rng(5)
        full = rng.standard_normal((2, 2, _BS, 2, 3)).astype(np.float32)
        proxy.kv_put(_shipment(full, 0, 2, pos=2 * _BS))
        assert "t1" in stub.kv_replica_store
        got = proxy.kv_fetch("t1")
        assert got is not None and got.n_blocks == 2
        assert (got.data[0] == full).all()
        assert proxy.kv_fetch("missing") is None
        # wire the buddy: the handler hands the engine a socket sink
        assert proxy.set_kv_buddy("127.0.0.1", 12345, token=None,
                                  origin="own", cadence_s=0.5)
        sink, origin, cadence = stub.buddy_cfg
        assert callable(sink) and origin == "own" and cadence == 0.5
        assert proxy.set_kv_buddy(None)
        assert stub.buddy_cfg[0] is None         # unwired
    finally:
        srv.stop()


# -- quick: adaptive RESULT-poll backoff -------------------------------------


class _StubDecodeEngine:
    """Submitted requests complete with ``prompt[:max_tokens]`` after
    ``delay_s`` — enough surface for SUBMIT/RESULT/ESTATUS."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.weight_version = 0
        self._next = 0
        self._requests_by_id = {}
        self._lock = threading.Lock()
        self.load = 0

        class _Sched:
            depth = 0
            occupancy = 0.0
        self.scheduler = _Sched()

    def has_work(self):
        return False

    def submit(self, prompt, sampling=None, *, resume=None,
               handoff=False, traceparent=None):
        sampling = sampling or SamplingParams()
        with self._lock:
            req = Request(id=self._next,
                          prompt=np.asarray(prompt, np.int32).ravel(),
                          sampling=sampling,
                          submit_s=time.monotonic())
            self._next += 1

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


def test_result_poll_backoff_widens_and_snaps_back():
    """SATELLITE: while every in-flight RESULT answers PEND the poll
    gap doubles toward ``poll_max_s``; the moment a result is adopted
    it snaps back to ``poll_s``. ESTATUS keeps its fixed cadence
    throughout (it IS the heartbeat)."""
    stub = _StubDecodeEngine(delay_s=1.0)
    srv, port = _serve(stub)
    proxy = RemoteEngineProxy(port, poll_s=0.01, poll_max_s=0.05)
    try:
        r = proxy.submit([1, 2, 3], SamplingParams(max_tokens=2))
        # the stub cannot stream: its ``drop`` answer arrives on the
        # channel's reader thread and puts the request on the poll lane
        # — only from then on does a RESULT poll answer PEND
        deadline = time.monotonic() + 5
        while not getattr(r, "_stream_denied", False) \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert proxy._result_delay == pytest.approx(0.01)
        delays = []
        for _ in range(4):
            proxy._next_result_poll = 0.0        # force the RESULT lane
            assert proxy._poll_once()
            delays.append(proxy._result_delay)
        assert delays == pytest.approx([0.02, 0.04, 0.05, 0.05]), \
            "PEND polls must double the gap, capped at poll_max_s"
        # a backing-off proxy still beats: ESTATUS ran every call above
        deadline = time.monotonic() + 10
        while not r.done.is_set() and time.monotonic() < deadline:
            proxy._next_result_poll = 0.0
            proxy._poll_once()
            time.sleep(0.01)
        assert r.done.is_set() and r.status == "done"
        assert list(r.tokens) == [1, 2]
        assert proxy._result_delay == pytest.approx(0.01), \
            "adoption must snap the backoff shut"
    finally:
        srv.stop()


# -- slow: compile-bearing acceptance ----------------------------------------


@pytest.fixture(scope="module")
def gpt():
    import jax
    import jax.numpy as jnp

    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, model, params


def _ref(model, params, prompt, max_tokens=4):
    import jax.numpy as jnp

    from hetu_tpu.models import generate
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=max_tokens, max_len=32)
    return np.asarray(out[0, len(prompt):]).tolist()


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,)).tolist()
            for n in lengths]


@pytest.mark.slow
def test_kv_export_import_cross_engine_token_identity(gpt, tele):
    """TENTPOLE acceptance (engine half): a whole-block prefix
    exported from one engine and imported into a peer serves the
    shared-prefix prompt token-identically with the cached span run
    through ZERO prefill-lane tokens; a stale-version entry is
    refused; a replicated decode resumes on the peer token-identically
    — all without a single serving_step recompile."""
    from hetu_tpu.engine.train_step import trace_counts
    from hetu_tpu.serving import ServingEngine
    cfg, model, params = gpt
    e1 = ServingEngine(model, params, slots=2, max_len=32,
                       prefill_chunk=8)
    e2 = ServingEngine(model, params, slots=2, max_len=32,
                       prefill_chunk=8)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, (16,)).tolist()  # 1 block
    p1 = shared + [3, 5]
    sp = SamplingParams(max_tokens=4)
    want1 = _ref(model, params, p1)
    assert e1.generate_many([p1], sp) == [want1]
    e2.generate_many([_prompts(cfg, [6], seed=9)[0]], sp)  # compile e2
    compiles = trace_counts().get("serving_step", 0)

    entry = e1.export_prefix(shared)
    assert entry is not None and entry.n_blocks == 1
    # through the REAL wire format, like a cross-process pull
    ok = e2.import_prefix(spill_from_wire(spill_to_wire(entry)))
    assert ok, "version-matched import refused"
    r = e2.submit(p1, sp)
    e2.run_until_drained()
    assert list(r.tokens) == want1, "cross-replica pull broke identity"
    assert r.timing()["cached_tokens"] >= 16, \
        "cached span ran prefill-lane tokens"

    # stale-version refusal: the engine-side line of defense
    stale = spill_from_wire(spill_to_wire(e1.export_prefix(shared)))
    stale.weight_version = 99
    assert not e2.import_prefix(stale), "stale entry must be refused"

    # buddy replication: stream e1's decode into a store, resume on e2
    store = KVReplicaStore()
    e1.configure_replication(store.put, origin="e1", cadence_s=0.005)
    p2 = rng.integers(1, cfg.vocab_size, (18,)).tolist()
    want2 = _ref(model, params, p2, 8)
    r2 = e1.submit(p2, SamplingParams(max_tokens=8))
    t = threading.Thread(target=e1.run_until_drained)
    t.start()
    got = None
    for _ in range(600):
        got = store.fetch(r2.trace_id)
        if got is not None:
            break
        time.sleep(0.005)
    t.join()
    e1.configure_replication(None)
    assert got is not None, "no replication shipment fetched"
    assert list(r2.tokens) == want2
    r3 = e2.submit(p2, SamplingParams(max_tokens=8), resume=got)
    e2.run_until_drained()
    assert list(r3.tokens) == want2, "buddy resume broke identity"
    assert r3.timing()["resumed"] is True
    assert trace_counts().get("serving_step", 0) == compiles, \
        "pull/replicate churn recompiled a fused step"


@pytest.mark.slow
def test_router_directory_pull_and_buddy_recovery(gpt, tele):
    """TENTPOLE acceptance (router half): the fleet directory routes a
    shared-prefix prompt's KV pull across replicas (drain forces the
    cross-replica placement) token-identically with the span
    counter-asserted warm; then a replica wedged mid-decode and killed
    resumes from its buddy's replica set token-identically with the
    recovery counter and ``resumed`` timing flag set."""
    from hetu_tpu.serving import ServingEngine
    cfg, model, params = gpt
    router = Router(poll_s=0.001, kv_pull=True, replicate_kv=True,
                    replicate_cadence_s=0.002)
    mk = lambda: ServingEngine(model, params, slots=2, max_len=32,
                               prefill_chunk=8)
    router.register("r0", mk())
    router.register("r1", mk())
    try:
        sp = SamplingParams(max_tokens=4)
        rng = np.random.default_rng(0)
        # compile both engines before measuring anything
        warm = _prompts(cfg, [6, 6], seed=1)
        assert router.generate_many(warm, sp) \
            == [_ref(model, params, p) for p in warm]

        # -- directory pull: 1 whole block shared across replicas ----
        shared = rng.integers(1, cfg.vocab_size, (16,)).tolist()
        p1, p2 = shared + [3, 5], shared + [7, 9, 11]
        want1, want2 = _ref(model, params, p1), _ref(model, params, p2)
        r = router.submit(p1, sp)
        assert r.done.wait(60) and r.status == "done"
        assert list(r.tokens) == want1
        owner = r.replica
        time.sleep(0.1)              # monitor finalizes + publishes
        assert len(router._directory) >= 1
        router.drain(owner, timeout_s=30)      # force cross-replica
        r2 = router.submit(p2, sp)
        assert r2.done.wait(60) and r2.status == "done"
        assert list(r2.tokens) == want2, "directory pull broke identity"
        assert r2.replica != owner
        snap = tele.snapshot()
        assert snap.get("fleet_kv_pull_blocks_total", 0) >= 1
        assert snap.get("fleet_prefix_hit_tokens_total", 0) >= 16
        assert r2.result()["timing"]["cached_tokens"] >= 16, \
            "pulled span ran prefill-lane tokens"
        router.resume(owner)

        # -- buddy recovery: wedge the victim, kill it mid-decode ----
        time.sleep(0.2)              # monitor tick wires buddies
        assert router._buddy_of, "buddies never assigned"
        p3 = rng.integers(1, cfg.vocab_size, (10,)).tolist()
        want3 = _ref(model, params, p3, 14)
        # slow every step so the kill lands mid-decode deterministically
        for h in router._replicas.values():
            orig = h.engine.step
            h.engine.step = \
                (lambda o=orig: (time.sleep(0.02), o())[1])
        r3 = router.submit(p3, SamplingParams(max_tokens=14))
        deadline = time.monotonic() + 60
        victim = None
        while time.monotonic() < deadline and victim is None:
            if r3.replica is not None and r3.inner is not None:
                b = router._buddy_of.get(r3.replica)
                if b and r3.trace_id in \
                        router._replicas[b].engine.kv_replica_store:
                    victim = r3.replica
            time.sleep(0.002)
        assert victim, "buddy never received a shipment"
        h = router._replicas[victim]
        # wedge: hold the step lock so local salvage times out and the
        # recovery path must go through the buddy's replica set
        h.engine._step_lock.acquire()
        try:
            router.kill_replica(victim)
        finally:
            h.engine._step_lock.release()
        assert r3.done.wait(120) and r3.status == "done", \
            (r3.status, r3.error)
        assert list(r3.tokens) == want3, "buddy recovery broke identity"
        tim = r3.result()["timing"]
        snap = tele.snapshot()
        assert snap.get("fleet_kv_recoveries_total", 0) >= 1
        assert tim.get("resumed") is True, \
            "recovery replayed prefill instead of resuming"
        assert tim.get("resumed_blocks", 0) >= 1
    finally:
        router.stop()
