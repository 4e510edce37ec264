"""Multi-process serving fleet: remote replicas over the coordinator.

PR 8's fleet plane proved the serving verbs and the rolling weight
push, but every replica was an in-process thread sharing one process's
devices. This module is the multi-process rung (ISSUE 15): the Router
keeps its exact dispatch/drain/death machinery, and a replica becomes a
*process* — one :class:`~hetu_tpu.serving.engine.ServingEngine` behind
its own line-protocol coordinator (``serving/server.py``), driven
through:

- :class:`RemoteEngineProxy` — satisfies the engine duck type the
  Router speaks (``submit``/``cancel_queued``/``evict_request``/
  ``has_work``/``load``/``weight_version``/``stop``) by translating
  each call into hardened :class:`~hetu_tpu.rpc.client.CoordinatorClient`
  verbs (SUBMIT with **idempotency keys** so retry-after-timeout is
  safe, ESTATUS polling, CANCELQ/EVICT for drains and salvage,
  SWAPWEIGHTS for the dist-checkpoint weight push, PREFILL for the
  prefill tier). A background poller keeps load/occupancy/version fresh
  and doubles as the liveness signal;
- :class:`RemoteReplicaHandle` — a
  :class:`~hetu_tpu.serving.router.ReplicaHandle` whose death detection
  is **heartbeat staleness** (every successful status poll is a beat;
  ``loop_alive()`` is never true because there is no local loop
  thread), so a SIGKILLed engine process is declared dead by the
  router's existing ``beat_timeout_s`` machinery and its in-flight
  requests requeue onto peers;
- the **KV wire format** (:func:`spill_to_wire` /
  :func:`spill_from_wire`) — a
  :class:`~hetu_tpu.serving.kv_pool.SpillEntry` serialized losslessly
  (raw bytes + dtype + shape per cache leaf, base64 on the one-line
  protocol), so preemptive drains, kill salvage and the prefill tier
  move KV **between processes** instead of assuming shared host RAM.
  Bitwise: ``from_wire(to_wire(e))`` reproduces every page exactly;
- :func:`replica_main` — the engine-process entry point
  (``python -m hetu_tpu.serving.fleet``): builds the engine from an
  env-named ``module:function`` spec, serves it on its port, and waits
  for SIGTERM. ``rpc/launcher.launch_serving_fleet(remote=True)``
  spawns one of these per replica and registers the proxies.

Prefill/decode disaggregation rides the same machinery: a replica
registered with ``role="prefill"`` runs admission + prefill only
(``ServingEngine.prefill_only`` parks the request after its first
token), the router evicts the finished KV blocks and streams them —
wire format and all — to a ``role="decode"`` replica, which maps them
in with a block-table edit and resumes through the existing
``submit(resume=)`` path. TTFT (prefill pool) and TPOT (decode pool)
then scale independently. ``docs/SERVING.md`` ("Disaggregated fleet")
has the state machines and failure semantics.
"""

from __future__ import annotations

import base64
import itertools
import threading
import time
import uuid
from typing import Optional, Sequence

import numpy as np

from hetu_tpu import telemetry
from hetu_tpu.serving.kv_pool import SpillEntry
from hetu_tpu.serving.router import ReplicaHandle
from hetu_tpu.serving.scheduler import SamplingParams
from hetu_tpu.utils.logging import get_logger

# -- KV wire format -----------------------------------------------------------


def array_to_wire(a: np.ndarray) -> dict:
    """One numpy array → a JSON-safe dict (dtype + shape + raw bytes,
    base64). Lossless for every arena dtype incl. int8 pages and their
    fp32 scales."""
    a = np.ascontiguousarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "b64": base64.b64encode(a.tobytes()).decode("ascii")}


def array_from_wire(d: dict) -> np.ndarray:
    return np.frombuffer(
        base64.b64decode(d["b64"]), dtype=np.dtype(d["dtype"])
    ).reshape(d["shape"]).copy()


def spill_to_wire(entry: SpillEntry) -> dict:
    """Serialize a SpillEntry for the line protocol — the payload that
    moves KV blocks replica→replica through the coordinator (preemptive
    drains, kill salvage, prefill→decode streaming)."""
    d = {"req_id": entry.req_id,
         "n_blocks": entry.n_blocks,
         "block_size": entry.block_size,
         "pos": entry.pos, "last_tok": entry.last_tok,
         "tokens": [int(t) for t in entry.tokens],
         "weight_version": entry.weight_version,
         "data": [array_to_wire(a) for a in entry.data]}
    if entry.traceparent:
        d["traceparent"] = entry.traceparent
    if entry.key_state is not None:
        # a sampled request's PRNG commit-key state must travel with
        # its KV — a cross-process resume without it would fork the
        # sample stream and diverge from the undisturbed run
        d["key_state"] = array_to_wire(np.asarray(entry.key_state))
    return d


def spill_from_wire(d: dict) -> SpillEntry:
    ks = d.get("key_state")
    return SpillEntry(
        req_id=int(d["req_id"]),
        data=tuple(array_from_wire(a) for a in d["data"]),
        n_blocks=int(d["n_blocks"]), block_size=int(d["block_size"]),
        pos=int(d["pos"]), last_tok=int(d["last_tok"]),
        tokens=[int(t) for t in d["tokens"]],
        weight_version=int(d["weight_version"]),
        traceparent=d.get("traceparent"),
        key_state=array_from_wire(ks) if ks is not None else None)


# -- decode-KV replication: the buddy-side store ------------------------------


class KVReplicaStore:
    """Buddy-side accumulator of a decoding peer's replicated KV
    (ISSUE 18).

    The origin engine streams newly committed blocks on a
    block-granular cadence (``ServingEngine.configure_replication``);
    each shipment is a JSON-safe doc carrying a contiguous block range
    ``[start, start+n)`` per cache leaf plus a CONSISTENT metadata
    snapshot (pos / tokens / last_tok / PRNG key state, captured under
    the origin's step lock in the same breath as the blocks). Entries
    are keyed by ``trace_id`` — the one identity that survives the
    origin's death and any number of requeues — and :meth:`fetch`
    assembles a full :class:`SpillEntry` the recovery path feeds to
    ``submit(resume=)`` on a live peer: bit-identical to a local
    preemption resume, because it IS one.

    Jax-free and lock-cheap: ``put`` runs on the buddy's verb-handler
    thread (wire) or the origin's replication thread (in-process) and
    only touches numpy."""

    def __init__(self, max_traces: int = 256):
        self.max_traces = int(max_traces)
        self._lock = threading.Lock()
        self._by_trace: dict[str, dict] = {}     # insertion order = LRU
        self.put_total = 0

    @property
    def blocks_held(self) -> int:
        with self._lock:
            return sum(len(e["blocks"])
                       for e in self._by_trace.values())

    def __contains__(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._by_trace

    def put(self, doc: dict) -> None:
        """Absorb one replication shipment (or a ``{"drop": tid}``
        tombstone when the origin finished the request)."""
        tid = doc.get("drop")
        if tid:
            with self._lock:
                self._by_trace.pop(tid, None)
            return
        tid = doc["trace_id"]
        data = [array_from_wire(a) for a in doc["data"]]
        start = int(doc["start"])
        with self._lock:
            ent = self._by_trace.pop(tid, None) or {"blocks": {}}
            self._by_trace[tid] = ent            # refresh LRU position
            for j in range(int(data[0].shape[1])):
                ent["blocks"][start + j] = [a[:, j:j + 1] for a in data]
            ent["meta"] = {k: doc.get(k) for k in (
                "origin", "req_id", "weight_version", "block_size",
                "pos", "last_tok", "tokens", "key_state",
                "traceparent")}
            self.put_total += 1
            while len(self._by_trace) > self.max_traces:
                self._by_trace.pop(next(iter(self._by_trace)))

    def fetch(self, trace_id: str) -> Optional[SpillEntry]:
        """Assemble the replica set into a resumable SpillEntry, or
        ``None`` while coverage is incomplete (a request that died
        before its first shipment simply replays from the prompt)."""
        with self._lock:
            ent = self._by_trace.get(trace_id)
            if ent is None or "meta" not in ent:
                return None
            m = ent["meta"]
            bs, pos = int(m["block_size"]), int(m["pos"])
            nb = max(1, -(-pos // bs))
            blocks = ent["blocks"]
            if any(i not in blocks for i in range(nb)):
                return None
            data = tuple(
                np.concatenate([blocks[i][leaf] for i in range(nb)],
                               axis=1)
                for leaf in range(len(blocks[0])))
        ks = m.get("key_state")
        return SpillEntry(
            req_id=int(m["req_id"]), data=data, n_blocks=nb,
            block_size=bs, pos=pos, last_tok=int(m["last_tok"]),
            tokens=[int(t) for t in m["tokens"]],
            weight_version=int(m["weight_version"]),
            traceparent=m.get("traceparent"),
            key_state=array_from_wire(ks) if ks is not None else None)

    def drop(self, trace_id: str) -> None:
        with self._lock:
            self._by_trace.pop(trace_id, None)


# -- the remote request -------------------------------------------------------


class RemoteRequest:
    """Router-side view of one request living on a REMOTE engine.

    Duck-typed to the slice of :class:`~hetu_tpu.serving.scheduler.
    Request` the router and the line-protocol front end read: ``id``,
    ``status``, ``error``, ``tokens``, ``weight_version``,
    ``first_token_s``, ``submit_s``, ``done``, ``spill``, ``handoff``,
    ``timing()``. The proxy's poller (or the blocking PREFILL call)
    fills it in from RESULT payloads."""

    def __init__(self, prompt, sampling: SamplingParams, *,
                 handoff: bool = False,
                 traceparent: Optional[str] = None):
        self.id: int = _next_provisional_id()
        self.prompt = [int(t) for t in prompt]
        self.sampling = sampling
        self.submit_s = time.monotonic()
        self.status = "queued"
        self.error: Optional[str] = None
        self.tokens: list = []
        self.weight_version: int = 0
        self.first_token_s: Optional[float] = None
        self.finish_s: Optional[float] = None
        self.traceparent = traceparent
        tid, _span = telemetry.parse_traceparent(traceparent)
        self.trace_id = tid or uuid.uuid4().hex[:12]
        self.handoff = bool(handoff)
        self.spill: Optional[SpillEntry] = None
        self.done = threading.Event()
        self._timing: dict = {}

    def timing(self) -> dict:
        return dict(self._timing)

    def result(self) -> dict:
        return {"id": self.id, "status": self.status,
                "tokens": list(self.tokens), "error": self.error,
                "weight_version": self.weight_version,
                "timing": self.timing()}

    def _fill_from(self, doc: dict) -> None:
        """Adopt a RESULT/PREFILL payload as this request's state."""
        self.status = doc.get("status", "done")
        self.error = doc.get("error")
        self.tokens = list(doc.get("tokens", []))
        self.weight_version = int(doc.get("weight_version", 0))
        self._timing = dict(doc.get("timing", {}))
        self.finish_s = time.monotonic()
        if self.first_token_s is None and self.tokens:
            # approximate: the real TTFT happened on the remote engine
            # and rides the timing breakdown; the local stamp only
            # feeds the router's EWMA tiebreak
            ttft_ms = self._timing.get("ttft_ms")
            self.first_token_s = self.submit_s + ttft_ms / 1e3 \
                if ttft_ms is not None else time.monotonic()


#: provisional ids are NEGATIVE so they can never collide with a remote
#: engine's real (>= 0) request ids inside one handle's inflight map
_provisional = itertools.count(1)


def _next_provisional_id() -> int:
    return -next(_provisional)


class _RemoteSched:
    """Duck-typed ``engine.scheduler`` view (depth/occupancy) for
    :meth:`ReplicaHandle.status`, fed by the proxy's status poller."""

    def __init__(self, proxy: "RemoteEngineProxy"):
        self._proxy = proxy

    @property
    def depth(self) -> int:
        return int(self._proxy._status.get("depth", 0))

    @property
    def occupancy(self) -> float:
        return float(self._proxy._status.get("occupancy", 0.0))


# -- the engine proxy ---------------------------------------------------------


class RemoteEngineProxy:
    """ServingEngine duck type over the coordinator line protocol.

    One persistent :class:`CoordinatorClient` (lock-guarded — the
    router thread and the poller share it) carries the short verbs;
    long-blocking calls (PREFILL, SWAPWEIGHTS, STOPENGINE) open their
    own connection so they never starve status polls. Every transport
    failure is survivable: a failed submit leaves the request queued
    and unreachable-marked (the router's heartbeat-staleness death
    detection requeues it onto a peer), a failed evict degrades to a
    fresh requeue, a failed status poll just ages the beat.
    """

    remote = True                    # Router.register picks the handle

    def __init__(self, port: int, host: str = "127.0.0.1", *,
                 token: Optional[str] = None,
                 poll_s: float = 0.05, poll_max_s: float = 0.25,
                 timeout_s: float = 5.0,
                 swap_timeout_s: float = 300.0,
                 use_stream: bool = True,
                 heartbeat_s: float = 0.25):
        self.port, self.host = int(port), host
        self._token = token
        self._poll_s = float(poll_s)
        # streaming control plane (ISSUE 19): subscribe to each
        # submitted request's token stream over one persistent
        # multiplexed channel instead of RESULT-polling it; the poll
        # lane survives only as the loud fallback on stream loss
        # (resubscribe-at-offset reconverges). With a healthy channel,
        # ESTATUS stretches to ``heartbeat_s`` cadence — it stays the
        # router's beat (a SIGKILLed replica is still reaped within
        # ``beat_timeout_s``) but stops being per-tick load noise.
        self.use_stream = bool(use_stream)
        self._heartbeat_s = max(float(heartbeat_s), float(poll_s))
        self._next_beat = 0.0
        self._schan = None
        self._schan_lock = threading.Lock()
        self._schan_next_try = 0.0
        # adaptive RESULT-poll backoff (ISSUE 18 satellite): ESTATUS
        # keeps its fixed cadence (it IS the heartbeat — backing it off
        # would trip the router's staleness reaper), but the per-request
        # RESULT polls back off exponentially toward ``poll_max_s``
        # while they keep answering PEND, and snap back to ``poll_s``
        # on any activity (a result adopted, a new submit)
        self._poll_max_s = max(float(poll_max_s), self._poll_s)
        self._result_delay = self._poll_s
        self._next_result_poll = 0.0
        self._timeout_s = float(timeout_s)
        self._swap_timeout_s = float(swap_timeout_s)
        self._lock = threading.RLock()
        self._cli = None
        self._kv_lock = threading.Lock()
        self._kv_cli = None              # dedicated replication socket
        self._pending: dict[int, RemoteRequest] = {}
        self._status: dict = {}
        #: wall-clock offset of the replica vs this process (replica
        #: clock = ours + offset), from the latest ESTATUS handshake;
        #: fleet_trace.py uses it to align merged spans
        self.clock_offset_s: float = 0.0
        self._handle: Optional[ReplicaHandle] = None   # beat sink
        self._stop = None            # duck parity with ServingEngine
        self._thread: Optional[threading.Thread] = None
        self._stop_ev: Optional[threading.Event] = None
        self.scheduler = _RemoteSched(self)

    # -- transport ----------------------------------------------------------
    def _client(self, *, fresh: bool = False, timeout: Optional[float]
                = None):
        from hetu_tpu.rpc.client import CoordinatorClient
        if fresh:
            return CoordinatorClient(
                self.port, host=self.host, token=self._token,
                timeout=timeout or self._timeout_s, retries=1)
        if self._cli is None:
            # one bounded retry: SUBMIT rides an idempotency key (a
            # duplicate delivery joins the original request), ESTATUS
            # is read-only — a single TCP hiccup must not strand work
            self._cli = CoordinatorClient(
                self.port, host=self.host, token=self._token,
                timeout=self._timeout_s, retries=1, backoff_s=0.02)
        return self._cli

    def _drop_client(self) -> None:
        with self._lock:
            if self._cli is not None:
                try:
                    self._cli.close()
                except OSError:
                    pass
                self._cli = None

    # -- streaming lane (ISSUE 19) -------------------------------------------
    def _stream_channel(self):
        """The proxy's one persistent multiplexed channel (lazily
        connected, throttled reconnect). Raises on connect failure —
        callers degrade to the poll lane."""
        with self._schan_lock:
            ch = self._schan
            if ch is not None and ch.alive:
                return ch
            now = time.monotonic()
            if now < self._schan_next_try:
                raise ConnectionError("stream reconnect backing off")
            self._schan_next_try = now + 0.25
            from hetu_tpu.rpc.stream import StreamChannel
            ch = StreamChannel(self.port, host=self.host,
                               token=self._token or "",
                               connect_timeout=self._timeout_s)
            self._schan = ch
            return ch

    def _subscribe_stream(self, rr: RemoteRequest, *,
                          resume: bool = False) -> bool:
        """Subscribe ``rr`` at its current token offset; False =
        unavailable (the RESULT poll lane keeps it)."""
        if not self.use_stream or rr.id < 0:
            return False
        from hetu_tpu.serving.streaming import (
            count_fallback, count_subscribe,
        )
        # claim the stream BEFORE the frame goes out: the server's
        # answer (a ``drop`` from an engine without streaming, a ``done``
        # for a request that already finished) can reach the reader
        # thread first, and its ``_stream_ok = False`` must not be
        # overwritten afterwards — the poll lane would never take the
        # request back
        rr._stream_ok = True
        try:
            ch = self._stream_channel()
            ch.subscribe(rr.id, offset=len(rr.tokens),
                         sink=lambda ev, _rr=rr:
                         self._on_stream_event(_rr, ev))
        except Exception:                             # noqa: BLE001
            rr._stream_ok = False
            count_fallback("subscribe_failed")
            return False
        count_subscribe("resume" if resume else "new")
        return True

    def _on_stream_event(self, rr: RemoteRequest, ev: dict) -> None:
        """Channel-reader-thread sink: fold one event into ``rr``.
        Token deltas append at their offset (idempotent across replays
        — a resubscribed stream clips the overlap); the ``done`` frame
        adopts the full result exactly like a RESULT poll would; any
        loss marker flips the request back to the poll lane, loudly."""
        from hetu_tpu.serving.streaming import count_fallback
        kind = ev.get("k")
        if kind == "ev":
            toks = [int(t) for t in ev.get("toks", [])]
            off = int(ev.get("off", 0))
            skip = len(rr.tokens) - off
            if skip < 0:
                # a gap means a lost frame — never guess: fall back
                rr._stream_ok = False
                count_fallback("gap")
                self._reset_result_backoff()
                return
            if skip:
                toks = toks[skip:]
            if toks:
                if rr.first_token_s is None:
                    rr.first_token_s = time.monotonic()
                rr.tokens.extend(toks)
            if ev.get("done"):
                rr._fill_from(ev.get("result") or {})
                rr._stream_ok = False
                self._pending.pop(rr.id, None)
                rr.done.set()
            elif ev.get("end"):
                # evicted/cancelled server-side — the router's
                # drain/requeue owns the request now
                rr._stream_ok = False
            for cb in list(getattr(rr, "_taps", ())):
                try:
                    cb(ev)
                except Exception:                     # noqa: BLE001
                    pass
            return
        if kind in ("drop", "lost", "err"):
            rr._stream_ok = False
            if kind == "drop" and ev.get("reason") in (
                    "unsupported", "unknown_request"):
                rr._stream_denied = True    # server can't stream this
            if not rr.done.is_set():
                count_fallback(str(ev.get("reason", kind)))
                self._reset_result_backoff()   # poll lane, eagerly

    def stream_tap(self, rr: RemoteRequest, cb) -> "callable":
        """Register a callback on ``rr``'s live event feed (the
        router's stream bridge). Returns the detach callable."""
        taps = rr.__dict__.setdefault("_taps", [])
        taps.append(cb)

        def _detach(taps=taps, cb=cb):
            try:
                taps.remove(cb)
            except ValueError:
                pass
        return _detach

    #: load reported while the engine is UNREACHABLE (a failed verb or
    #: status poll): effectively infinite, so least-loaded dispatch
    #: steers new work to healthy peers during the staleness window
    #: before the router declares the replica dead. Self-correcting —
    #: the next successful poll restores the real load.
    _SUSPECT_LOAD = 1 << 30

    def _mark_suspect(self) -> None:
        self._status = dict(self._status, load=self._SUSPECT_LOAD)

    # -- engine duck type (what Router calls) --------------------------------
    @property
    def load(self) -> int:
        return int(self._status.get("load", 0))

    @property
    def weight_version(self) -> int:
        return int(self._status.get("weight_version", 0))

    @property
    def block_size(self) -> int:
        """The remote arena's block size (0 until the first ESTATUS
        answers) — the prefix directory hashes at this granularity."""
        return int(self._status.get("block_size", 0))

    def has_work(self) -> bool:
        return bool(self._status.get("has_work", False))

    def submit(self, prompt: Sequence[int],
               sampling: Optional[SamplingParams] = None, *,
               resume: Optional[SpillEntry] = None,
               handoff: bool = False,
               traceparent: Optional[str] = None) -> RemoteRequest:
        sampling = sampling or SamplingParams()
        if traceparent is None and resume is not None:
            traceparent = resume.traceparent
        rr = RemoteRequest(prompt, sampling, handoff=handoff,
                           traceparent=traceparent)
        if handoff:
            # PREFILL blocks server-side until the KV is ready — run it
            # on its own connection + thread so dispatch stays snappy
            threading.Thread(target=self._prefill_call, args=(rr,),
                             daemon=True,
                             name=f"prefill-{self.port}").start()
            return rr
        try:
            with self._lock:
                doc = self._client().serving_submit_info(
                    rr.prompt, resume=spill_to_wire(resume)
                    if resume is not None else None,
                    traceparent=rr.traceparent,
                    **_sampling_kw(sampling))
        except Exception as e:                        # noqa: BLE001
            if _is_rejection(e):
                rr.status, rr.error = "rejected", str(e)
                rr.done.set()
                return rr
            # unreachable / flaky transport (retries exhausted): mark
            # the request so the router monitor requeues it onto a
            # peer even while this replica's beats stay fresh — a
            # transient failure must never strand a request forever
            self._drop_client()
            self._mark_suspect()
            rr.status = "transport_failed"
            rr.error = f"transport: {e}"
            return rr
        rr.id = int(doc["id"])
        rr.trace_id = doc.get("trace_id", rr.trace_id)
        if resume is not None and doc.get("resumed"):
            rr.spill = resume          # identity marker the router reads
        rr.status = "dispatched"
        self._pending[rr.id] = rr
        # push first: a healthy subscription delivers the result the
        # step it commits; the eager poll reset only matters when the
        # stream is unavailable (then the poll lane carries the load)
        if not self._subscribe_stream(rr):
            self._reset_result_backoff()
        return rr

    def _prefill_call(self, rr: RemoteRequest) -> None:
        try:
            cli = self._client(fresh=True,
                               timeout=self._swap_timeout_s)
            try:
                doc = cli.serving_prefill(rr.prompt,
                                          traceparent=rr.traceparent,
                                          **_sampling_kw(rr.sampling))
            finally:
                cli.close()
        except Exception as e:                        # noqa: BLE001
            if _is_rejection(e):
                rr.status, rr.error = "rejected", str(e)
                rr.done.set()
                return
            self._mark_suspect()
            rr.status = "transport_failed"    # monitor requeues onto
            rr.error = f"transport: {e}"      # a peer (or back here)
            return
        rr.id = int(doc["id"])
        rr.trace_id = doc.get("trace_id", rr.trace_id)
        if doc.get("done"):
            rr._fill_from(doc["result"])
            rr.done.set()
            return
        rr.tokens = list(doc.get("tokens", []))
        rr.weight_version = int(doc.get("weight_version", 0))
        rr.first_token_s = time.monotonic()
        rr.spill = spill_from_wire(doc["spill"])
        rr.status = "prefilled"       # the router monitor takes it from
        #                               here (evict → stream → requeue)

    def cancel_queued(self, ids=None) -> list[RemoteRequest]:
        want = [rid for rid in (ids if ids is not None
                                else list(self._pending))
                if rid in self._pending and rid >= 0]
        if not want:
            return []
        try:
            with self._lock:
                doc = self._client().serving_cancel_queued(want)
        except Exception:                             # noqa: BLE001
            self._drop_client()
            return []
        out = []
        for c in doc.get("cancelled", []):
            rr = self._pending.pop(int(c["id"]), None)
            if rr is None:
                continue
            rr.status = "cancelled"
            if c.get("spill") is not None:
                rr.spill = spill_from_wire(c["spill"])
            out.append(rr)
        return out

    def evict_request(self, req: RemoteRequest, *,
                      lock_timeout_s: Optional[float] = None
                      ) -> Optional[SpillEntry]:
        if req.spill is not None:
            # the PREFILL round trip already carried the KV
            entry, req.spill = req.spill, None
            req.status = "evicted"
            self._pending.pop(req.id, None)
            return entry
        try:
            with self._lock:
                doc = self._client().serving_evict(
                    req.id, lock_timeout_s=lock_timeout_s,
                    traceparent=getattr(req, "traceparent", None)
                    or telemetry.make_traceparent(req.trace_id))
        except Exception:                             # noqa: BLE001
            self._drop_client()
            return None                # salvage is best-effort
        req.status = doc.get("status", req.status)
        if req.status in ("evicted", "cancelled"):
            self._pending.pop(req.id, None)
        if doc.get("spill") is None:
            return None
        return spill_from_wire(doc["spill"])

    def swap_from_checkpoint(self, path: str, version: int) -> dict:
        """The remote leg of a ``transport="dist_ckpt"`` weight push:
        the engine process loads ``path`` (shared filesystem / blob
        store) onto its own topology and swaps. Own connection — a
        large load must not block status polls."""
        cli = self._client(fresh=True, timeout=self._swap_timeout_s)
        try:
            return cli.serving_swap_weights(
                path, version,
                traceparent=telemetry.current_traceparent())
        finally:
            cli.close()

    # -- fleet-global KV plane (ISSUE 18) ------------------------------------
    def export_prefix(self, tokens) -> Optional[SpillEntry]:
        """KVEXPORT: gather this replica's cached whole-block prefix of
        ``tokens`` into a SpillEntry (None on miss / transport loss —
        a pull is always best-effort, the puller just prefills)."""
        try:
            with self._lock:
                doc = self._client().serving_kv_export(
                    [int(t) for t in tokens])
        except Exception:                             # noqa: BLE001
            self._drop_client()
            return None
        if not doc or doc.get("spill") is None:
            return None
        return spill_from_wire(doc["spill"])

    def import_prefix(self, entry: SpillEntry) -> bool:
        """KVIMPORT: map a peer-exported prefix into the remote
        replica's prefix cache. False = refused (stale weight version,
        layout mismatch, arena full) or transport loss — the caller
        falls back to a plain prefill."""
        try:
            with self._lock:
                doc = self._client().serving_kv_import(
                    spill_to_wire(entry))
        except Exception:                             # noqa: BLE001
            self._drop_client()
            return False
        return bool(doc and doc.get("ok"))

    def _kv_client(self):
        from hetu_tpu.rpc.client import CoordinatorClient
        if self._kv_cli is None:
            # replication is a steady block stream — give it its own
            # socket so big shipments never starve the status poller
            self._kv_cli = CoordinatorClient(
                self.port, host=self.host, token=self._token,
                timeout=self._timeout_s, retries=1, backoff_s=0.02)
        return self._kv_cli

    def kv_put(self, doc: dict) -> None:
        """KVREPL: deliver one replication shipment to the remote
        buddy's :class:`KVReplicaStore`. Raises on transport loss —
        the origin's replication thread absorbs and retries next
        cadence."""
        with self._kv_lock:
            try:
                self._kv_client().serving_kv_put(doc)
            except Exception:
                if self._kv_cli is not None:
                    try:
                        self._kv_cli.close()
                    except OSError:
                        pass
                    self._kv_cli = None
                raise

    def kv_fetch(self, trace_id: str) -> Optional[SpillEntry]:
        """KVFETCH: assemble the buddy-held replica set for
        ``trace_id`` into a resumable SpillEntry (None = no/partial
        coverage — recovery replays from the prompt instead)."""
        try:
            with self._lock:
                doc = self._client().serving_kv_fetch(trace_id)
        except Exception:                             # noqa: BLE001
            self._drop_client()
            return None
        if not doc or doc.get("spill") is None:
            return None
        return spill_from_wire(doc["spill"])

    def set_kv_buddy(self, host: Optional[str], port: int = 0, *,
                     token: Optional[str] = None, origin: str = "",
                     cadence_s: float = 0.02) -> bool:
        """KVBUDDY: point the remote engine's replication stream at a
        buddy replica (``host=None`` disables it)."""
        try:
            with self._lock:
                self._client().serving_kv_buddy(
                    host, port, token=token, origin=origin,
                    cadence_s=cadence_s)
            return True
        except Exception:                             # noqa: BLE001
            self._drop_client()
            return False

    # -- federation scrape (Router._tick → FLEETMETRICS/fleet HEALTHZ) -------
    def metrics_text(self) -> str:
        """This replica's Prometheus exposition page."""
        with self._lock:
            return self._client().metrics_text()

    def healthz(self) -> dict:
        with self._lock:
            return self._client().healthz()

    def dump_obs(self) -> dict:
        """The replica's DUMPOBS bundle (chrome trace + flight ring) —
        what ``tools/fleet_trace.py`` collects for the merge. Fresh
        connection: a big trace dump must not starve status polls."""
        cli = self._client(fresh=True, timeout=self._swap_timeout_s)
        try:
            return cli.dump_obs()
        finally:
            cli.close()

    # -- lifecycle -----------------------------------------------------------
    def start(self, idle_sleep_s: float = 0.0) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop_ev = threading.Event()
        self._thread = threading.Thread(
            target=self._poll_loop, daemon=True,
            name=f"remote-engine-poll-{self.port}")
        self._thread.start()

    def stop(self) -> None:
        if self._stop_ev is not None:
            self._stop_ev.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        try:
            cli = self._client(fresh=True, timeout=2.0)
            try:
                cli.serving_stop_engine()
            finally:
                cli.close()
        except Exception:                             # noqa: BLE001
            pass                       # the process may already be gone
        self._drop_client()
        with self._schan_lock:
            if self._schan is not None:
                try:
                    self._schan.close()
                except Exception:                     # noqa: BLE001
                    pass
                self._schan = None
        with self._kv_lock:
            if self._kv_cli is not None:
                try:
                    self._kv_cli.close()
                except OSError:
                    pass
                self._kv_cli = None

    # -- the poller ----------------------------------------------------------
    def _poll_loop(self) -> None:
        while not self._stop_ev.is_set():
            self._poll_once()
            self._stop_ev.wait(self._poll_s)

    def _poll_once(self) -> bool:
        # ESTATUS coalesced with stream liveness (ISSUE 19 satellite):
        # with a healthy subscription channel the per-tick status poll
        # stretches to heartbeat-only cadence. ESTATUS stays the beat —
        # skipping it only delays the ``last_beat`` stamp by at most
        # ``heartbeat_s``, which must stay well under the router's
        # ``beat_timeout_s`` for SIGKILL reaping to keep its deadline.
        now = time.monotonic()
        ch = self._schan
        if self.use_stream and ch is not None and ch.alive \
                and now < self._next_beat:
            return self._poll_results()
        try:
            with self._lock:
                t0 = time.time()
                self._status = self._client().serving_estatus()
                t1 = time.time()
        except Exception:                             # noqa: BLE001
            self._drop_client()
            self._mark_suspect()
            return False               # no beat: staleness accumulates
        self._next_beat = time.monotonic() + self._heartbeat_s
        srv_ts = self._status.get("ts_unix")
        if srv_ts is not None:
            # NTP-style offset handshake (ISSUE 16): the replica
            # stamped its wall clock mid-RTT, so its offset from ours
            # is its stamp minus the RTT midpoint. Re-measured on every
            # poll — the merge tool reads the freshest value and the
            # skew gauge lets an operator spot a drifting host.
            off = float(srv_ts) - 0.5 * (t0 + t1)
            self.clock_offset_s = off
            name = self._handle.name if self._handle is not None \
                else f":{self.port}"
            telemetry.get_registry().gauge(
                "fleet_clock_skew_seconds",
                "per-replica wall-clock offset vs this process, "
                "measured at each status poll (replica label)").set(
                round(off, 6), replica=name)
        if self._handle is not None:
            self._handle.last_beat = time.monotonic()
        return self._poll_results()

    def _poll_results(self) -> bool:
        """The RESULT lane: streamed requests are skipped (push owns
        them); a request whose stream was lost first tries a
        resubscribe-at-offset, then polls — loudly counted either
        way."""
        if time.monotonic() < self._next_result_poll:
            return True                # RESULT lane is backing off
        adopted = polled = 0
        for rid, rr in list(self._pending.items()):
            if rr.done.is_set() or rr.status in ("prefilled",
                                                 "evicted",
                                                 "cancelled"):
                continue
            if getattr(rr, "_stream_ok", False):
                continue               # the push lane owns this one
            if self.use_stream and rid >= 0 \
                    and not getattr(rr, "_stream_denied", False) \
                    and self._subscribe_stream(rr, resume=True):
                continue               # back on the push lane, resumed
            #                            exactly at len(rr.tokens)
            polled += 1
            try:
                with self._lock:
                    doc = self._client().serving_result(rid,
                                                        timeout_ms=0)
            except Exception:                         # noqa: BLE001
                self._drop_client()
                return False
            if doc is None:
                # the poll cycle burned a RESULT round trip for nothing
                # — the empty-poll fraction is the case for streaming
                # RESULT (tests/test_fleet_observability.py reads it)
                telemetry.get_registry().counter(
                    "router_result_poll_empty_total",
                    "RESULT polls that returned PEND (wasted round "
                    "trips — the streaming-RESULT motivation)").inc()
                continue
            rr._fill_from(doc)
            self._pending.pop(rid, None)
            rr.done.set()
            adopted += 1
        if adopted:
            self._reset_result_backoff()
        elif polled:
            # every in-flight RESULT answered PEND: widen the gap
            self._result_delay = min(self._poll_max_s,
                                     self._result_delay * 2)
            self._next_result_poll = time.monotonic() \
                + self._result_delay
        return True

    def _reset_result_backoff(self) -> None:
        self._result_delay = self._poll_s
        self._next_result_poll = 0.0


def _sampling_kw(sp: SamplingParams) -> dict:
    kw = {"temperature": sp.temperature, "top_k": sp.top_k,
          "top_p": sp.top_p, "eos_id": sp.eos_id,
          "max_tokens": sp.max_tokens, "priority": sp.priority}
    if getattr(sp, "tenant", None) is not None:
        kw["tenant"] = sp.tenant
    if getattr(sp, "adapter", None) is not None:
        kw["adapter"] = sp.adapter
    return kw


def _is_rejection(e: Exception) -> bool:
    """Admission rejections come back as ``ERR rejected: ...`` lines
    the client surfaces as RuntimeError — terminal, not transport."""
    return isinstance(e, RuntimeError) and "rejected" in str(e)


# -- the replica handle -------------------------------------------------------


class RemoteReplicaHandle(ReplicaHandle):
    """A :class:`ReplicaHandle` whose replica lives in ANOTHER process.

    Liveness inverts: there is no loop thread to watch, so
    ``loop_alive()``/``loop_died()`` are always False and death comes
    exclusively from **heartbeat staleness** — the proxy's poller
    stamps ``last_beat`` on every successful status round trip, and the
    router's existing ``beat_timeout_s`` check declares the replica
    dead when the beats stop (process SIGKILLed, host gone, network
    partitioned). Registration itself counts as the first beat, so a
    replica that never answers is reaped after one timeout instead of
    living forever."""

    remote = True

    def __init__(self, name: str, proxy: RemoteEngineProxy):
        super().__init__(name, proxy)        # type: ignore[arg-type]
        proxy._handle = self
        self.last_beat = time.monotonic()    # registration = beat 0

    def loop_alive(self) -> bool:
        return False

    def loop_died(self) -> bool:
        return False

    def status(self) -> dict:
        doc = super().status()
        doc["remote"] = True
        doc["beat_age_s"] = round(
            time.monotonic() - self.last_beat, 3) \
            if self.last_beat is not None else None
        doc["clock_offset_s"] = round(
            getattr(self.engine, "clock_offset_s", 0.0), 6)
        return doc


# -- the engine-process entry point -------------------------------------------


def replica_main() -> int:
    """Entry point of one fleet engine process
    (``python -m hetu_tpu.serving.fleet``, spawned by
    ``rpc/launcher.launch_serving_fleet(remote=True)``).

    Env contract:

    - ``HETU_ENGINE_SPEC``   — ``module:function``; called with the
      replica index, must return a ready ServingEngine (the fleet
      analogue of the launcher's ``build_engine(i)``)
    - ``HETU_REPLICA_INDEX`` — this replica's index (default 0)
    - ``HETU_REPLICA_NAME``  — this replica's fleet name
    - ``HETU_REPLICA_ROLE``  — ``prefill``/``decode``/``both``
      (observability identity only — the router owns actual placement)
    - ``HETU_ENGINE_PORT``   — the line-protocol port to serve on
    - ``HETU_ENGINE_TOKEN``  — optional bearer token
    - ``HETU_TELEMETRY``     — ``1`` turns the tracer/registry on, so
      DUMPOBS bundles carry real spans for ``tools/fleet_trace.py``

    Serves until SIGTERM (clean launcher teardown); SIGKILL is the
    chaos path — the router's heartbeat staleness handles it.
    """
    import importlib
    import os
    import signal

    spec = os.environ["HETU_ENGINE_SPEC"]
    idx = int(os.environ.get("HETU_REPLICA_INDEX", "0"))
    port = int(os.environ["HETU_ENGINE_PORT"])
    name = os.environ.get("HETU_REPLICA_NAME", f"r{idx}")
    token = os.environ.get("HETU_ENGINE_TOKEN", "")
    if os.environ.get("HETU_TELEMETRY", "") not in ("", "0"):
        telemetry.enable(True)
    # stamp fleet identity into the flight recorder BEFORE the engine
    # builds, so even a crash-during-init dump says who it was
    telemetry.get_flight_recorder().set_identity(
        replica=name, role=os.environ.get("HETU_REPLICA_ROLE"))
    mod_name, fn_name = spec.split(":")
    build = getattr(importlib.import_module(mod_name), fn_name)
    engine = build(idx)

    from hetu_tpu.serving.server import ServingServer
    srv = ServingServer(engine, port, token=token)
    srv.start()
    srv.wait_ready()
    get_logger().info(
        f"fleet replica {name} (index {idx}) serving on :{port}")
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    srv.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(replica_main())
