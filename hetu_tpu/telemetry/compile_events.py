"""Compile events: what JAX spent tracing, lowering, compiling and
reading its compilation cache, by function.

``record_trace`` counts traces of step bodies; nothing said how much of
a process's start-up is tracing, lowering, XLA compilation or a cache
read, or which function a recompile in production belongs to. JAX
reports all four through ``jax.monitoring`` duration events; ONE
listener, registered when ``hetu_tpu.telemetry`` is imported, keeps them.
It fires only when something compiles — a warm step dispatch reports
nothing — so it is always on:

- every event is appended to a bounded module-level list
  (:func:`compile_events`); a trace nested in another function's trace
  is dropped when the outer one ends, so the kept durations add up;
- with telemetry enabled it is mirrored into the registry as
  ``jax_compile_seconds_total{stage=...}`` / ``jax_compiles_total{stage=...}``,
  ``jax_compile_cache_total{result=...}`` and
  ``jax_compile_cold_seconds_total``;
- a backend compile also leaves a ``jax_compile`` flight event naming
  the function and what the cache said.

Stages: ``trace`` (jaxpr tracing), ``lower`` (jaxpr → MLIR module),
``compile`` (the backend compile call, which INCLUDES a persistent-cache
read when the cache is on) and ``cache`` (that read alone). Time spent
is therefore ``trace + lower + compile``; ``cache`` says how much of
``compile`` was a disk read.

Whether a ``compile`` was that read or a real compilation is told by
JAX's plain events, which fire on the compiling thread INSIDE the call
whose duration follows (``jax/_src/compiler.py``): a second listener
keeps the thread's last outcome and the next ``compile`` event of that
thread carries it as ``cache`` — ``hit``, ``miss`` (compiled and
written), ``uncached`` (the cache was asked and the result neither
found nor written: under ``jax_persistent_cache_min_compile_time_secs``
/ ``_min_entry_size_bytes``, or no directory is set) or ``off`` (no
request: ``jax_enable_compilation_cache`` is off or the backend cannot
serialize an executable). The seconds of the ``compile`` events that
are not hits are ``jax_compile_cold_seconds_total``: what a warm cache
would have saved (a counter of its own: the stages of
``jax_compile_seconds_total`` are summed).
"""

from __future__ import annotations

import threading
import time
from typing import NamedTuple, Optional

import jax

#: jax.monitoring keys -> stage
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache",
}
#: jax.monitoring's plain events -> what the cache said of the compile
#: they fire in (the request comes first; a hit or a miss overrides it)
CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "uncached",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: one traced step can report tens of thousands of nested traces before
#: its own ends and takes them out again
MAX_EVENTS = 65536


class CompileEvent(NamedTuple):
    key: str                    # the jax.monitoring event key
    stage: str                  # trace | lower | compile | cache
    seconds: float
    t: float                    # time.perf_counter() when it ended
    fun_name: Optional[str]     # where JAX gives one
    # new fields go last, with defaults
    cache: Optional[str] = None   # compile: hit | miss | uncached | off
    thread: str = ""            # the name of the thread it fired on


_EVENTS: list[CompileEvent] = []
_LOCK = threading.Lock()
_LOCAL = threading.local()
_handles = None  # (registry, seconds, events, cache results, cold, flight)


def _cache_listener(event: str, **kwargs) -> None:
    result = CACHE_EVENTS.get(event)
    if result is not None:
        _LOCAL.cache = result


def _listener(event: str, duration: float, **kwargs) -> None:
    stage = STAGES.get(event)
    if stage is None:
        return
    fun_name = kwargs.get("fun_name")
    cache = None
    if stage == "compile":
        cache = getattr(_LOCAL, "cache", None) or "off"
        _LOCAL.cache = None
    ev = CompileEvent(event, stage, float(duration), time.perf_counter(),
                      None if fun_name is None else str(fun_name),
                      cache, threading.current_thread().name)
    own = ev.seconds
    with _LOCK:
        if stage == "trace":
            # tracing a function traces the jitted functions it calls,
            # and each reports its own duration INSIDE the caller's: the
            # events that ended after this one began (on this thread)
            # are nested in it — keep the outermost, so that the kept
            # durations add up to the time spent
            recent = getattr(_LOCAL, "traces", None)
            if recent is None:
                recent = _LOCAL.traces = []
            began = ev.t - ev.seconds
            while recent and recent[-1].t > began:
                inner = recent.pop()
                own -= inner.seconds
                for i in range(len(_EVENTS) - 1, -1, -1):
                    if _EVENTS[i] is inner:
                        del _EVENTS[i]
                        break
                    if _EVENTS[i].t < inner.t:
                        break           # trimmed away: the list is in
            recent.append(ev)           # order of arrival
            del recent[:-MAX_EVENTS]
        _EVENTS.append(ev)
        if len(_EVENTS) > MAX_EVENTS:
            del _EVENTS[:MAX_EVENTS // 2]
    # a traced step reports thousands of these: handles bound once
    global _handles
    if _handles is None:
        from hetu_tpu import telemetry
        reg = telemetry.get_registry()
        _handles = (reg, reg.counter(
            "jax_compile_seconds_total",
            "seconds JAX spent tracing / lowering / compiling / "
            "reading its compilation cache, by stage (cache is part "
            "of compile)"), reg.counter(
            "jax_compiles_total",
            "JAX trace / lower / compile / cache-read events, by "
            "stage (nested traces each count)"), reg.counter(
            "jax_compile_cache_total",
            "backend compiles by what the persistent cache said: hit, "
            "miss (compiled and written), uncached (asked, neither "
            "found nor written), off"), reg.counter(
            "jax_compile_cold_seconds_total",
            "backend-compile seconds of the compiles that were not "
            "cache hits"), telemetry.flight_record)
    reg, seconds_total, events_total, cache_total, cold_total, \
        flight_record = _handles
    if reg.enabled:
        seconds_total.inc(max(own, 0.0), stage=stage)
        events_total.inc(stage=stage)
        if cache is not None:
            cache_total.inc(result=cache)
            if cache != "hit":
                cold_total.inc(ev.seconds)
    if stage == "compile":
        flight_record("jax_compile", fun=ev.fun_name,
                      seconds=round(ev.seconds, 4), cache=cache)


def compile_events(since: Optional[float] = None) -> list[CompileEvent]:
    """The recorded events, oldest first; ``since`` keeps those stamped
    at or after that ``time.perf_counter()`` reading."""
    with _LOCK:
        events = list(_EVENTS)
    if since is not None:
        events = [e for e in events if e.t >= since]
    return events


# once per process: this module is imported by ``hetu_tpu.telemetry``
jax.monitoring.register_event_duration_secs_listener(_listener)
jax.monitoring.register_event_listener(_cache_listener)
