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
  ``jax_compile_seconds_total{stage=...}`` / ``jax_compiles_total{stage=...}``;
- a backend compile also leaves a ``jax_compile`` flight event naming
  the function.

Stages: ``trace`` (jaxpr tracing), ``lower`` (jaxpr → MLIR module),
``compile`` (the backend compile call, which INCLUDES a persistent-cache
read when the cache is on) and ``cache`` (that read alone). Time spent
is therefore ``trace + lower + compile``; ``cache`` says how much of
``compile`` was a disk read.
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
#: one traced step can report tens of thousands of nested traces before
#: its own ends and takes them out again
MAX_EVENTS = 65536


class CompileEvent(NamedTuple):
    key: str                    # the jax.monitoring event key
    stage: str                  # trace | lower | compile | cache
    seconds: float
    t: float                    # time.perf_counter() when it ended
    fun_name: Optional[str]     # where JAX gives one


_EVENTS: list[CompileEvent] = []
_LOCK = threading.Lock()
_LOCAL = threading.local()
_handles = None     # (registry, seconds counter, events counter, flight)


def _listener(event: str, duration: float, **kwargs) -> None:
    stage = STAGES.get(event)
    if stage is None:
        return
    fun_name = kwargs.get("fun_name")
    ev = CompileEvent(event, stage, float(duration), time.perf_counter(),
                      None if fun_name is None else str(fun_name))
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
            "stage (nested traces each count)"), telemetry.flight_record)
    reg, seconds_total, events_total, flight_record = _handles
    if reg.enabled:
        seconds_total.inc(max(own, 0.0), stage=stage)
        events_total.inc(stage=stage)
    if stage == "compile":
        flight_record("jax_compile", fun=ev.fun_name,
                      seconds=round(ev.seconds, 4))


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
