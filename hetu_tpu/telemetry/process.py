"""The process beside the loop: what ran in it that no span owns.

The serving loop and the trainer account for their OWN thread (spans on
``perf_counter``, ``thread_time``). What else ran in the process — the
wire's threads, XLA's pools, a compile thread — showed up only as the
loop's off-CPU remainder. :func:`sample` reads the process's own
counters in ONE pass of three cheap calls and sets them in the
registry; the call sites are the two places that already sample counter
tracks (``serve/account`` every ``counter_sample_every`` iterations,
the trainer's log cadence), right before their ``record_counters``: no
cadence, thread or argument of its own, nothing per iteration, token or
slot, and not called while telemetry is off.

Series (``docs/OBSERVABILITY.md``):

- ``process_cpu_seconds_total`` — ``time.process_time()``: every
  thread, XLA's native ones too;
- ``process_threads`` — ``threading.active_count()``;
- ``process_rss_peak_bytes`` — ``getrusage(RUSAGE_SELF).ru_maxrss``.

Page faults and context switches are NOT read: the chip's sandboxed
host reports 0 faults and 0 involuntary switches for a whole run, and a
pass over ``/proc/self/task`` for the CPU by thread group holds the
loop ~22 ms there (``PERF.md`` section 6, PR 53).

**Collections are spans.** ``telemetry.enable`` installs (and removes)
ONE ``gc.callbacks`` hook: a collection is a ``gc/collect`` span
(``cat="gc"``) on whichever thread allocated — it holds the interpreter
throughout. ``start`` enters and ``stop`` leaves a ``TraceAnnotation``
like every span's (``hetu:gc/collect`` in a profiler trace's host
plane); attrs ``generation``, ``collected``, ``thread`` and ``under``,
the innermost span open on that thread, so a collection names what
caused it. Counters ``gc_pause_seconds_total{generation}`` and
``gc_collections_total{generation}`` count every collection; a
``SpanEvent`` is kept only for pauses of ``GC_EVENT_MIN_S`` or more
(generation-0 sweeps are many). The hook runs at ANY bytecode boundary
of the collecting thread, also while that thread holds one of the
tracer's or the registry's locks — both are re-entrant — or reads the
registry: a counter's exposition iterates a copy of its series, so the
first collection of a generation may add its series meanwhile.
"""

from __future__ import annotations

import gc
import resource
import threading
import time

from hetu_tpu.telemetry.spans import _annotation

#: shorter collections are counted, not kept as events
GC_EVENT_MIN_S = 100e-6

_lock = threading.Lock()
_cpu_s = 0.0        # process_time() at the last pass
_handles = None     # (cpu counter, threads gauge, rss gauge)


def reset() -> None:
    """Forget the last reading (``telemetry.reset`` cleared the
    registry): the next pass counts from the process's start again."""
    global _cpu_s
    with _lock:
        _cpu_s = 0.0


def sample() -> None:
    """Read the process's counters once and set them in the global
    registry. The caller checks ``telemetry.enabled()`` (it does
    already, around ``record_counters``)."""
    global _handles, _cpu_s
    with _lock:
        if _handles is None:
            from hetu_tpu import telemetry
            reg = telemetry.get_registry()
            _handles = (reg.counter(
                "process_cpu_seconds_total",
                "CPU seconds of the whole process, every thread "
                "(time.process_time)"), reg.gauge(
                "process_threads", "live Python threads"), reg.gauge(
                "process_rss_peak_bytes", "largest resident set so far"))
        cpu, threads, rss = _handles
        # a counter only goes up: advanced by the difference from the
        # last reading (the first counts from the process's start)
        now = time.process_time()
        cpu.inc(now - _cpu_s)
        _cpu_s = now
        threads.set(threading.active_count())
        rss.set(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                * 1024)                         # Linux gives KiB


# -- collections --------------------------------------------------------------
_gc = None          # (tracer, pause counter, collections counter)
_gc_open = None     # (start, annotation, under) of the running collection


def _gc_hook(phase: str, info: dict) -> None:
    # one collection runs at a time, start and stop on its own thread
    global _gc_open
    if phase == "start":
        stack = _gc[0]._stack()
        under = stack[-1].name if stack else ""
        ann = _annotation("gc/collect", {
            "generation": info["generation"], "under": under})
        ann.__enter__()
        _gc_open = (time.perf_counter(), ann, under)
        return
    t1 = time.perf_counter()
    if _gc_open is None:
        return                      # installed inside a collection
    (t0, ann, under), _gc_open = _gc_open, None
    ann.set_metadata(collected=info["collected"])
    ann.__exit__(None, None, None)
    tracer, pause_s, collections = _gc
    gen = info["generation"]
    pause_s.inc(t1 - t0, generation=gen)
    collections.inc(generation=gen)
    if t1 - t0 >= GC_EVENT_MIN_S:
        tracer.complete(
            "gc/collect", t1 - t0, cat="gc", ts_s=t0 - tracer.epoch,
            generation=gen, collected=info["collected"],
            thread=threading.current_thread().name, under=under)


def install_gc_hook(on: bool) -> None:
    """Add (``on``) or remove the hook; ``telemetry.enable`` calls it.
    Idempotent: ``gc.callbacks`` never holds it twice."""
    global _gc
    if not on:
        if _gc_hook in gc.callbacks:
            gc.callbacks.remove(_gc_hook)
        return
    if _gc is None:
        from hetu_tpu import telemetry
        reg = telemetry.get_registry()
        _gc = (telemetry.get_tracer(), reg.counter(
            "gc_pause_seconds_total",
            "seconds the collector held the interpreter, by generation"),
            reg.counter("gc_collections_total",
                        "collections, by generation"))
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
