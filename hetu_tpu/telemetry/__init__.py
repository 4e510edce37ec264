"""Unified telemetry: spans, metrics, cross-rank aggregation, goodput.

One subsystem replaces the two disconnected islands the framework grew up
with (``utils/logging.py`` JSONL sink, ``utils/profiler.py`` step stats):

- :mod:`~hetu_tpu.telemetry.spans` — control-plane span tracer
  (plan compiles, hot switches, checkpoint writes, prefetch stalls),
  exportable as Chrome-trace JSON for Perfetto;
- :mod:`~hetu_tpu.telemetry.metrics` — Counter/Gauge/Histogram registry
  with snapshot-to-dict and Prometheus-text exposition;
- :mod:`~hetu_tpu.telemetry.aggregate` — per-host snapshots fanned
  through the coordinator KV; rank 0 emits cluster min/max/mean;
- :mod:`~hetu_tpu.telemetry.goodput` — goodput / MFU accountant;
- :mod:`~hetu_tpu.telemetry.device_scopes` — the ``hetu.*`` named
  scopes of the compiled steps, read back from their optimized HLO;
- :mod:`~hetu_tpu.telemetry.compile_events` — what JAX spent tracing,
  lowering and compiling, by function, thread and cache outcome (always
  on);
- :mod:`~hetu_tpu.telemetry.process` — the process beside the loop:
  its CPU, threads and resident peak on the counter tracks' cadence,
  and garbage collections as spans.

Process-global default instances live here (the Prometheus
default-registry idiom): instrumented hot paths write through
:func:`get_tracer` / :func:`get_registry` and pay near-zero cost until
:func:`enable` turns collection on. ``docs/OBSERVABILITY.md`` documents
what is emitted where.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from hetu_tpu.telemetry.aggregate import (
    aggregate_snapshots, cluster_aggregate, collect_snapshots,
    publish_snapshot,
)
from hetu_tpu.telemetry import process
from hetu_tpu.telemetry.compile_events import (
    CompileEvent, compile_events,
)
from hetu_tpu.telemetry.federation import (
    health_rollup, merge_prometheus, parse_prometheus,
)
from hetu_tpu.telemetry.flight import (
    FlightRecorder, HangWatchdog, atomic_write_text, flight_record,
    get_flight_recorder, install_crash_handlers,
)
from hetu_tpu.telemetry.goodput import (
    CATEGORIES, GoodputAccountant, GoodputReport, format_goodput_table,
    model_flops_per_token, report_from_records,
)
from hetu_tpu.telemetry.metrics import (
    Counter, Gauge, Histogram, MetricRegistry, percentile,
)
from hetu_tpu.telemetry.slo import (
    Alert, SLOEngine, default_serving_rules, default_training_rules,
    health_status,
)
from hetu_tpu.telemetry.spans import (
    DEFAULT_COUNTER_TRACK_PREFIXES, SpanEvent, Tracer,
)
from hetu_tpu.telemetry.tracecontext import (
    TRACEPARENT_VERBS, current_traceparent, make_traceparent,
    new_span_id, parse_traceparent, use_trace,
)

_TRACER = Tracer(enabled=False)
_REGISTRY = MetricRegistry(enabled=False)


def get_tracer() -> Tracer:
    """The process-global tracer (disabled until :func:`enable`)."""
    return _TRACER


def get_registry() -> MetricRegistry:
    """The process-global metric registry (disabled until :func:`enable`)."""
    return _REGISTRY


def enable(on: bool = True) -> None:
    """Master switch for the global tracer + registry. Off by default;
    the disabled fast path is a single attribute check per call site
    (<1% of any real step loop — asserted in ``tests/test_telemetry.py``).
    On, a garbage collection is a ``gc/collect`` span: the switch adds
    and removes the one ``gc.callbacks`` hook (``telemetry/process.py``)."""
    _TRACER.enabled = on
    _REGISTRY.enabled = on
    process.install_gc_hook(on)


def enabled() -> bool:
    return _TRACER.enabled


def reset() -> None:
    """Drop all recorded events and metrics (tests / between runs) —
    including the flight recorder's ring (it stays enabled; it is the
    always-on black box, not part of the opt-in switch)."""
    _TRACER.clear()
    _REGISTRY.clear()
    process.reset()
    get_flight_recorder().clear()
    from hetu_tpu.telemetry.flight import _clear_trip_totals
    _clear_trip_totals()


def span(name: str, cat: str = "span", **attrs):
    """``with telemetry.span("compile", plan=...):`` on the global tracer."""
    return _TRACER.span(name, cat=cat, **attrs)


def export_dir(path: str, *, extra_records=(),
               tracer: Optional[Tracer] = None,
               registry: Optional[MetricRegistry] = None) -> dict:
    """Write the standard artifact pair under ``path``:

    - ``trace.json`` — Chrome-trace (open in Perfetto);
    - ``telemetry.jsonl`` — span records + a metrics snapshot +
      ``extra_records`` (e.g. a goodput report), one JSON object/line.

    Both artifacts are written to a temp file and ``os.replace``d into
    place, so a process dying mid-export never leaves a truncated
    ``trace.json``/``telemetry.jsonl`` (the reader sees either the
    previous complete artifact or the new one).

    Returns ``{"trace": ..., "jsonl": ...}`` with the written paths."""
    tracer = tracer if tracer is not None else _TRACER
    registry = registry if registry is not None else _REGISTRY
    os.makedirs(path, exist_ok=True)
    trace_path = os.path.join(path, "trace.json")
    jsonl_path = os.path.join(path, "telemetry.jsonl")
    # final counter-track sample so every exported trace carries at
    # least one point per mem_*/comm_* series (Perfetto counter tracks)
    tracer.record_counters(registry.snapshot())
    tracer.export_chrome(trace_path)          # atomic (temp + replace)
    lines = [json.dumps(rec) for rec in tracer.records()]
    snap_rec = registry.to_record()
    if snap_rec["metrics"]:
        lines.append(json.dumps(snap_rec))
    lines.extend(json.dumps(rec) for rec in extra_records)
    atomic_write_text(jsonl_path, "".join(ln + "\n" for ln in lines))
    return {"trace": trace_path, "jsonl": jsonl_path}


__all__ = [
    "Tracer", "SpanEvent",
    "DEFAULT_COUNTER_TRACK_PREFIXES",
    "MetricRegistry", "Counter", "Gauge", "Histogram", "percentile",
    "GoodputAccountant", "GoodputReport", "CATEGORIES",
    "model_flops_per_token", "format_goodput_table",
    "report_from_records",
    "publish_snapshot", "collect_snapshots", "aggregate_snapshots",
    "cluster_aggregate",
    "FlightRecorder", "HangWatchdog", "atomic_write_text",
    "flight_record", "get_flight_recorder", "install_crash_handlers",
    "SLOEngine", "Alert", "default_training_rules",
    "default_serving_rules", "health_status",
    "TRACEPARENT_VERBS", "make_traceparent", "parse_traceparent",
    "new_span_id", "current_traceparent", "use_trace",
    "parse_prometheus", "merge_prometheus", "health_rollup",
    "CompileEvent", "compile_events", "process",
    "get_tracer", "get_registry", "enable", "enabled", "reset", "span",
    "export_dir",
]
