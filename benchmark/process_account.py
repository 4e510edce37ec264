"""The run's account of its PROCESS, read from what the program
recorded beside the loop's own account: the helper behind the per-layer
metrics ``setup_cold_compile_s``, ``window_compile_s.*``,
``host_other_cpu_ms.*``, ``gc_pause_ms.*``, ``process_threads_peak.*``
and ``idle_host_phases_ms.*`` (PERF.md section 3). It stands beside
``iteration_account.py`` (the loop thread) and ``program_trace.py`` (the
3 s the profiler saw) and reads what they leave out: which programs
compiled cold, and what ran in the process while the loop ran.

(i) **Compiles, cold or from the cache** — ``telemetry.compile_events()``:
each ``compile`` event says what the persistent cache said of it
(``cache`` = ``hit`` | ``miss`` | ``uncached`` | ``off``) and on which
thread it ran. ``cold_s`` = the ``compile``-stage seconds stamped before
the window whose ``cache`` is not ``hit`` (~0 on a warm side);
``window_s`` = trace + lower + compile seconds stamped INSIDE the
window, any thread, with the functions and threads named (0 is the
design; a reference that compiles after the window is not in it).

(ii) **The process in the counted part of the window** — the counter
tracks the loop samples every 32 iterations in ``serve/account``
(``telemetry/process.py::sample`` right before ``record_counters``).
The counted part is ``iteration_account``'s: the window outside the
profiled slice, one or two pieces. In each piece the FIRST and the LAST
sample of a series bracket the iterations that start between them:

* ``host_other_cpu_ms`` = (the increase of ``process_cpu_seconds_total``
  - the sum of those iterations' ``serve/step`` ``cpu_s``) over their
  number: the CPU of every thread but the loop inside its steps (the
  wire's threads, XLA's pools, a compile thread, the loop's own turn
  between two steps); the drainers' part of it is ``wire_cpu_ms.*``;
* ``gc_pause_ms`` = the increase of ``gc_pause_seconds_total`` (all
  generations, all threads) over the same iterations; the ``gc/collect``
  events of the counted part (pauses of 100 us or more) give the loop
  thread's share, the longest pause, the count by generation and what
  each ran ``under``;
* ``threads_peak`` = the largest ``process_threads`` sample of the whole
  window, ``rss_peak_bytes`` the last ``process_rss_peak_bytes`` in it;
  ``cpu_s_per_s`` = the process's CPU seconds a second in ``SERIES_S``
  steps through the window.

(iii) **The device's idle by the loop's phases** — ``program_trace``'s
``idle_by_span`` (the traced slice): the idle seconds under every span
of the loop thread other than ``serve/dispatch`` and
``serve/device_wait`` (``gc/collect`` among them) and under no span
between two steps, over the whole step spans the slice recorded (the
attribution reaches from the first to the last of them; the two steps
the slice's edges cut are neither in the seconds nor in the count).
With the launch and the fetch lag it is an iteration's idle by what the
LOOP was doing, where ``breakdown.idle_gaps`` names the innermost call
of ANY thread.

``read(run)`` prints ONE information line ``{"process_account":
{...}}``; a part is ``None`` — and its readers then leave their metric
out, nothing raises — on a commit without ``telemetry/process.py``,
where the tracer dropped events, or where no bracket holds
``MIN_STEPS`` iterations (the CPU rehearsal's short windows).
"""

from __future__ import annotations

import json
from typing import Optional

import numpy as np

from benchmark import iteration_account as ia

MIN_STEPS = ia.MIN_STEPS
SERIES_S = ia.SERIES_S
CPU = "process_cpu_seconds_total"
THREADS = "process_threads"
RSS = "process_rss_peak_bytes"
GC_PAUSE = "gc_pause_seconds_total"
GC_SPAN = "gc/collect"
#: the loop's spans whose idle the two lag metrics already hold, and
#: what the slice could not attribute
NOT_HOST_PHASES = ("serve/dispatch", "serve/device_wait",
                   "(outside the recorded steps)")
TOP = 8
SAME_SAMPLE_S = 0.02


# -- (i) compiles -------------------------------------------------------------
def compile_account(events, window) -> Optional[dict]:
    """``events``: ``CompileEvent``s; ``None`` where none says what the
    cache said (an older commit's)."""
    if not any(getattr(e, "cache", None) for e in events):
        return None
    lo, hi = window
    by_cache: dict[str, dict] = {}
    cold, inside = [], []
    for e in events:
        if e.stage == "compile" and e.t < lo:
            d = by_cache.setdefault(e.cache or "off", {"n": 0, "s": 0.0})
            d["n"] += 1
            d["s"] += e.seconds
            if e.cache != "hit":
                cold.append(e)
        if e.stage in ("trace", "lower", "compile") and lo <= e.t < hi:
            inside.append(e)

    def named(evs):
        return [[e.fun_name, e.stage, e.cache, e.thread,
                 round(e.seconds, 4)]
                for e in sorted(evs, key=lambda e: -e.seconds)[:TOP]]

    return {"cold_s": float(sum(e.seconds for e in cold)),
            "before_window_by_cache": by_cache,
            "cold_top": named(cold),
            "window_s": float(sum(e.seconds for e in inside)),
            "window_events": len(inside), "window_top": named(inside)}


# -- (ii) the counter tracks --------------------------------------------------
def series(samples, epoch: float) -> dict:
    """``{name: (times on perf_counter, values)}`` of the tracer's
    counter samples."""
    out: dict[str, list] = {}
    for name, ts, v in samples:
        out.setdefault(name, []).append((ts + epoch, v))
    return {k: (np.array([t for t, _ in sorted(v)]),
                np.array([x for _, x in sorted(v)]))
            for k, v in out.items()}


def bracket(track, piece) -> Optional[tuple]:
    """The first and the last sample of ``track`` inside ``piece``:
    ``(t0, t1, increase)``; ``None`` under two samples."""
    ts, vs = track
    pick = np.flatnonzero((ts >= piece[0]) & (ts <= piece[1]))
    if pick.size < 2:
        return None
    return (float(ts[pick[0]]), float(ts[pick[-1]]),
            float(vs[pick[-1]] - vs[pick[0]]))


def value_at(track, t: float) -> float:
    """A counter's value at ``t``: its latest sample up to there (one
    ``record_counters`` call stamps its series microseconds apart:
    ``SAME_SAMPLE_S``), 0 before its first — a series is born at its
    first increment."""
    ts, vs = track
    i = int(np.searchsorted(ts, t + SAME_SAMPLE_S, side="right")) - 1
    return float(vs[i]) if i >= 0 else 0.0


def counted_pieces(window, slice_) -> list[tuple]:
    lo, hi = window
    if slice_ is None:
        return [(lo, hi)]
    return [p for p in ((lo, min(slice_[0], hi)),
                        (max(slice_[1], lo), hi)) if p[1] > p[0]]


def window_account(tracks: dict, it: Optional[dict], gc_events, epoch,
                   window, slice_, loop_tids=()) -> Optional[dict]:
    """``tracks``: :func:`series`; ``it``: ``iteration_account.
    iterations`` of the window; ``gc_events``: the tracer's
    ``gc/collect`` events."""
    if it is None or CPU not in tracks:
        return None
    pieces = counted_pieces(window, slice_)
    start, cpu_s = it["start"], it["cpu_s"]

    # each piece between its own two samples of the process's CPU
    cpu, n, loop, brackets = 0.0, 0, 0.0, []
    for p in pieces:
        b = bracket(tracks[CPU], p)
        if b is None:
            continue
        m = (start > b[0]) & (start <= b[1])
        cpu, n, loop = cpu + b[2], n + int(m.sum()), \
            loop + float(cpu_s[m].sum())
        brackets.append(b)
    if n < MIN_STEPS:
        return None
    acc = {"iterations": n, "process_cpu_s": cpu, "loop_cpu_s": loop,
           "host_other_cpu_ms": 1e3 * (cpu - loop) / n}
    pause = sum(value_at(track, b[1]) - value_at(track, b[0])
                for name, track in tracks.items()
                if name.split("{")[0] == GC_PAUSE for b in brackets)
    acc["gc_pause_ms"] = 1e3 * pause / n
    kept = [e for e in gc_events
            if any(p[0] <= e.ts_s + epoch and e.ts_s + e.dur_s + epoch
                   <= p[1] for p in pieces)]
    by_gen: dict[str, int] = {}
    under: dict[str, float] = {}
    for e in kept:
        g = str(e.attrs.get("generation"))
        by_gen[g] = by_gen.get(g, 0) + 1
        u = e.attrs.get("under") or "(no span)"
        under[u] = under.get(u, 0.0) + e.dur_s
    acc["gc_events"] = {
        "n": len(kept), "by_generation": by_gen,
        "seconds": float(sum(e.dur_s for e in kept)),
        "loop_seconds": float(sum(e.dur_s for e in kept
                                  if e.tid in loop_tids)),
        "longest_ms": 1e3 * max((e.dur_s for e in kept), default=0.0),
        "under_s": dict(sorted(under.items(),
                               key=lambda kv: -kv[1])[:TOP])}
    lo, hi = window

    def inside(name):
        ts, vs = tracks.get(name, (np.empty(0), np.empty(0)))
        return vs[(ts >= lo) & (ts <= hi)]

    th, rss = inside(THREADS), inside(RSS)
    acc["threads_peak"] = float(th.max()) if th.size else None
    acc["rss_peak_bytes"] = float(rss[-1]) if rss.size else None
    ts, vs = tracks[CPU]
    at = np.interp(np.arange(lo, hi + 1e-9, SERIES_S), ts, vs,
                   left=np.nan, right=np.nan)
    acc["cpu_s_per_s"] = [
        None if np.isnan(r) else round(float(r), 3)
        for r in np.diff(at) / SERIES_S]
    return acc


# -- (iii) idle by the loop's phases ------------------------------------------
def idle_account(host: Optional[dict]) -> Optional[dict]:
    """``host``: ``program_trace.read(run)["host"]``."""
    if not host or not host.get("idle_by_span"):
        return None
    steps = (host["spans"].get(ia.STEP) or {}).get("n")
    if not steps:
        return None
    phases = {k: v for k, v in host["idle_by_span"].items()
              if k not in NOT_HOST_PHASES}
    return {"steps_recorded": steps, "idle_s_by_phase": phases,
            "idle_host_phases_ms": 1e3 * sum(phases.values()) / steps}


# -- what the readers ask for -------------------------------------------------
def read(run) -> dict:
    """``{"compile": ..., "window": ..., "idle": ...}`` (each ``None``
    where its source is absent), computed once per run; the first call
    prints the information line."""
    got = getattr(run, "_process_account", None)
    if got is not None:
        return got
    got = {"compile": None, "window": None, "idle": None}
    try:
        from hetu_tpu import telemetry
        # the program's half: absent on an older commit
        from hetu_tpu.telemetry import process  # noqa: F401
    except ImportError:
        telemetry = None
    window = run.records.get("window")
    if telemetry is not None and window is not None:
        window = tuple(window)
        got["compile"] = compile_account(telemetry.compile_events(),
                                         window)
        tracer = telemetry.get_tracer()
        if not tracer.dropped:
            prof = ia.read(run).get("profiled_s")
            slice_ = None if prof is None else \
                (window[0] + prof[0], window[0] + prof[1])
            events = tracer.events()
            it = ia.iterations(events, tracer.epoch, *window)
            steps = {e.tid for e in events if e.name == ia.STEP}
            got["window"] = window_account(
                series(tracer.counter_samples(), tracer.epoch), it,
                [e for e in events if e.name == GC_SPAN], tracer.epoch,
                window, slice_, loop_tids=steps)
        if run.trace is not None:
            from benchmark import program_trace
            got["idle"] = idle_account(program_trace.read(run)["host"])
    run._process_account = got
    print(json.dumps({"process_account": got}, default=float),
          flush=True)
    return got


def value(run, part: str, key: str) -> Optional[float]:
    node = read(run)[part]
    if node is None or node.get(key) is None:
        return None
    return float(node[key])
