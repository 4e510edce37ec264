"""The serving loop's account of its own iteration, read from what the
PROGRAM recorded: the helper behind the per-layer metrics
``engine_host_cpu_ms.*``, ``engine_host_offcpu_ms.*``,
``host_dispatch_ms.*``, ``wire_cpu_ms.*``, ``step_launch_lag_ms.*``,
``step_fetch_lag_ms.*``, ``iter_p95_ms.chat`` and
``iter_tail_host_pct.chat`` (PERF.md section 3). It stands beside
``program_trace.py``, which reads the 3 s the profiler saw.

(i) **The whole window, from the program's own tracer.** Both serving
runners call ``telemetry.enable(True)``, so ``telemetry.get_tracer()``
holds one ``SpanEvent`` for every ``serve/*`` span of the ramp and the
window, on ``perf_counter`` (``ts_s + tracer.epoch``), the clock
``run.records["window"]`` is on. A ``serve/step`` event carries the
loop's account as attributes set at its end (``serving/engine.py::
_step_spanned``): ``cpu_s`` (the loop thread's ``thread_time`` over the
step), ``wait_cpu_s`` (the same over ``serve/device_wait``),
``lock_wait_s`` (what acquiring the engine's lock took), ``frames``,
``admitted``, ``since_prev_s``. Per iteration:

* host wall = the step - its child ``serve/device_wait``;
* host CPU  = ``cpu_s - wait_cpu_s``;
* off-CPU   = host wall - host CPU: the lock (known) and the
  interpreter or a blocking call (the rest, a remainder);
* coverage  = the children's seconds over the step's.

The wire threads record ``stream/drain`` (one event a subscription, at
the drainer's exit) and ``server/submit`` (one a submit) with their own
``cpu_s``: wire CPU = those that END in the counted part of the window.

A ``--trace 1`` run (the only one in which ``harness.run_cell`` calls
per-layer readers) spends ``TRACE_LENGTH_S`` of its window under the
profiler's Python tracer, and ``stop_trace`` then collects what that
tracer holds for 5-20 s more: the host's Python runs about half as fast
until the trace file is written. The metrics are therefore computed
over the iterations that do NOT overlap the profiler's work — from its
start to the end of its slice or to the file's time of writing,
whichever is later, padded by ``SLICE_PAD_S``; the same means INSIDE
that part and a series of the step's mean wall per ``SERIES_S`` are
printed beside them, so that what the profiler costs is a printed
number.

(ii) **The device's start and finish, from the profiler's trace.** The
``serve/dispatch`` and ``serve/device_wait`` annotations carry ``iter``;
the device plane's line ``XLA Modules`` has one event per executed
program. For each iteration in the slice: launch lag = the module's
start - ``serve/dispatch``'s start; fetch lag = ``serve/device_wait``'s
end - the module's end (host and device planes share the trace's
clock). Medians over the paired iterations.

``read(run)`` returns ``None`` — and every reader then leaves its
metric out, nothing raises — when the tracer dropped events, the
attributes are absent (an older commit), or fewer than ``MIN_STEPS``
iterations are counted (the CPU rehearsal's short windows). It prints
ONE information line ``{"iteration_account": {...}}``.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

from benchmark import harness, stats, trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP = "serve/step"
WAIT = "serve/device_wait"
DISPATCH = "serve/dispatch"
WIRE = ("stream/drain", "server/submit")
PREFIX = "hetu:"                # the program's spans in the host plane
#: what an iteration's account needs of its ``serve/step`` event
ATTRS = ("cpu_s", "wait_cpu_s", "lock_wait_s", "frames", "since_prev_s")
MODULES_LINE = "XLA Modules"
MIN_STEPS = 20
SLICE_PAD_S = 0.5
SERIES_S = 5.0
TAIL_SHARE = 0.05


# -- (i) the tracer's events --------------------------------------------------
def iterations(events, epoch: float, lo: float, hi: float
               ) -> Optional[dict]:
    """The ``serve/step`` events that lie wholly in ``[lo, hi]``
    (``perf_counter``) as arrays, one entry an iteration: ``start``,
    ``wall``, the ``ATTRS``, ``admitted`` / ``active`` /
    ``prefill_tokens`` where present, ``children``: seconds per
    direct child span by name, and the host's part of the step on both
    clocks (``host_wall``, ``host_cpu``). A step that returned before
    the fused step (an auxiliary job alone) carries no account and is
    no iteration; ``None`` where no step carries one."""
    steps = sorted((e for e in events if e.name == STEP
                    and e.ts_s + epoch >= lo
                    and e.ts_s + e.dur_s + epoch <= hi
                    and all(a in e.attrs for a in ATTRS)),
                   key=lambda e: e.ts_s)
    if not steps:
        return None
    out = {"start": np.array([e.ts_s + epoch for e in steps]),
           "wall": np.array([e.dur_s for e in steps])}
    for a in ATTRS + ("admitted", "active", "prefill_tokens"):
        out[a] = np.array([float(e.attrs.get(a, 0.0)) for e in steps])
    # a child belongs to the step of its thread that starts last
    # before it; the loop is one thread, so steps never overlap
    tids = {e.tid for e in steps}
    starts = np.array([e.ts_s for e in steps])
    ends = starts + out["wall"]
    depth = steps[0].depth + 1
    kids: dict[str, np.ndarray] = {}
    first = ends.copy()             # the first child's start
    last = starts.copy()            # the last child's end
    for e in events:
        if e.tid not in tids or e.depth != depth \
                or not e.name.startswith("serve/") or e.name == STEP:
            continue
        i = int(np.searchsorted(starts, e.ts_s, side="right")) - 1
        if i < 0 or e.ts_s + e.dur_s > ends[i] + 1e-9:
            continue
        kids.setdefault(e.name, np.zeros(len(steps)))[i] += e.dur_s
        first[i] = min(first[i], e.ts_s)
        last[i] = max(last[i], e.ts_s + e.dur_s)
    out["children"] = kids
    # the host's part of the step, on both clocks
    out["host_wall"] = out["wall"] - kids.get(WAIT, np.zeros(len(steps)))
    out["host_cpu"] = out["cpu_s"] - out["wait_cpu_s"]
    # what no child covers, by where it lies in the step
    out["head"] = first - starts
    out["tail"] = ends - last
    return out


def _ms(values) -> dict:
    """Mean, median and 95th percentile (nearest rank) in ms."""
    xs = [1e3 * float(v) for v in values]
    return {"mean": sum(xs) / len(xs), "p50": stats.median(xs),
            "p95": stats.percentile(xs, 95)}


def _means(it: dict, pick: np.ndarray) -> dict:
    """The account's means over the iterations ``pick`` selects, ms."""
    wall = it["wall"][pick]
    host, cpu = it["host_wall"][pick], it["host_cpu"][pick]
    kids = sum(v[pick].sum() for v in it["children"].values())
    return {
        "iterations": int(pick.sum()),
        "step_ms": 1e3 * wall.mean(),
        "children_ms": {k: 1e3 * v[pick].mean()
                        for k, v in sorted(it["children"].items())},
        "host_wall_ms": 1e3 * host.mean(),
        "host_cpu_ms": 1e3 * cpu.mean(),
        "host_offcpu_ms": 1e3 * (host - cpu).mean(),
        "lock_wait_ms": 1e3 * it["lock_wait_s"][pick].mean(),
        "since_prev_ms": 1e3 * it["since_prev_s"][pick].mean(),
        "frames": float(it["frames"][pick].mean()),
        "admitted": float(it["admitted"][pick].mean()),
        "active": float(it["active"][pick].mean()),
        "prefill_tokens": float(it["prefill_tokens"][pick].mean()),
        "coverage": float(kids / wall.sum()),
        # the step's seconds under no child: before the first, after
        # the last (its locals are released there), and between them
        "uncovered_ms": {
            "head": 1e3 * it["head"][pick].mean(),
            "tail": 1e3 * it["tail"][pick].mean(),
            "between": 1e3 * (wall.sum() - kids - it["head"][pick].sum()
                              - it["tail"][pick].sum()) / pick.sum()},
        "host_pct": float(100.0 * host.sum() / wall.sum()),
    }


def window_account(events, epoch: float, window, *, dropped: int = 0,
                   slice_: Optional[tuple] = None) -> Optional[dict]:
    """The account of the iterations wholly in ``window`` (a
    ``perf_counter`` pair). With ``slice_`` (the profiled part, padded)
    the account counts the iterations that do not overlap it and gives
    those that do under ``in_slice``."""
    if dropped:
        return None                 # the record has holes
    lo, hi = window
    it = iterations(events, epoch, lo, hi)
    if it is None:
        return None
    start, end = it["start"], it["start"] + it["wall"]
    inside = np.zeros(len(start), bool) if slice_ is None else \
        (end > slice_[0]) & (start < slice_[1])
    out = ~inside
    if out.sum() < MIN_STEPS:
        return None
    acc = _means(it, out)
    wall = it["wall"][out]
    acc["step"] = _ms(wall)
    acc["children"] = {k: _ms(v[out])
                       for k, v in sorted(it["children"].items())}
    acc["host_cpu"] = _ms(it["host_cpu"][out])
    acc["host_offcpu"] = _ms((it["host_wall"] - it["host_cpu"])[out])
    acc["lock_wait"] = _ms(it["lock_wait_s"][out])
    # the slowest iterations: what a tail is made of
    n_tail = max(1, int(round(TAIL_SHARE * out.sum())))
    cut = np.sort(wall)[-n_tail]
    acc["tail"] = _means(it, out & (it["wall"] >= cut))
    acc["in_slice"] = _means(it, inside) if inside.any() else None
    # the wire threads' events that end in the counted part
    wire = [e for e in events if e.name in WIRE and e.cat == "wire"]
    ends = np.array([e.ts_s + e.dur_s + epoch for e in wire])
    keep = (ends >= lo) & (ends <= hi)
    if slice_ is not None:
        keep &= ~((ends > slice_[0]) & (ends < slice_[1]))
    kept = [e for e, k in zip(wire, keep) if k]
    acc["wire_events"] = len(kept)
    acc["wire_cpu_s"] = float(sum(e.attrs.get("cpu_s", 0.0)
                                  for e in kept))
    acc["wire_frames"] = int(sum(e.attrs.get("frames", 0) for e in kept))
    acc["submit_lock_wait_s"] = float(sum(
        e.attrs.get("lock_wait_s", 0.0) for e in kept))
    acc["wire_cpu_ms"] = 1e3 * acc["wire_cpu_s"] / acc["iterations"]
    # the step's mean wall through the window: where a run loses time
    acc["step_ms_series"] = []
    for a in np.arange(lo, hi, SERIES_S):
        m = (start >= a) & (start < a + SERIES_S)
        acc["step_ms_series"].append(
            round(1e3 * float(it["wall"][m].mean()), 3) if m.any()
            else None)
    return acc


def _xplane(run) -> Optional[str]:
    return trace_mod.newest_xplane(
        os.path.join(ROOT, ".bench_trace", run.cell["name"]))


def profiled_slice(run, written: Optional[float] = None
                   ) -> Optional[tuple]:
    """The part of the window the profiler worked in, padded: from where
    ``harness.Context.start_trace_slice`` started it to the end of its
    ``TRACE_LENGTH_S`` or, where that is later, to ``written`` — when
    ``stop_trace`` had written the trace (``perf_counter``): collecting
    what the Python tracer holds slows the host long after the traced
    seconds. ``None`` for a run without ``--trace``."""
    if run.trace is None:
        return None
    lo = run.records["window"][0]
    delay = harness.TRACE_DELAY_S if run.seconds > \
        harness.TRACE_DELAY_S + harness.TRACE_LENGTH_S else 0.0
    length = min(harness.TRACE_LENGTH_S,
                 max(run.seconds - delay, 0.5))
    return (lo + delay - SLICE_PAD_S,
            max(lo + delay + length, written or 0.0) + SLICE_PAD_S)


# -- (ii) the profiler's trace ------------------------------------------------
def pair_lags(spans: dict, modules) -> Optional[dict]:
    """``spans``: ``{iter: {"dispatch": (start, end), "wait": (start,
    end)}}`` in ns; ``modules``: ``[(start, end)]`` of the device's
    executed programs. An iteration pairs with the longest program that
    STARTS between its ``serve/dispatch``'s start and its
    ``serve/device_wait``'s end (the fused step; an auxiliary program
    of the same iteration is shorter). Medians in ms."""
    if not modules:
        return None
    ms = np.array([m[0] for m in modules], np.float64)
    me = np.array([m[1] for m in modules], np.float64)
    launch, fetch, busy, total = [], [], [], []
    for sp in spans.values():
        if "dispatch" not in sp or "wait" not in sp:
            continue
        d0, w1 = sp["dispatch"][0], sp["wait"][1]
        hit = np.flatnonzero((ms >= d0) & (ms <= w1))
        if hit.size == 0:
            continue
        j = hit[np.argmax((me - ms)[hit])]
        launch.append((ms[j] - d0) * 1e-6)
        fetch.append((w1 - me[j]) * 1e-6)
        busy.append((me[j] - ms[j]) * 1e-6)
        total.append((w1 - d0) * 1e-6)
    if not launch:
        return None
    return {"pairs": len(launch),
            "launch_ms": stats.median(launch),
            "fetch_ms": stats.median(fetch),
            "module_ms": stats.median(busy),
            "dispatch_to_fetched_ms": stats.median(total)}


def _lags(path: Optional[str]) -> Optional[dict]:
    """``pair_lags`` of the xplane at ``path`` (``None``: no trace)."""
    if path is None:
        return None
    from jax.profiler import ProfileData
    want = {PREFIX + DISPATCH: "dispatch", PREFIX + WAIT: "wait"}
    spans: dict[int, dict] = {}
    modules, names = [], {}
    for pl in ProfileData.from_file(path).planes:
        if pl.name == trace_mod.HOST_PLANE:
            for ln in pl.lines:
                for ev in ln.events:
                    kind = want.get(ev.name)
                    if kind is None:
                        continue
                    it = dict(ev.stats).get("iter")
                    if it is not None:
                        spans.setdefault(int(it), {})[kind] = (
                            ev.start_ns, ev.start_ns + ev.duration_ns)
        elif pl.name.startswith(trace_mod.DEVICE_PLANE) and not modules:
            for ln in pl.lines:
                if ln.name != MODULES_LINE:
                    continue
                for ev in ln.events:
                    modules.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
                    names[ev.name] = names.get(ev.name, 0) + 1
    got = pair_lags(spans, modules)
    if got is not None:
        got["modules"] = sorted(names.items(), key=lambda kv: -kv[1])[:4]
    return got


# -- what the readers ask for -------------------------------------------------
def read(run) -> Optional[dict]:
    """``{"window": window_account(...) | None, "lags": ... | None}``,
    computed once per run; the first call prints the information
    line."""
    got = getattr(run, "_iteration_account", None)
    if got is not None:
        return got
    got = {"window": None, "lags": None}
    try:
        from hetu_tpu import telemetry
        tracer = telemetry.get_tracer()
    except ImportError:
        tracer = None
    window = run.records.get("window")
    path = _xplane(run) if run.trace is not None else None
    if tracer is not None and window is not None:
        # the trace file's time of writing, on the tracer's clock
        written = None if path is None else \
            os.path.getmtime(path) - tracer.epoch_unix + tracer.epoch
        slice_ = profiled_slice(run, written)
        events = tracer.events()
        got["window"] = window_account(
            events, tracer.epoch, tuple(window),
            dropped=tracer.dropped, slice_=slice_)
        # how near the record came to its bound (Tracer.max_events)
        got["dropped"] = tracer.dropped
        got["events"] = len(events)
        # seconds into the window: where the profiler worked
        got["profiled_s"] = None if slice_ is None else \
            [slice_[0] - window[0], slice_[1] - window[0]]
    got["lags"] = _lags(path)
    run._iteration_account = got
    print(json.dumps({"iteration_account": got}, default=float),
          flush=True)
    return got


def window_value(run, *path) -> Optional[float]:
    """One number of the window's account, e.g. ``("step", "p95")``."""
    node = read(run)["window"]
    for key in path:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return float(node)


def lag_value(run, key: str) -> Optional[float]:
    lags = read(run)["lags"]
    return None if lags is None else float(lags[key])
