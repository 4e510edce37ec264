"""Reduction of a profiler trace (``*.xplane.pb``) to what the
benchmark reports: device busy time, per-operation time, and the idle
gaps named by what the host was doing. Reads the file with nothing but
``jax.profiler.ProfileData``.

Layout of a TPU trace (seen on the v5e, jax 0.9): one plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one event per
executed HLO operation (fusions, custom calls = Pallas kernels, copies)
and whose line ``XLA Modules`` one event per executed program; the host
is the plane ``/host:CPU``, one line per thread, holding the
``TraceAnnotation`` spans the benchmark writes (``bench:<what>``), JAX's
own (``PjitFunction(...)``) and, with the Python tracer on, one event
per Python call (``$file.py:line function``).
"""

from __future__ import annotations

import glob
import os
from typing import Optional

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench:trace_window"
#: only the longest idle gaps are named (the rest are microseconds)
MAX_NAMED_GAPS = 2000


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _union_seconds(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals of (starts, ends) in ns -> (busy_ns, merged
    starts, merged ends)."""
    if starts.size == 0:
        return 0.0, starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    ms = s[new]
    idx = np.flatnonzero(new)
    me = np.maximum.reduceat(e, idx)
    return float((me - ms).sum()), ms, me


def _host_events(planes):
    """(names, starts, ends) of every host event that has a duration,
    the window span apart."""
    names, starts, ends = [], [], []
    window = None
    for pl in planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.duration_ns <= 0:
                    continue
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    continue
                names.append(ev.name)
                starts.append(ev.start_ns)
                ends.append(ev.start_ns + ev.duration_ns)
    return (names, np.asarray(starts, np.float64),
            np.asarray(ends, np.float64), window)


def _name_gap(mid: float, names, starts, ends, python) -> str:
    """What the host was doing at ``mid``: the innermost (shortest)
    covering span among the benchmark's ``bench:`` spans and the Python
    calls, whichever thread it is on; a runtime-internal span only
    where no such span covers the moment."""
    hit = np.flatnonzero((starts <= mid) & (mid < ends))
    if hit.size == 0:
        return "(no host span)"
    own = hit[python[hit]]
    if own.size:
        hit = own
    return names[hit[np.argmin((ends - starts)[hit])]]


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: a device
    event is named by the whole HLO instruction."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")[:120]


def _self_seconds(starts, ends):
    """Each event's duration minus the events nested in it (a ``while``
    spans its body's operations on the same line)."""
    order = np.lexsort((-(ends - starts), starts))
    self_ns = (ends - starts).astype(np.float64)
    stack = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(ends[i], ends[stack[-1]]) \
                - starts[i]
        stack.append(i)
    return np.maximum(self_ns, 0.0)


def reduce_trace(path: str, *, top: int = 10) -> dict:
    """``{"window_s", "busy_s", "n_devices", "op_seconds": {name: s},
    "op_calls": {name: n}, "op_text": {name: HLO text},
    "device_ops": [[name, s]...], "idle_gaps": [[host span, s]...]}``.

    The window is the ``bench:trace_window`` span where the trace has
    one, else the extent of the device events; events are clipped to
    it. ``busy_s`` is the union of the intervals in which an operation
    ran on a device, averaged over the device planes. ``op_seconds`` is
    each operation's SELF time (nested operations taken out), summed
    over all devices and divided by their number. Idle gaps are those
    of the first device, each given to the host span covering its
    midpoint and summed by name."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    h_names, h_starts, h_ends, window = _host_events(planes)
    python = np.array([n.startswith(("$", "bench:")) for n in h_names],
                      bool)
    per_dev = []
    op_seconds: dict[str, float] = {}
    op_calls: dict[str, int] = {}
    op_text: dict[str, str] = {}
    for pl in planes:
        if not pl.name.startswith(DEVICE_PLANE):
            continue
        for ln in pl.lines:
            if ln.name != OPS_LINE:
                continue
            names, starts, durs = [], [], []
            for ev in ln.events:
                names.append(ev.name)
                starts.append(ev.start_ns)
                durs.append(ev.duration_ns)
            starts = np.asarray(starts, np.float64)
            ends = starts + np.asarray(durs, np.float64)
            if window is not None:
                starts = np.clip(starts, window[0], window[1])
                ends = np.clip(ends, window[0], window[1])
            keep = ends > starts
            names = [n for n, k in zip(names, keep) if k]
            starts, ends = starts[keep], ends[keep]
            for n, d in zip(names, _self_seconds(starts, ends)):
                key = short_name(n)
                op_seconds[key] = op_seconds.get(key, 0.0) + d * 1e-9
                op_calls[key] = op_calls.get(key, 0) + 1
                op_text.setdefault(key, n)
            per_dev.append((starts, ends))
    n_dev = len(per_dev)
    if n_dev == 0:
        return {"window_s": 0.0, "busy_s": 0.0, "n_devices": 0,
                "op_seconds": {}, "op_calls": {}, "op_text": {},
                "device_ops": [], "idle_gaps": []}
    merged = [_union_seconds(s, e) for s, e in per_dev]
    busy_s = sum(m[0] for m in merged) / n_dev * 1e-9
    if window is None:
        window = (min(s.min() for s, _ in per_dev if s.size),
                  max(e.max() for _, e in per_dev if e.size))
    op_seconds = {k: v / n_dev for k, v in op_seconds.items()}
    device_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]
    # idle gaps of the first device, named by the host
    _, ms, me = merged[0]
    gap_s = np.concatenate([[window[0]], me])
    gap_e = np.concatenate([ms, [window[1]]])
    gaps: dict[str, float] = {}
    for i in np.argsort(gap_s - gap_e)[:MAX_NAMED_GAPS]:  # longest first
        g = gap_e[i] - gap_s[i]
        if g <= 0:
            break
        name = _name_gap(0.5 * (gap_s[i] + gap_e[i]), h_names, h_starts,
                         h_ends, python)[:120]
        gaps[name] = gaps.get(name, 0.0) + g * 1e-9
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (window[1] - window[0]) * 1e-9,
            "busy_s": float(busy_s), "n_devices": n_dev,
            "op_seconds": op_seconds, "op_calls": op_calls,
            "op_text": op_text,
            "device_ops": [[k, float(v)] for k, v in device_ops],
            "idle_gaps": [[k, float(v)] for k, v in idle]}
