"""What the PROGRAM says about a traced run: device time by the program's
named scopes, host time by the program's spans, and the device's idle
gaps given to the span that covered them — the one helper behind every
per-layer metric that reads a scope or a span (PERF.md section 3).

The program (``hetu_tpu.telemetry``) gives the names:

* ``jax.named_scope("hetu.<what>")`` where the work happens; the
  compiled steps register a thunk for their optimized HLO
  (``telemetry.device_scopes``), whose ``metadata={op_name=...}`` maps
  the instruction names a device trace shows to those scopes;
* every program span is also a ``TraceAnnotation`` ``hetu:<name>`` in
  the trace's host plane (``telemetry.spans``);
* ``telemetry.compile_events()``: what JAX spent tracing, lowering,
  compiling, by ``perf_counter`` stamp.

``read(run)`` joins them with the reduced trace (``run.trace``) once per
run, prints ONE information line ``{"program_trace": {...}}`` and hands
the readers in ``layer_metrics/`` a dict. Where the program has no such
scope, span or counter (an older commit), or the run has no device
plane (the CPU rehearsal), the part that needs it is ``None`` and the
reader returns ``None``: the metric is left out, nothing raises.

(a) ``device``: ``run.trace["op_seconds"]`` (self time per instruction,
    clipped to the slice) joined BY INSTRUCTION NAME with the registered
    steps' scopes. A name that two registered steps share is left
    ``unscoped`` and listed under ``shared_names``. Buckets:
    train — ``fwd`` / ``bwd`` / ``opt`` by the rule of
    ``device_scopes.classify`` (kernels in their phase, recomputation is
    ``bwd`` and also under ``recompute_s``); serve — ``decode``,
    ``prefill``, ``kv_arena``, ``sample`` by the path's lanes, where an
    instruction that MOVES THE ARENA (its name says copy / slice /
    dynamic-update-slice and its result is at least one layer's leaf of
    the arena, ``kv_blocks x block_size x n_embd`` elements — XLA's
    per-layer slices and whole-arena copies carry a lane's name or
    none) goes to ``kv_arena`` and is listed under ``arena_moves``.
(b) ``host``: the ``hetu:`` events of the host plane that lie wholly
    inside ``bench:trace_window``, per name: count, total and SELF
    seconds (duration minus the spans nested in it on the same thread).
(c) ``idle_by_span``: the first device's idle time under each ``hetu:``
    span of the loop thread (the one that holds the step spans), by
    overlap with the span's own segments (its children taken out). A
    span cut by the slice's edge is never recorded (a TraceMe is written
    when it ends, inside a running trace), so only the part of the slice
    between the first and the last recorded step span is attributed; the
    rest is ``(outside the recorded steps)``.

Per step / per iteration: divided by ``steps_in_slice`` = the slice's
length over the mean start-to-start period of the ``train/step`` /
``serve/step`` spans inside it (a count of whole spans would charge the
two cut steps at the slice's ends to the others).
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

import numpy as np

from benchmark import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "hetu:"
STEP_SPANS = ("train/step", "serve/step")
_ARENA_MOVE = re.compile(r"copy|slice")
_SHAPE = re.compile(r"[a-z]\w*\[([\d,]*)\]")
_OPCODE = re.compile(r"\s[a-z][\w\-]*\(")


def _result_elements(hlo_text: str) -> int:
    """Elements of an instruction's (largest) result, from its text
    ``%name = bf16[12,9473,16,768]{...} fusion(...)``."""
    head = hlo_text.split(" = ", 1)[-1]
    m = _OPCODE.search(head)
    head = head[:m.start()] if m else head
    best = 0
    for dims in _SHAPE.findall(head):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        best = max(best, n)
    return best


def _registered_scopes() -> Optional[dict]:
    try:
        from hetu_tpu.telemetry import device_scopes
    except ImportError:
        return None
    return device_scopes.registered_scopes()


def _device(run) -> Optional[dict]:
    t = run.trace
    if not t or not t.get("n_devices"):
        return None
    steps = _registered_scopes()
    if not steps:
        return None
    seen: dict[str, list] = {}
    for key, scopes in steps.items():
        for name in scopes:
            seen.setdefault(name, []).append(key)
    r = run.records
    serve = "kv_blocks" in r
    leaf = r["kv_blocks"] * r["block_size"] * run.config["n_embd"] \
        if serve else None

    by_scope: dict[str, float] = {}
    buckets: dict[str, float] = {}
    kernel_s: dict[str, float] = {}      # scope seconds around a kernel
    kernel_calls: dict[str, int] = {}
    instr: dict[str, str] = {}
    unscoped: dict[str, float] = {}
    arena_moves: dict[str, float] = {}
    shared, recompute_s, found_s = [], 0.0, 0.0
    for name, s in t["op_seconds"].items():
        keys = seen.get(name, [])
        if len(keys) > 1:
            shared.append(name)
        sc = steps[keys[0]][name] if len(keys) == 1 else None
        if sc is not None:
            found_s += s
        path = sc.path if sc is not None else ()
        label = sc.label if sc is not None else "unscoped"
        text = t["op_text"].get(name, "")
        if serve:
            if "hetu.kv_arena" in path:
                bucket = "kv_arena"
            elif "hetu.sample" in path:
                bucket = "sample"
            elif "hetu.decode_lane" in path:
                bucket = "decode"
            elif "hetu.prefill_lane" in path:
                bucket = "prefill"
            else:
                bucket = "unscoped"
            if bucket in ("decode", "prefill", "unscoped") \
                    and "hetu.paged_attn" not in path \
                    and _ARENA_MOVE.search(name) \
                    and _result_elements(text) >= leaf:
                bucket, label = "kv_arena", "hetu.kv_arena"
                arena_moves[name] = s
        elif sc is not None and sc.phase is not None:
            bucket = sc.phase
            recompute_s += s if sc.remat else 0.0
        elif label == "hetu.opt":
            bucket = "opt"
        else:
            bucket = "unscoped"
        if bucket == "unscoped":
            unscoped[name] = s
        by_scope[label] = by_scope.get(label, 0.0) + s
        buckets[bucket] = buckets.get(bucket, 0.0) + s
        instr[name] = label
        # kernels: the scope around a Pallas call, told apart by lane
        kern = next((p for p in reversed(path) if p in (
            "hetu.flash_fwd", "hetu.flash_bwd", "hetu.paged_attn",
            "hetu.fused_ce")), None)
        if kern is not None:
            lane = next((p for p in path if p.endswith("_lane")), None)
            k = f"{lane}>{kern}" if lane else kern
            kernel_s[k] = kernel_s.get(k, 0.0) + s
            if "custom-call(" in text or "custom_call" in text:
                kernel_calls[k] = kernel_calls.get(k, 0) \
                    + t["op_calls"][name]
    top = sorted(t["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    total = sum(t["op_seconds"].values())
    return {
        "by_scope": by_scope, "buckets": buckets,
        "kernel_s": kernel_s, "kernel_calls": kernel_calls,
        "recompute_s": recompute_s, "arena_moves": arena_moves,
        "unscoped_top": sorted(unscoped.items(),
                               key=lambda kv: -kv[1])[:10],
        "instruction_scopes": {n: instr[n] for n, _ in top},
        "shared_names": sorted(shared)[:20],
        "named_share": found_s / total if total else 0.0,
        "busy_s": t["busy_s"], "op_s": total,
    }


def _host(run) -> Optional[dict]:
    """Host spans and idle attribution from the run's xplane."""
    if run.trace is None:
        return None
    path = trace_mod.newest_xplane(
        os.path.join(ROOT, ".bench_trace", run.cell["name"]))
    if path is None:
        return None
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    window = None
    lines = []                      # per thread: (names, starts, ends)
    for pl in planes:
        if pl.name != trace_mod.HOST_PLANE:
            continue
        for ln in pl.lines:
            names, starts, ends = [], [], []
            for ev in ln.events:
                if ev.duration_ns <= 0:
                    continue
                if ev.name == trace_mod.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    names.append(ev.name[len(PREFIX):])
                    starts.append(ev.start_ns)
                    ends.append(ev.start_ns + ev.duration_ns)
            if names:
                lines.append((names, np.asarray(starts, np.float64),
                              np.asarray(ends, np.float64)))
    if not lines:
        return None
    if window is None:
        window = (min(s.min() for _, s, _ in lines),
                  max(e.max() for _, _, e in lines))
    spans: dict[str, dict] = {}
    step_starts = []
    for names, starts, ends in lines:
        self_ns = trace_mod._self_seconds(starts, ends)
        for n, s, e, own in zip(names, starts, ends, self_ns):
            if s < window[0] or e > window[1]:
                continue            # wholly inside the slice only
            d = spans.setdefault(n, {"n": 0, "total_s": 0.0,
                                     "self_s": 0.0})
            d["n"] += 1
            d["total_s"] += (e - s) * 1e-9
            d["self_s"] += own * 1e-9
            if n in STEP_SPANS:
                step_starts.append(s)
    window_s = (window[1] - window[0]) * 1e-9
    steps_in_slice = None
    if len(step_starts) >= 2:
        period = (max(step_starts) - min(step_starts)) * 1e-9 \
            / (len(step_starts) - 1)
        steps_in_slice = window_s / period if period > 0 else None
    elif step_starts:
        steps_in_slice = 1.0

    idle = _idle_by_span(planes, window, lines)
    return {"spans": spans, "window_s": window_s,
            "steps_in_slice": steps_in_slice, "idle_by_span": idle}


def _innermost_segments(names, starts, ends):
    """One thread's nested spans cut into disjoint segments, each
    owned by the innermost span covering it: ``[(start, end, name)]``."""
    order = np.lexsort((-(ends - starts), starts))
    out, stack = [], []          # stack of [name, end, cursor]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, cur = stack.pop()
            if end > cur:
                out.append((cur, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for i in order:
        close(starts[i])
        if stack and starts[i] > stack[-1][2]:
            out.append((stack[-1][2], starts[i], stack[-1][0]))
        if stack:
            stack[-1][2] = max(stack[-1][2], starts[i])
        stack.append([names[i], ends[i], starts[i]])
    close(np.inf)
    return out


def _idle_by_span(planes, window, lines) -> Optional[dict]:
    """(c): the first device's idle time under each ``hetu:`` span of
    the LOOP thread (the one holding the step spans), by overlap with
    the span's own segments (children taken out). Only the part of the
    slice between the first and the last recorded step span can be
    attributed — a span cut by the slice's edge is never recorded — so
    the rest is given apart as ``(outside the recorded steps)``."""
    loop = max(lines, key=lambda ln: sum(n in STEP_SPANS for n in ln[0]))
    names, starts, ends = loop
    is_step = np.array([n in STEP_SPANS for n in names], bool)
    if not is_step.any():
        return None
    lo, hi = starts[is_step].min(), ends[is_step].max()
    for pl in planes:
        if not pl.name.startswith(trace_mod.DEVICE_PLANE):
            continue
        for ln in pl.lines:
            if ln.name != trace_mod.OPS_LINE:
                continue
            st = np.asarray([ev.start_ns for ev in ln.events],
                            np.float64)
            en = st + np.asarray([ev.duration_ns for ev in ln.events],
                                 np.float64)
            st = np.clip(st, window[0], window[1])
            en = np.clip(en, window[0], window[1])
            _, ms, me = trace_mod._union_seconds(st[en > st],
                                                 en[en > st])
            # cumulative idle time F(t), piecewise linear over the gaps
            gap_s = np.concatenate([[window[0]], me])
            gap_e = np.concatenate([ms, [window[1]]])
            xs = np.ravel(np.column_stack([gap_s, gap_e]))
            ys = np.ravel(np.column_stack([
                np.concatenate([[0.0], np.cumsum(gap_e - gap_s)[:-1]]),
                np.cumsum(gap_e - gap_s)]))

            def idle_in(a, b):
                return float(np.interp(b, xs, ys) - np.interp(a, xs, ys))

            out: dict[str, float] = {}
            for a, b, name in _innermost_segments(names, starts, ends):
                a, b = max(a, lo), min(b, hi)
                if b > a:
                    out[name] = out.get(name, 0.0) + idle_in(a, b) * 1e-9
            inside = idle_in(lo, hi) * 1e-9
            out["(no hetu span)"] = max(
                inside - sum(out.values()), 0.0)
            out["(outside the recorded steps)"] = \
                idle_in(window[0], window[1]) * 1e-9 - inside
            return out
    return None


def _compile(run) -> Optional[dict]:
    """Compile events stamped before the window opened, by stage."""
    try:
        from hetu_tpu.telemetry import compile_events
    except ImportError:
        return None
    w = run.records.get("window")
    if w is None:
        return None
    stages: dict[str, float] = {}
    counts: dict[str, int] = {}
    for ev in compile_events():
        if ev.t < w[0]:
            stages[ev.stage] = stages.get(ev.stage, 0.0) + ev.seconds
            counts[ev.stage] = counts.get(ev.stage, 0) + 1
    return {"seconds": stages, "events": counts}


def read(run) -> dict:
    """``{"device": ..., "host": ..., "compile": ...}`` (each ``None``
    where its source is absent), computed once per run; the first call
    prints the information line."""
    got = getattr(run, "_program_trace", None)
    if got is not None:
        return got
    got = {"device": _device(run), "host": _host(run),
           "compile": _compile(run)}
    run._program_trace = got
    dev, host = got["device"] or {}, got["host"] or {}

    def rounded(d):
        return {k: round(v, 6) for k, v in (d or {}).items()}

    print(json.dumps({"program_trace": {
        "device_by_scope": rounded(dev.get("by_scope")),
        "device_buckets": rounded(dev.get("buckets")),
        "kernel_s": rounded(dev.get("kernel_s")),
        "kernel_calls": dev.get("kernel_calls"),
        "recompute_s": dev.get("recompute_s"),
        "arena_moves": rounded(dev.get("arena_moves")),
        "unscoped_top": dev.get("unscoped_top"),
        "instruction_scopes": dev.get("instruction_scopes"),
        "shared_names": dev.get("shared_names"),
        "named_share": dev.get("named_share"),
        "host_spans": host.get("spans"),
        "steps_in_slice": host.get("steps_in_slice"),
        "idle_by_span": rounded(host.get("idle_by_span")),
        "compile_before_window": got["compile"],
    }}), flush=True)
    return got


# -- what the readers ask for -----------------------------------------------
def device_ms_per_step(run, bucket: str) -> Optional[float]:
    """Device self time of one bucket per step / iteration, ms."""
    got = read(run)
    dev, host = got["device"], got["host"]
    if dev is None or host is None or not host["steps_in_slice"]:
        return None
    return 1e3 * dev["buckets"].get(bucket, 0.0) / host["steps_in_slice"]


def kernel_seconds_per_call(run, kernel: str,
                            kernels_per_call: int = 1) -> Optional[float]:
    """Device seconds one call of a kernel takes: the seconds under
    its scope over the Pallas calls in it."""
    dev = read(run)["device"]
    if dev is None or not dev["kernel_calls"].get(kernel):
        return None
    return dev["kernel_s"][kernel] * kernels_per_call \
        / dev["kernel_calls"][kernel]


def host_span(run, name: str) -> Optional[dict]:
    host = read(run)["host"]
    return None if host is None else host["spans"].get(name)
