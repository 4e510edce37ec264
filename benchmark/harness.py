"""The data-driven harness: everything a cell needs is found by the
names in the manifest (``BENCHMARK.json``), never by an edit here.

* configuration  -> the ``file`` of its ``configs`` entry;
* traffic mix    -> ``<path>/traffic/<traffic>.json`` under one of the
  manifest's ``paths``; its ``kind`` names the runner,
  ``<path>/runners/<kind>.py`` (a module with ``run(ctx) -> dict``);
* metric         -> ``<path>/end_to_end/<name>.py`` or
  ``<path>/layer_metrics/<name>.py``, a module with
  ``read(run) -> number | None``. ``None`` leaves the metric out.

``run_cell`` is what ``run.py`` calls on the chip and what the CPU
rehearsal (``tests/benchmark``) calls at a tiny size.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Any, Optional

from benchmark import trace as trace_mod

#: the traced slice of a ``--trace 1`` run: it starts this long after
#: the measured window opens and lasts this long (guide: a few seconds
#: of the steady window; traces are large)
TRACE_DELAY_S = 2.0
TRACE_LENGTH_S = 3.0


def say(**facts) -> None:
    """An information line (never the last line of a run)."""
    print(json.dumps(facts, default=str), flush=True)


@dataclasses.dataclass
class Context:
    """What a runner is handed."""
    root: str                 # the checkout
    cell: dict                # the manifest's workload entry
    config: dict              # the configuration file
    mix: dict                 # the traffic file
    seed: int
    seconds: float
    trace: bool
    devices: list
    on_chip: bool             # a TPU: kernels are expected, none may fall back
    t_process: float          # perf_counter at process start
    trace_dir: str
    _tracer: Optional[threading.Thread] = None

    def start_trace_slice(self, window_start: float) -> None:
        """With ``--trace 1``: profile ``TRACE_LENGTH_S`` seconds of the
        steady window from a thread of its own (``window_start`` is a
        ``perf_counter`` reading). A window too short for the delay is
        traced from its start."""
        if not self.trace:
            return
        import jax
        delay = TRACE_DELAY_S if self.seconds > \
            TRACE_DELAY_S + TRACE_LENGTH_S else 0.0
        length = min(TRACE_LENGTH_S, max(self.seconds - delay, 0.5))
        shutil.rmtree(self.trace_dir, ignore_errors=True)

        def work():
            time.sleep(max(0.0, window_start + delay
                           - time.perf_counter()))
            jax.profiler.start_trace(self.trace_dir)
            try:
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    time.sleep(length)
            finally:
                jax.profiler.stop_trace()

        self._tracer = threading.Thread(target=work, daemon=True,
                                        name="bench-tracer")
        self._tracer.start()

    def finish_trace_slice(self) -> None:
        if self._tracer is not None:
            self._tracer.join(timeout=120.0)
            if self._tracer.is_alive():
                raise RuntimeError("the profiler did not stop")

    @staticmethod
    def span(name: str):
        """A host span on the profiler's clock: ``bench:<name>``."""
        import jax
        return jax.profiler.TraceAnnotation(f"bench:{name}")


@dataclasses.dataclass
class Run:
    """What a metric's reader is handed."""
    cell: dict
    config: dict
    mix: dict
    seconds: float
    records: dict             # the runner's raw records
    trace: Optional[dict]     # trace.reduce_trace(), or None
    device_kind: str
    peaks: Optional[dict]     # peaks.peaks_for(device_kind) on a chip


def load_manifest(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _find(root: str, manifest: dict, *parts: str) -> str:
    for p in manifest["paths"]:
        cand = os.path.join(root, p, *parts)
        if os.path.exists(cand):
            return cand
    raise FileNotFoundError(
        f"{os.path.join(*parts)} under none of {manifest['paths']}")


def _load_module(path: str):
    name = "bench_" + os.path.relpath(path).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_reader(root: str, manifest: dict, name: str):
    for kind in ("end_to_end", "layer_metrics"):
        with contextlib.suppress(FileNotFoundError):
            return _load_module(_find(root, manifest, kind,
                                      f"{name}.py"))
    raise FileNotFoundError(f"no reader for metric {name!r}")


def cell_metrics(manifest: dict, cell: str, group: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def run_cell(manifest: dict, root: str, workload: str, *, seed: int,
             seconds: float, trace: bool, devices: list,
             on_chip: bool, t_process: float) -> dict:
    """Run one cell and return the last line's object (plus ``info``,
    which ``run.py`` prints on earlier lines)."""
    import jax
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in the manifest")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(_find(root, manifest, "traffic",
                    f"{cell['traffic']}.json")) as f:
        mix = json.load(f)
    runner = _load_module(_find(root, manifest, "runners",
                                f"{mix['kind']}.py"))
    if len(devices) < cell["chips"]:
        raise RuntimeError(f"{workload} needs {cell['chips']} devices, "
                           f"got {len(devices)}")
    devices = list(devices[:cell["chips"]])
    ctx = Context(root=root, cell=cell, config=config, mix=mix,
                  seed=int(seed), seconds=float(seconds),
                  trace=bool(trace), devices=devices, on_chip=on_chip,
                  t_process=t_process,
                  trace_dir=os.path.join(root, ".bench_trace", workload))
    t_runner = time.perf_counter()
    out = runner.run(ctx)
    ctx.finish_trace_slice()
    t_ran = time.perf_counter()
    # where the run's wall goes: imports and device start-up come
    # before the runner, the runner gives its own account, and reading
    # the trace and the metrics comes after it (set below)
    info = out.setdefault("info", {})
    info["process_to_runner_s"] = t_runner - t_process

    kind = devices[0].device_kind
    peaks = None
    if on_chip:
        from benchmark.peaks import peaks_for
        peaks = peaks_for(kind)
    reduced = None
    if trace:
        xplane = trace_mod.newest_xplane(ctx.trace_dir)
        if xplane is None:
            raise RuntimeError("the profiler wrote no trace")
        reduced = trace_mod.reduce_trace(xplane)
    run = Run(cell=cell, config=config, mix=mix, seconds=float(seconds),
              records=out["records"], trace=reduced, device_kind=kind,
              peaks=peaks)
    group = "per_layer" if trace else "end_to_end"
    metrics: dict[str, Any] = {}
    for m in cell_metrics(manifest, workload, group):
        value = find_reader(root, manifest, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value),
                                  "unit": m["unit"]}
    peak = max([out.get("program_peak_bytes") or 0]
               + [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    # the run's wall from the process's start to here, all but the
    # printing of its lines: the driver stops a run at 360 s
    info["metrics_read_s"] = time.perf_counter() - t_ran
    info["run_wall_s"] = time.perf_counter() - t_process
    return {"line": line, "info": info,
            "why_incorrect": out.get("why_incorrect", [])}
