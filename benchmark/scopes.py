"""Device time and calls of ONE named scope (``hetu.<what>``) in a
traced run, for the readers in ``layer_metrics/`` whose scope
``program_trace`` has no bucket for. Everything comes from
``program_trace.read(run)`` and the reduced trace; where the program has
no such scope (an older commit) or the run no device plane (the CPU
rehearsal), the answer is ``None`` and the reader leaves its metric
out."""

from __future__ import annotations

from typing import Optional

from benchmark import program_trace


def seconds(run, scope: str) -> Optional[float]:
    """Device self seconds of the instructions whose innermost scope is
    ``scope``, in the traced slice."""
    dev = program_trace.read(run)["device"]
    if dev is None or scope not in dev["by_scope"]:
        return None
    return dev["by_scope"][scope]


def ms_per_step(run, scope: str) -> Optional[float]:
    """... per engine iteration, in milliseconds."""
    s = seconds(run, scope)
    host = program_trace.read(run)["host"]
    if s is None or host is None or not host["steps_in_slice"]:
        return None
    return 1e3 * s / host["steps_in_slice"]


def calls(run, scope: str, marker: str) -> Optional[int]:
    """Executions, in the traced slice, of the instructions under
    ``scope`` whose HLO text holds ``marker`` (a kernel's custom call)."""
    steps = program_trace._registered_scopes()
    t = run.trace
    if not steps or not t or not t.get("n_devices"):
        return None
    owners: dict[str, list] = {}
    for by_name in steps.values():
        for name, sc in by_name.items():
            owners.setdefault(name, []).append(sc)
    n = 0
    for name, count in t["op_calls"].items():
        found = owners.get(name, [])
        if len(found) == 1 and found[0].label == scope \
                and marker in t["op_text"].get(name, ""):
            n += count
    return n or None
