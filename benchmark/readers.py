"""Arithmetic shared by the metric readers (``end_to_end/*.py`` and
``layer_metrics/*.py``). A reader takes a ``harness.Run`` and returns a
number, or ``None`` when what it reads is not there (the harness then
leaves the metric out of the line)."""

from __future__ import annotations

from typing import Optional


def window(run) -> tuple[float, float]:
    return tuple(run.records["window"])


def judged(run) -> list[dict]:
    """The requests a serving run is judged on: those due in the
    window (open loop), or those that ended in it (backlog)."""
    reqs = run.records["requests"]
    return [reqs[i] for i in run.records["judged"]]


def engine_iter_ms(run) -> Optional[float]:
    """Milliseconds per engine iteration: the measured window over the
    increase of the program's counter ``serving_attn_kernel_total``
    (one per fused step that had a decoding slot)."""
    n = run.records.get("engine_iterations")
    if not n:
        return None
    return 1e3 * run.records["engine_iterations_s"] / n
