"""Auto-parallel strategy search.

Reference: Galvatron's profiler → cost model → dynamic-programming search
(``tools/Galvatron``, DP core ``csrc/dp_core.cpp:22``), emitting runtime
configs. Here the search emits :class:`~hetu_tpu.parallel.strategy.Strategy`
JSON directly, so the Trainer (and hot switching) consume it unchanged —
preserving the reference's planner pluggability (SURVEY §7.1).

Two modes:
- :func:`search_uniform` — enumerate dp/tp/pp/cp/ep factorizations (+ zero/
  fsdp/remat variants), score with the analytic cost model, return every
  feasible candidate ranked. This is the path the runtime consumes today.
- :func:`search_layerwise` — per-layer strategy assignment under a memory
  budget via the native DP core (the reference's hetero-layer formulation;
  informative for hetero-parallel planning).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Sequence

import numpy as np

from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.tools.galvatron.cost_model import (
    CostBreakdown, ModelDims, TPUTopology, estimate,
)
from hetu_tpu.tools.galvatron.dp_core import solve_layer_dp


@dataclasses.dataclass
class Candidate:
    strategy: Strategy
    cost: CostBreakdown
    measured_step_time: Optional[float] = None   # observed seconds/step
                                                 # (rerank_by_measured)

    @property
    def effective_step_time(self) -> float:
        """What the ranking sorts on: the observed step time when a
        measurement exists, the analytic estimate otherwise."""
        return self.measured_step_time if self.measured_step_time \
            is not None else self.cost.step_time

    def __repr__(self):
        c = self.cost
        meas = "" if self.measured_step_time is None else \
            f", measured={self.measured_step_time * 1e3:.2f}ms"
        return (f"Candidate({self.strategy.to_json()}, "
                f"step={c.step_time * 1e3:.2f}ms, "
                f"mem={c.mem_per_device / 1e9:.1f}GB{meas})")


def _factorizations(n: int, dims: ModelDims, max_tp: int = 16,
                    max_pp: int = 16, max_cp: int = 16):
    for tp in _divisors(n, max_tp):
        if dims.num_heads % tp or dims.num_kv_heads % tp:
            continue
        for pp in _divisors(n // tp, max_pp):
            if dims.num_layers % pp:
                continue
            for cp in _divisors(n // (tp * pp), max_cp):
                if dims.seq_len % cp:
                    continue
                rest = n // (tp * pp * cp)
                eps = [1]
                if dims.num_experts > 0:
                    eps += [e for e in _divisors(rest, rest)
                            if e > 1 and dims.num_experts % e == 0]
                for ep in eps:
                    dp = rest // ep
                    if dp < 1 or dims.global_batch % (dp * ep):
                        continue
                    yield dp, tp, pp, cp, ep


def _divisors(n: int, cap: int):
    return [d for d in range(1, min(n, cap) + 1) if n % d == 0]


def enumerate_candidates(dims: ModelDims, topo: TPUTopology, *,
                         num_microbatches: Sequence[int] = (1, 4, 8),
                         remats: Sequence[str] = ("none", "full"),
                         ) -> list[Candidate]:
    out = []
    for dp, tp, pp, cp, ep in _factorizations(topo.num_devices, dims):
        for remat in remats:
            for zero in ({True, dp > 1} if dp > 1 else {False}):
                nms = [nm for nm in num_microbatches
                       if nm % pp == 0 or pp == 1] or [pp]
                for nm in nms:
                    if pp > 1 and nm % pp != 0:
                        continue
                    if dims.global_batch % (dp * ep * nm):
                        continue
                    s = Strategy(dp=dp, tp=tp, pp=pp, cp=cp, ep=ep,
                                 zero=bool(zero), remat=remat,
                                 num_microbatches=nm)
                    out.append(Candidate(s, estimate(dims, s, topo)))
    return out


def search_uniform(dims: ModelDims, topo: TPUTopology, *,
                   mem_budget: Optional[float] = None,
                   hbm_budget_bytes: Optional[float] = None,
                   measured_path: Optional[str] = None,
                   **kw) -> list[Candidate]:
    """All feasible candidates, fastest first. ``[0]`` is the pick.

    ``hbm_budget_bytes``: explicit per-device HBM ceiling (the memory
    plane's knob — same meaning as ``mem_budget``, named for operators).
    Passing it also widens the default remat sweep to
    ``("none", "selective", "full")`` so the search prices recompute
    (``engine.memory.REMAT_COMPUTE_FACTORS`` via the cost model) jointly
    with parallel degrees instead of treating remat as an afterthought;
    over-budget candidates are REJECTED, not penalized.

    The memory constraint uses the AOT-measured activation scales when
    a calibration is loaded (``mem_calibration.json`` — conservative:
    fitted on a 124M model, so it can over-reject at much larger
    scales). If NO candidate survives the calibrated constraint, the
    search falls back to the uncalibrated analytic model with a warning
    instead of starving the caller — a best-effort plan beats none, and
    the warning tells the operator which regime they are in.

    ``measured_path``: a telemetry JSONL (a Trainer's
    ``telemetry.jsonl``) whose ``measured_step`` records carry
    OBSERVED per-strategy step times — when present, the final ranking
    is re-ordered by measurement via :func:`rerank_by_measured` (the
    ROADMAP's "feed measured goodput back into the planner" loop)."""
    if hbm_budget_bytes is not None:
        mem_budget = hbm_budget_bytes
        kw.setdefault("remats", ("none", "selective", "full"))
    budget = mem_budget if mem_budget is not None else topo.hbm_bytes
    cands = [c for c in enumerate_candidates(dims, topo, **kw)
             if c.cost.mem_per_device <= budget]
    if not cands and (topo.mem_scale != 1.0 or topo.mem_scale_remat):
        import dataclasses
        import warnings
        relaxed = dataclasses.replace(topo, mem_scale=1.0,
                                      mem_scale_remat=())
        cands = [c for c in enumerate_candidates(dims, relaxed, **kw)
                 if c.cost.mem_per_device <= budget]
        if cands:
            warnings.warn(
                "no strategy fits under the CALIBRATED memory model; "
                "falling back to the uncalibrated analytic model — the "
                "picked strategy may OOM on real hardware (verify with "
                "workloads/aot_check.py check_step)", stacklevel=2)
    cands.sort(key=lambda c: c.cost.step_time)
    if measured_path is None:
        import os
        measured_path = os.environ.get("HETU_MEASURED_TELEMETRY")
    if measured_path:
        measured = load_measured_step_times(measured_path)
        if measured:
            cands = rerank_by_measured(cands, measured)
    return cands


def load_measured_step_times(path: str) -> dict[str, float]:
    """``{strategy-json: observed seconds/step}`` from a telemetry JSONL.

    Consumes ``measured_step`` records (emitted by
    ``Trainer.export_telemetry`` — strategy JSON + ``step_time_s``).
    Later records win (the freshest measurement of a strategy).
    Missing/unreadable files return ``{}`` — measurement is an overlay,
    never a requirement."""
    import json
    import os
    out: dict[str, float] = {}
    if not path or not os.path.exists(path):
        return out
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") != "measured_step":
                    continue
                s, t = rec.get("strategy"), rec.get("step_time_s")
                if isinstance(s, str) and isinstance(t, (int, float)) \
                        and t > 0:
                    # normalize through Strategy so key spelling (field
                    # order, defaults) can't split identical strategies
                    try:
                        s = Strategy.from_json(s).to_json()
                    except Exception:
                        pass
                    out[s] = float(t)
    except OSError:
        return {}
    return out


def rerank_by_measured(cands: Sequence[Candidate],
                       measured: dict[str, float]) -> list[Candidate]:
    """Re-rank candidates by OBSERVED step time.

    Candidates with a measurement adopt it outright. Unmeasured ones
    stay comparable by scaling their analytic estimate with the median
    observed/analytic ratio of the measured set — a one-point
    calibration of the cost model against reality, so a systematically
    optimistic (or pessimistic) model cannot bury a measured winner or
    crown an unmeasured laggard. Returns a NEW sorted list; the inputs
    are not mutated."""
    if not measured:
        return list(cands)
    ratios = []
    out = []
    for c in cands:
        t = measured.get(c.strategy.to_json())
        out.append(dataclasses.replace(c, measured_step_time=t))
        if t is not None and c.cost.step_time > 0:
            ratios.append(t / c.cost.step_time)
    ratios.sort()
    scale = ratios[len(ratios) // 2] if ratios else 1.0
    out.sort(key=lambda c: c.measured_step_time
             if c.measured_step_time is not None
             else c.cost.step_time * scale)
    return out


def search_layerwise(dims: ModelDims, topo: TPUTopology,
                     candidates: Sequence[Strategy], *,
                     mem_budget: Optional[float] = None,
                     mem_units: int = 256,
                     switch_penalty: float = 1e-4):
    """Per-layer strategy assignment via the native DP core.

    Each candidate's per-layer (time, mem) comes from the cost model;
    memory is discretized to ``mem_units`` knapsack units of the budget.
    Returns (total_time, [Strategy per layer]) or (inf, None).
    """
    budget = mem_budget if mem_budget is not None else topo.hbm_bytes
    L, S = dims.num_layers, len(candidates)
    time_cost = np.zeros((L, S))
    mem_cost = np.zeros((L, S), np.int64)
    unit = budget / mem_units
    for j, s in enumerate(candidates):
        c = estimate(dims, s, topo)
        time_cost[:, j] = c.step_time / dims.num_layers
        mem_cost[:, j] = max(1, int(np.ceil(
            c.mem_per_device / dims.num_layers / unit)))
    switch = np.full((S, S), switch_penalty) - \
        switch_penalty * np.eye(S)
    total, choice = solve_layer_dp(time_cost, mem_cost, mem_units, switch)
    if choice is None:
        return float("inf"), None
    return total, [candidates[int(j)] for j in choice]


def remat_mask_from_layerwise(per_layer: Sequence[Strategy]
                              ) -> tuple[bool, ...]:
    """Compress a layerwise search result into the executable per-layer
    recompute mask (``Strategy(remat_mask=...)`` →
    ``StackedBlocks(remat_mask=...)``): True where that layer's chosen
    strategy uses recompute."""
    return tuple(s.remat != "none" for s in per_layer)
