"""Measured calibration of the auto-parallel cost model.

Galvatron grounds its cost model in hardware profiling
(``tools/Galvatron/galvatron/profile_hardware`` + model profiler) before
searching. This module does the TPU equivalent:

- :func:`measure_matmul_efficiency` — MXU efficiency curve from timed
  matmuls at transformer-relevant shapes.
- :func:`calibrate_topology` — fit ``TPUTopology.mxu_efficiency`` from
  per-module measurements (``utils.profiler.profile_modules``) of the
  actual model on the actual chip.
- :func:`measure_strategies` / :func:`validate_ranking` — time real train
  steps for a set of single-chip-feasible strategies and check the cost
  model ranks them like the hardware does.

Run on hardware via ``workloads/calibrate_run.py``, which writes
``workloads/out/calibration.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.tools.galvatron.cost_model import (
    ModelDims, TPUTopology, estimate,
)


from hetu_tpu.utils.profiler import sync_result as _sync, time_fn_ms


def measure_matmul_efficiency(peak_flops: float, *,
                              sizes: Sequence[tuple[int, int, int]] = (
                                  (4096, 768, 768),
                                  (8192, 768, 3072),
                                  (8192, 768, 50304),
                                  (16384, 4096, 4096),
                              ),
                              dtype=jnp.bfloat16) -> dict:
    """Measured FLOP/s fraction of peak for (M,K,N) matmuls."""
    out = {}
    for m, k, n in sizes:
        a = jax.random.normal(jax.random.key(0), (m, k), dtype)
        b = jax.random.normal(jax.random.key(1), (k, n), dtype)
        f = jax.jit(lambda a, b: a @ b)
        dt = time_fn_ms(f, a, b) / 1e3
        out[(m, k, n)] = (2.0 * m * k * n / dt) / peak_flops
    return out


def calibrate_topology(model, params, batch, topo: TPUTopology,
                       dims: ModelDims) -> TPUTopology:
    """Fit ``mxu_efficiency`` so the model's predicted per-layer compute
    matches the measured block fwd+bwd time (the dominant term)."""
    from hetu_tpu.utils.profiler import profile_modules

    timings = {t.name: t for t in profile_modules(model, params, batch)}
    blk = timings["block"]
    # analytic per-layer fwd+bwd flops at these shapes (6N + causal attn)
    tokens = batch["input_ids"].size
    flops = 6.0 * tokens * dims.layer_params() \
        + 6.0 * tokens * dims.seq_len * dims.hidden / 2
    eff = flops / (blk.bwd_ms / 1e3) / topo.peak_flops
    eff = float(np.clip(eff, 0.02, 0.95))
    return dataclasses.replace(topo, mxu_efficiency=eff)


def measure_strategies(model, opt, strategies, batch_shape,
                       vocab: int, *, policy=None, steps=8,
                       warmup=2) -> list[float]:
    """Measured step time (s) for each single-chip Strategy."""
    from hetu_tpu.core.dtypes import autocast
    from hetu_tpu.engine import build_train_step, init_state, make_plan

    B, S = batch_shape
    times = []
    for st in strategies:
        ids = jax.random.randint(jax.random.key(1), (B, S + 1), 0, vocab)
        ctx = autocast(policy) if policy is not None \
            else contextlib.nullcontext()
        with ctx:
            plan = make_plan(model, opt, st)
            state = init_state(model, opt, plan, jax.random.key(0))
            step = build_train_step(model, opt, plan)
            b = plan.shard_batch({"input_ids": ids[:, :-1],
                                  "labels": ids[:, 1:]})
            for _ in range(max(1, warmup)):
                state, m = step(state, b)
            _sync(m["loss"])
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step(state, b)
            _sync(m["loss"])
            times.append((time.perf_counter() - t0) / steps)
        del state
    return times


def predicted_times(dims: ModelDims, strategies,
                    topo: TPUTopology) -> list[float]:
    return [estimate(dims, st, topo).step_time for st in strategies]


def validate_ranking(measured: Sequence[float],
                     predicted: Sequence[float]) -> dict:
    """Spearman-style check: does the model order strategies like the
    hardware does?"""
    m_rank = np.argsort(np.argsort(measured))
    p_rank = np.argsort(np.argsort(predicted))
    n = len(measured)
    agree = int(np.sum(m_rank == p_rank))
    d2 = float(np.sum((m_rank - p_rank) ** 2))
    rho = 1.0 - 6.0 * d2 / (n * (n * n - 1)) if n > 1 else 1.0
    return {"exact_positions": agree, "n": n, "spearman_rho": rho,
            "ranking_correct": bool((m_rank == p_rank).all())}
