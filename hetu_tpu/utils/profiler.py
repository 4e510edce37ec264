"""Profiling: per-step timing, compile-time separation, device memory.

Parity target: the reference's op profiler (``impl/profiler/profiler.h:25``),
graph/memory profiler (``graph/profiler.h:40`` — mempool peaks, per-micro-
batch ``MicroBatchMemoryInfo``) and subgraph fwd/bwd/update timing
(``subgraph.h:53-56``). On TPU the op/stream layer belongs to XLA, so the
equivalents are: wall-step statistics with first-step (compile) isolation,
``device.memory_stats()`` peaks; op-level drill-down is a
``jax.profiler`` trace read through the program's own names
(``telemetry/device_scopes.py``, ``docs/OBSERVABILITY.md``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
import time
from typing import Any, Optional

import jax


@dataclasses.dataclass
class StepStats:
    count: int
    mean_s: float
    p50_s: float
    min_s: float
    max_s: float
    compile_s: Optional[float]
    # tail latencies — operators page on p99, not on the mean
    # (linear-interpolation percentiles, telemetry.metrics.percentile)
    p90_s: float = 0.0
    p99_s: float = 0.0
    total_s: float = 0.0         # sum over counted steps (compile excluded)

    def tokens_per_sec(self, tokens_per_step: int) -> float:
        return tokens_per_step / self.mean_s if self.mean_s else 0.0


class StepProfiler:
    """Wall-clock step profiler; treats the first step as compile+run.

    Usage::

        prof = StepProfiler()
        for batch in data:
            with prof.step():
                state, m = step_fn(state, batch)
                jax.block_until_ready(m["loss"])
        print(prof.stats())
    """

    def __init__(self):
        self._times: list[float] = []

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        self._times.append(time.perf_counter() - t0)

    def record(self, seconds: float):
        self._times.append(seconds)

    def stats(self, *, skip_first: bool = True) -> StepStats:
        times = self._times
        compile_s = None
        if skip_first and len(times) > 1:
            compile_s = times[0]
            times = times[1:]
        if not times:
            return StepStats(0, 0.0, 0.0, 0.0, 0.0, compile_s)
        from hetu_tpu.telemetry.metrics import percentile
        svals = sorted(times)
        return StepStats(len(times), statistics.fmean(times),
                         statistics.median(times), min(times), max(times),
                         compile_s,
                         p90_s=percentile(svals, 0.9),
                         p99_s=percentile(svals, 0.99),
                         total_s=sum(times))


def device_memory_stats(device=None) -> dict[str, Any]:
    """Allocator peaks — the ``CUDACachingMemoryPool`` counters analogue
    (``graph/profiler.h:15-75``). Empty dict where the backend doesn't
    report."""
    device = device or jax.devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        stats = {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size", "num_allocs")
    return {k: stats[k] for k in keep if k in stats}


def live_array_bytes() -> int:
    """Total bytes of live device arrays (coarse leak/occupancy check)."""
    return sum(x.nbytes for x in jax.live_arrays())


# -- per-module timing -------------------------------------------------------
#
# The reference records per-subgraph fwd/bwd/update times via CUDA events on
# the module tree (``subgraph.h:53-56``, ``Graph::SubGraphProfiling``). XLA
# fuses across module boundaries inside one jit; what each phase costs
# INSIDE the real step is read off a device trace through the hetu.* named
# scopes (telemetry/device_scopes.py). What stays here measures each module
# *as its own jit* on real shapes — embed / one transformer block / LM head
# — the decomposition the Galvatron cost model calibrates against
# (tools/galvatron/calibrate.py, its one reader).

@dataclasses.dataclass
class ModuleTiming:
    name: str
    fwd_ms: float
    bwd_ms: float        # fwd+bwd walltime of grad-of-sum (includes fwd)
    param_bytes: int
    count: int = 1       # e.g. num_layers for the block entry

    @property
    def total_fwd_ms(self):
        return self.fwd_ms * self.count

    @property
    def total_bwd_ms(self):
        return self.bwd_ms * self.count


def sync_result(o):
    """Force completion via a host fetch of one element —
    ``block_until_ready`` can return before a remote runtime is done.

    Sharded arrays are fetched through their first addressable shard
    (indexing a sharded array eagerly is a collective / type error)."""
    import numpy as np
    leaf = jax.tree.leaves(o)[0]
    if isinstance(leaf, jax.Array) and leaf.ndim:
        local = leaf.addressable_shards[0].data   # single-device view
        np.asarray(jax.device_get(local[(0,) * local.ndim]))
    else:
        np.asarray(jax.device_get(leaf))


def time_fn_ms(fn, *args, iters: int = 10, warmup: int = 2) -> float:
    """Mean wall-clock ms/call of a (jitted) function.

    At least one warmup call always runs (compile must not be timed)."""
    for _ in range(max(1, warmup)):
        o = fn(*args)
    sync_result(o)
    t0 = time.perf_counter()
    for _ in range(iters):
        o = fn(*args)
    sync_result(o)
    return (time.perf_counter() - t0) / iters * 1e3





def profile_modules(model, params, batch, *, iters: int = 10,
                    warmup: int = 2, attn_impl: str = "auto"
                    ) -> list[ModuleTiming]:
    """Per-module fwd and fwd+bwd wall times on real shapes.

    ``model`` must follow the embed/blocks/head_loss protocol (GPT/Llama).
    Returns embed, block (per layer, with ``count=num_layers``), and head
    entries. Calibration consumers: ``tools.galvatron.calibrate``.
    """
    import functools

    import jax.numpy as jnp

    ids, labels = batch["input_ids"], batch["labels"]
    B, S = ids.shape

    def pbytes(tree):
        return sum(x.nbytes for x in jax.tree.leaves(tree))

    out = []
    # embed
    embed_params = {k: v for k, v in params.items() if k != "blocks"}
    fwd = jax.jit(lambda p, i: model.embed(p, i))
    bwd = jax.jit(jax.grad(
        lambda p, i: model.embed(p, i).astype(jnp.float32).sum()))
    out.append(ModuleTiming(
        "embed", time_fn_ms(fwd, embed_params, ids, iters=iters,
                          warmup=warmup),
        time_fn_ms(bwd, embed_params, ids, iters=iters, warmup=warmup),
        pbytes(params.get("wte", {})) + pbytes(params.get("wpe", {}))))

    # one transformer block (layer 0 of the stacked params)
    h = jax.jit(lambda p, i: model.embed(p, i))(embed_params, ids)
    layer0 = jax.tree.map(lambda x: x[0], params["blocks"])
    block = functools.partial(model.blocks.block, attn_impl=attn_impl)

    def block_fwd(lp, x):
        o = block(lp, x)
        return o[0] if isinstance(o, tuple) else o

    bfwd = jax.jit(block_fwd)
    bbwd = jax.jit(jax.grad(
        lambda lp, x: block_fwd(lp, x).astype(jnp.float32).sum()))
    nl = model.blocks.num_layers
    out.append(ModuleTiming(
        "block", time_fn_ms(bfwd, layer0, h, iters=iters, warmup=warmup),
        time_fn_ms(bbwd, layer0, h, iters=iters, warmup=warmup),
        pbytes(layer0), count=nl))

    # head (final norm + vocab projection + CE)
    hfwd = jax.jit(lambda p, x, y: model.head_loss(p, x, y))
    hbwd = jax.jit(jax.grad(
        lambda p, x, y: model.head_loss(p, x, y), argnums=(0, 1)))
    head_bytes = sum(pbytes(params.get(k, {}))
                     for k in ("ln_f", "final_norm", "lm_head"))
    if "lm_head" not in params:
        head_bytes += pbytes(params.get("wte", {}))  # tied projection
    out.append(ModuleTiming(
        "head", time_fn_ms(hfwd, embed_params, h, labels, iters=iters,
                         warmup=warmup),
        time_fn_ms(hbwd, embed_params, h, labels, iters=iters,
                 warmup=warmup),
        head_bytes))
    return out


def memory_breakdown(state, batch: Optional[dict] = None,
                     device=None) -> dict[str, Any]:
    """Live memory accounting: state/batch bytes by component + allocator
    peaks (per-micro-batch activation residency is the allocator peak
    minus the resident state). Reference: ``MicroBatchMemoryInfo``
    (``graph/profiler.h:31-38``).

    ``activation_peak_bytes`` is an ESTIMATE with known error bars:

    - donated buffers double-count: while a donated train step runs, the
      allocator's peak can include both the old and new copies of any
      leaf XLA chose not to update in place, so the raw
      ``peak - resident`` overestimates activations by up to
      ``param_bytes + opt_bytes`` in the worst case;
    - to bound that, the peak is clamped to the device's ``bytes_limit``
      before subtracting residents (a peak above the limit is allocator
      bookkeeping, not live tensors);
    - allocator fragmentation and transient fusion temporaries are
      indistinguishable from activations here — treat the value as an
      upper bound, and use XLA's AOT ``memory_analysis`` (see
      ``workloads/mem_calibrate.py``) when a tight number matters.
    """
    def tree_bytes(t):
        return int(sum(x.nbytes for x in jax.tree.leaves(t)
                       if hasattr(x, "nbytes")))

    out = {
        "param_bytes": tree_bytes(getattr(state, "params", state)),
        "opt_bytes": tree_bytes(getattr(state, "opt_state", ())),
    }
    if batch is not None:
        out["batch_bytes"] = tree_bytes(batch)
    stats = device_memory_stats(device)
    out.update(stats)
    if "peak_bytes_in_use" in stats:
        resident = out["param_bytes"] + out["opt_bytes"] \
            + out.get("batch_bytes", 0)
        peak = stats["peak_bytes_in_use"]
        if "bytes_limit" in stats:
            peak = min(peak, stats["bytes_limit"])
        out["activation_peak_bytes"] = max(0, peak - resident)
    return out
