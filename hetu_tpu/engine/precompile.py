"""AOT strategy pre-compilation + persistent compilation cache wiring.

Hot switching (HotSPa) is only "hot" if the destination strategy's step
executable already exists; otherwise the switch pays a full re-trace +
XLA compile on the critical path — exactly the compile/switch slices the
goodput accountant (``telemetry/goodput.py``) itemizes. This module
removes that tax along two axes:

- **Background AOT compilation** — :func:`precompile_strategies` runs
  ``jax.jit(step).lower(abstract_state, abstract_batch).compile()`` for
  candidate strategies on a worker thread while step N of the *current*
  strategy trains, parking the executables in the shared
  :class:`~hetu_tpu.engine.train_step.StepCache`. A later
  ``Trainer.set_strategy`` is then a cache hit, and the first step after
  the switch dispatches the ahead-of-time executable — zero traces, zero
  compiles on the critical path. :func:`precompile_top_k` feeds the
  worker from the Galvatron search's best plans (Alpa/Galvatron-style
  plan reuse).
- **Persistent compilation cache** —
  :func:`enable_persistent_compilation_cache` wires jax's on-disk XLA
  cache so restarts (and the AOT worker itself) start warm: a re-trace
  still happens, but the minutes-long XLA compile becomes a disk read.

Everything is thread-safe: compile state is per-entry via the cache's
single-flight builds, and the dtype policy (``core.dtypes.autocast``) is
thread-local so a background lowering never leaks its policy into the
training thread.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from typing import Iterable, Optional, Sequence

import jax

from hetu_tpu.core.dtypes import Policy, autocast
from hetu_tpu.engine.train_step import (
    CachedStep, StepCache, _batch_key, abstract_batch,
    abstract_train_state, compile_strategy, get_step_cache,
)
from hetu_tpu.parallel.strategy import Strategy


@dataclasses.dataclass
class PrecompileResult:
    """Outcome of one strategy's pre-compilation."""

    strategy: Strategy
    ok: bool
    seconds: float
    aot: bool                      # an AOT executable was compiled
    cached: bool = False           # entry already existed (cache hit)
    error: Optional[str] = None
    bucket: int = 0                # seq-len bucket (0 = unbucketed)


class PrecompileHandle:
    """Join handle for a (possibly background) pre-compilation run."""

    def __init__(self):
        self._done = threading.Event()
        self._results: list[PrecompileResult] = []
        self._thread: Optional[threading.Thread] = None

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None
             ) -> list[PrecompileResult]:
        """Block until every candidate finished compiling; returns the
        per-strategy results (partial list if ``timeout`` expires)."""
        self._done.wait(timeout)
        return list(self._results)

    @property
    def results(self) -> list[PrecompileResult]:
        return list(self._results)


def _precompile_one(model, opt, strategy: Strategy, *, devices, attn_impl,
                    donate, policy: Optional[Policy], policy_key,
                    batch_shape, batch_keys,
                    cache: StepCache, bucket: int = 0) -> PrecompileResult:
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry import device_scopes
    t0 = time.perf_counter()
    # EVERY key-bearing field must be forwarded here (the shape-plane
    # lint asserts it): a field the enumeration drops would silently
    # compile into the wrong entry and the runtime would re-trace
    key = cache.key_for(model, opt, strategy, attn_impl=attn_impl,
                        donate=donate, policy_key=policy_key,
                        devices=devices, bucket=bucket)
    with telemetry.span("precompile", strategy=strategy.to_json()) as sp:
        existed = cache.lookup(key) is not None

        def build() -> CachedStep:
            ctx = autocast(policy) if policy is not None else _nullctx()
            with ctx:
                return compile_strategy(model, opt, strategy,
                                        devices=devices,
                                        attn_impl=attn_impl,
                                        donate=donate)

        entry = cache.get_or_build(key, build)
        did_aot = False
        if batch_shape is not None:
            # one source of truth for the AOT dict key: the exact batch
            # the executable is lowered for
            batch_sds = abstract_batch(entry.plan, batch_shape,
                                       keys=batch_keys)
            bkey = _batch_key(batch_sds)
            if bkey not in entry.aot:
                ctx = autocast(policy) if policy is not None else _nullctx()
                with ctx:
                    # dtype left to the autocast policy — must mirror
                    # what Trainer.initialize's init_state produces
                    state_sds = abstract_train_state(model, opt,
                                                     entry.plan)
                    exe = entry.step_fn.lower(state_sds,
                                              batch_sds).compile()
                entry.aot[bkey] = exe
                did_aot = True
                # readable device scopes: the executable's text is
                # fetched only if someone asks for the map
                device_scopes.register_step(
                    "train_step", _hlo_text_of(exe), key=(key, bkey))
        if telemetry.enabled():
            sp.set(cached=existed, aot=did_aot)
            if not existed or did_aot:   # count real work, not no-ops
                telemetry.get_registry().counter(
                    "precompiled_strategies_total",
                    "strategies compiled ahead of time").inc()
    return PrecompileResult(strategy, ok=True,
                            seconds=time.perf_counter() - t0,
                            aot=did_aot, cached=existed, bucket=bucket)


def _hlo_text_of(exe):
    """A thunk for ``device_scopes.register_step`` that does not pin the
    executable: the text of ``exe`` while it lives, else ``None``."""
    ref = weakref.ref(exe)

    def text():
        live = ref()
        return live.as_text() if live is not None else None

    return text


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def precompile_strategies(model, opt, strategies: Iterable[Strategy], *,
                          batch_shape: Optional[tuple] = None,
                          batch_keys: Sequence[str] = ("input_ids",
                                                       "labels"),
                          buckets: Optional[Sequence[int]] = None,
                          bucket_rows: Optional[dict] = None,
                          devices=None, attn_impl: str = "auto",
                          donate: bool = True,
                          policy: Optional[Policy] = None,
                          policy_key: str = "",
                          cache: Optional[StepCache] = None,
                          background: bool = True) -> PrecompileHandle:
    """Compile every candidate strategy into the step cache.

    ``batch_shape`` — global (batch, seq) the training loop will feed;
    when given, each strategy is ALSO AOT-compiled for that shape
    (``lower().compile()``) so the first post-switch step dispatches a
    ready executable. Without it only the plan + jitted step are built
    (the first step after a switch still traces once).

    ``buckets`` — the seq-len bucket ladder of a shape-plane run
    (``TrainerConfig(seq_buckets=...)``): candidates become the full
    (strategy x bucket) product, each keyed with its bucket in the
    StepCache (``key_for(bucket=)``) and AOT-compiled at
    ``(rows, bucket)`` where ``rows`` comes from ``bucket_rows[bucket]``
    (falling back to ``batch_shape[0]``). Without it the bucket ladder's
    variants would silently miss AOT coverage and the first step at each
    new bucket would trace on the critical path.

    ``batch_keys`` must name EXACTLY the keys the real (post
    ``shard_batch``) batches carry — the AOT executable is selected by
    shape/dtype signature, so a mismatch silently falls back to the
    jitted path. Packed loaders (``build_data_loader(pack=True)``) need
    ``("input_ids", "labels", "positions", "segment_ids")``.

    ``background=True`` returns immediately; compilation proceeds on a
    daemon worker thread (one worker: XLA already parallelizes a single
    compile, and serial candidates keep host memory bounded). Failures
    are per-strategy — one infeasible candidate never aborts the rest.
    """
    cache = cache if cache is not None else get_step_cache()
    strategies = list(strategies)
    handle = PrecompileHandle()
    rows0 = batch_shape[0] if batch_shape is not None else None
    if buckets is not None:
        cands = [(s, int(L)) for s in strategies
                 for L in sorted(set(int(b) for b in buckets))]
    else:
        cands = [(s, 0) for s in strategies]

    def _shape_for(bucket: int) -> Optional[tuple]:
        if bucket == 0:
            return batch_shape
        rows = (bucket_rows or {}).get(bucket, rows0)
        return None if rows is None else (int(rows), bucket)

    def work():
        for s, bkt in cands:
            try:
                res = _precompile_one(
                    model, opt, s, devices=devices, attn_impl=attn_impl,
                    donate=donate, policy=policy, policy_key=policy_key,
                    batch_shape=_shape_for(bkt), batch_keys=batch_keys,
                    cache=cache, bucket=bkt)
            except Exception as e:   # noqa: BLE001 — per-candidate
                res = PrecompileResult(s, ok=False, seconds=0.0,
                                       aot=False, error=str(e)[:500],
                                       bucket=bkt)
            handle._results.append(res)
        handle._done.set()

    if background:
        t = threading.Thread(target=work, daemon=True,
                             name="hetu-precompile")
        handle._thread = t
        t.start()
    else:
        work()
    return handle


def precompile_top_k(model, opt, dims, topo, *, k: int = 3,
                     batch_shape: Optional[tuple] = None,
                     num_devices: Optional[int] = None,
                     measured_path: Optional[str] = None,
                     **kw) -> PrecompileHandle:
    """Drive the AOT worker from the Galvatron search: take the top-``k``
    feasible candidates of :func:`~hetu_tpu.tools.galvatron.search.
    search_uniform` over (``dims``, ``topo``) and pre-compile them, so a
    planner-directed hot switch to ANY of its likely picks is warm.

    ``num_devices`` filters candidates to what the live mesh can host
    (defaults to ``jax.device_count()``). ``measured_path`` (or
    ``$HETU_MEASURED_TELEMETRY``) points at a telemetry JSONL whose
    ``measured_step`` records re-rank the candidates by OBSERVED step
    time before the top-``k`` cut — the precompiled set then reflects
    what actually ran fastest, not just the analytic model."""
    from hetu_tpu.tools.galvatron.search import search_uniform
    n = num_devices if num_devices is not None else jax.device_count()
    cands = [c.strategy
             for c in search_uniform(dims, topo,
                                     measured_path=measured_path)
             if c.strategy.num_devices <= n]
    return precompile_strategies(model, opt, cands[:k],
                                 batch_shape=batch_shape, **kw)


#: The one fixed cache directory inside the checkout (git-ignored). The
#: path is part of the cache key, so it never moves with a pid or a time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_compilation_cache(
        path: Optional[str] = None, *,
        min_compile_seconds: float = 1.0) -> str:
    """Turn on jax's persistent (on-disk) compilation cache so process
    restarts start warm: the cache is keyed on the XLA program, so an
    identical strategy re-compiled after a restart is a disk read
    instead of a full XLA compile.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, jax's own handling of
    it is the whole mechanism and nothing is set here. Otherwise the
    cache goes to ``path``, by default :data:`COMPILE_CACHE_DIR`. The
    entry points (``chip_smoke.py``, ``benchmark/``, ``examples/``) call
    this with no argument; ``import hetu_tpu`` never does. Returns the
    directory in force."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = path or COMPILE_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_seconds))
    return path
