"""Trainer: the user-facing training engine.

Parity target: ``python/hetu/engine/trainer.py:66`` — builds the graph
under autocast (:187-244), runs steps with a strategy id (:279-323), packs
data, checkpoints, and hot-switches strategies (``examples/hotspa``).
TPU-native shape: a Trainer owns (model, optimizer, TrainPlan, TrainState);
``set_strategy`` recompiles the plan and re-shards the live state
(HotSPa switch = ``parallel.switch.switch_strategy``); data arrives as an
iterator of host batches (``hetu_tpu.data.build_data_loader``).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu import telemetry
from hetu_tpu.core.dtypes import BF16_COMPUTE, FP32, Policy, autocast
from hetu_tpu.engine.state import TrainState
from hetu_tpu.engine.train_step import (
    CachedStep, StepCache, compile_strategy, get_step_cache, init_state,
    trace_total,
)
from hetu_tpu.optim.base import Transform
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.parallel.switch import switch_strategy
from hetu_tpu.telemetry import GoodputAccountant
from hetu_tpu.utils.checkpoint import (
    CheckpointWriter, load_checkpoint, save_checkpoint,
)
from hetu_tpu.utils.logging import MetricsLogger, get_logger


@dataclasses.dataclass
class TrainerConfig:
    """Reference: ``engine/trainer_config.py`` TrainingConfig."""

    total_steps: int = 1000
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0          # 0 = only final
    async_ckpt: bool = True
    seed: int = 0
    precision: str = "bf16"      # "bf16" | "fp32"
    attn_impl: str = "auto"
    distributed_ckpt: bool = False   # per-host shard files, no gather
    delta_ckpt: bool = False     # distributed saves after the first one
                                 # rewrite only CHANGED pieces (content
                                 # hashes; unchanged pieces reference
                                 # the previous save's step-stamped
                                 # file) — docs/ELASTICITY.md
    prefetch: int = 2            # device-prefetch depth for train();
                                 # 0 disables (reference: async C++
                                 # dataloader + dedicated H2D stream)
    eval_every: int = 0          # validation cadence for train(); 0 = off
                                 # (needs eval_batches passed to train)
    telemetry: bool = False      # turn the global telemetry switch ON at
                                 # construction (spans + metric registry;
                                 # docs/OBSERVABILITY.md). Off: the
                                 # instrumented call sites cost <1% of
                                 # the step loop (asserted in tests).
    trace_dir: Optional[str] = None
                                 # where train() exports artifacts when
                                 # telemetry is enabled: trace.json
                                 # (Perfetto) + telemetry.jsonl (unified
                                 # span/metric/goodput records)
    peak_flops: Optional[float] = None
                                 # per-chip peak for MFU in the goodput
                                 # report; None = report goodput only
    step_cache: bool = True      # memoize compiled (plan, step, eval)
                                 # per strategy in the shared StepCache
                                 # so A→B→A switching never re-traces;
                                 # False rebuilds on every set_strategy
                                 # (the cache-disabled baseline for
                                 # goodput A/B runs — docs/PERFORMANCE.md)
    compile_cache_dir: Optional[str] = None
                                 # persistent XLA compilation cache dir
                                 # (engine.precompile.enable_persistent_
                                 # compilation_cache): restarts re-trace
                                 # but skip the XLA compile. Where
                                 # $JAX_COMPILATION_CACHE_DIR is set,
                                 # jax's handling of it wins.
    aggregate_every: int = 0     # cadence (steps) for publishing this
                                 # rank's metric snapshot through
                                 # telemetry.cluster_aggregate during
                                 # train() (multi-host: pass dist= to
                                 # the Trainer; single-process runs
                                 # reduce locally). 0 = off. Aggregates
                                 # land in telemetry.jsonl as
                                 # kind=cluster_aggregate records.
    watchdog: bool = False       # hang watchdog around train(): a
                                 # monitor thread trips when no step
                                 # completes within watchdog_factor x
                                 # the rolling median step interval,
                                 # dumps the flight record + all-thread
                                 # stacks to trace_dir (or cwd) and
                                 # bumps watchdog_trips_total
                                 # (docs/OBSERVABILITY.md "Flight
                                 # recorder & watchdog")
    watchdog_factor: float = 8.0
    watchdog_min_timeout_s: float = 30.0
    slo: bool = False            # SLO/anomaly engine on the log
                                 # cadence: step-time regression, loss
                                 # spike, grad-norm spike against
                                 # rolling baselines; alerts are logged,
                                 # counted (slo_alerts_total) and
                                 # written to telemetry.jsonl as
                                 # kind=slo_alert records
    seq_buckets: Optional[tuple] = None
                                 # seq-len bucket ladder (shape plane,
                                 # docs/PERFORMANCE.md): each host batch
                                 # is snapped to the smallest bucket >=
                                 # its max REAL length
                                 # (data.bucket.ShapeBucketer) and
                                 # routed through a per-(strategy,
                                 # bucket) StepCache entry — a ragged
                                 # epoch compiles at most len(buckets)
                                 # step programs instead of one per
                                 # distinct width, and pad FLOPs drop
                                 # from pad-to-max to pad-to-bucket
                                 # (counters data_padding_tokens_total /
                                 # data_bucket_hits_total). None = off
                                 # (exact historical behavior).

    def policy(self) -> Policy:
        return BF16_COMPUTE if self.precision == "bf16" else FP32


class Trainer:
    def __init__(self, model, opt: Transform, strategy: Strategy,
                 config: Optional[TrainerConfig] = None, devices=None,
                 step_cache: Optional[StepCache] = None, dist=None):
        self.model = model
        self.opt = opt
        self.config = config if config is not None else TrainerConfig()
        self.devices = devices
        # dist: a rpc.launcher.DistContext (or anything with .client /
        # .rank / .num_processes) — enables the cross-rank telemetry
        # aggregation cadence (config.aggregate_every) on multi-host runs
        self._dist = dist
        self.state: Optional[TrainState] = None
        self.plan = None
        self._step_fn = None
        self._eval_fn = None
        self._live_prefetcher = None   # re-pointed on mid-run hot switch
        self._ckpt_writer: Optional[CheckpointWriter] = None
        if self.config.compile_cache_dir:
            from hetu_tpu.engine.precompile import (
                enable_persistent_compilation_cache)
            enable_persistent_compilation_cache(
                self.config.compile_cache_dir)
        if self.config.telemetry:
            telemetry.enable(True)
        self.tracer = telemetry.get_tracer()
        self.registry = telemetry.get_registry()
        # production-observability side-band (telemetry/flight.py,
        # telemetry/slo.py): the flight recorder is always on; the
        # watchdog and SLO engine are created on demand by train()
        self.flight = telemetry.get_flight_recorder()
        self.slo: Optional[telemetry.SLOEngine] = None
        if self.config.slo:
            self.slo = telemetry.default_training_rules(
                telemetry.SLOEngine(self.registry))
        self.goodput: Optional[GoodputAccountant] = None
        # JSONL export high-water mark; keyed to the tracer epoch so a
        # telemetry.reset() between runs restarts the window instead of
        # silently dropping the next run's spans
        self._spans_exported = 0
        self._spans_epoch = self.tracer.epoch
        metrics_path = None
        if self.config.trace_dir:
            os.makedirs(self.config.trace_dir, exist_ok=True)
            metrics_path = os.path.join(self.config.trace_dir,
                                        "telemetry.jsonl")
        # one unified record per log interval: training metrics + the
        # registry snapshot ride the same JSONL stream
        self.metrics = MetricsLogger(path=metrics_path,
                                     registry=self.registry)
        # step cache: one compiled (plan, step, eval) per strategy, so
        # switching A -> B -> A reuses executables (the reference's
        # ExecGraphPlan pool, define_and_run_graph.h:23-64). Shared with
        # engine.precompile's background AOT worker by default, so
        # planner-announced candidate strategies are already warm when
        # set_strategy asks for them.
        self.cache = step_cache if step_cache is not None \
            else get_step_cache()
        # kept as an alias: tests / callers may inspect the pool size
        self._plan_cache = self.cache
        # shape plane: bucketed steps (config.seq_buckets) — host batches
        # are snapped to the ladder and each bucket gets its own
        # StepCache entry (cleared on strategy change)
        self.bucketer = None
        if self.config.seq_buckets:
            from hetu_tpu.data.bucket import SeqLenBuckets, ShapeBucketer
            self.bucketer = ShapeBucketer(
                SeqLenBuckets(sizes=self.config.seq_buckets))
        self._bucket_entries: dict = {}
        self.set_strategy(strategy)

    # -- strategy / hot switching ------------------------------------------
    def _cache_key(self, strategy, bucket: int = 0):
        return self.cache.key_for(
            self.model, self.opt, strategy,
            attn_impl=self.config.attn_impl, donate=True,
            policy_key=self.config.precision, devices=self.devices,
            bucket=bucket)

    def set_strategy(self, strategy):
        """Compile the plan for ``strategy`` (a :class:`Strategy` or a
        Malleus :class:`~hetu_tpu.parallel.hetero.HeteroStrategy`); if
        training is live, hot-switch the full train state — params AND
        optimizer moments — onto the new layout (HotSPa; hetero via the
        homo<->hetero converters).

        The compiled artifacts come from the :class:`StepCache`: a
        strategy seen before (or pre-compiled by ``precompile()`` /
        ``engine.precompile``) makes the switch pure data movement —
        cache lookup + one ``device_put`` of the live state."""
        from hetu_tpu.parallel.hetero import (
            HeteroState, HeteroStrategy, build_hetero_train_step,
            make_hetero_plan, state_from_hetero, state_to_hetero,
        )
        strategy.validate(len(self.devices or jax.devices()))
        hetero = isinstance(strategy, HeteroStrategy)

        def to_homo_state():
            if isinstance(self.state, HeteroState):
                return state_from_hetero(self.state, self.plan, self.model)
            return self.state

        def build() -> CachedStep:
            t0 = time.perf_counter()
            with telemetry.span("compile", hetero=hetero,
                                strategy=strategy.to_json()), \
                    autocast(self.config.policy()):
                if hetero:
                    plan = make_hetero_plan(self.model, strategy,
                                            self.devices)
                    step_fn = build_hetero_train_step(
                        self.model, self.opt, plan,
                        attn_impl=self.config.attn_impl)
                    entry = CachedStep(plan, step_fn, None,
                                       refs=(self.model, self.opt))
                    entry.compile_seconds = time.perf_counter() - t0
                else:
                    entry = compile_strategy(
                        self.model, self.opt, strategy,
                        devices=self.devices,
                        attn_impl=self.config.attn_impl)
            dt = time.perf_counter() - t0
            self._note("compile", dt)
            self.flight.record("compile", hetero=hetero,
                               seconds=round(dt, 3))
            return entry

        if self.config.step_cache:
            entry = self.cache.get_or_build(self._cache_key(strategy),
                                            build)
        else:
            entry = build()

        if self.state is not None:
            t0 = time.perf_counter()
            if hetero:
                with telemetry.span("switch", hetero=True):
                    self.state = state_to_hetero(to_homo_state(),
                                                 entry.plan)
            else:
                # switch_strategy records the "switch" span itself (with
                # cross-topology + volume attrs); only the ledger lives
                # here
                self.state = switch_strategy(to_homo_state(), entry.plan)
            dt = time.perf_counter() - t0
            self._note("switch", dt)
            self.flight.record("switch", hetero=hetero,
                               seconds=round(dt, 3))
            get_logger().info(
                f"hot-switched to {'hetero ' if hetero else ''}"
                f"{strategy.to_json()} at step "
                f"{int(jax.device_get(self.state.step))}")
        self.plan = entry.plan
        self._step_fn = entry
        self._eval_fn = entry.eval_fn  # None under hetero: switch back
        self._bucket_entries.clear()   # per-(strategy, bucket) entries
        if self._live_prefetcher is not None:
            # a mid-run switch re-points the input pipeline: batches
            # staged under the old plan are re-placed lazily on fetch
            self._live_prefetcher.set_place(self.plan.shard_batch)
        return entry.plan

    def precompile(self, strategies, *, batch_shape=None,
                   batch_keys=("input_ids", "labels"),
                   buckets=None, bucket_rows=None,
                   block: bool = False):
        """Warm the step cache for candidate ``strategies`` (e.g. the
        Galvatron search's top-k) on a background thread — see
        :func:`hetu_tpu.engine.precompile.precompile_strategies`. With a
        ``batch_shape`` each candidate is AOT-compiled for it, making a
        later ``set_strategy`` + first step completely compile-free;
        ``batch_keys`` must match the run's real batch dict (packed
        loaders carry positions + segment_ids). ``buckets`` defaults to
        this Trainer's ``config.seq_buckets`` ladder so a bucketed run's
        AOT coverage automatically spans every (strategy, bucket)
        variant."""
        from hetu_tpu.engine.precompile import precompile_strategies
        if buckets is None and self.config.seq_buckets:
            buckets = self.config.seq_buckets
        handle = precompile_strategies(
            self.model, self.opt, strategies, batch_shape=batch_shape,
            batch_keys=batch_keys, buckets=buckets,
            bucket_rows=bucket_rows,
            devices=self.devices, attn_impl=self.config.attn_impl,
            policy=self.config.policy(),
            policy_key=self.config.precision, cache=self.cache,
            background=not block)
        if block:
            handle.wait()
        return handle

    # -- shape plane (bucketed steps) --------------------------------------
    def _bucket_entry(self, bucket: int) -> CachedStep:
        """CachedStep for (current strategy, ``bucket``) — one entry per
        bucket so each holds exactly one shape in its jit/AOT caches and
        the ragged-epoch compile count is bounded by the ladder size."""
        entry = self._bucket_entries.get(bucket)
        if entry is not None:
            return entry
        strategy = self.strategy
        key = self._cache_key(strategy, bucket=bucket)
        first_build = self.cache.lookup(key) is None

        def build() -> CachedStep:
            t0 = time.perf_counter()
            with telemetry.span("compile", bucket=bucket,
                                strategy=strategy.to_json()), \
                    autocast(self.config.policy()):
                e = compile_strategy(
                    self.model, self.opt, strategy,
                    devices=self.devices,
                    attn_impl=self.config.attn_impl)
            dt = time.perf_counter() - t0
            self._note("compile", dt)
            self.flight.record("compile", bucket=bucket,
                               seconds=round(dt, 3))
            return e

        entry = self.cache.get_or_build(key, build) \
            if self.config.step_cache else build()
        if first_build and telemetry.enabled():
            self.registry.counter(
                "data_bucket_compiles_total",
                "step entries built per seq-len bucket (the re-trace "
                "audit's per-bucket view)").inc(bucket=str(bucket))
        self._bucket_entries[bucket] = entry
        return entry

    def _step_entry_for(self, sbatch: dict) -> CachedStep:
        """Pick the step entry for an (already fitted, already sharded)
        batch: the per-bucket entry when bucketing is on and the batch
        carries a seq dim, else the strategy's base entry. Hetero plans
        keep the base entry (the hetero executor owns its own shapes)."""
        if self.bucketer is None or self._eval_fn is None \
                or "input_ids" not in sbatch:
            return self._step_fn
        return self._bucket_entry(int(sbatch["input_ids"].shape[1]))

    def _note(self, category: str, seconds: float) -> None:
        """Goodput ledger + cumulative counter for an overhead event."""
        if self.goodput is not None:
            self.goodput.record(category, seconds)
        if telemetry.enabled():
            self.registry.counter(
                f"{category}_seconds_total",
                f"cumulative {category} time").inc(seconds)

    def shrink_to(self, devices, strategy: Optional[Strategy] = None):
        """Elastic recovery on the live controller: rebuild plans over
        the SURVIVING ``devices`` and reshard the live state onto them —
        no checkpoint read (``parallel.switch`` cross-topology path; see
        also ``engine.elastic.elastic_resume`` for the non-Trainer form).

        ``strategy``: the recovery strategy (e.g. from
        ``ElasticController.recovery_plan``); defaults to the current one,
        which must fit the surviving device count.
        """
        return self._retarget(devices, strategy, kind="shrink")

    def grow_to(self, devices, strategy: Optional[Strategy] = None):
        """Elastic re-admission: a recovered worker's devices rejoin the
        mesh and the live state hot-switches onto the GROWN plan — the
        same cross-topology switch a shrink uses, in the other direction
        (``engine.elastic.ElasticSupervisor.grow`` drives this from the
        membership side)."""
        return self._retarget(devices, strategy, kind="grow")

    def _retarget(self, devices, strategy, *, kind: str):
        self.devices = list(devices)
        # cached plans pin departed devices — drop the whole pool (the
        # cache may be process-shared: a membership change invalidates
        # every plan compiled for the old topology anyway)
        self.cache.clear()
        self.flight.record(f"elastic_{kind}", n_devices=len(self.devices))
        return self.set_strategy(strategy if strategy is not None
                                 else self.strategy)

    @property
    def strategy(self) -> Strategy:
        return self.plan.strategy

    # -- state lifecycle ---------------------------------------------------
    def initialize(self, key: Optional[jax.Array] = None) -> TrainState:
        from hetu_tpu.parallel.hetero import HeteroPlan, init_hetero_state
        key = key if key is not None else jax.random.key(self.config.seed)
        with autocast(self.config.policy()):
            if isinstance(self.plan, HeteroPlan):
                self.state = init_hetero_state(self.model, self.opt,
                                               self.plan, key)
            else:
                self.state = init_state(self.model, self.opt, self.plan,
                                        key)
        return self.state

    def resume(self, path: str) -> TrainState:
        import os
        from hetu_tpu.parallel.hetero import HeteroPlan, state_to_hetero
        hetero = isinstance(self.plan, HeteroPlan)
        plan = None if hetero else self.plan
        if os.path.exists(os.path.join(path, "index-host00000.json")):
            from hetu_tpu.utils.dist_checkpoint import (
                load_checkpoint_distributed)
            self.state = load_checkpoint_distributed(
                path, self.model, self.opt, plan)
        else:
            self.state = load_checkpoint(path, self.model, self.opt,
                                         plan)
        if hetero:
            self.state = state_to_hetero(self.state, self.plan)
        get_logger().info(
            f"resumed from {path} at step "
            f"{int(jax.device_get(self.state.step))}")
        return self.state

    def save(self, path: Optional[str] = None, *, wait: bool = False):
        path = path or self.config.ckpt_dir
        if path is None:
            raise ValueError("no checkpoint path configured")
        t0 = time.perf_counter()
        with telemetry.span("checkpoint", path=path, wait=wait):
            if self._ckpt_writer is not None:
                self._ckpt_writer.wait()  # one in-flight save at a time
            from hetu_tpu.parallel.hetero import (
                HeteroState, state_from_hetero)
            state = self.state
            if isinstance(state, HeteroState):
                # checkpoints are layout-independent: merge to one
                # TrainState
                state = state_from_hetero(state, self.plan, self.model)
            if self.config.distributed_ckpt:
                import glob
                from hetu_tpu.utils.dist_checkpoint import (
                    save_checkpoint_distributed)
                delta = None
                if self.config.delta_ckpt and glob.glob(
                        os.path.join(path, "index-host*.json")):
                    delta = path   # in-place series: delta vs last save
                self._ckpt_writer = save_checkpoint_distributed(
                    path, state, delta_base=delta,
                    # hash even the series' first, full save — the next
                    # one deltas against it
                    hash_pieces=self.config.delta_ckpt or None,
                    async_save=self.config.async_ckpt and not wait)
            else:
                self._ckpt_writer = save_checkpoint(
                    path, state,
                    async_save=self.config.async_ckpt and not wait)
            if wait:
                self._ckpt_writer.wait()
        # the span/ledger cover what BLOCKED the loop (previous writer
        # drain + device→host gather + sync write); an async write's own
        # latency is tracked by checkpoint_write_seconds on its thread
        dt = time.perf_counter() - t0
        self._note("checkpoint", dt)
        self.flight.record("checkpoint", path=path,
                           blocked_s=round(dt, 3))
        return path

    # -- training ----------------------------------------------------------
    def train_step(self, batch: dict) -> dict:
        if self.state is None:
            self.initialize()
        if self.bucketer is not None and self._eval_fn is not None:
            batch = self.bucketer.fit(batch)
        sbatch = self.plan.shard_batch(batch)
        self.state, metrics = self._step_entry_for(sbatch)(self.state,
                                                           sbatch)
        return metrics

    def train(self, batches: Iterable[dict],
              steps: Optional[int] = None, *,
              eval_batches=None) -> list[dict]:
        """Run up to ``steps`` (default config.total_steps) steps; returns
        the logged metric records.

        The loop keeps the device pipeline full: the step counter is
        tracked host-side (a per-step ``device_get(state.step)`` would
        sync every step and serialize dispatch), the host only blocks on
        metrics at log boundaries, and batches are staged through the
        device prefetcher (``data/prefetch.py``) so H2D transfers overlap
        the previous step's compute.

        ``eval_batches``: a *callable returning an iterable* of held-out
        batches; every ``config.eval_every`` steps it is re-invoked and
        the mean validation loss (dropout off) is logged as
        ``eval_loss``."""
        if self.state is None:
            self.initialize()
        steps = steps if steps is not None else self.config.total_steps
        history = []
        tel = telemetry.enabled()
        # goodput ledger for THIS run: every loop second lands in a
        # category (compute/stall/eval here; compile/switch/checkpoint
        # via set_strategy()/save()); report exported at the end
        acct = GoodputAccountant(peak_flops=self.config.peak_flops)
        self.goodput = acct
        t_last = time.perf_counter()
        tokens_since = 0
        tokens_total = 0
        # MFU pricing under varying widths (bucketed ragged epochs): the
        # attention FLOPs/token depend on seq width, so the accountant's
        # single flops_per_token is kept as the running FLOPS-WEIGHTED
        # mean — tokens_total * flops_per_token stays exact per batch
        fpt_by_width: dict[int, Optional[float]] = {}
        flops_sum = 0.0
        slo_blocked_s = 0.0   # eval/checkpoint time inside the current
                              # log interval — excluded from the SLO
                              # step-time observation
        host_step = int(jax.device_get(self.state.step))
        # hang watchdog for THIS run: fed once per completed step; trips
        # dump the flight record + thread stacks to trace_dir (or cwd)
        watchdog = None
        if self.config.watchdog:
            watchdog = telemetry.HangWatchdog(
                name="train", factor=self.config.watchdog_factor,
                min_timeout_s=self.config.watchdog_min_timeout_s,
                dump_dir=self.config.trace_dir or ".",
                registry=self.registry).start()
        if self.bucketer is not None and self._eval_fn is not None:
            # snap every host batch to its bucket BEFORE placement: the
            # prefetcher stages the fitted (bucket-wide) arrays, so the
            # step entry picked at dispatch time sees exactly one shape
            # per bucket
            fit = self.bucketer.fit
            batches = (fit(b) for b in batches)
        if tel:
            # counted where the loader's batch is taken: the prefetch
            # thread when there is one, never the dispatch path
            batches = map(self._count_flash_tiles, batches)
        prefetcher = None
        if self.config.prefetch > 0:
            from hetu_tpu.data.prefetch import DevicePrefetcher
            prefetcher = DevicePrefetcher(
                batches, self.plan.shard_batch,
                buffer_size=self.config.prefetch, max_items=steps)
            # registered so a mid-run set_strategy() re-points placement
            # at the new plan (staged batches re-place lazily on fetch)
            self._live_prefetcher = prefetcher
            it: Iterator[dict] = prefetcher
        else:
            it = (self.plan.shard_batch(b) for b in batches)
        failed: Optional[str] = None   # exception name when train() dies
        try:
            for _ in range(steps):
                # one span per phase (docs/OBSERVABILITY.md): in a
                # jax.profiler trace hetu:train/step and its children
                # sit above the ops the step dispatched
                with telemetry.span("train/step",
                                    step=host_step + 1) as step_span:
                    t_iter = time.perf_counter()
                    try:
                        # what the loop waited for its batch (the
                        # goodput ledger's "stall"), not what the
                        # loader took
                        with telemetry.span("train/next_batch"):
                            sbatch = next(it)
                    except StopIteration:
                        step_span.set(exhausted=True)   # not a step
                        break
                    t_fetch = time.perf_counter()
                    # waiting on the data path is a stall (the prefetcher
                    # additionally emits a "stall" span + counter itself)
                    acct.record("stall", t_fetch - t_iter)
                    width = int(sbatch["input_ids"].shape[-1]) \
                        if "input_ids" in sbatch else None
                    if width is not None and width not in fpt_by_width:
                        fpt_by_width[width] = self._flops_per_token(width)
                    n_traces = trace_total()
                    with telemetry.span("train/dispatch"):
                        self.state, metrics = \
                            self._step_entry_for(sbatch)(self.state,
                                                         sbatch)
                    host_step += 1
                    acct.add_step()
                    # step boundary into the black box; one beat per
                    # completed step feeds the watchdog's rolling median
                    self.flight.record("step", step=host_step)
                    if watchdog is not None:
                        watchdog.beat()
                    ntok = int(sbatch["input_ids"].size)
                    tokens_since += ntok
                    tokens_total += ntok
                    acct.add_tokens(ntok)
                    fpt = fpt_by_width.get(width) if width is not None \
                        else None
                    if fpt:
                        flops_sum += fpt * ntok
                        acct.flops_per_token = flops_sum / tokens_total
                    if self.config.log_every and \
                            host_step % self.config.log_every == 0:
                        with telemetry.span("train/loss_fetch"):
                            loss = float(
                                jax.device_get(metrics["loss"]))
                            grad_norm = float(
                                jax.device_get(metrics["grad_norm"]))
                        now = time.perf_counter()
                        rec = self.metrics.log(
                            host_step, loss=loss,
                            grad_norm=grad_norm,
                            tokens_per_sec=round(
                                tokens_since / (now - t_last), 1),
                            tokens_total=tokens_total)
                        history.append(rec)
                        if self.slo is not None:
                            # one observation per log interval, then run
                            # every detector (burn rates + regressions).
                            # Known blocking work (eval, checkpoint drain)
                            # is subtracted — it is accounted overhead, not
                            # a step-time regression
                            self.slo.observe("loss", loss)
                            self.slo.observe("grad_norm", grad_norm)
                            self.slo.observe(
                                "step_time_s",
                                max(now - t_last - slo_blocked_s, 0.0)
                                / self.config.log_every)
                            slo_blocked_s = 0.0
                            for a in self.slo.evaluate():
                                get_logger().warning(f"SLO alert: "
                                                     f"{a.message}")
                                self.metrics.write_record(a.to_record())
                        t_last, tokens_since = now, 0
                        if tel:
                            # sample the mem_*/comm_* registry series into
                            # Perfetto counter tracks on the log cadence
                            telemetry.process.sample()
                            self.tracer.record_counters(
                                self.registry.snapshot())
                    # step dispatch + the log boundary's blocking fetch: the
                    # productive slice of this iteration — UNLESS the step
                    # body re-traced, in which case the wall went to
                    # trace+XLA-compile (a cold/cache-disabled first step)
                    # and belongs in the compile ledger, not compute
                    step_s = time.perf_counter() - t_fetch
                    if trace_total() > n_traces:
                        acct.record("compile", step_s)
                        if tel:
                            self.tracer.complete("compile", step_s,
                                                 where="step_trace")
                    else:
                        acct.record("compute", step_s)
                    if self.config.eval_every and eval_batches is not None \
                            and host_step % self.config.eval_every == 0:
                        # eval/checkpoint are legitimately long blocking
                        # operations, not hangs: suspend trip checks so a
                        # slow eval pass or writer drain never produces a
                        # false "the run HUNG" flight dump
                        if watchdog is not None:
                            watchdog.pause()
                        t0 = time.perf_counter()
                        with telemetry.span("eval", step=host_step):
                            ev = self.evaluate(eval_batches())
                        ev_s = time.perf_counter() - t0
                        acct.record("eval", ev_s)
                        slo_blocked_s += ev_s
                        history.append(self.metrics.log(host_step,
                                                        eval_loss=ev))
                        if watchdog is not None:
                            watchdog.resume()
                    if self.config.aggregate_every and telemetry.enabled() \
                            and host_step % self.config.aggregate_every == 0:
                        self._aggregate_cluster(host_step)
                    if self.config.ckpt_every and self.config.ckpt_dir and \
                            host_step % self.config.ckpt_every == 0:
                        if watchdog is not None:
                            watchdog.pause()
                        t0 = time.perf_counter()
                        self.save()   # notes "checkpoint" in the ledger
                        slo_blocked_s += time.perf_counter() - t0
                        if watchdog is not None:
                            watchdog.resume()
            if self.config.ckpt_dir:
                if watchdog is not None:
                    watchdog.pause()
                self.save(wait=True)
        except BaseException as e:
            # explicit capture, NOT sys.exc_info() in the finally: that
            # would also see a CALLER's in-flight handled exception and
            # overwrite the flight postmortem after a successful run
            failed = type(e).__name__
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
            if prefetcher is not None:
                self._live_prefetcher = None
                prefetcher.close()
            acct.freeze()   # later manual exports must not dilute goodput
            # export in the failure path too: a crashed run is exactly
            # when the operator needs the trace (best-effort — an export
            # problem must not mask the training error)
            if failed is not None:
                try:
                    self.flight.record("train_error", error=failed)
                    self.flight.dump(
                        self.flight.default_path(self.config.trace_dir),
                        reason="train_error", stacks=True)
                except Exception:
                    pass
            if tel:
                try:
                    self.export_telemetry()
                except Exception as e:
                    get_logger().warning(f"telemetry export failed: {e}")
        return history

    def train_dynamic(self, dispatcher, seqs, epochs: int = 1, *,
                      use_bucket_strategies: bool = False) -> list[dict]:
        """Hydraulis flow: train over a DynamicDispatcher's per-bucket
        batches, one cached jitted step per bucket length (jit cache
        keyed on shape).

        ``use_bucket_strategies=True`` is the COMPOSED Hydraulis planner
        (reference ``examples/hydraulis/strategy/new_planning.py``): each
        bucket trains under ITS OWN parallel strategy from
        ``plan_buckets``'s cost-model search (short buckets dp-heavy,
        long buckets cp+remat), hot-switching the live state between
        plans at bucket boundaries. The dispatcher emits largest buckets
        first, so switches happen once per bucket class per epoch, and
        the plan pool makes A→B→A reuse free. False keeps this Trainer's
        single strategy (per-bucket shapes only)."""
        if self.state is None:
            self.initialize()
        history = []
        tel = telemetry.enabled()
        acct = GoodputAccountant(peak_flops=self.config.peak_flops)
        self.goodput = acct   # set_strategy switches/compiles feed it
        host_step = int(jax.device_get(self.state.step))
        # per-bucket FLOP pricing, same running weighted mean as train()
        fpt_by_width: dict[int, Optional[float]] = {}
        flops_sum = 0.0
        tokens_sum = 0
        try:
            for _ in range(epochs):
                for batch, plan in dispatcher.batches(seqs):
                    if use_bucket_strategies \
                            and plan.strategy != self.strategy:
                        self.set_strategy(plan.strategy)
                    t0 = time.perf_counter()
                    width = int(batch["input_ids"].shape[-1])
                    if width not in fpt_by_width:
                        fpt_by_width[width] = self._flops_per_token(
                            width)
                    n_traces = trace_total()
                    metrics = self.train_step(batch)
                    host_step += 1   # host-side: no per-step device sync
                    acct.add_step()
                    ntok = int(batch["input_ids"].size)
                    acct.add_tokens(ntok)
                    tokens_sum += ntok
                    if fpt_by_width.get(width):
                        flops_sum += fpt_by_width[width] * ntok
                        acct.flops_per_token = flops_sum / tokens_sum
                    if self.config.log_every and \
                            host_step % self.config.log_every == 0:
                        extra = {"strategy": plan.strategy.to_json()} \
                            if use_bucket_strategies else {}
                        history.append(self.metrics.log(
                            host_step,
                            loss=float(jax.device_get(metrics["loss"])),
                            bucket=plan.bucket_len, **extra))
                    acct.record(
                        "compile" if trace_total() > n_traces
                        else "compute", time.perf_counter() - t0)
        finally:
            acct.freeze()
            if tel:
                try:
                    self.export_telemetry()
                except Exception as e:
                    get_logger().warning(f"telemetry export failed: {e}")
        return history

    # -- telemetry ---------------------------------------------------------
    def _aggregate_cluster(self, step: int) -> Optional[dict]:
        """One cross-rank aggregation round on the train() cadence
        (``config.aggregate_every``): publish this rank's registry
        snapshot through the coordinator KV, take back the cluster
        min/max/mean reduction, and log it as a ``cluster_aggregate``
        record. Without a ``dist`` context (single process) the snapshot
        reduces locally — same record shape, ranks=1 — so the cadence
        and artifact schema are exercised everywhere. Failures are
        logged, never fatal: telemetry must not kill training."""
        snap = self.registry.snapshot()
        t0 = time.perf_counter()
        try:
            with telemetry.span("cluster_aggregate", step=step):
                if self._dist is not None and \
                        getattr(self._dist, "num_processes", 1) > 1:
                    agg = telemetry.cluster_aggregate(
                        self._dist.client, self._dist.rank,
                        self._dist.num_processes, snap, run="trainer-agg")
                    ranks = self._dist.num_processes
                else:
                    agg = telemetry.aggregate_snapshots([snap])
                    ranks = 1
        except Exception as e:   # noqa: BLE001 — observability side-path
            get_logger().warning(
                f"cluster aggregation failed at step {step}: {e}")
            return None
        finally:
            # the blocking barrier time is overhead the goodput ledger
            # must see (the cadence is the operator's knob against it)
            self._note("telemetry", time.perf_counter() - t0)
        rec = {"kind": "cluster_aggregate", "step": step,
               "ranks": ranks, "metrics": agg}
        self.metrics.write_record(rec)
        return agg

    def _count_flash_tiles(self, batch: dict) -> dict:
        """``flash_tiles_total{pass, class}``: the compute tiles one
        head's causal flash calls make of this packed batch, by the class
        the kernels walk them in (``ops.flash_pallas.tile_classes``, from
        the host's ``segment_ids``; ``bwd`` = dq's + dk/dv's). Returns the
        batch."""
        seg = batch.get("segment_ids") if isinstance(batch, dict) else None
        if seg is None:
            return batch
        from hetu_tpu.ops.flash_pallas import train_tile_classes
        counter = self.registry.counter(
            "flash_tiles_total",
            "flash attention compute tiles of one head over the packed "
            "batches, by pass and class (dead: never visited; interior: "
            "no mask built; edge: masked)")
        for which, n in train_tile_classes(np.asarray(seg)).items():
            for cls, x in zip(("dead", "interior", "edge"), n):
                counter.inc(int(x), **{"pass": which, "class": cls})
        return batch

    def _flops_per_token(self, seq_len: int) -> Optional[float]:
        """Model FLOPs/token from the config shapes (cost-model dims);
        None when the model family doesn't expose transformer dims."""
        cfg = getattr(self.model, "cfg", None)
        if cfg is None or not hasattr(cfg, "num_layers") \
                or not hasattr(cfg, "hidden_size"):
            return None
        try:
            from hetu_tpu.tools.galvatron.cost_model import ModelDims
            dims = ModelDims.from_config(cfg, seq_len=seq_len,
                                         global_batch=1)
            return telemetry.model_flops_per_token(dims)
        except Exception:
            return None

    def export_telemetry(self) -> Optional[dict]:
        """Flush telemetry artifacts for the last run to
        ``config.trace_dir``: rewrite ``trace.json`` (all spans so far,
        Perfetto-loadable) and append the new span records plus the
        goodput report to ``telemetry.jsonl``. Returns the goodput
        record (also without a trace_dir, for programmatic use)."""
        rec = None
        if self.goodput is not None:
            rec = self.goodput.report().to_record()
        if not self.config.trace_dir or not telemetry.enabled():
            return rec
        import os
        self.tracer.export_chrome(
            os.path.join(self.config.trace_dir, "trace.json"))
        if self._spans_epoch != self.tracer.epoch:   # reset() since last
            self._spans_exported = 0
            self._spans_epoch = self.tracer.epoch
        events = self.tracer.events()
        for ev in events[self._spans_exported:]:
            self.metrics.write_record(ev.to_record())
        self._spans_exported = len(events)
        if rec is not None:
            self.metrics.write_record(rec)
            # per-strategy OBSERVED step time: the record the Galvatron
            # search's measured re-rank consumes
            # (tools.galvatron.search.rerank_by_measured) — closing the
            # planner loop from the gain side
            comp, steps = rec["components"].get("compute", 0.0), \
                rec.get("steps", 0)
            if comp > 0 and steps:
                try:
                    self.metrics.write_record({
                        "kind": "measured_step",
                        "strategy": self.strategy.to_json(),
                        "step_time_s": comp / steps, "steps": steps})
                except Exception:   # hetero strategies: no to_json parity
                    pass
        # final registry snapshot: the control-plane counters (cache
        # hits, prefetch overlap, switch fast path) as of run end —
        # trace_summary's "control plane" section reads the LAST one
        snap = self.registry.to_record()
        if snap["metrics"]:
            self.metrics.write_record(snap)
        return rec

    def close(self) -> None:
        """Release resources: drain any in-flight checkpoint write and
        close the metrics JSONL stream (idempotent)."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait()
            self._ckpt_writer = None
        self.metrics.close()

    def evaluate(self, batches: Iterable[dict]) -> float:
        if self._eval_fn is None:
            raise RuntimeError(
                "evaluate() is not supported under a hetero strategy — "
                "set_strategy(Strategy(...)) back to a homogeneous plan "
                "first (the hot switch preserves the state)")
        total, n = 0.0, 0
        for batch in batches:
            loss = self._eval_fn(self.state.params,
                                 self.plan.shard_batch(batch))
            total += float(jax.device_get(loss))
            n += 1
        return total / max(n, 1)
