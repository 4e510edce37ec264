"""Train-step compiler: Strategy → jitted sharded train step.

This is the TPU-native replacement for the reference's exec-graph pipeline
(``DefineAndRunGraph::Run`` → ``Instantiate`` → ``SubstituteCommOp`` →
``CrucialRun``, SURVEY §3.3): a :class:`TrainPlan` compiles a Strategy into
(mesh, param/opt-state/batch shardings, activation-sharding context), and
:func:`build_train_step` closes a jitted step over it. Every Strategy flag is
consumed here:

- ``dp``      — batch sharded over dp; GSPMD emits the grad allreduce.
- ``tp``      — param logical axes + activation constraints; vocab-parallel
                LM head under ``shard_map``.
- ``cp``      — sequence dim sharded; ring attention (``parallel.ring_attention``).
- ``zero``    — optimizer moments sharded over dp
                (``parallel.zero.opt_state_partition_specs``).
- ``fsdp``    — params themselves sharded over dp via the "embed" axis rule.
- ``remat``/``offload`` — ``jax.checkpoint`` policy applied per block.
- ``num_microbatches`` — grad-accumulation ``lax.scan`` (pp=1) or the
                pipeline schedule (pp>1).

Control-plane latency: a :class:`StepCache` memoizes the compiled
artifacts of :func:`compile_strategy` — (TrainPlan, jitted step, eval) per
(model, optimizer, Strategy, attn/donate/policy) — so hot switching
A→B→A never re-traces on the return leg (the reference's ExecGraphPlan
pool), and :mod:`hetu_tpu.engine.precompile` can AOT-compile candidate
strategies into the same entries on a background thread.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_tpu.engine.state import TrainState, new_train_state
from hetu_tpu.nn.module import Module
from hetu_tpu.optim.base import Transform, apply_updates
from hetu_tpu.optim.clipping import global_norm
from hetu_tpu.parallel.sharding import (
    ActivationSharding, named_shardings, param_partition_specs,
)
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.parallel.zero import opt_state_partition_specs


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """Compiled sharding plan for one Strategy (the analogue of the
    reference's ``ExecGraphPlan``, ``define_and_run_graph.h:23-64``)."""

    strategy: Strategy
    mesh: Mesh
    param_specs: Any
    state_specs: TrainState          # pytree of PartitionSpec
    state_shardings: TrainState      # pytree of NamedSharding
    act: ActivationSharding

    def batch_sharding(self, ndim: int = 2) -> NamedSharding:
        return NamedSharding(self.mesh, self.strategy.data_spec(ndim))

    def shard_batch(self, batch: dict) -> dict:
        """Place a host batch onto the mesh per the data spec.

        Under zigzag CP the sequence dim (axis 1) of every batch array is
        permuted into the load-balanced layout first (tokens, labels,
        positions and segment ids all move together, so the per-token loss
        is unchanged); ``positions`` is synthesized when absent so rotary
        still sees *original* positions.
        """
        st = self.strategy
        if st.effective_cp_layout == "zigzag":
            from hetu_tpu.data.packing import zigzag_permute
            batch = dict(batch)
            if batch.get("positions") is None and "input_ids" in batch:
                b, s = batch["input_ids"].shape[:2]
                batch["positions"] = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
            # only the known seq-dim arrays move; custom keys (per-row
            # weights etc.) keep their layout
            seq_keys = ("input_ids", "labels", "positions", "segment_ids")
            batch = {
                k: zigzag_permute(v, st.cp, axis=1)
                if k in seq_keys and v is not None else v
                for k, v in batch.items()
            }
        return {
            k: jax.device_put(v, self.batch_sharding(jnp.ndim(v)))
            for k, v in batch.items() if v is not None
        }


def make_plan(model: Module, opt: Transform, strategy: Strategy,
              devices=None) -> TrainPlan:
    from hetu_tpu import telemetry
    with telemetry.span("make_plan", strategy=strategy.to_json()):
        return _make_plan(model, opt, strategy, devices)


def _make_plan(model: Module, opt: Transform, strategy: Strategy,
               devices=None) -> TrainPlan:
    mesh = strategy.build_mesh(devices)
    rules = strategy.axis_rules()
    param_specs = param_partition_specs(model, rules, mesh=mesh)
    fsdp_gather_specs = None
    if strategy.fsdp:
        # ZeRO-3 completeness pass: the rule table's "embed"→dp covers the
        # transformer families' big params, but ANY param another model
        # family declares must shard too — add dp onto the first unsharded
        # divisible dim of every leaf the rules left fully replicated
        # (r3 VERDICT weak-7: rule table was model-family-coupled).
        from hetu_tpu.nn.module import ParamSpec
        from hetu_tpu.parallel.zero import add_axis_to_spec
        shapes = jax.tree.map(lambda ps: ps.shape, model.abstract_specs(),
                              is_leaf=lambda x: isinstance(x, ParamSpec))
        # per-layer gather ring (fsdp_overlap="ring"): every block leaf's
        # dp shard must live on an INNER dim — a shard on the stacked
        # ``layers`` dim cannot be regathered one layer at a time — so
        # the completeness pass skips dim 0 for the block subtree. Models
        # without a stacked block list keep the GSPMD formulation.
        ring_blocks = (strategy.fsdp_overlap == "ring"
                       and isinstance(param_specs, dict)
                       and "blocks" in param_specs)

        def _complete(spec_tree, shape_tree, skip0: bool):
            return jax.tree.map(
                lambda spec, shape: add_axis_to_spec(
                    spec, shape, mesh, "dp",
                    skip_dims=(0,) if skip0 else ()),
                spec_tree, shape_tree,
                is_leaf=lambda x: isinstance(x, P))

        if ring_blocks:
            param_specs = {
                k: _complete(v, shapes[k], k == "blocks")
                for k, v in param_specs.items()}
            if mesh.shape.get("dp", 1) > 1:
                from hetu_tpu.parallel.overlap import per_layer_gather_specs
                fsdp_gather_specs = per_layer_gather_specs(
                    param_specs["blocks"])
        else:
            param_specs = _complete(param_specs, shapes, False)
    params_struct = model.abstract_params()
    opt_struct = jax.eval_shape(opt.init, params_struct)
    opt_specs = opt_state_partition_specs(
        opt_struct, params_struct, param_specs, mesh=mesh,
        zero_axis="dp" if strategy.zero else None)
    state_specs = TrainState(P(), param_specs, opt_specs)
    act = ActivationSharding(
        mesh,
        batch=("dp", "ep") if strategy.ep > 1 else "dp",
        seq="cp", tp="tp", cp_layout=strategy.effective_cp_layout,
        cp_impl=strategy.cp_impl, sp=strategy.sp,
        tp_overlap=strategy.tp_overlap,
        fsdp_overlap=strategy.fsdp_overlap if strategy.fsdp else "off",
        fsdp_specs=fsdp_gather_specs,
        ep_overlap=strategy.ep_overlap if strategy.ep > 1 else "off",
        ep_chunks=strategy.ep_chunks)
    return TrainPlan(strategy, mesh, param_specs, state_specs,
                     named_shardings(mesh, state_specs), act)


def init_state(model: Module, opt: Transform, plan: TrainPlan,
               key: jax.Array, dtype=None) -> TrainState:
    """Initialize the train state directly in its sharded layout."""
    fn = jax.jit(lambda k: new_train_state(model.init(k, dtype=dtype), opt),
                 out_shardings=plan.state_shardings)
    return fn(key)


# -- trace accounting -------------------------------------------------------
# jit re-traces run the Python step body; executions do not. A counter
# bumped INSIDE the body is therefore an exact re-trace/recompile count —
# the signal the compile-count regression tests assert on (and the
# telemetry registry mirrors it when enabled).
_TRACE_COUNTS: dict[str, int] = {}
_TRACE_LOCK = threading.Lock()
_TRACE_LOCAL = threading.local()   # per-thread total, see trace_total()


def record_trace(what: str) -> None:
    """Count one (re)trace of a jitted step body. Called at trace time
    only — a warm executable never re-enters the Python body."""
    with _TRACE_LOCK:
        _TRACE_COUNTS[what] = _TRACE_COUNTS.get(what, 0) + 1
    _TRACE_LOCAL.total = getattr(_TRACE_LOCAL, "total", 0) + 1
    from hetu_tpu import telemetry
    if telemetry.enabled():
        telemetry.get_registry().counter(
            "step_traces_total",
            "jit traces of step bodies (recompile detector)").inc(
                what=what)


def trace_counts() -> dict[str, int]:
    """``{step-kind: trace count}`` since process start (or last reset),
    across ALL threads (background AOT lowers included)."""
    with _TRACE_LOCK:
        return dict(_TRACE_COUNTS)


def trace_total() -> int:
    """Step-body traces recorded ON THE CALLING THREAD. The Trainer
    snapshots this around each step call to attribute a traced step's
    wall time to the ``compile`` goodput category instead of
    ``compute`` — per-thread so a background precompile worker tracing
    concurrently never misclassifies foreground compute as compile."""
    return getattr(_TRACE_LOCAL, "total", 0)


def reset_trace_counts() -> None:
    with _TRACE_LOCK:
        _TRACE_COUNTS.clear()


# -- step cache -------------------------------------------------------------
def _batch_key(batch: dict) -> tuple:
    """Shape/dtype signature of a batch dict (device arrays, host numpy
    or ShapeDtypeStructs — anything with .shape/.dtype)."""
    def sig(v):
        if not hasattr(v, "shape") or not hasattr(v, "dtype"):
            import numpy as np
            v = np.asarray(v)
        return tuple(v.shape), str(v.dtype)

    return tuple(sorted((k,) + sig(v) for k, v in batch.items()
                        if v is not None))


class CachedStep:
    """One compiled strategy: plan + jitted step/eval + AOT executables.

    Calling the entry runs the step. When an ahead-of-time executable for
    the batch signature exists (``engine.precompile``), it is used — zero
    traces even on the very first step after a switch; otherwise the
    jitted ``step_fn`` runs (which re-uses ITS executable cache across
    A→B→A switches because the entry object itself is memoized).
    """

    __slots__ = ("plan", "step_fn", "eval_fn", "aot", "_aot_ok",
                 "compile_seconds", "_refs")

    def __init__(self, plan, step_fn, eval_fn=None, *,
                 compile_seconds: float = 0.0, refs: tuple = ()):
        self.plan = plan
        self.step_fn = step_fn
        self.eval_fn = eval_fn
        self.aot: dict = {}          # batch signature -> Compiled
        self._aot_ok: set = set()    # signatures proven callable
        self.compile_seconds = compile_seconds
        # strong refs (model, opt): entries are keyed by object identity,
        # pinning the objects guarantees an id() is never reused while
        # its cache entry is alive
        self._refs = refs

    def __call__(self, state, batch):
        if self.aot:
            key = _batch_key(batch)
            exe = self.aot.get(key)
            if exe is not None:
                # the AOT executable bypasses step_fn, and with it the
                # host-side data/memory-plane accounting — invoke the
                # hook build_train_step attached (None for pipeline /
                # hetero step fns, which do their own accounting)
                hook = getattr(self.step_fn, "on_execute", None)
                if key in self._aot_ok:
                    if hook is not None:
                        hook(batch)
                    return exe(state, batch)
                try:
                    out = exe(state, batch)
                except (TypeError, ValueError):
                    # aval drift raises TypeError, sharding drift raises
                    # ValueError — both BEFORE consuming donated buffers
                    # — drop the stale executable and fall back to jit
                    self.aot.pop(key, None)
                else:
                    self._aot_ok.add(key)
                    if hook is not None:
                        hook(batch)
                    return out
        return self.step_fn(state, batch)


class StepCache:
    """Memo of :class:`CachedStep` entries keyed by
    (model, optimizer, Strategy, attn_impl, donate, policy, devices).

    The analogue of the reference's ``ExecGraphPlan`` pool
    (``define_and_run_graph.h:23-64``) lifted to a process-wide resource:
    every Trainer (and the AOT pre-compiler) shares the default instance,
    so a strategy compiled once — eagerly, in the background, or by a
    previous run via the persistent XLA cache — is a lookup forever
    after. Bounded LRU so long sweeps cannot pin unbounded executables.
    Thread-safe with single-flight builds (a background precompile and a
    foreground switch racing to the same key compile once).
    """

    def __init__(self, capacity: int = 64):
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._entries: dict = {}          # insertion-ordered => LRU
        self._building: dict = {}         # key -> threading.Event
        self._gen = 0                     # bumped by clear(): in-flight
                                          # builds from before a clear
                                          # must not re-populate it
        self._lock = threading.RLock()

    @staticmethod
    def key_for(model, opt, strategy, *, attn_impl: str = "auto",
                donate: bool = True, policy_key: str = "",
                devices=None, bucket: int = 0) -> tuple:
        """``bucket``: the seq-len bucket this entry serves (0 = the
        unbucketed entry). Bucketed training (``TrainerConfig(
        seq_buckets=...)``) keeps one CachedStep per (strategy, bucket)
        so each entry's jit/AOT caches hold exactly one shape and the
        AOT pre-compiler (``engine.precompile``) can enumerate bucketed
        variants addressably — every key-bearing field here must
        round-trip through its candidate enumeration (quick-tier lint
        in tests/test_shape_plane.py)."""
        dev_key = None if devices is None else \
            tuple(getattr(d, "id", d) for d in devices)
        return (id(model), id(opt), strategy, attn_impl, donate,
                policy_key, dev_key, int(bucket))

    def _count(self, hit: bool) -> None:
        from hetu_tpu import telemetry
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        if telemetry.enabled():
            telemetry.get_registry().counter(
                "step_cache_hits_total" if hit
                else "step_cache_misses_total",
                "StepCache lookups that found / missed a compiled "
                "entry").inc()

    def lookup(self, key) -> Optional[CachedStep]:
        """Peek without building (does not count a miss)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:                    # refresh LRU order
                self._entries.pop(key)
                self._entries[key] = entry
            return entry

    def get_or_build(self, key, builder: Callable[[], CachedStep]
                     ) -> CachedStep:
        """Return the cached entry for ``key``, building it (once, even
        under concurrent callers) via ``builder`` on a miss."""
        while True:
            with self._lock:
                entry = self._entries.get(key)
                if entry is not None:
                    self._entries.pop(key)
                    self._entries[key] = entry
                    self._count(hit=True)
                    return entry
                ev = self._building.get(key)
                if ev is None:
                    ev = self._building[key] = threading.Event()
                    gen = self._gen
                    break
            ev.wait()        # another thread is compiling this key
        try:
            entry = builder()
        except BaseException:
            with self._lock:
                self._building.pop(key, None)
            ev.set()
            raise
        with self._lock:
            if self._gen == gen:
                # a clear() during the build (device loss) invalidates
                # what we just compiled — hand it to the caller but do
                # NOT resurrect it in the pool
                self._entries[key] = entry
                while len(self._entries) > self.capacity:
                    self._entries.pop(next(iter(self._entries)))
                    self.evictions += 1
            self._count(hit=False)
            self._building.pop(key, None)
        ev.set()
        return entry

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {"entries": len(self._entries), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "hit_rate": self.hits / total if total else 0.0}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._gen += 1   # in-flight builds must not re-insert

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries


_STEP_CACHE = StepCache()


def get_step_cache() -> StepCache:
    """The process-default :class:`StepCache` (shared by Trainers and
    ``engine.precompile`` unless one is injected explicitly)."""
    return _STEP_CACHE


def compile_strategy(model: Module, opt: Transform, strategy: Strategy, *,
                     devices=None, attn_impl: str = "auto",
                     donate: bool = True, loss_fn: Optional[Callable] = None,
                     build_eval: bool = True) -> CachedStep:
    """Plan + build the jitted step (and eval) for one Strategy, returning
    a :class:`CachedStep`. Callers wanting memoization go through
    :meth:`StepCache.get_or_build`; callers wanting dtype policy wrap this
    in ``autocast(policy)`` (tracing happens lazily at first call / AOT
    lower, but ``make_plan``'s init shapes are taken here)."""
    from hetu_tpu import telemetry
    t0 = time.perf_counter()
    with telemetry.span("build_plan_and_step",
                        strategy=strategy.to_json()):
        plan = make_plan(model, opt, strategy, devices)
        step_fn = build_train_step(model, opt, plan, loss_fn=loss_fn,
                                   attn_impl=attn_impl, donate=donate)
        eval_fn = None
        if build_eval:
            eval_fn = build_eval_step(model, plan, loss_fn=loss_fn,
                                      attn_impl=attn_impl)
    return CachedStep(plan, step_fn, eval_fn,
                      compile_seconds=time.perf_counter() - t0,
                      refs=(model, opt))


def abstract_train_state(model: Module, opt: Transform, plan: TrainPlan,
                         dtype=None) -> TrainState:
    """ShapeDtypeStruct pytree of the sharded train state — the abstract
    argument AOT lowering needs (``engine.precompile``). Run under the
    same ``autocast`` policy as the real ``init_state`` so dtypes match."""
    shapes = jax.eval_shape(
        lambda k: new_train_state(model.init(k, dtype=dtype), opt),
        jax.random.key(0))
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, plan.state_shardings)


def abstract_batch(plan: TrainPlan, batch_shape: tuple, *,
                   keys=("input_ids", "labels"), dtype=jnp.int32) -> dict:
    """ShapeDtypeStruct batch dict for AOT lowering: ``batch_shape`` is
    the global (batch, seq) the training loop will feed (post
    ``shard_batch`` — zigzag permutes never change shapes)."""
    sharding = plan.batch_sharding(len(batch_shape))
    return {k: jax.ShapeDtypeStruct(tuple(batch_shape), dtype,
                                    sharding=sharding) for k in keys}


def effective_remat(strategy: Strategy) -> str:
    if strategy.offload:
        return "offload"
    return strategy.remat


def step_dropout_key(step) -> jax.Array:
    """Per-step dropout base key. One definition shared by every train
    path (plain/pipeline/hetero-dp) — the resume-reproducibility guarantee
    (same step => same masks) depends on them deriving keys identically."""
    return jax.random.fold_in(jax.random.key(0x0d0), step)


def model_dropout_active(model: Module) -> bool:
    """True iff the model's config enables any dropout rate."""
    cfg = getattr(model, "cfg", None)
    return any(getattr(cfg, f, 0.0) > 0.0 for f in
               ("embd_pdrop", "resid_pdrop", "attn_pdrop", "hidden_pdrop"))


def default_loss_fn(model: Module, strategy: Strategy,
                    attn_impl: str = "auto") -> Callable:
    """loss(params, batch[, dropout_key]) for LM models exposing ``.loss``.

    ``dropout_key`` is threaded by the train step (derived from
    ``state.step``, so a resumed run reproduces the same mask sequence);
    eval paths omit it and dropout is off.
    """
    remat = effective_remat(strategy)

    def loss_fn(params, batch, dropout_key=None):
        return model.loss(params, batch["input_ids"], batch["labels"],
                          positions=batch.get("positions"),
                          segment_ids=batch.get("segment_ids"),
                          attn_impl=attn_impl, remat=remat,
                          remat_mask=strategy.remat_mask,
                          unroll=strategy.unroll,
                          dropout_key=dropout_key)

    return loss_fn


def _spec_has_axis(spec: P, axis: str) -> bool:
    return any(p == axis or (isinstance(p, (tuple, list)) and axis in p)
               for p in spec)


def _manual_projection(spec: P, manual: tuple) -> P:
    """Project a param PartitionSpec onto ``manual`` axes: entries keep
    only the components bound by the partial-manual region (the rest —
    tp, cp — ride GSPMD-auto, which in_specs must not name)."""
    parts = []
    for p in spec:
        if isinstance(p, (tuple, list)):
            kept = tuple(a for a in p if a in manual)
            parts.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            parts.append(p if p in manual else None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def _delayed_acc_layout(plan: "TrainPlan", ndp: int, nep: int):
    """Lane layout of the delayed-sync grad accumulator, shared by the
    in-scan (``build_train_step``) and split-phase
    (``build_grad_accum_steps``) paths: dense leaves carry a
    ``("dp","ep")``-sharded lane dim of ``ndp·nep`` local grads, expert
    leaves (an "ep" component in their spec) a dp-sharded one of
    ``ndp`` — their ep sum already happened through the backward
    all_to_all. Returns ``(acc_specs, acc_shardings, acc_leads)``."""
    def spec(s):
        if nep > 1 and not _spec_has_axis(s, "ep"):
            return P(("dp", "ep"), *tuple(s))
        return P("dp", *tuple(s))

    leaf = lambda x: isinstance(x, P)
    acc_specs = jax.tree.map(spec, plan.state_specs.params, is_leaf=leaf)
    acc_leads = jax.tree.map(
        lambda s: ndp if (nep > 1 and _spec_has_axis(s, "ep"))
        else ndp * nep,
        plan.state_specs.params, is_leaf=leaf)
    return acc_specs, named_shardings(plan.mesh, acc_specs), acc_leads


def build_local_grad_fn(base_loss, mesh: Mesh, ndp: int, *,
                        nep: int = 1, param_specs=None,
                        ep_overlap: str = "off",
                        ep_chunks: int = 2) -> Callable:
    """Per-group ``(loss, grads)`` with a leading group dim and ZERO
    cross-group gradient traffic: a partial-manual ``shard_map`` over
    the group axes — each group differentiates its local batch shard;
    tp/cp collectives stay GSPMD-auto exactly as in the pipeline
    executor's manual region. Shared by the split-phase path
    (``build_grad_accum_steps(delay_grad_sync=True)``) and the in-scan
    path (``Strategy(delay_grad_sync=True)`` with
    ``num_microbatches > 1``). Returns ``local_grads(params, batch,
    key)``; the key-vs-keyless shard_map variant is picked at trace
    time from ``key is None``.

    With ``nep > 1`` the group is **dp×ep** (the batch dim is sharded
    over both): "ep" joins the manual set so the MoE layers run the real
    all_to_all dispatch on the bound axis (``nn.moe`` consults
    ``current_manual_axes``), and the param handling splits by spec —

    - **dense leaves** enter replicated over the whole group (``P()``
      projection) and come back with a ``("dp","ep")``-sharded leading
      lane dim: every group holds its own local grad;
    - **expert leaves** (an "ep" component in ``param_specs``) enter
      ep-SHARDED on their expert dim — each rank differentiates only
      its local experts, and the backward ``all_to_all`` already sums
      their grads over ep — so their leading lane dim is sharded over
      dp only.

    Either way ONE post-scan sum over the leading dim divided by
    ``ndp·nep`` (per microbatch) reproduces the eager gradient."""
    from hetu_tpu.parallel.sharding import ManualAxes, no_act_sharding
    group = ("dp", "ep") if nep > 1 else ("dp",)
    manual = frozenset(group)
    ngroups = ndp * nep
    if nep > 1 and param_specs is None:
        raise ValueError(
            "param_specs is required for ep-aware delayed grad sync "
            "(the dense/expert spec split drives the lane layout)")

    def param_in_spec(spec: P) -> P:
        # expert leaves keep their ep shard inside the region; dense
        # leaves replicate over the group
        if nep > 1 and _spec_has_axis(spec, "ep"):
            return _manual_projection(spec, ("ep",))
        return P()

    def grad_out_spec(spec: P) -> P:
        if nep > 1 and _spec_has_axis(spec, "ep"):
            return P("dp", *tuple(_manual_projection(spec, ("ep",))))
        return P(group if nep > 1 else "dp")

    def local_grads(params, batch, key):
        def body(params, batch_l, gid, *key_arg):
            def lloss(p):
                k = None
                if key_arg:
                    # decorrelate groups via the explicit group-id
                    # operand (axis_index would lower to PartitionId,
                    # which SPMD partitioning of the auto axes rejects)
                    k = jax.random.fold_in(key_arg[0], gid[0])
                with no_act_sharding(), \
                        ManualAxes(mesh, manual, ep_overlap=ep_overlap,
                                   ep_chunks=ep_chunks), \
                        jax.named_scope("hetu.loss"):
                    if k is not None:
                        return base_loss(p, batch_l, dropout_key=k)
                    return base_loss(p, batch_l)

            loss, g = jax.value_and_grad(lloss)(params)
            return loss.reshape(1), jax.tree.map(lambda v: v[None], g)

        in_b = {k: P(group if nep > 1 else "dp") for k in batch}
        if param_specs is not None:
            in_p = jax.tree.map(param_in_spec, param_specs,
                                is_leaf=lambda x: isinstance(x, P))
            out_g = jax.tree.map(grad_out_spec, param_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        else:
            in_p = jax.tree.map(lambda _: P(), params)
            out_g = jax.tree.map(lambda _: P("dp"), params)
        gids = jnp.arange(ngroups, dtype=jnp.int32)
        lane = P(group if nep > 1 else "dp")
        if key is None:
            f = shard_map(lambda p, b, g: body(p, b, g), mesh=mesh,
                          in_specs=(in_p, in_b, lane),
                          out_specs=(lane, out_g),
                          axis_names=manual, check_vma=False)
            losses, grads = f(params, batch, gids)
        else:
            f = shard_map(body, mesh=mesh,
                          in_specs=(in_p, in_b, lane, P()),
                          out_specs=(lane, out_g),
                          axis_names=manual, check_vma=False)
            losses, grads = f(params, batch, gids, key)
        # scalarizing the per-group loss vector moves 4·ngroups bytes —
        # a metric read, not a gradient sync
        return jnp.mean(losses), grads

    return local_grads


def _fsdp_gspmd_gather_bytes(model: Module, param_specs, ndp: int, *,
                             skip_blocks: bool) -> int:
    """Analytic payload of the monolithic GSPMD param all-gather: every
    dp-sharded leaf's (ndp-1)/ndp remote share. With the per-layer ring
    active (``skip_blocks``) the block subtree gathers on the ring and
    only the remaining leaves (embeddings, LM head, final norm) stay on
    the serialized GSPMD path — they must still be accounted, or the
    overlap ratio overstates the ring's coverage."""
    from hetu_tpu.parallel.overlap import _dp_dim
    abstract = model.abstract_params()
    if skip_blocks and isinstance(param_specs, dict):
        param_specs = {k: v for k, v in param_specs.items()
                       if k != "blocks"}
        abstract = {k: v for k, v in abstract.items() if k != "blocks"}
    spec_leaves = jax.tree.leaves(param_specs,
                                  is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(abstract)
    if len(spec_leaves) != len(leaves):
        return 0
    total = 0
    for leaf, spec in zip(leaves, spec_leaves):
        if _dp_dim(spec) is None:
            continue
        size = functools.reduce(lambda a, b: a * int(b), leaf.shape, 1)
        total += size * leaf.dtype.itemsize * (ndp - 1) // ndp
    return total


def build_train_step(model: Module, opt: Transform, plan: TrainPlan, *,
                     loss_fn: Optional[Callable] = None,
                     attn_impl: str = "auto",
                     donate: bool = True) -> Callable:
    """Return jitted ``step(state, batch) -> (state, metrics)``.

    pp>1 routes through the pipeline executor
    (``hetu_tpu.parallel.pipeline.build_pipeline_train_step``).

    ``Strategy(delay_grad_sync=True)`` with ``num_microbatches > 1``
    moves the DP gradient reduction OUT of the accumulation ``lax.scan``:
    microbatch grads stay dp-group-local (leading dp-sharded accumulator
    dim, grads computed in a partial-manual ``shard_map`` over dp) and
    ONE reduction fires per optimizer update instead of one per
    microbatch — the in-jit twin of
    ``build_grad_accum_steps(delay_grad_sync=True)``, counter-audited by
    ``dp_grad_syncs_total`` / ``optimizer_updates_total``.
    """
    from hetu_tpu import telemetry
    strategy = plan.strategy
    if strategy.delay_grad_sync and strategy.pp > 1:
        raise ValueError(
            "delay_grad_sync=True is unsupported with pp > 1 — the "
            "pipeline executor owns its own microbatch schedule")
    if strategy.pp > 1:
        if loss_fn is not None:
            raise ValueError(
                "custom loss_fn is not supported with pp > 1 — the pipeline "
                "executor schedules model.embed/blocks/head_loss itself; "
                "override model.head_loss instead")
        from hetu_tpu.parallel.pipeline import build_pipeline_train_step
        with telemetry.span("build_step", kind="pipeline"):
            return build_pipeline_train_step(
                model, opt, plan, attn_impl=attn_impl, donate=donate)

    base_loss = loss_fn or default_loss_fn(model, strategy, attn_impl)
    nm = strategy.num_microbatches

    # thread dropout keys only when the model config asks for dropout AND
    # the loss fn accepts them (custom loss fns keep their 2-arg form)
    import inspect
    sig = inspect.signature(base_loss)
    explicit_key = "dropout_key" in sig.parameters
    var_kw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                 for p in sig.parameters.values())
    thread_dropout = model_dropout_active(model) and \
        (explicit_key or var_kw)
    if model_dropout_active(model) and loss_fn is not None:
        import warnings
        if not thread_dropout:
            warnings.warn(
                "model config enables dropout but the custom loss_fn has "
                "no dropout_key parameter — dropout will be OFF; accept a "
                "dropout_key kwarg (and pass it to model.loss) to enable "
                "it", stacklevel=2)
        elif var_kw and not explicit_key:
            warnings.warn(
                "dropout_key will be passed to the custom loss_fn via "
                "**kwargs — make sure it forwards the key to model.loss, "
                "or dropout silently stays off", stacklevel=2)

    def compute_loss(params, batch, dropout_key=None):
        # device scope (telemetry/device_scopes.py): forward, backward
        # and recomputation of the loss are told apart by the wrappers
        # autodiff puts around this name
        with plan.act, jax.named_scope("hetu.loss"):
            if thread_dropout:
                return base_loss(params, batch, dropout_key=dropout_key)
            return base_loss(params, batch)

    grad_fn = jax.value_and_grad(compute_loss)
    ndp = plan.mesh.shape.get("dp", 1)
    nep = plan.mesh.shape.get("ep", 1)
    ngroups = ndp * nep
    if strategy.delay_grad_sync and strategy.fsdp:
        raise ValueError(
            "delay_grad_sync=True is incompatible with fsdp: params are "
            "dp-sharded, so group-local gradients would require the "
            "param all-gather the delay is meant to avoid")
    delayed = strategy.delay_grad_sync and ngroups > 1 and nm > 1
    if delayed:
        # group-local grads need the RAW loss fn (no GSPMD activation
        # constraints inside the manual region). With ep > 1 the group
        # is dp×ep: dense grads carry a ("dp","ep")-sharded lane dim,
        # expert grads a dp-sharded one (their ep sum already happened
        # through the backward all_to_all) — ONE post-scan reduction
        # per update either way.
        local_grad_fn = build_local_grad_fn(
            base_loss, plan.mesh, ndp, nep=nep,
            param_specs=plan.state_specs.params,
            ep_overlap=strategy.ep_overlap, ep_chunks=strategy.ep_chunks)
        _, acc_shardings, acc_leads = _delayed_acc_layout(plan, ndp, nep)

    from hetu_tpu.parallel import overlap as _overlap
    fsdp_gspmd_bytes = 0
    if strategy.fsdp and ndp > 1:
        # GSPMD gather accounting (serialized): ALL dp-sharded leaves on
        # the fallback path; with the per-block ring active, just the
        # non-block leaves (embeddings/head) — the ring path accounts
        # its per-block gathers itself, as overlapped
        fsdp_gspmd_bytes = _fsdp_gspmd_gather_bytes(
            model, plan.param_specs, ndp,
            skip_blocks=getattr(plan.act, "fsdp_specs", None) is not None)

    def step(state: TrainState, batch: dict):
        record_trace("train_step")   # runs at trace time only
        if fsdp_gspmd_bytes:         # trace-time, like the ring kernels
            _overlap.record_comm_bytes("fsdp_gather", fsdp_gspmd_bytes,
                                       overlapped=False)
        # deterministic per-step key: resume-at-step-N reproduces masks
        key = step_dropout_key(state.step) if thread_dropout else None
        if nm > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape((nm, x.shape[0] // nm) + x.shape[1:]),
                batch)

            if delayed:
                # leading dp-sharded dim: each dp group accumulates its
                # OWN grads — no cross-dp traffic inside the scan
                def body(acc, xs):
                    mb, i = xs
                    mb_key = None if key is None \
                        else jax.random.fold_in(key, i)
                    loss, grads = local_grad_fn(state.params, mb, mb_key)
                    acc_loss, acc_g = acc
                    return (acc_loss + loss,
                            jax.tree.map(
                                lambda a, g: a + g.astype(jnp.float32),
                                acc_g, grads)), None

                zeros = jax.lax.with_sharding_constraint(
                    jax.tree.map(
                        lambda p, lead: jnp.zeros((lead,) + p.shape,
                                                  jnp.float32),
                        state.params, acc_leads),
                    acc_shardings)
                (loss, acc_g), _ = jax.lax.scan(
                    body, (jnp.zeros([], jnp.float32), zeros),
                    (mbs, jnp.arange(nm)))
                loss = loss / nm
                # THE one gradient reduction of the whole update:
                # summing the leading (group-sharded) lane dim down to
                # the synced grad — dense lanes sum over dp×ep, expert
                # lanes over dp; under ZeRO it becomes the
                # reduce-scatter → update → all-gather triplet, once
                grads = jax.tree.map(
                    lambda g: jnp.sum(g, axis=0) / (ngroups * nm), acc_g)
            else:
                def body(acc, xs):
                    mb, i = xs
                    mb_key = None if key is None \
                        else jax.random.fold_in(key, i)
                    loss, grads = grad_fn(state.params, mb, mb_key)
                    acc_loss, acc_g = acc
                    return (acc_loss + loss,
                            jax.tree.map(
                                lambda a, g: a + g.astype(jnp.float32),
                                acc_g, grads)), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state.params)
                (loss, grads), _ = jax.lax.scan(
                    body, (jnp.zeros([], jnp.float32), zeros),
                    (mbs, jnp.arange(nm)))
                loss = loss / nm
                grads = jax.tree.map(lambda g: g / nm, grads)
        else:
            loss, grads = grad_fn(state.params, batch, key)

        with jax.named_scope("hetu.opt"):
            gnorm = global_norm(grads)
            updates, new_opt = opt.update(grads, state.opt_state,
                                          state.params)
            new_params = apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return TrainState(state.step + 1, new_params, new_opt), metrics

    jitted = jax.jit(
        step,
        out_shardings=(plan.state_shardings, None),
        donate_argnums=(0,) if donate else ())

    # host-side data-plane accounting (exact per call, mirroring
    # build_grad_accum_steps): the jitted path issues one DP grad
    # reduction per microbatch when eager, exactly one per update when
    # delayed (or when nm == 1 — nothing to delay). First call also
    # seeds the memory-plane ledger from the model config + batch shape.
    syncs_per_call = 0 if ngroups <= 1 \
        else (1 if (nm == 1 or delayed) else nm)
    grad_bytes = 4 * int(sum(
        functools.reduce(lambda a, b: a * b, l.shape, 1)
        for l in jax.tree.leaves(model.abstract_params())))
    seeded = []

    def _host_account(batch):
        if not seeded:
            seeded.append(True)
            try:
                from hetu_tpu.engine.memory import record_model_memory_plane
                record_model_memory_plane(model, strategy, batch)
            except Exception:   # ledger is observability, never fatal
                pass
        if syncs_per_call:
            _overlap.record_dp_sync(syncs_per_call, grad_bytes=grad_bytes)
        _overlap.record_optimizer_update(1)

    def step_call(state, batch):
        _host_account(batch)
        return jitted(state, batch)

    # AOT lowering (engine.precompile) goes through .lower on the entry;
    # AOT EXECUTION bypasses step_call (CachedStep dispatches the
    # executable directly), so the accounting hook rides along for
    # CachedStep.__call__ to invoke on that path
    step_call.lower = jitted.lower
    step_call.on_execute = _host_account
    return step_call


def build_eval_step(model: Module, plan: TrainPlan, *,
                    loss_fn: Optional[Callable] = None,
                    attn_impl: str = "auto") -> Callable:
    base_loss = loss_fn or default_loss_fn(model, plan.strategy, attn_impl)

    def step(params, batch):
        with plan.act:
            return base_loss(params, batch)

    return jax.jit(step)


def build_grad_accum_steps(model: Module, opt: Transform, plan: TrainPlan,
                           *, loss_fn: Optional[Callable] = None,
                           attn_impl: str = "auto",
                           donate_acc: bool = True,
                           delay_grad_sync: bool = False):
    """Split-phase training — the reference's partial-execution RunLevels
    (``graph.h:33-39``): RunLevel::GRAD accumulates gradients across
    *separate step calls* (arbitrary-size global batches without holding
    every microbatch in one feed), RunLevel::UPDATE applies them.

    Returns ``(init_acc, grad_step, apply_step)``:

    - ``acc = init_acc()`` — zeroed fp32 grad buffer (param-sharded)
    - ``acc, loss = grad_step(state, acc, batch, accum_index=i)`` — one
      forward/backward, grads added into ``acc`` (donated). Pass the
      per-update accumulation counter ``i`` when dropout is active —
      dropout keys fold (step, i) so every grad call draws independent
      masks (``i`` is a traced operand: no recompile per index)
    - ``state, metrics = apply_step(state, acc, n_accum)`` — mean over
      ``n_accum`` accumulations, optimizer update; ``acc`` is consumed

    Accumulator buffer lifecycle (``donate_acc``): with the default
    ``True``, ``apply_step`` donates ``acc`` so XLA reuses its fp32
    param-shaped buffers for the update's outputs — optimal *peak*
    memory, but the next update must allocate a fresh buffer via
    ``init_acc()``. With ``donate_acc=False``, ``apply_step`` only reads
    ``acc`` and the caller recycles the same buffer across updates with
    ``acc = init_acc(like=acc)`` — the ``like`` argument is donated to a
    zero-fill, so steady-state training performs **no** accumulator
    allocation at all (HBM allocator churn is the enemy on long runs).
    ``init_acc(like=...)`` after a donating ``apply_step`` raises jax's
    deleted-buffer error — the two modes are mutually exclusive by
    construction.

    Delayed gradient synchronization (``delay_grad_sync=True``, ZeRO
    SC'20 §5 / DDP ``no_sync``): per-microbatch gradients stay **local
    to each dp group** — the accumulator gains a leading ``dp`` dim
    sharded over dp and ``grad_step`` computes group-local grads inside
    a partial-manual ``shard_map`` over dp (tp/cp stay GSPMD-auto), so
    NO cross-dp gradient traffic moves until ``apply_step`` reduces the
    leading dim once per optimizer update — an O(accum_steps) reduction
    in DP bytes. With ZeRO on, that single reduction feeds the sharded
    optimizer directly (reduce-scatter → update → all-gather, once).
    The per-call ``dp_grad_syncs_total`` / ``optimizer_updates_total``
    counters (``parallel.overlap``) make the rate auditable:
    eager = ``accum_steps`` syncs/update, delayed = exactly 1.
    With ``ep > 1`` the group is dp×ep: "ep" joins the manual region so
    MoE layers run the real all_to_all dispatch, dense grads carry a
    ``("dp","ep")``-sharded lane dim, and expert grads (ep-sharded
    specs) a dp-sharded one — their ep sum already happened through the
    backward all_to_all. Unsupported with ``fsdp`` (params are
    dp-sharded — group-local grads of a sharded param would need the
    very gather being delayed); raises.
    """
    strategy = plan.strategy
    if strategy.pp > 1:
        raise NotImplementedError(
            "split-phase accumulation with pp > 1: use "
            "num_microbatches inside the pipeline step instead")
    if delay_grad_sync and strategy.fsdp:
        raise ValueError(
            "delay_grad_sync=True is incompatible with fsdp: params are "
            "dp-sharded, so group-local gradients would require the "
            "param all-gather the delay is meant to avoid")
    base_loss = loss_fn or default_loss_fn(model, strategy, attn_impl)

    def compute_loss(params, batch, key):
        with plan.act, jax.named_scope("hetu.loss"):
            if key is not None:
                return base_loss(params, batch, dropout_key=key)
            return base_loss(params, batch)

    grad_fn = jax.value_and_grad(compute_loss)
    param_shardings = plan.state_shardings.params
    ndp = plan.mesh.shape.get("dp", 1)
    nep = plan.mesh.shape.get("ep", 1)
    ngroups = ndp * nep
    delayed = delay_grad_sync and ngroups > 1  # one group: nothing to delay
    # same dropout contract as build_train_step: thread keys when the
    # model wants dropout AND the loss fn can take them; warn otherwise
    import inspect
    sig = inspect.signature(base_loss)
    accepts_key = "dropout_key" in sig.parameters or any(
        p.kind is inspect.Parameter.VAR_KEYWORD
        for p in sig.parameters.values())
    thread_dropout = model_dropout_active(model) and accepts_key
    if model_dropout_active(model) and loss_fn is not None \
            and not accepts_key:
        import warnings
        warnings.warn(
            "model config enables dropout but the custom loss_fn has no "
            "dropout_key parameter — dropout will be OFF in "
            "build_grad_accum_steps; accept a dropout_key kwarg to "
            "enable it", stacklevel=2)

    if delayed:
        # the accumulator gains a leading lane dim (one local grad
        # shard per group) — group-sharded specs keep each group's
        # shard on its own devices, so accumulation is comm-free
        _, acc_shardings, acc_leads = _delayed_acc_layout(plan, ndp, nep)
    else:
        acc_shardings = param_shardings
        acc_leads = jax.tree.map(lambda s: 0, plan.state_specs.params,
                                 is_leaf=lambda x: isinstance(x, P))

    @functools.partial(jax.jit, out_shardings=acc_shardings)
    def _fresh_acc():
        return jax.tree.map(
            lambda s, lead: jnp.zeros(
                ((lead,) if lead else ()) + tuple(s.shape), jnp.float32),
            model.abstract_params(), acc_leads)

    # zero-fill INTO the donated previous accumulator: XLA rewrites this
    # to an in-place memset of the existing buffer — no allocation
    @functools.partial(jax.jit, donate_argnums=(0,),
                       out_shardings=acc_shardings)
    def _rezero_acc(like):
        return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                            like)

    def init_acc(like=None):
        """Zeroed fp32 grad accumulator. Pass the previous update's
        ``acc`` as ``like`` (requires ``donate_acc=False``) to recycle
        its buffer instead of allocating a fresh one."""
        if like is None:
            return _fresh_acc()
        return _rezero_acc(like)

    @functools.partial(jax.jit, donate_argnums=(1,),
                       out_shardings=(acc_shardings, None))
    def grad_step(state: TrainState, acc, batch, accum_index=0):
        record_trace("grad_step")
        # accum_index is traced (fold_in takes traced ints): one compile
        # serves every index
        key = jax.random.fold_in(step_dropout_key(state.step),
                                 accum_index) if thread_dropout else None
        if not delayed:
            loss, grads = grad_fn(state.params, batch, key)
            return jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                acc, grads), loss
        loss, grads = _local_grads(state.params, batch, key)
        return jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                            acc, grads), loss

    # shared with the in-scan path (Strategy(delay_grad_sync=True)):
    # partial-manual shard_map over the group axes, group-local grads,
    # leading lane dim
    _local_grads = build_local_grad_fn(
        base_loss, plan.mesh, ndp, nep=nep,
        param_specs=plan.state_specs.params,
        ep_overlap=strategy.ep_overlap, ep_chunks=strategy.ep_chunks) \
        if delayed else None

    # delayed acc buffers ((ndp, ...) leaves) can never alias the
    # update's outputs — donating them only buys a warning per compile
    @functools.partial(jax.jit,
                       donate_argnums=(0, 1) if donate_acc and not delayed
                       else (0,),
                       out_shardings=(plan.state_shardings, None))
    def apply_step(state: TrainState, acc, n_accum):
        if delayed:
            # THE one gradient reduction of the whole update: the
            # leading (group-sharded) lane dim sums down to the synced
            # grad (dense lanes over dp×ep, expert lanes over dp) —
            # under ZeRO the sharded moment specs turn it into the
            # reduce-scatter → update → all-gather triplet, once
            grads = jax.tree.map(
                lambda g: jnp.sum(g, axis=0) / (ngroups * n_accum), acc)
        else:
            grads = jax.tree.map(lambda g: g / n_accum, acc)
        with jax.named_scope("hetu.opt"):
            gnorm = global_norm(grads)
            updates, new_opt = opt.update(grads, state.opt_state,
                                          state.params)
            new_params = apply_updates(state.params, updates)
        return (TrainState(state.step + 1, new_params, new_opt),
                {"grad_norm": gnorm})

    # host-side data-plane accounting (exact per call): eager issues one
    # DP grad reduction per MICROBATCH, delayed exactly one per UPDATE
    from hetu_tpu.parallel import overlap as _overlap
    grad_bytes = 4 * int(sum(
        int(functools.reduce(lambda a, b: a * b, l.shape, 1))
        for l in jax.tree.leaves(model.abstract_params())))

    def grad_step_fn(state, acc, batch, accum_index=0):
        if ngroups > 1 and not delayed:
            _overlap.record_dp_sync(1, grad_bytes=grad_bytes)
        return grad_step(state, acc, batch, accum_index)

    def apply_step_fn(state, acc, n_accum):
        _overlap.record_optimizer_update(1)
        if delayed:
            _overlap.record_dp_sync(1, grad_bytes=grad_bytes)
        return apply_step(state, acc, n_accum)

    return init_acc, grad_step_fn, apply_step_fn
