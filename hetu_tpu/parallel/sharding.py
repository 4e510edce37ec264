"""Logical-axis → PartitionSpec compiler and sharding helpers.

This is the TPU-native replacement for the reference's per-tensor
``DistributedStates`` algebra (``hetu/graph/distributed_states.h:13``:
``{dim→splits}``, ``-1`` duplicate, ``-2`` partial) and the ds-deduction pass
(``DoDeduceStates``). Parameters declare *logical* axis names once (in their
``ParamSpec``); an :class:`AxisRules` table maps those names to mesh axes per
strategy. Partial-reduction states (ds ``-2``) have no explicit spec — they
exist transiently inside ``shard_map`` blocks as pre-psum values.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hetu_tpu.nn.module import Module, ParamSpec


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping from logical axis names to mesh axis names (or None)."""

    rules: Mapping[str, Optional[str | tuple[str, ...]]]

    def spec_for(self, axes: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None,
                 shape: Optional[Sequence[int]] = None) -> P:
        """Build a PartitionSpec from per-dim logical names.

        If ``mesh``+``shape`` are given, axes whose mesh degree does not
        divide the dim size fall back to replication (mirrors the reference's
        ds validity check ``states_can_be_split``).
        """
        parts = []
        for i, name in enumerate(axes):
            mesh_axis = self.rules.get(name) if name else None
            if mesh_axis is not None and mesh is not None and shape is not None:
                size = _axis_size(mesh, mesh_axis)
                if size <= 1 or shape[i] % size != 0:
                    mesh_axis = None
            parts.append(mesh_axis)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def extended(self, extra: Mapping[str, Optional[str]]) -> "AxisRules":
        merged = dict(self.rules)
        merged.update(extra)
        return AxisRules(merged)


def _axis_size(mesh: Mesh, axis) -> int:
    """Product of mesh-axis sizes for an axis name / tuple / None; axes
    absent from the mesh count as 1 (shared by the rule table and the
    Pallas shard_map wrappers in ops.attention / ops.losses)."""
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(axis, 1)


def home_sharding(params, mesh: Optional[Mesh] = None):
    """Where state lives that a compiled step hands back and takes
    again beside ``params`` (a KV arena, advanced control vectors):
    replicated on ``mesh`` — by default the mesh the params are typed
    with (their first ``NamedSharding`` leaf's) — else committed to the
    params' one device.

    jit keys an executable on each argument's sharding and on whether
    it is committed, types an array by its sharding's mesh, and returns
    outputs committed and typed like the inputs. State born anywhere
    else than where the step returns it is a second program on the
    second call (same trace or not): allocate it here and pin the
    step's ``out_shardings`` to the same."""
    leaves = jax.tree.leaves(params)
    if mesh is None:
        mesh = next((x.sharding.mesh for x in leaves
                     if isinstance(getattr(x, "sharding", None),
                                   NamedSharding)), None)
    if mesh is not None:
        return NamedSharding(mesh, P())
    on = [x for x in leaves if isinstance(x, jax.Array)]
    dev = next(iter(on[0].devices())) if on else jax.devices()[0]
    return jax.sharding.SingleDeviceSharding(dev)


def manual_unbound_axes(b: int, heads) -> Optional[tuple]:
    """(abstract_mesh, axis_names, batch_ax, head_ax) when the trace is
    inside a partial-manual region (the pipeline executor) that left
    mesh axes auto — GSPMD rejects raw Mosaic kernels even over size-1
    auto axes, so Pallas call sites nest their own fully-local
    ``shard_map`` over the remaining axes (collectives stay OUTSIDE the
    nested region). None when not in a manual region or nothing is
    unbound. ``b``/``heads``: the batch size and every head count that
    must divide their axes — a non-divisible dim rides replicated
    (slower, still correct). Shared by ``ops.attention`` and
    ``parallel.ring_attention``; call at FORWARD trace time and thread
    the result (hand-written backwards trace after the context exits).
    """
    mctx = current_manual_axes()
    if mctx is None:
        return None
    unbound = [a for a in mctx.mesh.shape if a not in mctx.axes]
    if not unbound:
        return None
    batch_ax = tuple(a for a in unbound if a in ("dp", "ep")) or None
    head_ax = "tp" if "tp" in unbound else None
    nb = _axis_size(mctx.mesh, batch_ax)
    nh = _axis_size(mctx.mesh, head_ax)
    if nb > 1 and b % nb:
        batch_ax = None
    if nh > 1 and any(h % nh for h in heads):
        head_ax = None
    from jax.sharding import get_abstract_mesh
    return get_abstract_mesh(), set(unbound), batch_ax, head_ax


def param_partition_specs(module: Module, rules: AxisRules,
                          mesh: Optional[Mesh] = None) -> Any:
    """Pytree of PartitionSpec matching ``module.init(...)`` structure."""
    specs = module.abstract_specs()

    def to_spec(ps: ParamSpec) -> P:
        axes = ps.axes if ps.axes is not None else (None,) * len(ps.shape)
        return rules.spec_for(axes, mesh=mesh, shape=ps.shape)

    return jax.tree.map(to_spec, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def named_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def shard_params(params: Any, mesh: Mesh, spec_tree: Any) -> Any:
    """Place a param pytree onto the mesh per spec (initial distribution or
    hot-switch resharding — XLA computes the minimal collective plan, doing
    the job of the reference's ``SwitchExecGraph`` P2P slicing)."""
    return jax.device_put(params, named_shardings(mesh, spec_tree))


def constrain(x, spec: P):
    """``with_sharding_constraint`` under the ambient mesh — the equivalent
    of inserting an explicit comm op in the reference graph."""
    return jax.lax.with_sharding_constraint(x, spec)


# -- activation sharding context -------------------------------------------
#
# The reference inserts comm ops between layers via ``SubstituteCommOp``
# (``hetu/graph/executable_graph.cc:366``) by comparing producer/consumer
# DistributedStates. On TPU the analogue is ``with_sharding_constraint`` on
# activations; models call :func:`act_constrain` at the canonical cut points
# and the trainer activates an :class:`ActivationSharding` context (built
# from the Strategy) around tracing. Outside the context the calls are
# no-ops, so models stay mesh-agnostic.

_ACT_CTX: list["ActivationSharding"] = []


@dataclasses.dataclass(frozen=True)
class ActivationSharding:
    """Per-kind PartitionSpecs for activations + the mesh they live on.

    ``batch``/``seq``/``tp`` are mesh axis names (or axis tuples / None).
    """

    mesh: Mesh
    batch: Any = None       # mesh axes for the batch dim (e.g. "dp" or ("dp","ep"))
    seq: Any = None         # mesh axes for the sequence dim (cp; "tp" if Megatron-SP)
    tp: Any = None          # plain axis NAME for tp-sharded feature/head dims
                            # (the shard_map vocab-parallel paths need a string)
    cp_layout: str = "contiguous"   # how the global seq maps to cp shards:
                            # "contiguous" | "zigzag" (see data.packing)
    cp_impl: str = "ring"   # attention impl for the sharded seq dim
    sp: bool = False        # Megatron-SP: "tokens" activations (norms,
                            # residual stream) also shard seq over tp —
                            # GSPMD emits the reduce-scatter/all-gather
                            # pairs Megatron inserts by hand
    tp_overlap: str = "off"  # "ring": parallel layers decompose their
                            # AG→matmul / matmul→RS pairs into ppermute
                            # rings (parallel.overlap) instead of
                            # relying on GSPMD's serialized collectives
    fsdp_overlap: str = "off"  # "ring": StackedBlocks gathers each
                            # block's dp-sharded params via the ppermute
                            # ring (parallel.overlap.ring_gather_block_
                            # params), prefetching block k+1's gather
                            # under block k's compute
    fsdp_specs: Any = None  # per-layer PartitionSpec pytree for the
                            # block params (parallel.overlap.
                            # per_layer_gather_specs output); None =
                            # no per-layer gather (GSPMD fallback)
    ep_overlap: str = "off"  # "chunk": MoE dispatch/combine all_to_alls
                            # decompose into ep_chunks capacity slices
                            # so each a2a hides behind the neighbouring
                            # chunk's expert FFN (nn.moe._ep_dispatch)
    ep_chunks: int = 2      # capacity slices for ep_overlap="chunk"

    def spec(self, kind: str) -> Optional[P]:
        if kind == "tokens":        # (batch, seq, embed)
            if self.sp and isinstance(self.tp, str):
                seq = (self.seq, self.tp) if isinstance(self.seq, str) \
                    else self.tp
                return P(self.batch, seq, None)
            return P(self.batch, self.seq, None)
        if kind == "hidden":        # (batch, seq, features/tp)
            return P(self.batch, self.seq, self.tp)
        if kind == "heads":         # (batch, seq, heads/tp, head_dim)
            return P(self.batch, self.seq, self.tp, None)
        if kind == "logits":        # (batch, seq, vocab/tp)
            return P(self.batch, self.seq, self.tp)
        raise ValueError(f"unknown activation kind {kind!r}")

    def __enter__(self):
        _ACT_CTX.append(self)
        return self

    def __exit__(self, *exc):
        _ACT_CTX.pop()
        return False


def current_act_sharding() -> Optional[ActivationSharding]:
    return _ACT_CTX[-1] if _ACT_CTX else None


_MANUAL_CTX: list["ManualAxes"] = []


@dataclasses.dataclass(frozen=True)
class ManualAxes:
    """Marks that tracing happens inside a ``shard_map`` manual over
    ``axes`` of ``mesh`` (the pipeline region). Layers that would
    otherwise open their own ``shard_map`` (MoE all_to_all, vocab-parallel
    CE, ring attention) consult this to use bound-axis collectives
    directly instead — nested shard_maps are not allowed.

    ``cp_layout`` describes how the global sequence was laid out when
    "cp" is one of the bound axes (ring attention needs it to pick the
    per-hop masks); ``cp_impl`` selects ring vs ulysses for attention
    inside the region. ``ep_overlap``/``ep_chunks`` carry the MoE
    chunked-a2a setting into regions where "ep" is bound (the delayed
    grad-sync body; the pipeline executor leaves the default)."""

    mesh: Mesh
    axes: frozenset
    cp_layout: str = "contiguous"
    cp_impl: str = "ring"
    ep_overlap: str = "off"
    ep_chunks: int = 2

    def __enter__(self):
        _MANUAL_CTX.append(self)
        return self

    def __exit__(self, *exc):
        _MANUAL_CTX.pop()
        return False


def current_manual_axes() -> Optional["ManualAxes"]:
    return _MANUAL_CTX[-1] if _MANUAL_CTX else None


class no_act_sharding:
    """Suppress the active ActivationSharding (pushes None).

    Used while tracing code inside a manual ``shard_map`` region (the
    pipeline executor), where GSPMD constraints don't apply and ring
    attention must not nest another shard_map.
    """

    def __enter__(self):
        _ACT_CTX.append(None)
        return None

    def __exit__(self, *exc):
        _ACT_CTX.pop()
        return False


def act_constrain(x, kind: str):
    """Constrain an activation to the active context's spec for ``kind``.

    No-op when no :class:`ActivationSharding` context is active (single
    device, oracle tests) — models may therefore call this unconditionally.
    """
    ctx = current_act_sharding()
    if ctx is None:
        return x
    spec = ctx.spec(kind)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def sharded_init(module: Module, key, mesh: Mesh, rules: AxisRules,
                 dtype=None) -> Any:
    """Initialize params directly in their sharded layout (jit + out
    shardings) so giant models never materialize replicated."""
    specs = param_partition_specs(module, rules, mesh=mesh)
    shardings = named_shardings(mesh, specs)
    fn = jax.jit(lambda k: module.init(k, dtype=dtype),
                 out_shardings=shardings)
    return fn(key)
