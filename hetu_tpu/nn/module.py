"""Functional Module system.

The reference builds an ``nn.Module`` tree whose ``forward`` emits graph ops
into a C++ static graph (``python/hetu/nn/modules/module.py`` →
``Graph::MakeOp``, SURVEY §3.2). On TPU the graph *is* the jaxpr: modules here
are plain Python objects that (a) declare parameters with shapes, initializers
and **logical sharding axes**, (b) build a nested-dict param pytree in
``init``, and (c) apply pure functions in ``__call__(params, ...)``. The
logical axes are what the strategy compiler (``hetu_tpu.parallel.sharding``)
maps onto mesh axes — the equivalent of the reference's per-tensor
``DistributedStates`` annotation (``hetu/graph/distributed_states.h:13``),
but declared once per parameter instead of propagated through a C++ pass.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from hetu_tpu.core.dtypes import current_policy

Initializer = Callable[[jax.Array, Sequence[int], Any], jax.Array]


@dataclasses.dataclass
class ParamSpec:
    """Declaration of one parameter.

    ``axes`` holds one *logical axis name* (or None) per dimension, e.g. a
    column-parallel kernel is ``("embed", "tp")``. The sharding compiler turns
    these into a ``PartitionSpec`` under the active strategy.
    """

    shape: tuple[int, ...]
    init: Initializer
    dtype: Any = None  # defaults to policy param_dtype at init time
    axes: tuple[Optional[str], ...] | None = None

    def instantiate(self, key: jax.Array, dtype=None) -> jax.Array:
        dtype = self.dtype or dtype or current_policy().param_dtype
        return self.init(key, self.shape, dtype)


class StackedLeaf(NamedTuple):
    """A parameter a layer scan does NOT slice: every layer's values,
    ``(layers, ...)``, and which layer this call is
    (``StackedBlocks.decode`` hands it in place of the layer's slice for
    the paths a block lists as ``unsliced``). For an operand of a
    kernel that cannot read through a dynamic slice — the slice would
    be a copy of the layer's values, per layer, per call."""
    stack: Any
    layer: Any


class Module:
    """Base class. Subclasses declare params with :meth:`param` in
    ``__init__`` and implement ``__call__(self, params, *args, **kwargs)``.

    Child modules are discovered from instance attributes (including lists /
    tuples / dicts of modules), so the param pytree mirrors the attribute
    tree — the analogue of the reference's subgraph module tree
    (``hetu/graph/subgraph.h:36``).
    """

    #: modules whose __call__ returns (output, aux_loss) — e.g. MoE layers
    #: with a load-balance term — set this True so containers (Sequential,
    #: StackedBlocks, the pipeline executor) thread the aux accumulation.
    returns_aux: bool = False
    #: ``{name: (shape, dtype, emit)}``: what a cached call of this
    #: module reports beside its result, a layer (a third result
    #: ``{name: value}``; ``StackedBlocks.decode`` stacks them over the
    #: layers and the serving engine hands them to ``emit`` on the host)
    layer_stats: dict = {}

    def __init__(self):
        self._param_specs: dict[str, ParamSpec] = {}

    # -- declaration -------------------------------------------------------
    def param(self, name: str, shape: Sequence[int], init: Initializer,
              dtype: Any = None, axes: Sequence[Optional[str]] | None = None):
        if not hasattr(self, "_param_specs"):
            self._param_specs = {}
        axes_t = tuple(axes) if axes is not None else None
        if axes_t is not None and len(axes_t) != len(tuple(shape)):
            raise ValueError(
                f"param {name}: axes {axes_t} rank != shape {tuple(shape)} rank")
        self._param_specs[name] = ParamSpec(tuple(shape), init, dtype, axes_t)

    # -- structure ---------------------------------------------------------
    def children(self) -> dict[str, "Module | list | dict"]:
        out = {}
        for k, v in vars(self).items():
            if k.startswith("_"):
                continue
            if isinstance(v, Module):
                out[k] = v
            elif isinstance(v, (list, tuple)) and v and all(
                    isinstance(e, Module) for e in v):
                out[k] = list(v)
            elif isinstance(v, dict) and v and all(
                    isinstance(e, Module) for e in v.values()):
                out[k] = v
        return out

    def named_modules(self, prefix: str = ""):
        """Yield ``(dotted_path, module)`` over the subtree, self first."""
        yield prefix, self
        for name, child in self.children().items():
            base = f"{prefix}.{name}" if prefix else name
            if isinstance(child, Module):
                yield from child.named_modules(base)
            elif isinstance(child, list):
                for i, m in enumerate(child):
                    yield from m.named_modules(f"{base}.{i}")
            else:
                for k, m in child.items():
                    yield from m.named_modules(f"{base}.{k}")

    # -- init --------------------------------------------------------------
    def init(self, key: jax.Array, dtype=None) -> dict:
        """Materialize the param pytree (nested dicts).

        The pytree structurally mirrors the module tree, including empty
        subtrees for param-less modules (Dropout, activations), so containers
        can always index ``params[child_name]``.
        """
        dtype = dtype or current_policy().param_dtype
        specs = self.abstract_specs()
        flat = _flatten_specs(specs)
        keys = jax.random.split(key, max(len(flat), 1))
        keymap = dict(zip(flat.keys(), keys))

        def build(tree: Mapping, prefix: str = "") -> dict:
            out = {}
            for k, v in tree.items():
                path = f"{prefix}.{k}" if prefix else str(k)
                if isinstance(v, ParamSpec):
                    out[k] = v.instantiate(keymap[path], dtype)
                else:
                    out[k] = build(v, path)
            return out

        return build(specs)

    def abstract_specs(self) -> dict:
        """Nested dict of ParamSpec mirroring the module tree structure.

        Param-less children contribute empty dicts (NOT pruned) so the param
        pytree always has the same structure as the module tree.
        """
        out: dict[str, Any] = dict(getattr(self, "_param_specs", {}))
        for name, child in self.children().items():
            if isinstance(child, Module):
                out[name] = child.abstract_specs()
            elif isinstance(child, list):
                out[name] = {str(i): m.abstract_specs()
                             for i, m in enumerate(child)}
            else:
                out[name] = {k: m.abstract_specs()
                             for k, m in child.items()}
        return out

    def abstract_params(self, dtype=None) -> dict:
        """ShapeDtypeStruct pytree — for sharding planning / eval_shape."""
        dtype = dtype or current_policy().param_dtype
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or dtype),
            self.abstract_specs(),
            is_leaf=lambda x: isinstance(x, ParamSpec))

    def param_axes(self) -> dict:
        """Pytree of logical-axes tuples matching the param structure."""
        return jax.tree.map(
            lambda s: s.axes if s.axes is not None else (None,) * len(s.shape),
            self.abstract_specs(),
            is_leaf=lambda x: isinstance(x, ParamSpec))

    # -- application -------------------------------------------------------
    def __call__(self, params, *args, **kwargs):
        raise NotImplementedError

    def compute_dtype(self):
        return current_policy().compute_dtype


def _flatten_specs(tree: Mapping, prefix: str = "") -> dict[str, ParamSpec]:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, ParamSpec):
            out[path] = v
        else:
            out.update(_flatten_specs(v, path))
    return out


def _unflatten(flat: Mapping[str, Any]) -> dict:
    root: dict = {}
    for path, leaf in flat.items():
        parts = path.split(".")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


class Sequential(Module):
    """Apply modules in order; params keyed by index."""

    def __init__(self, *mods: Module):
        super().__init__()
        self.layers = list(mods)

    def __call__(self, params, x, **kwargs):
        for i, m in enumerate(self.layers):
            x = m(params["layers"][str(i)], x, **kwargs)
        return x


# -- initializers ----------------------------------------------------------
def zeros_init():
    return lambda key, shape, dtype: jnp.zeros(shape, dtype)


def ones_init():
    return lambda key, shape, dtype: jnp.ones(shape, dtype)


def constant_init(v):
    return lambda key, shape, dtype: jnp.full(shape, v, dtype)


def normal_init(stddev=0.02):
    def f(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)
    return f


def uniform_init(scale=0.01):
    def f(key, shape, dtype):
        return jax.random.uniform(
            key, shape, jnp.float32, -scale, scale).astype(dtype)
    return f


def xavier_uniform_init(in_axis=-2, out_axis=-1):
    def f(key, shape, dtype):
        fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
        fan_out = shape[out_axis] if len(shape) > 1 else shape[0]
        limit = jnp.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(
            key, shape, jnp.float32, -limit, limit).astype(dtype)
    return f


def kaiming_uniform_init(in_axis=-2):
    def f(key, shape, dtype):
        fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
        limit = jnp.sqrt(3.0 / fan_in)
        return jax.random.uniform(
            key, shape, jnp.float32, -limit, limit).astype(dtype)
    return f
