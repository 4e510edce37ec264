"""The fused serving step's packed results.

What the host reads of an iteration — committed tokens, commit counts,
first tokens, the key state, the lanes' statistics — comes back in ONE
device→host transfer: a fetch costs a round trip whatever its size
(``PERF.md``, PR 38), so the small arrays travel as one int32 vector
with every field at a static offset.

:class:`PackedFields` is that format: the step lays a pytree of arrays
out as one vector (:meth:`~PackedFields.pack_device`), the loop takes
the fetched vector apart into the same pytree of views
(:meth:`~PackedFields.unpack_host`). Values cross unchanged, bit for
bit — ``int32`` as it is, any other 32-bit dtype (``uint32`` key words,
a ``float32``) bitcast, never rounded.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


class PackedFields:
    """A pytree of 32-bit arrays as ONE int32 vector.

    ``tree`` gives the fields: a pytree whose leaves have ``shape`` and
    ``dtype`` (``jax.ShapeDtypeStruct``, arrays). Fields lie in the
    tree's flattening order (dict keys sorted), each ``prod(shape)``
    words wide; ``fields`` lists ``(path, offset, shape, dtype)`` and
    ``size`` is the vector's length."""

    def __init__(self, tree):
        paths, self._treedef = jax.tree.flatten_with_path(tree)
        self.fields = []
        self.size = 0
        for path, leaf in paths:
            path = jax.tree_util.keystr(path)
            dtype, shape = np.dtype(leaf.dtype), tuple(leaf.shape)
            if dtype.itemsize != 4:
                raise ValueError(
                    f"packed field {path} is {dtype}: a 32-bit dtype "
                    f"travels in an int32 word")
            self.fields.append((path, self.size, shape, dtype))
            self.size += math.prod(shape)

    def pack_device(self, tree):
        """``tree`` (device values of this layout's shapes and dtypes,
        inside a jit) as one vector."""
        words = []
        for (path, _, shape, dtype), leaf in zip(
                self.fields, self._treedef.flatten_up_to(tree)):
            if leaf.shape != shape or leaf.dtype != dtype:
                raise ValueError(
                    f"packed field {path}: got {leaf.dtype}"
                    f"{leaf.shape}, laid out as {dtype}{shape}")
            words.append(jax.lax.bitcast_convert_type(
                leaf, jnp.int32).reshape(-1))
        return jnp.concatenate(words)

    def unpack_host(self, vec: np.ndarray):
        """The tree back from the fetched vector, as views of it."""
        if vec.shape != (self.size,) or vec.dtype != np.int32:
            raise ValueError(
                f"packed vector is {vec.dtype}{vec.shape}, laid out as "
                f"int32({self.size},)")
        return jax.tree.unflatten(self._treedef, [
            vec[off:off + math.prod(shape)].view(dtype).reshape(shape)
            for _, off, shape, dtype in self.fields])
