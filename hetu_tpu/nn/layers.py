"""Basic layers: Linear, Embedding, norms, Dropout, MLP.

Covers the dense end of the reference's op library (``hetu/graph/ops/``:
Linear/MatMul, LayerNorm/RMSNorm via fused kernels ``impl/kernel/RMSNorm.cu``,
``FusedLayerNorm.cu``, embedding lookup) as idiomatic JAX modules. Norms call
into ``hetu_tpu.ops.normalization`` so a fused Pallas path can slot in
underneath without touching model code.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from hetu_tpu.nn.module import (
    Module, normal_init, zeros_init, ones_init, kaiming_uniform_init,
)
from hetu_tpu.ops import embedding as embed_ops
from hetu_tpu.ops import normalization as norm_ops


class Linear(Module):
    def __init__(self, in_features: int, out_features: int, bias: bool = True,
                 init=None, axes: Sequence[Optional[str]] = (None, None)):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias
        self.param("weight", (in_features, out_features),
                   init or kaiming_uniform_init(), axes=axes)
        if bias:
            self.param("bias", (out_features,), zeros_init(), axes=(axes[1],))

    def __call__(self, params, x):
        dt = self.compute_dtype()
        y = jnp.matmul(x.astype(dt), params["weight"].astype(dt))
        if self.use_bias:
            y = y + params["bias"].astype(dt)
        return y


class Embedding(Module):
    """``bwd`` selects the gradient formulation for the table update:
    "auto" uses the scatter-vs-onehot winner measured on this chip by
    ``workloads/embed_probe.py`` (see ``ops/embedding.py``)."""

    def __init__(self, num_embeddings: int, features: int, init=None,
                 axes: Sequence[Optional[str]] = (None, None),
                 bwd: str = "auto"):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.features = features
        self.bwd = bwd
        self.param("weight", (num_embeddings, features),
                   init or normal_init(0.02), axes=axes)

    def __call__(self, params, ids):
        return embed_ops.embedding_lookup(
            params["weight"], ids, bwd=self.bwd).astype(
            self.compute_dtype())


class LayerNorm(Module):
    def __init__(self, features: int, eps: float = 1e-5,
                 use_bias: bool = True, use_scale: bool = True,
                 axes: Sequence[Optional[str]] = (None,)):
        super().__init__()
        self.features = features
        self.eps = eps
        self.use_bias = use_bias
        self.use_scale = use_scale
        if use_scale:
            self.param("scale", (features,), ones_init(), axes=axes)
        if use_bias:
            self.param("bias", (features,), zeros_init(), axes=axes)

    def __call__(self, params, x):
        scale = params["scale"] if self.use_scale else None
        bias = params["bias"] if self.use_bias else None
        return norm_ops.layer_norm(x, scale, bias, eps=self.eps).astype(
            self.compute_dtype())


class RMSNorm(Module):
    """``x / sqrt(mean x^2 + eps) * scale``; ``zero_centered``: the gain
    is ``1 + scale`` (the parameter is the gain's distance from one,
    drawn ``normal(0, zero_centered)`` so that a program that reads it
    as the gain itself must differ)."""

    def __init__(self, features: int, eps: float = 1e-6,
                 axes: Sequence[Optional[str]] = (None,),
                 zero_centered: float = 0.0):
        super().__init__()
        self.features = features
        self.eps = eps
        self.zero_centered = bool(zero_centered)
        self.param("scale", (features,),
                   normal_init(zero_centered) if zero_centered
                   else ones_init(), axes=axes)

    def __call__(self, params, x):
        scale = params["scale"]
        if self.zero_centered:
            scale = 1.0 + scale.astype(jnp.float32)
        return norm_ops.rms_norm(x, scale, eps=self.eps).astype(
            self.compute_dtype())


class Dropout(Module):
    def __init__(self, rate: float):
        super().__init__()
        self.rate = rate

    def __call__(self, params, x, *, rng: Optional[jax.Array] = None,
                 deterministic: bool = True):
        if deterministic or self.rate == 0.0:
            return x
        if rng is None:
            raise ValueError("Dropout needs an rng when not deterministic")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


class MLP(Module):
    """Plain 2-layer MLP (GELU) — GPT-2 style."""

    def __init__(self, features: int, hidden: int, bias: bool = True,
                 activation=jax.nn.gelu):
        super().__init__()
        self.fc_in = Linear(features, hidden, bias=bias,
                            init=normal_init(0.02), axes=("embed", "mlp"))
        self.fc_out = Linear(hidden, features, bias=bias,
                             init=normal_init(0.02), axes=("mlp", "embed"))
        self.activation = activation

    def __call__(self, params, x):
        h = self.activation(self.fc_in(params["fc_in"], x))
        return self.fc_out(params["fc_out"], h)


class Conv2D(Module):
    """2-D convolution (NHWC), lowered to ``lax.conv_general_dilated``
    (XLA tiles it onto the MXU). Reference kernels: ``impl/kernel``
    Conv2d CPU/CUDA pair driven by ``tests/test_cifar10.py``."""

    def __init__(self, in_channels: int, out_channels: int,
                 kernel_size: int = 3, stride: int = 1,
                 padding: str = "SAME", bias: bool = True, init=None):
        super().__init__()
        self.stride = (stride, stride)
        self.padding = padding
        init = init or normal_init(0.02)
        self.param("kernel",
                   (kernel_size, kernel_size, in_channels, out_channels),
                   init, axes=(None, None, None, "mlp"))
        if bias:
            self.param("bias", (out_channels,), zeros_init(),
                       axes=("mlp",))

    def __call__(self, params, x):
        dt = self.compute_dtype()
        y = jax.lax.conv_general_dilated(
            x.astype(dt), params["kernel"].astype(dt),
            window_strides=self.stride, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if "bias" in params:
            y = y + params["bias"].astype(dt)
        return y


def max_pool2d(x, window: int = 2, stride: int = 2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def avg_pool2d(x, window: int = 2, stride: int = 2):
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")
    return s / (window * window)
