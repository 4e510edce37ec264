"""Rotary position embeddings (RoPE), incl. packed/varlen positions.

Equivalent of the reference's ``hetu/impl/kernel/Rotary.cc`` / ``rotary.cu``
(which supports varlen/packing via cu_seqlens). Here packing is expressed
with explicit per-token ``positions`` (reset at each segment start), which is
the segment-id-native formulation TPU flash kernels use.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     dtype=jnp.float32):
    """Precompute cos/sin tables of shape (max_len, head_dim//2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x, cos, sin, positions: Optional[jnp.ndarray] = None,
                 *, interleaved: bool = False):
    """Apply RoPE to ``x`` of shape (..., seq, heads, head_dim).

    ``cos``/``sin``: (max_len, head_dim//2) tables. ``positions``: optional
    (..., seq) int array for packed sequences; defaults to arange(seq).
    Rotation uses the "split-half" convention (Llama/NeoX style: pair
    ``i`` is dims ``i, i + head_dim/2``) unless ``interleaved``, the
    GPT-J convention (``rope_gptj``): pair ``i`` is dims ``2i, 2i+1``.
    """
    seq = x.shape[-3]
    if positions is None:
        cos_t = cos[:seq]
        sin_t = sin[:seq]
        # broadcast to (..., seq, 1, head_dim//2)
        cos_t = cos_t[:, None, :]
        sin_t = sin_t[:, None, :]
    else:
        cos_t = jnp.take(cos, positions, axis=0)[..., :, None, :]
        sin_t = jnp.take(sin, positions, axis=0)[..., :, None, :]
    half = x.shape[-1] // 2
    if interleaved:
        pairs = x.reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos_t - xf2 * sin_t
    out2 = xf2 * cos_t + xf1 * sin_t
    if interleaved:
        return jnp.stack([out1, out2], axis=-1).reshape(x.shape) \
            .astype(x.dtype)
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
