"""Fused streaming LM-head+CE Pallas kernel vs the XLA oracles.

Runs in interpret mode on the CPU mesh (same approach as
test_flash_pallas.py); its timing on the chip is not measured
(workloads/ce_tune.py has not run there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.fused_ce_pallas import fused_lm_ce
from hetu_tpu.ops.losses import chunked_lm_loss, cross_entropy_mean


def _data(B=2, S=128, E=64, V=1000, dtype=jnp.float32, seed=0):
    h = jax.random.normal(jax.random.key(seed), (B, S, E), dtype)
    w = jax.random.normal(jax.random.key(seed + 1), (V, E), jnp.float32) * 0.05
    labels = jax.random.randint(jax.random.key(seed + 2), (B, S), 0, V)
    return h, w, labels


def _oracle(h, w, labels, ignore_index=-100):
    logits = jnp.einsum("bse,ve->bsv", h.astype(jnp.float32),
                        w.astype(jnp.float32))
    return cross_entropy_mean(logits, labels, ignore_index)


def test_fused_ce_matches_oracle():
    h, w, labels = _data()
    # V=1000 not divisible by block_v=256 -> exercises vocab padding
    got = fused_lm_ce(h, w, labels, block_n=128, block_v=256)
    np.testing.assert_allclose(got, _oracle(h, w, labels), rtol=2e-5)


def test_fused_ce_ignore_index():
    h, w, labels = _data()
    labels = labels.at[0, :17].set(-100)
    got = fused_lm_ce(h, w, labels, block_n=128, block_v=256)
    np.testing.assert_allclose(got, _oracle(h, w, labels), rtol=2e-5)


def test_fused_ce_grads_match():
    h, w, labels = _data()
    labels = labels.at[1, 5:9].set(-100)
    gr = jax.grad(lambda h, w: _oracle(h, w, labels), argnums=(0, 1))(h, w)
    gf = jax.grad(lambda h, w: fused_lm_ce(h, w, labels, block_n=128,
                                           block_v=256),
                  argnums=(0, 1))(h, w)
    for a, b, name in zip(gf, gr, ("dh", "dw")):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-4, err_msg=name)


def test_fused_ce_token_padding():
    """N not divisible by block_n -> token padding must not leak into
    the mean or the grads."""
    h, w, labels = _data(B=1, S=100, E=64, V=512)
    got = fused_lm_ce(h, w, labels, block_n=128, block_v=256)
    np.testing.assert_allclose(got, _oracle(h, w, labels), rtol=2e-5)
    gf = jax.grad(lambda h: fused_lm_ce(h, w, labels, block_n=128,
                                        block_v=256))(h)
    gr = jax.grad(lambda h: _oracle(h, w, labels))(h)
    np.testing.assert_allclose(gf, gr, atol=3e-5, rtol=3e-4)


def test_fused_ce_bf16_hidden_matches_chunked():
    """bf16 hidden (the autocast layout): parity with chunked_lm_loss at
    the same matmul dtype."""
    h, w, labels = _data(dtype=jnp.bfloat16)
    got = fused_lm_ce(h, w, labels, block_n=128, block_v=256)
    ref = chunked_lm_loss(h, w, labels, mm_dt=jnp.bfloat16,
                          chunk_tokens=128)
    np.testing.assert_allclose(got, ref, rtol=3e-3)


def test_fused_vocab_parallel_matches_dense():
    """fused_lse_tgt + psum logsumexp combine inside shard_map == dense
    oracle, value and grads (vocab sharded over 4 devices)."""
    import functools
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from hetu_tpu.ops.fused_ce_pallas import fused_vocab_parallel_ce

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("tp",))
    B, S, E, V = 2, 64, 32, 512
    h = jax.random.normal(jax.random.key(1), (B * S, E), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (V, E), jnp.float32) * 0.05
    labels = jax.random.randint(jax.random.key(3), (B * S,), 0, V)
    labels = labels.at[:5].set(-100)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P("tp", None), P()),
        out_specs=(P(), P()), check_vma=False)
    def run(h, w_local, y):
        vs = jax.lax.axis_index("tp") * (V // 4)
        return fused_vocab_parallel_ce(
            h, w_local, y, axis_name="tp", vocab_start=vs,
            block_n=64, block_v=64)

    def mean_loss(h, w):
        loss, valid = run(h, w, labels)
        return loss.sum() / jnp.maximum(valid.sum(), 1)

    def oracle(h, w):
        logits = (h @ w.T)[None]
        return cross_entropy_mean(logits, labels[None])

    np.testing.assert_allclose(mean_loss(h, w), oracle(h, w), rtol=2e-5)
    gf = jax.grad(mean_loss, argnums=(0, 1))(h, w)
    gr = jax.grad(oracle, argnums=(0, 1))(h, w)
    for a, b, name in zip(gf, gr, ("dh", "dw")):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-4, err_msg=name)


def test_fused_ce_sharded_wrapper_matches_unsharded():
    """_fused_ce_sharded (the GSPMD shard_map wrap for the Mosaic CE
    kernel) rebuilds the global mean from per-shard (sum, count) — must
    equal the unsharded fused mean, including ignore_index rows landing
    unevenly across shards, and grads must flow."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hetu_tpu.ops.fused_ce_pallas import fused_lm_ce
    from hetu_tpu.ops.losses import _fused_ce_sharded
    from hetu_tpu.parallel.sharding import ActivationSharding

    mesh = jax.make_mesh((4,), ("dp",))
    rs = np.random.RandomState(0)
    B, S, E, V = 8, 32, 64, 640
    h = jnp.asarray(rs.randn(B, S, E), jnp.float32)
    w = jnp.asarray(rs.randn(V, E), jnp.float32) * 0.05
    y = jnp.asarray(rs.randint(0, V, (B, S)))
    y = y.at[0, :20].set(-100).at[5, :].set(-100)  # uneven ignore rows

    ctx = ActivationSharding(mesh, batch="dp", seq=None, tp=None)
    hs = jax.device_put(h, NamedSharding(mesh, P("dp", None, None)))
    ys = jax.device_put(y, NamedSharding(mesh, P("dp", None)))

    def sharded(h, w, y):
        out = _fused_ce_sharded(h, w, y, ctx, -100)
        assert out is not None  # dp=4 > 1: the wrap must engage
        return out

    got = jax.jit(sharded)(hs, w, ys)
    want = fused_lm_ce(h, w, y)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    gw = jax.jit(jax.grad(sharded, argnums=1))(hs, w, ys)
    gw_ref = jax.grad(lambda w: fused_lm_ce(h, w, y))(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-6)

    # dp2 x tp2 with the vocab NOT tp-sharded: tp must join the token
    # split (disjoint slices) — duplicated work across tp would psum
    # identical dW copies and scale the head grad by tp_deg
    mesh2 = jax.make_mesh((2, 2), ("dp", "tp"))
    ctx2 = ActivationSharding(mesh2, batch="dp", seq=None, tp="tp")
    hs2 = jax.device_put(h, NamedSharding(mesh2, P("dp", "tp", None)))
    ys2 = jax.device_put(y, NamedSharding(mesh2, P("dp", "tp")))

    def sharded2(h, w, y):
        out = _fused_ce_sharded(h, w, y, ctx2, -100)
        assert out is not None
        return out

    got2 = jax.jit(sharded2)(hs2, w, ys2)
    np.testing.assert_allclose(float(got2), float(want), rtol=1e-6)
    gw2 = jax.jit(jax.grad(sharded2, argnums=1))(hs2, w, ys2)
    np.testing.assert_allclose(np.asarray(gw2), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-6)


def test_fused_ce_sharded_replicated_mesh_matches():
    """factor==1 (e.g. pp-only mesh): tokens are replicated and every
    device computes the full loss — the wrap exists only to satisfy the
    partitioner. Loss and grads must still match the unsharded oracle
    (no mesh-size scaling from the transpose)."""
    import numpy as np

    from hetu_tpu.ops.fused_ce_pallas import fused_lm_ce
    from hetu_tpu.ops.losses import _fused_ce_sharded
    from hetu_tpu.parallel.sharding import ActivationSharding

    mesh = jax.make_mesh((2,), ("pp",))
    rs = np.random.RandomState(1)
    B, S, E, V = 4, 16, 32, 320
    h = jnp.asarray(rs.randn(B, S, E), jnp.float32)
    w = jnp.asarray(rs.randn(V, E), jnp.float32) * 0.05
    y = jnp.asarray(rs.randint(0, V, (B, S)))

    ctx = ActivationSharding(mesh, batch=None, seq=None, tp=None)

    def sharded(h, w, y):
        out = _fused_ce_sharded(h, w, y, ctx, -100)
        assert out is not None
        return out

    got = jax.jit(sharded)(h, w, y)
    want = fused_lm_ce(h, w, y)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    gw = jax.jit(jax.grad(sharded, argnums=1))(h, w, y)
    gw_ref = jax.grad(lambda w: fused_lm_ce(h, w, y))(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-6)
