"""Pallas flash attention vs the pure-jnp oracle (interpret mode on CPU).

Mirrors the reference's op-parity test discipline (``tests/test_ops.py``
there compares every op fwd+grad against torch; here the oracle is
``attention_reference``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.attention import attention_reference, flash_attention
from hetu_tpu.ops.flash_pallas import flash_attention_pallas


def _rand_qkv(key, b, sq, sk, hq, hkv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, d), dtype)
    k = jax.random.normal(kk, (b, sk, hkv, d), dtype)
    v = jax.random.normal(kv, (b, sk, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_flash_fwd_matches_reference(rng, causal, hq, hkv):
    q, k, v = _rand_qkv(rng, 2, 256, 256, hq, hkv, 128)
    out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_fwd_segment_ids(rng):
    b, s, h, d = 2, 256, 2, 128
    q, k, v = _rand_qkv(rng, b, s, s, h, h, d)
    seg = jnp.concatenate([
        jnp.zeros((b, s // 2), jnp.int32),
        jnp.ones((b, s // 2), jnp.int32)], axis=1)
    out = flash_attention_pallas(q, k, v, causal=True, segment_ids=seg,
                                 interpret=True)
    ref = attention_reference(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
def test_flash_grads_match_reference(rng, causal, hq, hkv):
    q, k, v = _rand_qkv(rng, 1, 256, 256, hq, hkv, 128)

    def loss_pallas(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_flash_grads_segment_ids(rng):
    b, s, h, d = 1, 256, 2, 128
    q, k, v = _rand_qkv(rng, b, s, s, h, h, d)
    seg = jnp.concatenate([
        jnp.zeros((b, 96), jnp.int32),
        jnp.ones((b, 96), jnp.int32),
        jnp.full((b, 64), 2, jnp.int32)], axis=1)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    fp = lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, segment_ids=seg, interpret=True)
    fr = lambda q, k, v: attention_reference(
        q, k, v, causal=True, segment_ids=seg)
    gp = jax.grad(lambda *a: loss(fp, *a), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: loss(fr, *a), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_dispatch_pallas_importable(rng):
    """impl='pallas' must not crash (ADVICE r1 high-severity finding)."""
    q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 2, 64)
    out = flash_attention(q, k, v, causal=True, impl="pallas")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_tuned_block_defaults_lookup():
    """_default_blocks consults flash_tune winners (exact q-seq match
    whose blocks divide both lengths) and falls back to _pick_block."""
    from hetu_tpu.ops import flash_pallas as fp

    entries = (
        tuple(sorted({"seq": 1024, "fwd": [256, 512],
                      "bwd": [512, 256]}.items())),
        tuple(sorted({"seq": 4096, "fwd": [512, 1024],
                      "bwd": [1024, 512]}.items())),
    )
    orig = fp._tuned_entries
    fp._tuned_entries = lambda: entries
    try:
        assert fp._default_blocks(1024, 1024, "fwd") == (256, 512)
        assert fp._default_blocks(1024, 1024, "bwd") == (512, 256)
        assert fp._default_blocks(4096, 4096, "fwd") == (512, 1024)
        # unmeasured seq -> static heuristic
        assert fp._default_blocks(2048, 2048, "fwd") == \
            (fp._pick_block(2048), fp._pick_block(2048))
        # measured q-seq but kv length the tuned block doesn't divide
        # (ring hop with ragged kv) -> fallback
        assert fp._default_blocks(1024, 384, "fwd") == \
            (fp._pick_block(1024), fp._pick_block(384))
    finally:
        fp._tuned_entries = orig


def test_tuned_entries_absent_on_cpu():
    from hetu_tpu.ops import flash_pallas as fp
    assert fp._tuned_entries() == ()


def _drop_oracle_mask(key, b, h, sq, sk, rate):
    """Whole-matrix draw of the kernel's position-addressable counter
    RNG: one (sq, sk) 'block' at iq=ik=0 — equality with the kernel's
    per-block draws IS the position-addressability property."""
    from hetu_tpu.ops.flash_pallas import _dropout_keep

    seed = jax.random.bits(key, (1,), jnp.uint32).astype(jnp.int32)
    rows = [[_dropout_keep(seed[0], ib, ih, 0, 0, rate=rate,
                           block_q=sq, block_k=sk, q_offset=0,
                           kv_offset=0)
             for ih in range(h)] for ib in range(b)]
    return jnp.stack([jnp.stack(r) for r in rows])     # (b, h, sq, sk)


def _drop_oracle(q, k, v, mask_keep, *, causal, rate):
    """jnp attention applying a GIVEN keep-mask to the softmax probs."""
    from hetu_tpu.ops.attention import _expand_kv
    b, sq, hq, d = q.shape
    kf = _expand_kv(k, hq).astype(jnp.float32)
    vf = _expand_kv(v, hq).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk",
                        q.astype(jnp.float32) / d ** 0.5, kf)
    if causal:
        cm = jnp.tril(jnp.ones((sq, k.shape[1]), bool))
        logits = jnp.where(cm[None, None], logits, -1e30)
    a = jax.nn.softmax(logits, axis=-1)
    a = jnp.where(mask_keep, a / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", a, vf).astype(q.dtype)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_flash_dropout_matches_hash_oracle(rng, hq, hkv):
    """In-kernel dropout (reference p_dropout, FlashAttention.cu:1-50):
    forward AND gradients equal a jnp oracle applying the same
    position-hashed mask — proving the fwd/bwd kernels regenerate one
    identical mask."""
    rate = 0.3
    q, k, v = _rand_qkv(rng, 2, 128, 128, hq, hkv, 64)
    key = jax.random.key(7)
    mask = _drop_oracle_mask(key, 2, hq, 128, 128, rate)

    def flash_loss(q, k, v):
        o = flash_attention_pallas(q, k, v, causal=True, interpret=True,
                                   dropout_rate=rate, dropout_key=key)
        return (o.astype(jnp.float32) ** 2).sum(), o

    def oracle_loss(q, k, v):
        o = _drop_oracle(q, k, v, mask, causal=True, rate=rate)
        return (o.astype(jnp.float32) ** 2).sum(), o

    (lf, of), gf = jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    (lo, oo), go = jax.value_and_grad(oracle_loss, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(of), np.asarray(oo),
                               rtol=2e-5, atol=2e-5)
    for a, b_ in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_flash_dropout_block_size_invariant(rng):
    """The mask is addressed by absolute position, so DIFFERENT tilings
    (fwd vs tuned bwd blocks) produce identical outputs and grads."""
    rate = 0.25
    q, k, v = _rand_qkv(rng, 1, 256, 256, 2, 2, 64)
    key = jax.random.key(3)

    def run(bq, bk):
        def loss(q):
            o = flash_attention_pallas(q, k, v, causal=True,
                                       interpret=True, block_q=bq,
                                       block_k=bk, dropout_rate=rate,
                                       dropout_key=key)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.value_and_grad(loss)(q)

    l1, g1 = run(128, 128)
    l2, g2 = run(256, 64)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-4, atol=2e-4)


def test_flash_dropout_lse_and_determinism(rng):
    """Dropout masks only the value mix: LSE is bit-identical to the
    undropped kernel; same key → same output; no key → no dropout."""
    from hetu_tpu.ops.flash_pallas import _flash_fwd

    rate = 0.4
    q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 2, 64)
    key = jax.random.key(11)
    seed = jax.random.bits(key, (1,), jnp.uint32).astype(jnp.int32)
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    _, lse0 = _flash_fwd(qh, kh, vh, None, None, causal=True,
                         scale=0.125, interpret=True)
    od, lsed = _flash_fwd(qh, kh, vh, None, None, causal=True,
                          scale=0.125, interpret=True,
                          dropout_rate=rate, seed=seed)
    np.testing.assert_array_equal(np.asarray(lse0), np.asarray(lsed))
    od2, _ = _flash_fwd(qh, kh, vh, None, None, causal=True,
                        scale=0.125, interpret=True,
                        dropout_rate=rate, seed=seed)
    np.testing.assert_array_equal(np.asarray(od), np.asarray(od2))
    # a different key draws a different mask
    seed2 = jax.random.bits(jax.random.key(12), (1,),
                            jnp.uint32).astype(jnp.int32)
    od3, _ = _flash_fwd(qh, kh, vh, None, None, causal=True,
                        scale=0.125, interpret=True,
                        dropout_rate=rate, seed=seed2)
    assert not np.allclose(np.asarray(od), np.asarray(od3))
    # keep-rate sanity on the raw mask: fraction ~ 1-rate
    from hetu_tpu.ops.flash_pallas import _dropout_keep
    m = _dropout_keep(seed[0], 0, 0, 0, 0, rate=rate, block_q=256,
                      block_k=256, q_offset=0, kv_offset=0)
    assert abs(float(m.mean()) - (1 - rate)) < 0.02
