"""Ring-attention CP vs the full-sequence oracle (fwd + grads).

Reference semantics under test: ``AttnCommRing``
(``hetu/graph/ops/ParallelAttention.h:391-470``) — per-hop masks, LSE
correction, backward ring with dKV piggyback.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hetu_tpu.ops.attention import attention_reference
from hetu_tpu.parallel.ring_attention import ring_attention
from hetu_tpu.parallel.sharding import ActivationSharding
from hetu_tpu.parallel.strategy import Strategy


def _env(cp, dp=1):
    mesh = Strategy(dp=dp, cp=cp).build_mesh()
    return ActivationSharding(mesh, batch="dp", seq="cp", tp="tp"), mesh


def _qkv(key, b=2, s=32, hq=4, hkv=2, d=8):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_oracle_fwd(rng, cp, causal):
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng)
    ref = attention_reference(q, k, v, causal=causal)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, ctx=ctx, causal=causal)

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    out = f(*(jax.device_put(x, sh) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cp", [2, 4])
def test_ring_matches_oracle_grads(rng, cp):
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gq_ref, gk_ref, gv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    @jax.jit
    def g(q, k, v):
        def loss(q, k, v):
            return jnp.sum(
                ring_attention(q, k, v, ctx=ctx, causal=True) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    gq, gk, gv = g(*(jax.device_put(x, sh) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(gq_ref), np.asarray(gq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk_ref), np.asarray(gk),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv_ref), np.asarray(gv),
                               rtol=1e-4, atol=1e-4)


def test_ring_packed_segments(rng):
    """Packed sequences must not attend across segment boundaries, even
    when a segment spans a cp chunk boundary."""
    cp = 2
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng, s=32)
    # segment 0: tokens 0..19 (spans the cp boundary at 16); segment 1: rest
    segs = (jnp.arange(32) >= 20).astype(jnp.int32)[None, :].repeat(2, 0)
    ref = attention_reference(q, k, v, causal=True, segment_ids=segs)

    @jax.jit
    def f(q, k, v, s):
        return ring_attention(q, k, v, ctx=ctx, causal=True,
                              segment_ids=s)

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    ssh = NamedSharding(mesh, P("dp", "cp"))
    out = f(jax.device_put(q, sh), jax.device_put(k, sh),
            jax.device_put(v, sh), jax.device_put(segs, ssh))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_ring_with_dp_and_tp(rng):
    """cp composed with dp on the same mesh."""
    ctx, mesh = _env(cp=2, dp=2)
    q, k, v = _qkv(rng, b=4)
    ref = attention_reference(q, k, v, causal=True)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, ctx=ctx, causal=True)

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    out = f(*(jax.device_put(x, sh) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_ring_pallas_interpret(rng):
    """The Pallas per-hop kernel path (interpret mode on CPU)."""
    cp = 2
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng, b=1, s=256, hq=2, hkv=1, d=64)
    ref = attention_reference(q, k, v, causal=True)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, ctx=ctx, causal=True, impl="pallas")

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    out = f(*(jax.device_put(x, sh) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-3, atol=2e-3)


def test_model_uses_ring_under_cp(rng):
    """End-to-end: GPT loss under cp=4 matches single-device (the model
    routes attention through the ring when ctx.seq is sharded)."""
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.sharding import shard_params

    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(rng, dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    ref = float(model.loss(params, batch["input_ids"], batch["labels"]))

    plan = make_plan(model, optim.adam(1e-3), Strategy(dp=2, cp=4))
    sp = shard_params(params, plan.mesh, plan.param_specs)
    sbatch = plan.shard_batch(batch)

    @jax.jit
    def loss_fn(p, b):
        with plan.act:
            return model.loss(p, b["input_ids"], b["labels"],
                              positions=b.get("positions"))

    got = float(loss_fn(sp, sbatch))
    np.testing.assert_allclose(ref, got, rtol=1e-5)


# --------------------------------------------------------------------------
# Zigzag (load-balanced SYM) layout
# --------------------------------------------------------------------------


@pytest.mark.parametrize("cp", [2, 4])
def test_zigzag_matches_oracle_fwd(rng, cp):
    from hetu_tpu.data.packing import zigzag_permute, zigzag_restore
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng)
    ref = attention_reference(q, k, v, causal=True)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, ctx=ctx, causal=True,
                              layout="zigzag")

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    out = f(*(jax.device_put(zigzag_permute(x, cp, axis=1), sh)
              for x in (q, k, v)))
    out = zigzag_restore(np.asarray(out), cp, axis=1)
    np.testing.assert_allclose(np.asarray(ref), out, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cp", [2, 4])
def test_zigzag_matches_oracle_grads(rng, cp):
    from hetu_tpu.data.packing import zigzag_permute, zigzag_restore
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 3)

    refs = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    @jax.jit
    def g(q, k, v):
        def loss(q, k, v):
            return jnp.sum(ring_attention(q, k, v, ctx=ctx, causal=True,
                                          layout="zigzag") ** 3)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    grads = g(*(jax.device_put(zigzag_permute(x, cp, axis=1), sh)
                for x in (q, k, v)))
    for gref, got in zip(refs, grads):
        got = zigzag_restore(np.asarray(got), cp, axis=1)
        np.testing.assert_allclose(np.asarray(gref), got,
                                   rtol=1e-4, atol=1e-4)


def test_zigzag_packed_segments(rng):
    """Packing + zigzag: segment ids ride the ring in permuted order."""
    from hetu_tpu.data.packing import zigzag_permute, zigzag_restore
    cp = 4
    ctx, mesh = _env(cp)
    q, k, v = _qkv(rng, s=32)
    segs = (jnp.arange(32) >= 20).astype(jnp.int32)[None, :].repeat(2, 0)
    ref = attention_reference(q, k, v, causal=True, segment_ids=segs)

    @jax.jit
    def f(q, k, v, s):
        return ring_attention(q, k, v, ctx=ctx, causal=True,
                              segment_ids=s, layout="zigzag")

    sh = NamedSharding(mesh, P("dp", "cp", None, None))
    ssh = NamedSharding(mesh, P("dp", "cp"))
    out = f(*(jax.device_put(zigzag_permute(x, cp, axis=1), sh)
              for x in (q, k, v)),
            jax.device_put(zigzag_permute(segs, cp, axis=1), ssh))
    out = zigzag_restore(np.asarray(out), cp, axis=1)
    np.testing.assert_allclose(np.asarray(ref), out, rtol=2e-5, atol=2e-5)


def test_zigzag_indices_roundtrip():
    from hetu_tpu.data.packing import (
        zigzag_indices, zigzag_permute, zigzag_restore)
    idx = zigzag_indices(16, 2)
    # rank 0 owns chunks (0, 3), rank 1 owns (1, 2)
    np.testing.assert_array_equal(
        idx, [0, 1, 2, 3, 12, 13, 14, 15, 4, 5, 6, 7, 8, 9, 10, 11])
    x = np.arange(32).reshape(2, 16)
    np.testing.assert_array_equal(
        zigzag_restore(zigzag_permute(x, 4, axis=1), 4, axis=1), x)


def test_zigzag_default_strategy_end_to_end(rng):
    """Strategy defaults to cp_layout=zigzag; shard_batch permutes +
    synthesizes positions; loss matches the unpermuted single-device run."""
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan
    from hetu_tpu.models import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.parallel.sharding import shard_params

    cfg = LlamaConfig.tiny()
    model = LlamaLMHeadModel(cfg)
    params = model.init(rng, dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    ref = float(model.loss(params, batch["input_ids"], batch["labels"]))

    strategy = Strategy(dp=2, cp=4)
    assert strategy.cp_layout == "zigzag"
    plan = make_plan(model, optim.adam(1e-3), strategy)
    sp = shard_params(params, plan.mesh, plan.param_specs)
    sbatch = plan.shard_batch(batch)
    assert "positions" in sbatch

    @jax.jit
    def loss_fn(p, b):
        with plan.act:
            return model.loss(p, b["input_ids"], b["labels"],
                              positions=b.get("positions"))

    got = float(loss_fn(sp, sbatch))
    np.testing.assert_allclose(ref, got, rtol=1e-5)


def _ring_drop_mask(key, cp, b, h, s, rate):
    """Reconstruct the GLOBAL keep mask the contiguous causal ring draws
    for (cp ranks, per-hop T_FULL calls): cell (qg, kg) is computed by
    rank r = qg//c at hop (r - kg//c) % cp with hop-local coordinates —
    the same stream `_make_ring_core._call_seed` + `dropout_keep_bh`
    define."""
    from hetu_tpu.core.bits import fmix32
    from hetu_tpu.ops.flash_pallas import dropout_keep_bh

    T_FULL = 6
    seed = jax.random.bits(key, (1,), jnp.uint32).astype(jnp.int32)
    c = s // cp
    keep = np.zeros((b, h, s, s), bool)
    for r in range(cp):                       # q-owner rank
        for src in range(cp):                 # kv source chunk
            hop = (r - src) % cp
            s_call = fmix32(
                seed.astype(jnp.uint32)
                ^ (jnp.uint32(hop) * jnp.uint32(0x9E3779B1))
                ^ (jnp.uint32(T_FULL) * jnp.uint32(0x85EBCA77))
                ^ (jnp.uint32(r) * jnp.uint32(0x27D4EB2F))
            ).astype(jnp.int32)
            m = np.asarray(dropout_keep_bh(s_call[0], b, h, c, c,
                                           rate=rate))
            keep[:, :, r * c:(r + 1) * c, src * c:(src + 1) * c] = m
    return keep


def test_ring_dropout_matches_masked_oracle(rng):
    """Attention dropout under ring CP (contiguous, ref hops): the ring
    output and grads EXACTLY match a full-sequence oracle applying the
    reconstructed global mask — proving per-hop mask regeneration is
    consistent across the forward and the hand-written backward ring."""
    cp, rate = 2, 0.3
    ctx, mesh = _env(cp)
    b, s, h, d = 2, 32, 2, 8
    q, k, v = _qkv(rng, b=b, s=s, hq=h, hkv=h, d=d)
    key = jax.random.key(21)
    keep = jnp.asarray(_ring_drop_mask(key, cp, b, h, s, rate))

    def ring_loss(q, k, v):
        with ctx:
            o = ring_attention(q, k, v, ctx=ctx, causal=True,
                               impl="reference", dropout_rate=rate,
                               dropout_key=key)
        return (o.astype(jnp.float32) ** 2).sum(), o

    def oracle_loss(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk",
                            q.astype(jnp.float32) / d ** 0.5,
                            k.astype(jnp.float32))
        cm = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(cm[None, None], logits, -1e30)
        a = jax.nn.softmax(logits, axis=-1)
        a = jnp.where(keep, a / (1 - rate), 0.0)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v.astype(jnp.float32))
        return (o ** 2).sum(), o

    # (each side compiled once: op by op, a ring hop under shard_map is
    # dispatched eight devices at a time)
    (lr, outr), gr = jax.jit(jax.value_and_grad(
        ring_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (lo, outo), go = jax.jit(jax.value_and_grad(
        oracle_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(outr), np.asarray(outo),
                               rtol=2e-5, atol=2e-5)
    for a, b_ in zip(gr, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_ring_dropout_zigzag_and_model(rng):
    """Zigzag ring dropout: deterministic, loss-changing, finite grads;
    and the model path trains under cp2 ring + attn_pdrop (the round-5
    gate that forced attn_pdrop=0 under cp is gone)."""
    from hetu_tpu import optim
    from hetu_tpu.engine import build_train_step, init_state, make_plan
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel

    ctx, mesh = _env(2)
    ctx = ActivationSharding(mesh, batch="dp", seq="cp", tp="tp",
                             cp_layout="zigzag")
    q, k, v = _qkv(rng, b=2, s=32, hq=2, hkv=2, d=8)
    key = jax.random.key(4)

    @jax.jit
    def dropped(q, k, v, key):          # ONE program, run twice
        with ctx:
            return ring_attention(q, k, v, ctx=ctx, causal=True,
                                  impl="reference", layout="zigzag",
                                  dropout_rate=0.3, dropout_key=key)

    with ctx:
        base = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, ctx=ctx, causal=True, impl="reference",
            layout="zigzag"))(q, k, v)
    d1, d2 = dropped(q, k, v, key), dropped(q, k, v, key)
    assert not np.allclose(np.asarray(base), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

    cfg = GPTConfig(vocab_size=256, max_positions=128, hidden_size=64,
                    num_layers=2, num_heads=4, attn_pdrop=0.2)
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    ids = jax.random.randint(jax.random.key(1), (8, 65), 0, 256)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    plan = make_plan(model, opt, Strategy(dp=2, cp=2))
    state = init_state(model, opt, plan, jax.random.key(0))
    step = build_train_step(model, opt, plan)
    _, m = step(state, plan.shard_batch(batch))
    assert np.isfinite(float(m["loss"]))


def test_ring_dropout_pallas_matches_ref_hops(rng):
    """The pallas hop family and the ref hop family draw the SAME
    counter-RNG stream (dropout_keep_bh == in-kernel _dropout_keep at
    block origin), so ring dropout outputs must be equal across
    families — interpret-mode kernels on the CPU mesh."""
    ctx, mesh = _env(2)
    q, k, v = _qkv(rng, b=1, s=256, hq=2, hkv=2, d=64)
    key = jax.random.key(13)
    import os
    os.environ["HETU_PALLAS_INTERPRET"] = "1"
    try:
        with ctx:
            ref, pal = (jax.jit(lambda q, k, v, impl=impl: ring_attention(
                q, k, v, ctx=ctx, causal=True, impl=impl, dropout_rate=0.3,
                dropout_key=key))(q, k, v) for impl in ("reference",
                                                         "pallas"))
    finally:
        del os.environ["HETU_PALLAS_INTERPRET"]
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=2e-5, atol=2e-5)
