"""SDAR-MoE (``models/sdar_moe.py``) and the block lane
(``serving/block_diffusion.py``): the block bound in the attention
paths against an explicit mask, the softmax route, the eight shares of
an expert layer, and generation by diffusion over blocks through the
engine against the plain reference's ``block_diffusion_generate``
(``benchmark/reference/sdar_moe.py``) token for token and pass for
pass — on the CPU, at the tiny preset, seeded."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from served import ServedArchContract
from benchmark.reference import sdar_moe as reference
from hetu_tpu import telemetry
from hetu_tpu.engine import trace_counts
from hetu_tpu.models import generation
from hetu_tpu.models.sdar_moe import (
    BlockDiffusion, SDARMoEConfig, SDARMoEForCausalLM,
)
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.ops.attention import (
    attention_reference, attention_with_lse, block_bound,
)
from hetu_tpu.ops.paged_pallas import (
    decode_work_list, paged_attention_pallas, paged_attention_reference,
)
from hetu_tpu.serving import ServingEngine
from hetu_tpu.serving.block_diffusion import (
    BlockGenerationNotSupported, denoise_slots, lane_rows,
)
from hetu_tpu.serving.scheduler import SamplingParams


def _config(cfg: SDARMoEConfig, **over) -> dict:
    """``cfg`` as the reference reads it (the published keys)."""
    g = cfg.generation
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "block_length": g.block_length,
            "mask_token_id": g.mask_token_id,
            "denoising_steps": g.denoising_steps,
            "remasking": g.remasking,
            "confidence_threshold": g.confidence_threshold, **over}


@pytest.fixture(scope="module")
def tiny():
    cfg = SDARMoEConfig.tiny()
    model = SDARMoEForCausalLM(cfg)
    return cfg, model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def sharp():
    """A tiny model whose confidences pass a threshold: weights drawn
    ten times as wide."""
    cfg = SDARMoEConfig.tiny(init_std=0.2)
    model = SDARMoEForCausalLM(cfg)
    return cfg, model, model.init(jax.random.key(3))


# -- the block bound ---------------------------------------------------------

def _explicit(q, k, v, qpos, block):
    """Attention under the mask written out: key ``j`` is seen from
    position ``p`` iff ``j // block <= p // block``."""
    hq, hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.arange(k.shape[1])[None, None, :] // block \
        <= qpos[:, :, None] // block
    s = jnp.where(seen[:, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("block", [1, 4])
def test_block_bound_in_attention_reference(block):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 8, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
            for _ in range(2))
    off = jnp.asarray([4, 12], jnp.int32)
    out = attention_reference(q, k, v, causal=True, q_offset=off,
                              block=block)
    qpos = off[:, None] + jnp.arange(8)[None]
    np.testing.assert_allclose(out, _explicit(q, k, v, qpos, block),
                               atol=1e-5)
    plain = attention_reference(q, k, v, causal=True, q_offset=off)
    if block == 1:
        assert (np.asarray(out) == np.asarray(plain)).all()
    else:
        assert np.abs(np.asarray(out - plain)).max() > 1e-3
    # the scalar-offset branch (the whole-sequence forward)
    out0 = attention_reference(q, k[:, :8], v[:, :8], causal=True,
                               block=block)
    np.testing.assert_allclose(out0, _explicit(
        q, k[:, :8], v[:, :8], jnp.arange(8)[None].repeat(2, 0), block),
        atol=1e-5)
    assert block_bound(5, 1) == 5 and block_bound(5, 4) == 7
    with pytest.raises(ValueError):
        block_bound(5, 3)
    with pytest.raises(ValueError):
        attention_reference(q, k, v, block=4)


@pytest.mark.parametrize("block,R", [(1, 4), (4, 4), (4, 8)])
def test_block_bound_in_the_paged_call(block, R):
    """The kernel (interpret mode) over block tables == the mask
    written out, rows of a slot at its own offset — ``B`` rows, or the
    block lane's ``2B`` that straddle two blocks: the lower four see
    nothing of the upper four's block; at 1 the call is the causal one
    to the bit, and so is the work list."""
    rng = np.random.default_rng(1)
    S, hq, hkv, d, bs, W, nb = 3, 4, 2, 16, 4, 8, 40
    q = jnp.asarray(rng.normal(size=(S, R, hq, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(nb, bs, hkv * d)), jnp.float32)
            for _ in range(2))
    tbl = jnp.asarray(np.stack([rng.permutation(np.arange(1, nb))[:W]
                                for _ in range(S)]), jnp.int32)
    off = jnp.asarray([0, 8, 20], jnp.int32)
    live = jnp.asarray([True, True, True])
    kw = {} if block == 1 else {"block": block}
    gather = lambda x: jnp.take(x, tbl, axis=0).reshape(S, W * bs, hkv, d)
    want = _explicit(q, gather(k), gather(v),
                     off[:, None] + jnp.arange(R)[None], block)
    for pages in (1, 2, 8):
        out = paged_attention_pallas(q, k, v, tbl, off, live=live,
                                     pages_per_step=pages, **kw)
        np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_allclose(
        paged_attention_reference(q, k, v, tbl, off, **kw), want,
        atol=1e-5)
    if R == 2 * block:
        # the carry rows' result is the call's of those rows alone, and
        # the work list ends at the chunk of the upper block's end
        alone = paged_attention_pallas(q[:, :block], k, v, tbl, off,
                                       live=live, pages_per_step=1, **kw)
        np.testing.assert_allclose(out[:, :block], alone, atol=1e-6)
        _, chunk, n = decode_work_list(off, live, rows=R, span=4,
                                       n_steps=8, **kw)
        assert int(n) == 2 + 4 + 7
        assert np.asarray(chunk)[:int(n)].tolist() == [
            *range(2), *range(4), *range(7)]
    if block == 1:
        plain = paged_attention_pallas(q, k, v, tbl, off, live=live)
        assert (np.asarray(plain) == np.asarray(
            paged_attention_pallas(q, k, v, tbl, off, live=live,
                                   block=1))).all()
        for a, b in zip(
                decode_work_list(off, live, rows=R, span=8, n_steps=4),
                decode_work_list(off, live, rows=R, span=8, n_steps=4,
                                 block=1)):
            assert (np.asarray(a) == np.asarray(b)).all()
    with pytest.raises(ValueError):
        paged_attention_pallas(q, k, v, tbl, off, block=4, window=8)


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_block_bound_in_the_flash_forward(block, impl):
    """The packed lane's in-pack attention: two requests' runs of whole
    blocks in one row, ids isolating them."""
    rng = np.random.default_rng(2)
    C, hq, hkv, d = 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(1, C, hq, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, C, hkv, d)), jnp.float32)
            for _ in range(2))
    seg = jnp.asarray([[0] * 12 + [1] * 16 + [-1] * 4], jnp.int32)
    kw = {} if block == 1 else {"block": block}
    out, lse = attention_with_lse(q, k, v, causal=True, segment_ids=seg,
                                  impl=impl, interpret=True, **kw)
    kk, vv = (jnp.repeat(x, hq // hkv, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(d)
    at = jnp.arange(C)
    seen = (at[None, :] // block <= at[:, None] // block) \
        & (seg[0][:, None] == seg[0][None, :])
    s = jnp.where(seen[None, None], s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
    live = np.asarray(seg[0] >= 0)
    np.testing.assert_allclose(np.asarray(out)[0][live],
                               np.asarray(want)[0][live], atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(lse)[0][:, live],
        np.asarray(jax.nn.logsumexp(s, -1))[0][:, live], atol=1e-5)
    if block == 1:
        plain, _ = attention_with_lse(q, k, v, causal=True,
                                      segment_ids=seg, impl=impl,
                                      interpret=True)
        assert (np.asarray(plain) == np.asarray(out)).all()


def test_flash_forward_refuses_a_bound_its_tiles_do_not_hold():
    from hetu_tpu.ops.flash_pallas import _flash_fwd
    q = jnp.zeros((1, 2, 8, 16))
    with pytest.raises(ValueError, match="whole blocks"):
        _flash_fwd(q, q, q, None, None, causal=True, scale=1.0,
                   interpret=True, block=16)
    with pytest.raises(ValueError, match="whole blocks"):
        _flash_fwd(q, q, q, None, None, causal=False, scale=1.0,
                   interpret=True, block=4)


# -- the route and the shares ------------------------------------------------

def test_softmax_route_is_the_references_and_sigmoid_is_untouched():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    moe = ExpertShareMoE(32, 16, 8, k=2, score="softmax")
    p = moe.init(jax.random.key(1))
    idx, w = moe.route(p, x)
    ridx, rw, _ = reference.route(p["router"], x,
                                  {"num_experts_per_tok": 2})
    assert (np.asarray(idx) == np.asarray(ridx)).all()
    np.testing.assert_allclose(w, rw, rtol=1e-6)
    # the sigmoid routes, as they were: the formula written out
    z = jnp.matmul(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    for kw in ({}, {"select_bias": True, "scale": 2.5},
               {"select_bias": True, "n_group": 4, "topk_group": 2}):
        m = ExpertShareMoE(32, 16, 8, k=2, **kw)
        pp = dict(m.init(jax.random.key(1)), router=p["router"])
        idx, w = m.route(pp, x)
        s = jax.nn.sigmoid(z)
        if not kw:
            top, want = jax.lax.top_k(s, 2)
            assert (np.asarray(idx) == np.asarray(want)).all()
            assert (np.asarray(w) == np.asarray(
                top / top.sum(-1, keepdims=True))).all()
        else:
            top = jnp.take_along_axis(s, idx, axis=-1)
            ww = top / top.sum(-1, keepdims=True) * (kw.get("scale") or 1)
            assert (np.asarray(w) == np.asarray(ww)).all()
    for bad in ({"score": "softmax", "select_bias": True},
                {"score": "softmax", "n_group": 2, "topk_group": 1},
                {"score": "tanh"}):
        with pytest.raises(ValueError):
            ExpertShareMoE(32, 16, 8, k=2, **bad)


def test_the_eight_shares_of_one_expert_layer_add_up():
    """Each share holds one expert of eight (the router eight wide);
    their outputs add up to the uncut reference's layer."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(20, 32)), jnp.float32)
    whole = ExpertShareMoE(32, 16, 8, k=2, score="softmax")
    p = whole.init(jax.random.key(2))
    want, _ = reference.moe({"moe": p}, x, {
        "num_experts": 8, "num_experts_per_tok": 2})
    total = 0.0
    for e in range(8):
        share = ExpertShareMoE(32, 16, 8, k=2, score="softmax",
                               local_experts=(e, 1))
        ps = dict(p, **{n: p[n][e:e + 1] for n in ("wg", "wi", "wo")})
        total = total + share(ps, x)
        one, _ = reference.moe({"moe": ps}, x, {
            "num_experts": 8, "num_experts_per_tok": 2}, (e, 1))
        np.testing.assert_allclose(share(ps, x), one, atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    np.testing.assert_allclose(whole(p, x), want, atol=1e-5)


def test_a_share_in_both_forms_equals_ragged_dot(ragged_dot_experts,
                                                 grouped_form):
    """Two of eight experts under the softmax top-2 (the block lane's
    share in small): the fused call and the three split ones against
    ``ragged_dot`` over the dense sorted rows."""
    moe = ExpertShareMoE(32, 16, 8, k=2, score="softmax",
                         local_experts=(2, 2))
    p = moe.init(jax.random.key(2))
    x = jax.random.normal(jax.random.key(3), (40, 32))
    assert moe.grouped_form(40 * 2) == grouped_form
    out, st = jax.jit(lambda p, x: moe(p, x, return_stats=True))(p, x)
    assert 0 < int(st["sizes"].sum()) < 40 * 2
    want = ragged_dot_experts(moe, p, x)
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(out, want, atol=1e-5)


# -- the model against the reference ----------------------------------------

def test_prefill_then_block_passes_through_the_arena(tiny):
    """The prompt's whole blocks written through the paged arena (a
    batch row a token, the reference lane), then the block lane's rows
    — a noised block of 4 at its positions — read it back: the logits
    are the reference's streams' (the clean one's where the block holds
    no mask)."""
    cfg, model, p = tiny
    rng = np.random.default_rng(6)
    B, M = 4, cfg.vocab_size - 1
    seq = rng.integers(1, M, 16).astype(np.int32)
    bs, W = 4, 8
    caches = model.blocks.init_paged_caches(W + 1, bs, jnp.float32)
    bt = jnp.arange(1, W + 1, dtype=jnp.int32)[None]
    for lo in (0, 8):                      # two chunks of two blocks
        pos = np.arange(lo, lo + 8)
        _, caches = generation.decode(
            model, p, seq[pos][:, None], pos[:, None].astype(np.int32),
            caches, slot_mask=jnp.ones(8, bool),
            block_tables=jnp.repeat(bt, 8, axis=0))
    noised = np.stack([np.where([1, 0, 1, 1], M, seq[12:16]),
                       np.where([0, 0, 1, 0], M, seq[12:16]),
                       seq[12:16]]).astype(np.int32)
    clean = seq.copy()
    hc, hn, _ = reference.streams(
        p, clean, np.concatenate(
            [np.repeat(seq[None, 8:12], 3, 0), noised], axis=1),
        _config(cfg), start=8)
    want = np.asarray(hn @ p["lm_head"]["weight"].T)[:, 4:]
    for i, blk in enumerate(noised):
        lg, caches = generation.decode(
            model, p, blk[None], np.arange(12, 16, dtype=np.int32)[None],
            caches, slot_mask=jnp.ones(1, bool), block_tables=bt)
        np.testing.assert_allclose(lg[0], want[i], atol=3e-5)
    # the last rows held no mask: the clean stream's logits
    np.testing.assert_allclose(
        lg[0], np.asarray(hc @ p["lm_head"]["weight"].T)[12:], atol=3e-5)


# -- the sampler -------------------------------------------------------------

V, M = 12, 11


def _slot_logits(slots):
    """Logits whose top tokens are 3, 5, 7, 2 at heights -2, 6, -1, 5
    (confidence order: positions 1, 3, 2, 0), the mask id above all."""
    lg = np.full((slots, 4, V), -4.0, np.float32)
    lg[..., M] = 9.0                         # never drawn
    for i, (tok, height) in enumerate(
            [(3, -2.0), (5, 6.0), (7, -1.0), (2, 5.0)]):
        lg[:, i, tok] = height
    return jnp.asarray(lg)


def _denoise(tok, masked, passes, steps, dynamic, thresh, live, prev,
             carry):
    out = denoise_slots(
        _slot_logits(len(tok)), jnp.asarray(tok, jnp.int32),
        jnp.asarray(masked), jnp.asarray(passes), jnp.asarray(steps),
        jnp.asarray(dynamic), jnp.asarray(thresh, jnp.float32),
        jnp.asarray(live), jnp.asarray(prev, jnp.int32),
        jnp.asarray(carry), mask_id=M)
    return [np.asarray(x).tolist() for x in out]


#: one slot a case: (tok, masked, pass, steps, dynamic, thresh, live,
#: prev, carry) -> (committed, ncommit, tok, masked, pass, prev, carry)
T, F = True, False
SLOT_CASES = {
    # static, one a pass: the most confident MASKED position (3); the
    # carry rows ran in this pass
    "a_carried_slot_clears_it": (
        ([M, 9, M, M], [T, F, T, T], 1, 4, F, 0.0, T, [1, 2, 3, 4], T),
        ([0] * 4, 0, [M, 9, M, 2], [T, F, T, F], 2, [1, 2, 3, 4], F)),
    # the pass that removes the last mask hands the block on: B tokens,
    # the carry set, B masks at pass 0
    "the_last_mask_hands_the_block_on": (
        ([M, 9, 8, 6], [T, F, F, F], 3, 4, F, 0.0, T, [1, 2, 3, 4], F),
        ([3, 9, 8, 6], 4, [M] * 4, [T] * 4, 0, [3, 9, 8, 6], T)),
    # steps of 1: every pass finishes a block, carried or not
    "steps_of_1": (
        ([M] * 4, [T] * 4, 0, 1, F, 0.0, T, [1, 2, 3, 4], T),
        ([3, 5, 7, 2], 4, [M] * 4, [T] * 4, 0, [3, 5, 7, 2], T)),
    # two a pass, the second pass of two finishes
    "steps_of_2_finish": (
        ([M, 5, M, 2], [T, F, T, F], 1, 2, F, 0.0, T, [0] * 4, F),
        ([3, 5, 7, 2], 4, [M] * 4, [T] * 4, 0, [3, 5, 7, 2], T)),
    # dynamic: positions 1 and 3 pass 0.9 (two, at least one)
    "the_dynamic_rule": (
        ([M] * 4, [T] * 4, 0, 4, T, 0.9, T, [1, 2, 3, 4], T),
        ([0] * 4, 0, [M, 5, M, 2], [T, F, T, F], 1, [1, 2, 3, 4], F)),
    # dynamic, a threshold every position passes: one pass finishes
    "the_dynamic_rule_finishes": (
        ([M] * 4, [T] * 4, 0, 4, T, 0.0, T, [1, 2, 3, 4], F),
        ([3, 5, 7, 2], 4, [M] * 4, [T] * 4, 0, [3, 5, 7, 2], T)),
    # a dead slot keeps its own, its pending carry too
    "a_dead_slot_keeps_its_own": (
        ([M, 9, M, M], [T, F, T, T], 2, 4, F, 0.0, F, [1, 2, 3, 4], T),
        ([0] * 4, 0, [M, 9, M, M], [T, F, T, T], 2, [1, 2, 3, 4], T)),
}


@pytest.mark.parametrize("case", sorted(SLOT_CASES))
def test_denoise_slots_by_the_state_of_a_slot(case):
    state, want = SLOT_CASES[case]
    got = _denoise(*([x] for x in state))
    assert [g[0] for g in got] == list(want)


def test_denoise_slots_of_different_states_share_one_call():
    """Every case's slot in ONE call: what a slot gets is its own
    state's, whatever its neighbours are at."""
    cases = sorted(SLOT_CASES)
    got = _denoise(*map(list, zip(*(SLOT_CASES[c][0] for c in cases))))
    for i, c in enumerate(cases):
        assert [g[i] for g in got] == list(SLOT_CASES[c][1]), c


def test_lane_rows_stand_below_and_at_the_block():
    """The ``2B`` rows a slot: carrying, not carrying, a first block
    below ``B`` (no position below zero, its logits' rows first), dead."""
    tok = np.arange(10, 26).reshape(4, 4)
    prev = np.arange(50, 66).reshape(4, 4)
    tokens, pos, writes, early = map(np.asarray, lane_rows(
        jnp.asarray([8, 12, 0, 4]), jnp.asarray(tok), jnp.asarray(prev),
        jnp.asarray([True, False, True, True]),
        jnp.asarray([True, True, True, False]), mask_id=M))
    assert early.tolist() == [False, False, True, False]
    assert pos[:, 0].tolist() == [4, 8, 0, 0] and (pos >= 0).all()
    assert (np.diff(pos, axis=1) == 1).all()
    for s in (0, 1, 3):
        assert tokens[s].tolist() == [*prev[s], *tok[s]]
    assert tokens[2].tolist() == [*tok[2], M, M, M, M]
    assert writes.tolist() == [[True] * 8, [False] * 4 + [True] * 4,
                               [True] * 4 + [False] * 4, [False] * 8]


# -- the engine against the reference's generation loop ----------------------

def _engine(model, p, **kw):
    return ServingEngine(model, p, **{**dict(
        slots=2, max_len=64, prefill_chunk=8, block_size=8), **kw})


def _generate(eng, prompts, params):
    reqs = [eng.submit(list(map(int, pr)), sp)
            for pr, sp in zip(prompts, params)]
    eng.run_until_drained()
    return [(r.tokens, r.unmask_pass) for r in reqs]


CASES = {
    # (prompt lengths, max_tokens, sampling knobs)
    "static4_tails_and_cuts": ((8, 9, 10, 11, 3, 16), (8, 7, 5, 9, 12, 4),
                               {}),
    "static2": ((8, 5, 14), (8, 6, 11), {"denoising_steps": 2}),
    "static3_remainder_first": ((8, 6), (8, 9), {"denoising_steps": 3}),
    # every pass finishes a block and carries the one before it
    "static1_a_block_a_pass": ((8, 7, 2), (12, 9, 10),
                               {"denoising_steps": 1}),
    # prompts shorter than a block: the first block starts at 0, where
    # carry rows would stand below it
    "prompts_shorter_than_a_block": ((1, 2, 3, 3), (11, 6, 9, 4), {}),
    # tails of 1-3 tokens begin the first block
    "tails_of_1_to_3": ((5, 6, 7, 13, 14, 15), (8, 8, 8, 5, 6, 7), {}),
}



class TestSDARMoE(ServedArchContract):
    """Generation by diffusion over blocks: the forward is the
    reference's block-causal one, the engine's cases hold it to the
    reference's generation loop token for token and PASS for pass, and
    what a block lane cannot do is refused as such."""
    tol = 2e-5
    forward_ids = jax.random.randint(jax.random.key(1), (2, 20), 0, 95)
    small_engine = dict(slots=2, max_len=64, prefill_chunk=8, block_size=8)
    refused = sorted(dict(
        spec_depth=dict(spec_depth=2), prefix_cache=dict(prefix_cache=True),
        preempt=dict(preempt=True),
        spill=dict(spill_host_budget_bytes=1e6),
        long_max_len=dict(long_max_len=128), int8=dict(cache_dtype=jnp.int8),
        w8a8=dict(w8a8="on"), tenancy=dict(tenancy=True)).items())

    def one_sequence(self, tiny, ids, **control):
        cfg, _, p = tiny
        return reference.logits(p, ids[None], _config(cfg), **control)[0]

    def refusal(self, name):
        return BlockGenerationNotSupported, "diffusion over blocks"

    def test_model_matches_the_reference(self, tiny):
        super().test_model_matches_the_reference(tiny)
        cfg, model, p = tiny
        ids = np.asarray(self.forward_ids)
        # a later block is not seen; the own block is, whole
        later = np.asarray(model(p, np.where(np.arange(20) == 8, 7, ids)))
        assert np.abs(later - np.asarray(model(p, ids)))[:, :8].max() == 0
        own = np.asarray(model(p, np.where(np.arange(20) == 7, 7, ids)))
        assert np.abs(own - np.asarray(model(p, ids)))[:, 4].max() > 1e-4
        assert model.generation == BlockDiffusion(4, 95, 4)
        with pytest.raises(ValueError):
            BlockDiffusion(3, 0, 2)
        with pytest.raises(ValueError):
            BlockDiffusion(4, 0, 5)

    @pytest.fixture(scope="class")
    def served(self, tiny):
        """ONE engine for the cases below — they differ only in what
        they ask of it — and the traces counted before it was built."""
        _, model, p = tiny
        return trace_counts().get("serving_step", 0), _engine(model, p)

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_engine_serves_tokens_the_reference_puts_on_top(
            self, tiny, served, case):
        """Token for token and pass for pass. More requests than slots
        (a slot is taken by a second request, and by the next case's),
        of lengths that put the two slots at different passes in one
        step; every case through the ONE executable of one trace."""
        cfg, model, p = tiny
        traces0, eng = served
        lens, outs, knobs = CASES[case]
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 95, n) for n in lens]
        got = _generate(eng, prompts, [SamplingParams(max_tokens=t, **knobs)
                                       for t in outs])
        for pr, t, (toks, at) in zip(prompts, outs, got):
            want = reference.block_diffusion_generate(
                p, pr, _config(cfg), max_tokens=t, **knobs)
            assert (toks, at) == want
            assert len(toks) == t and 95 not in toks
        assert eng.step_executables() == 1
        assert trace_counts()["serving_step"] - traces0 == 1


def test_engine_dynamic_remasking_with_a_threshold_that_fires(sharp):
    cfg, model, p = sharp
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 95, n) for n in (8, 7, 12, 9)]
    knobs = dict(remasking="low_confidence_dynamic",
                 confidence_threshold=0.5)
    eng = _engine(model, p)
    got = _generate(eng, prompts, [SamplingParams(max_tokens=12, **knobs),
                                   SamplingParams(max_tokens=9, **knobs),
                                   SamplingParams(max_tokens=8),
                                   SamplingParams(max_tokens=10, **knobs)])
    fired = False
    for pr, (toks, at), kn in zip(prompts, got,
                                  (knobs, knobs, {}, knobs)):
        want = reference.block_diffusion_generate(
            p, pr, _config(cfg), max_tokens=len(toks), **kn)
        assert (toks, at) == want
        # a pass that unmasked two positions: the threshold fired
        fired |= bool(kn) and len(at) - len(set(
            (i // 4, a) for i, a in enumerate(at))) > 0
    assert fired
    assert eng.step_executables() == 1


def test_engine_stops_at_a_block_holding_the_stop_id(tiny):
    cfg, model, p = tiny
    prompt = np.random.default_rng(9).integers(1, 95, 8)
    free, _ = reference.block_diffusion_generate(
        p, prompt, _config(cfg), max_tokens=12)
    eos = free[5]
    want = reference.block_diffusion_generate(
        p, prompt, _config(cfg), max_tokens=12, eos_id=eos)
    eng = _engine(model, p)
    (toks, at), = _generate(eng, [prompt], [SamplingParams(
        max_tokens=12, eos_id=eos)])
    assert (toks, at) == want and toks[-1] == eos and len(toks) < 12


def _drain_one_slot(eng, reqs):
    """Step ``eng`` (one slot) until drained: each request's block
    table as it stood while the request held the slot, and whether the
    slot's carry was pending after each step."""
    tables, pending = {}, []
    while eng.has_work():
        eng.step()
        for r in reqs:
            if eng._slot_req[0] is r:
                tables[r.id] = eng._bt[0].copy()
        pending.append(bool(eng._blk["blk_carry"][0]))
    return tables, pending


def _arena_gap(model, p, eng, req, table):
    """The largest distance between the arena's K/V at the positions of
    ``req``'s prompt and of every block it was handed but the last, and
    the CLEAN stream's: the same tokens written through a fresh arena
    of the same table, a batch row a token (the prefill lane's own
    arithmetic, ``test_prefill_then_block_passes_through_the_arena``)."""
    B = model.generation.block_length
    seq = np.asarray(list(req.prompt) + req.tokens, np.int32)
    n = (len(seq) - 1) // B * B          # the last block is never carried
    bt = jnp.asarray(table)[None]
    clean = jax.tree.map(jnp.zeros_like, eng.pool.caches)
    pos = np.arange(n, dtype=np.int32)
    _, clean = generation.decode(
        model, p, seq[:n, None], pos[:, None], clean,
        slot_mask=jnp.ones(n, bool), block_tables=jnp.repeat(bt, n, 0))
    bs = eng.pool.block_size
    rows = np.asarray(bt)[0, pos // bs] * bs + pos % bs
    flat = lambda c: np.asarray(c).reshape(  # noqa: E731
        c.shape[0], -1, c.shape[-1])[:, rows]
    return max(np.abs(flat(a) - flat(b)).max()
               for a, b in zip(eng.pool.caches, clean))


@pytest.mark.parametrize("carry_writes", [True, False])
def test_the_arena_holds_the_clean_keys_of_every_handed_block(
        tiny, monkeypatch, carry_writes):
    """After a request of a tail and three blocks: the K/V of its
    prompt and of every block but the last are the clean stream's — and
    NOT where the carry rows are kept from writing (the benchmark's
    ``no_commit_pass`` control in small: the keys then are the last
    denoise pass's, written from a block that still held a mask)."""
    from hetu_tpu.serving import engine as engine_mod
    cfg, model, p = tiny
    if not carry_writes:
        def no_carry_writes(*a, **kw):
            tokens, pos, writes, early = lane_rows(*a, **kw)
            B = tokens.shape[1] // 2
            return tokens, pos, writes & (
                early[:, None] | (jnp.arange(2 * B) >= B)), early
        monkeypatch.setattr(engine_mod, "lane_rows", no_carry_writes)
    prompt = np.random.default_rng(11).integers(1, 95, 10)
    eng = _engine(model, p, slots=1)
    req = eng.submit(list(map(int, prompt)), SamplingParams(max_tokens=10))
    tables, _ = _drain_one_slot(eng, [req])
    assert len(req.tokens) == 10
    gap = _arena_gap(model, p, eng, req, tables[req.id])
    if carry_writes:
        assert gap < 2e-5
        assert (req.tokens, req.unmask_pass) == \
            reference.block_diffusion_generate(
                p, prompt, _config(cfg), max_tokens=10)
    else:
        assert gap > 1e-2


def test_block_lane_counters_span_field_and_result(tiny):
    cfg, model, p = tiny
    telemetry.enable(True)
    telemetry.get_tracer().clear()
    reg = telemetry.get_registry()
    passes = reg.counter("serving_diffusion_passes_total")
    per_block = reg.histogram("serving_diffusion_passes_per_block")
    names = ("serving_diffusion_blocks_total",
             "serving_diffusion_carried_blocks_total",
             "serving_diffusion_tokens_total")

    def read():
        h = per_block.summary()
        return [passes.value(kind="denoise"), passes.value(kind="commit")] \
            + [reg.counter(n).value() for n in names] \
            + [h["count"], h["sum"]]

    before = read()
    eng = _engine(model, p)
    req = eng.submit(list(range(1, 9)), SamplingParams(max_tokens=10))
    eng.run_until_drained()
    # 3 blocks of 4 passes and no pass beside them: the first two were
    # carried into the next one's first pass, the last never; 10 of 12
    # tokens handed on; 4 passes a block
    assert [b - a for a, b in zip(before, read())] == \
        [12, 0, 3, 2, 10, 3, 12]
    # the lane's LIVE rows: a block a pass, and the carried block's on
    # passes 4 and 8 (the first iteration is the prompt's prefill)
    rows = [e.attrs["lane_rows"] for e in telemetry.get_tracer().events()
            if e.name == "serve/step" and e.attrs["active"]]
    assert rows == [4, 4, 4, 4, 8, 4, 4, 4, 8, 4, 4, 4]
    res = req.result()
    assert res["unmask_pass"] == req.unmask_pass
    assert sorted(res["unmask_pass"][:4]) == [0, 1, 2, 3]
    assert res["timing"]["ttft_ms"] > 0
    # a model that yields a token a step has no such field
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    gpt = GPTLMHeadModel(GPTConfig.tiny())
    e2 = ServingEngine(gpt, gpt.init(jax.random.key(0)), slots=1,
                       max_len=32, prefill_chunk=8)
    r2 = e2.submit([1, 2, 3], SamplingParams(max_tokens=2))
    e2.run_until_drained()
    assert "unmask_pass" not in r2.result()
    with pytest.raises(ValueError, match="diffusion over blocks"):
        e2.submit([1, 2, 3], SamplingParams(denoising_steps=2))


def test_a_slot_taken_again_begins_without_its_last_requests_carry(tiny):
    """A request's last block leaves its carry pending; the slot's next
    request (a prompt of whole blocks: its first pass stands above
    positions the prefill wrote) must not write it."""
    cfg, model, p = tiny
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, 95, n) for n in (8, 12)]
    eng = _engine(model, p, slots=1)
    reqs = [eng.submit(list(map(int, pr)), SamplingParams(max_tokens=8))
            for pr in prompts]
    tables, pending = _drain_one_slot(eng, reqs)
    # a prefill and 2 blocks of 4 passes; the second request's 2
    # prefills (pending until the second one ends and its first block
    # begins) and its 2 blocks
    a_request = ([False] * 3 + [True]) * 2
    assert pending == [False] + a_request + [True, False] + a_request
    assert _arena_gap(model, p, eng, reqs[1], tables[reqs[1].id]) < 2e-5
    for pr, r in zip(prompts, reqs):
        assert (r.tokens, r.unmask_pass) == \
            reference.block_diffusion_generate(
                p, pr, _config(cfg), max_tokens=8)


# -- what a block lane refuses -----------------------------------------------

def test_block_generation_refuses_at_submit_and_hand_over(tiny):
    _, model, p = tiny
    eng = _engine(model, p)
    assert eng.prefix_cache is None and not eng.preempt
    for sp, kw in ((SamplingParams(temperature=0.7), {}),
                   (SamplingParams(), {"handoff": True})):
        with pytest.raises(BlockGenerationNotSupported):
            eng.submit([1, 2, 3, 4], sp, **kw)
    for bad in (dict(denoising_steps=5), dict(denoising_steps=0),
                dict(remasking="sequential")):
        with pytest.raises(ValueError):
            eng.submit([1, 2, 3, 4], SamplingParams(**bad))
    with pytest.raises(BlockGenerationNotSupported):
        eng.export_prefix([1, 2, 3, 4])
    with pytest.raises(BlockGenerationNotSupported):
        eng.prefill_only([1, 2, 3, 4])
    req = eng.submit([1, 2, 3, 4], SamplingParams(max_tokens=4))
    with pytest.raises(BlockGenerationNotSupported):
        eng.evict_request(req)
    with pytest.raises(ValueError, match="whole blocks"):
        _engine(model, p, max_len=62)
    # a request's worst case ends on a whole block
    assert eng.scheduler.blocks_needed(dataclasses.replace(
        req, prompt=np.arange(6), sampling=SamplingParams(
            max_tokens=3))) == 2            # 9 -> 12 positions, pages of 8
