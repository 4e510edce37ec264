"""Command A+ (``cohere2_moe``) against its plain reference
(``benchmark/reference/cohere2_moe.py``) on seeded random weights at a
tiny size: two periods of [window, window, window, full] layers, window
8, 16 experts top-4, 2 shared experts, GQA 8:2 — everything float32 on
the CPU, so each tolerance is float32 rounding through 8 layers unless
it says otherwise."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from served import ServedArchContract
from benchmark.reference import cohere2_moe as reference
from hetu_tpu.models import Cohere2MoEConfig, Cohere2MoEForCausalLM
from hetu_tpu.models.generation import decode, init_paged_caches
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.ops.rotary import apply_rotary, rope_frequencies

#: float32 sums in another order, through 8 layers, on logits of
#: magnitude ~1: measured ~2e-6, so 1e-4 has room and a wrong mask, a
#: missed rotation or a dropped expert (>= 1e-2) has none
ATOL = 1e-4


VOCAB = Cohere2MoEConfig.tiny().vocab_size


@pytest.fixture(scope="module")
def tiny():
    """The dataclass as the published keys the reference reads, the
    model, its weights."""
    cfg = Cohere2MoEConfig.tiny()
    model = Cohere2MoEForCausalLM(cfg)
    # init_std 0.02 at hidden 64 would leave every logit ~1e-3 and the
    # routing a coin toss on rounding; spread the weights instead
    params = jax.tree.map(
        lambda x: x * 8.0 if x.ndim > 2 or x.shape[0] > 64 else x,
        model.init(jax.random.key(0), dtype=jnp.float32))
    return dataclasses.asdict(cfg), model, params


class TestCohere2MoE(ServedArchContract):
    """(a) ``model(params, ids)`` — the window through the reference
    attention path, RoPE or none by layer, the expert layer, the shared
    mean, the parallel block — against the plain forward. (b) through
    ``ServingEngine``: packed prefill in chunks of 8 beside decode, one
    trace; every emitted token within 1e-3 (float32 both sides; a tie
    closer than that may break either way) of the reference's top logit
    at its position, teacher-forced."""
    reference = reference
    forward_ids = jnp.asarray(
        np.random.default_rng(0).integers(1, VOCAB, (2, 24)))
    tol = ATOL
    lanes = [dict(attn_kernel="paged", prefill_attn="flash_pallas"),
             dict(attn_kernel="reference", prefill_attn="reference")]
    engine = dict(slots=3, max_len=32, prefill_chunk=8, block_size=4)
    prompts = (3, 1, VOCAB, (5, 13, 19))
    max_tokens = 10
    token_tol = 1e-3

    def one_sequence(self, tiny, ids, **control):
        """One sequence, zeros behind it up to the engine's ``max_len``
        where it is no whole number of the reference's attention blocks
        of 8."""
        config, _, params = tiny
        pad = 0 if len(ids) % 8 == 0 else 32 - len(ids)
        seq = jnp.pad(jnp.asarray(ids), (0, pad))[None]
        return reference.logits(params, seq, config,
                                attn_block=8)[0, :len(ids)]

    def close(self, got, want, atol):
        assert np.abs(want).max() > 0.1      # the comparison has teeth
        super().close(got, want, atol)


@pytest.mark.parametrize("kernel", ["paged", "reference"])
@pytest.mark.parametrize("block_size", [4, 8])
def test_chunked_prefill_then_decode_through_the_paged_cache(
        tiny, kernel, block_size):
    """(b) Two requests prefilled in chunks of 6 and then decoded a
    token at a time through the paged arena (the Pallas kernel
    interpreted, and the gather lane), against the reference's full
    forward. Contexts run to 24 with window 8: the window's lower edge
    ``p - 8`` falls inside a page and on a page boundary for both block
    sizes, and pages wholly below it are skipped by the kernel."""
    config, model, params = tiny
    total, chunk, n_req = 24, 6, 2
    ids = np.random.default_rng(1).integers(1, VOCAB, (n_req, total))
    want = np.asarray(reference.logits(params, ids, config, attn_block=8))
    per = total // block_size
    caches = init_paged_caches(model, 1 + n_req * per, block_size)
    # scattered pages, so that a table lookup is really needed
    order = np.random.default_rng(2).permutation(n_req * per) + 1
    tables = jnp.asarray(order.reshape(n_req, per), jnp.int32)
    live = jnp.ones((n_req,), bool)
    step = jax.jit(lambda c, tok, pos: decode(
        model, params, tok, pos, c, slot_mask=live, block_tables=tables,
        attn_kernel=kernel))
    got = []
    at = 0
    for width in [chunk] * 2 + [1] * (total - 2 * chunk):
        pos = jnp.broadcast_to(at + jnp.arange(width), (n_req, width))
        lg, caches = step(caches, jnp.asarray(ids[:, at:at + width]), pos)
        got.append(np.asarray(lg))
        at += width
    np.testing.assert_allclose(np.concatenate(got, axis=1), want,
                               atol=ATOL)


def test_engine_counts_the_share_and_the_dead_window_blocks(tiny):
    """With telemetry on, the engine counts the expert layer's (token,
    choice) pairs from what the fused step RETURNS (each lane's group
    sizes of all layers; no host callback in the step): the per-expert
    counter sums to the assignments, every layer call of a lane that
    ran is counted and none of a lane that did not, and the engine's
    gauge counts the blocks a decoding slot holds wholly below the
    window."""
    from hetu_tpu import telemetry
    from hetu_tpu.serving import SamplingParams, ServingEngine
    _, model, params = tiny
    cfg = model.cfg
    share = Cohere2MoEForCausalLM(
        dataclasses.replace(cfg, local_experts=(4, 8)))
    held = jax.tree.map(lambda x: x, params)
    held["blocks"]["moe"] = {
        "router": params["blocks"]["moe"]["router"],
        **{n: params["blocks"]["moe"][n][:, 4:12]
           for n in ("wg", "wi", "wo")}}
    telemetry.enable(True)
    try:
        reg = telemetry.get_registry()
        names = ("moe_local_calls_total", "moe_local_assignments_total",
                 "moe_local_experts_touched_total")
        before = {n: reg.counter(n).value() for n in names}
        per0 = [reg.counter("moe_local_expert_tokens").value(
            expert=str(e)) for e in range(8)]
        rows0 = {kind: reg.counter("moe_grouped_rows_total").value(
            kind=kind) for kind in ("live", "computed")}
        eng = ServingEngine(share, held, slots=2, max_len=32,
                            prefill_chunk=8, block_size=4,
                            prefix_cache=False)
        prompt = np.random.default_rng(6).integers(
            1, cfg.vocab_size, 11).tolist()
        req = eng.submit(prompt, SamplingParams(max_tokens=12))
        dead = []
        while eng.has_work():
            eng.step()
            dead.append(reg.gauge("kv_window_dead_blocks").value())
        got = {n: reg.counter(n).value() - before[n] for n in names}
        per = [reg.counter("moe_local_expert_tokens").value(
            expert=str(e)) - per0[e] for e in range(8)]
        rows = {kind: reg.counter("moe_grouped_rows_total").value(
            kind=kind) - rows0[kind] for kind in rows0}
    finally:
        telemetry.enable(False)
    assert len(req.tokens) == 12
    # 2 prefill chunks and 11 decode steps, 8 layers each
    assert got["moe_local_calls_total"] == 8 * (2 + 11)
    assert sum(per) == got["moe_local_assignments_total"] > 0
    # what the aligned layout costs: the kernel visits whole tiles of
    # 16 rows, one or more a touched expert and layer call — counted on
    # the host from the same group sizes and the layer's tile rule
    assert rows["live"] == got["moe_local_assignments_total"]
    assert rows["computed"] % 16 == 0 and rows["live"] < rows["computed"] \
        <= 16 * got["moe_local_experts_touched_total"] + rows["live"]
    assert rows["computed"] >= 16 * got["moe_local_experts_touched_total"]
    assert 0 < got["moe_local_experts_touched_total"] \
        <= 8 * got["moe_local_calls_total"]
    # 8 of 16 experts held, top-4: about half of the (token, choice)
    # pairs of 11 + 11 tokens x 8 layers, never more than all of them
    assert got["moe_local_assignments_total"] <= 22 * 4 * 8
    # the next query sits at pos: (pos - 8 + 1) // 4 blocks are dead,
    # 3 once the context has reached 20
    assert max(dead) == (22 - 8 + 1) // 4 and dead[0] == 0


def test_shares_add_up_to_the_uncut_layer(tiny):
    """(c) One layer's FFN: the routed parts of all 8 shares ``(0, 2),
    (2, 2), ...`` of the 16 experts, computed by the program's expert
    layer, plus the shared mean counted once, equal the uncut
    reference's layer output — to float32 rounding (1e-5 on outputs of
    magnitude ~1: nothing here is approximate)."""
    config, model, params = tiny
    cfg = model.cfg
    blk = jax.tree.map(lambda x: x[0], params["blocks"])
    u = jax.random.normal(jax.random.key(4), (37, cfg.hidden_size))
    whole, _ = reference.ffn(blk, u, config)
    total = jnp.zeros_like(u)
    for first in range(0, cfg.num_experts, 2):
        share = ExpertShareMoE(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_experts,
            k=cfg.num_experts_per_tok, local_experts=(first, 2))
        held = {"router": blk["moe"]["router"],
                **{n: blk["moe"][n][first:first + 2]
                   for n in ("wg", "wi", "wo")}}
        total = total + share(held, u)
        # and the reference, given the same share, says the same
        part, _ = reference.ffn({**blk, "moe": held}, u, config,
                                local_experts=(first, 2))
        only_shared, _ = reference.ffn(
            {**blk, "moe": {**held, "wo": held["wo"] * 0}}, u,
            config, local_experts=(first, 2))
        np.testing.assert_allclose(
            np.asarray(share(held, u)),
            np.asarray(part - only_shared), atol=1e-5)
    shared_mean, _ = reference.ffn(
        {**blk, "moe": {**blk["moe"], "wo": blk["moe"]["wo"] * 0}}, u,
        config)
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(np.asarray(total + shared_mean),
                               np.asarray(whole), atol=1e-5)


def test_no_token_is_dropped_when_all_go_to_one_expert():
    """(d) A router that sends every token to expert 5 first (and to
    the same three others): all 64 tokens come back with expert 5's
    output at its full weight — there is no capacity to overflow."""
    moe = ExpertShareMoE(16, 8, 8, k=4, local_experts=(4, 4))
    params = moe.init(jax.random.key(0), dtype=jnp.float32)
    bias = jnp.asarray([3., 2., 1., -9., -9., 9., -9., -9.])
    # a constant input feature carries the bias into every token
    x = jnp.concatenate([jax.random.normal(jax.random.key(1), (64, 15))
                         * 1e-3, jnp.ones((64, 1))], axis=-1)
    params = {**params, "router": jnp.zeros((16, 8)).at[15].set(bias)}
    idx, w = moe.route(params, x)
    assert (np.sort(np.asarray(idx), -1) == [0, 1, 2, 5]).all()
    got = np.asarray(moe(params, x))
    e = 5 - 4
    h = jax.nn.silu(x @ params["wg"][e]) * (x @ params["wi"][e])
    want = np.asarray(w[np.arange(64), np.asarray(idx == 5).argmax(-1)]
                      [:, None] * (h @ params["wo"][e]))
    assert np.abs(want).min(0).max() > 0      # every row has a value
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_interleaved_rope_is_a_pairwise_rotation():
    """(e) ``apply_rotary(interleaved=True)`` against a literal
    rotation of each pair ``(2i, 2i+1)`` by ``p * theta^(-2i/d)``
    (float64 on the host; 1e-5 is float32 rounding of cos and sin at
    positions up to 40) — and it is NOT the split-half convention."""
    d, theta = 16, 50000.0
    x = np.random.default_rng(5).normal(size=(1, 6, 3, d))
    pos = np.asarray([[0, 1, 2, 7, 23, 40]])
    cos, sin = rope_frequencies(d, 64, theta=theta)
    got = np.asarray(apply_rotary(jnp.asarray(x, jnp.float32), cos, sin,
                                  positions=jnp.asarray(pos),
                                  interleaved=True))
    want = np.empty_like(x)
    for t, p in enumerate(pos[0]):
        for i in range(d // 2):
            a = p * theta ** (-2.0 * i / d)
            rot = np.array([[np.cos(a), -np.sin(a)],
                            [np.sin(a), np.cos(a)]])
            want[0, t, :, 2 * i:2 * i + 2] = \
                x[0, t, :, 2 * i:2 * i + 2] @ rot.T
    np.testing.assert_allclose(got, want, atol=1e-5)
    half = np.asarray(apply_rotary(jnp.asarray(x, jnp.float32), cos, sin,
                                   positions=jnp.asarray(pos)))
    assert np.abs(half - want).max() > 0.1
    # the reference's own rotation is the same literal one
    ref = np.asarray(reference.rope_interleaved(
        jnp.asarray(x[0], jnp.float32), jnp.asarray(pos[0]), theta))
    np.testing.assert_allclose(ref, want[0], atol=1e-5)


def test_kernel_experts_equal_ragged_dot_through_two_windows(
        ragged_dot_experts):
    """A share of 2 of 16 experts that the router crowds (every token
    picks both): 160 live pairs walk the window loop in TWO windows of
    128 rows, each laid out on its own — against three ``ragged_dot``
    calls over all the sorted rows at once."""
    moe = ExpertShareMoE(16, 8, 16, k=4, local_experts=(4, 2))
    params = moe.init(jax.random.key(0), dtype=jnp.float32)
    bias = jnp.full((16,), -9.0).at[jnp.asarray([0, 1, 4, 5])].set(
        jnp.asarray([3., 2., 6., 5.]))
    x = jnp.concatenate([jax.random.normal(jax.random.key(1), (80, 15)),
                         jnp.ones((80, 1))], axis=-1)
    params = {**params, "router": params["router"].at[15].set(bias)}
    assert moe._window_rows(80 * 4) == 128
    out, st = jax.jit(lambda p, x: moe(p, x, return_stats=True))(
        params, x)
    assert st["sizes"].tolist() == [80, 80]      # two windows of 128
    want = ragged_dot_experts(moe, params, x)
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(out, want, atol=1e-6)

