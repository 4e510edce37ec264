"""The latent-attention (MLA) decoder with bias-corrected routed experts
behind a leading dense layer (``hetu_tpu/models/mla_moe.py``,
``nn.parallel.LatentAttention``, the latent paged call of
``ops/paged_pallas.py``), on the CPU at tiny widths with seeded random
weights: 1 dense + 3 expert layers, hidden 64, 4 heads of nope 16 / rope
8 / v 16, latent 32, 8 experts top-3 with a selection bias, 2 shared,
scale 2.446. The oracle is the plain reference
(``benchmark/reference/mla_moe.py``), which shares no code with the
model. Every tolerance says why it is what it is."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from served import ServedArchContract
from benchmark.reference import mla_moe as reference
from hetu_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import LatentKVNotSupported
from hetu_tpu.ops.paged_pallas import (
    pack_history_tiles, paged_attention_pallas,
    paged_attention_reference, paged_history_attention,
)

#: both sides compute in float32 at the highest matmul precision
#: (conftest) and differ by the order of their sums only: a few ulps of
#: logits of magnitude ~1
F32 = 2e-5


def _published(cfg: MLAMoEConfig) -> dict:
    """The reference reads the published key names."""
    keys = ("vocab_size hidden_size intermediate_size "
            "moe_intermediate_size num_hidden_layers num_attention_heads "
            "kv_lora_rank qk_nope_head_dim qk_rope_head_dim v_head_dim "
            "n_routed_experts n_shared_experts num_experts_per_tok "
            "routed_scaling_factor first_k_dense_replace rms_norm_eps "
            "rope_theta").split()
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module", params=[None, 48],
                ids=["row40", "row48padded"])
def tiny(request):
    """The tiny model, with the arena row as it is (40) and padded;
    its configuration under the published keys the reference reads."""
    model = MLAMoEForCausalLM(MLAMoEConfig.tiny(stored_row=request.param))
    return _published(model.cfg), model, model.init(jax.random.key(30))


def _ids(n, seed=0, rows=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 128, (rows, n)), jnp.int32)


# -- (a) whole-sequence logits; (b) chunked prefill, then decoding through
# -- the latent arena --------------------------------------------------------

class TestMLAMoE(ServedArchContract):
    """(b): prompts of 37, 9 and 20 tokens over pages of 8 and chunks
    of 16 (contexts cross page and chunk boundaries; three requests on
    two slots, so a freed slot stands beside a live one and is taken
    again), through the gather lane and through the interpreted kernel
    with the history read in tiles. Every emitted token must be the
    reference's argmax at its position, up to float32 ties."""
    reference = reference
    forward_ids = _ids(40, rows=2)
    tol = token_tol = F32
    lanes = [dict(attn_kernel="reference", prefill_attn="reference"),
             dict(attn_kernel="paged", prefill_attn="flash_pallas")]
    engine = dict(max_len=64, prefill_chunk=16, block_size=8, slots=2,
                  kv_blocks=20, prefix_cache=False)

    def one_sequence(self, tiny, ids, **control):
        config, _, params = tiny
        return reference.logits(params, ids[None], config, **control)[0]

    def test_model_matches_the_reference(self, tiny):
        super().test_model_matches_the_reference(tiny)
        # the bias is live: a reference that routes by s alone differs by
        # far more than rounding, so a program that left it out would too
        ids = self.forward_ids[0]
        off = self.ref_logits(tiny, ids, ignore_bias=True)
        assert float(jnp.abs(off - self.ref_logits(tiny, ids)).max()) \
            > 100 * F32

    def draw_prompts(self):
        ids = _ids(37, seed=1)[0]
        return [[int(t) for t in ids[:n]] for n in (37, 9, 20)]

    def engine_served(self, eng, model, lanes, counted):
        assert eng.attn_kernel == lanes["attn_kernel"]
        assert len(eng.pool.caches) == 1


# -- (c) absorbed equals expanded, per head ---------------------------------

def test_absorbed_form_equals_expanded_form_per_head(tiny):
    """``q~_h . [c ‖ k_r] = q_nope,h . k_nope,h + q_rope,h . k_r`` and
    ``(sum p c) W_uv,h = sum p v_h`` for every head: the same products
    reassociated, so they agree to float32 rounding of sums of ~40
    terms of magnitude ~0.1."""
    _, model, params = tiny
    cfg, attn = model.cfg, model.blocks.block.attn
    p = jax.tree.map(lambda x: x[0], params["blocks"]["experts"]["attn"])
    u = jax.random.normal(jax.random.key(3), (1, 24, cfg.hidden_size))
    pos = jnp.arange(24)[None]
    q_nope, q_rope = attn._queries(p, u, pos)
    rows = attn._latent_rows(p, u, pos)          # (1, s, stored row)
    w_uk, w_uv = attn._up(p, jnp.float32)
    c = rows[..., :cfg.kv_lora_rank]
    k_r = rows[..., cfg.kv_lora_rank:attn.row]
    k_nope = jnp.einsum("bsc,chn->bshn", c, w_uk)
    v = jnp.einsum("bsc,chv->bshv", c, w_uv)
    expanded = jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope) \
        + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_r)
    qt = attn._absorbed_queries(p, u, pos)
    assert qt.shape[-1] == attn.head_dim
    assert not rows[..., attn.row:].any()        # the pad is zeros
    absorbed = jnp.einsum("bqhd,bkd->bhqk", qt, rows)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)
    prob = jax.nn.softmax(jnp.where(
        jnp.tril(jnp.ones((24, 24), bool)), expanded * attn.scale, -1e30))
    o_exp = jnp.einsum("bhqk,bkhv->bqhv", prob, v)
    o_abs = jnp.einsum("bqhc,chv->bqhv",
                       jnp.einsum("bhqk,bkc->bqhc", prob, c), w_uv)
    np.testing.assert_allclose(o_abs, o_exp, atol=1e-6)


# -- (d) routing against a literal loop -------------------------------------

def _route_loop(router, bias, x, k, scale):
    z = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    s = 1 / (1 + np.exp(-z))
    idx, w = [], []
    for t in range(len(s)):
        chosen = sorted(range(s.shape[1]),
                        key=lambda e: -(s[t, e] + bias[e]))[:k]
        tot = sum(s[t, e] for e in chosen)
        idx.append(chosen)
        w.append([scale * s[t, e] / tot for e in chosen])
    return np.asarray(idx), np.asarray(w)


def test_routing_selects_by_biased_score_and_weighs_by_score():
    moe = ExpertShareMoE(16, 8, 8, k=3, select_bias=True, scale=2.446)
    params = moe.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (32, 16))
    # a bias large enough to flip the chosen set for most tokens
    bias = np.asarray([0.3, -0.3, 0.0, 0.2, -0.2, 0.1, 0.0, -0.1],
                      np.float32)
    params["select_bias"] = jnp.asarray(bias)
    idx, w = moe.route(params, x)
    want_idx, want_w = _route_loop(params["router"], bias, x, 3, 2.446)
    plain, _ = _route_loop(params["router"], 0 * bias, x, 3, 2.446)
    assert (np.sort(want_idx, 1) != np.sort(plain, 1)).any(1).mean() > .5
    np.testing.assert_array_equal(idx, want_idx)
    # float32 sigmoid and one division against float64
    np.testing.assert_allclose(w, want_w, rtol=2e-6)
    np.testing.assert_allclose(w.sum(-1), 2.446, rtol=1e-6)
    # no bias, no scale: the path every other model takes
    bare = ExpertShareMoE(16, 8, 8, k=3)
    assert "select_bias" not in bare.init(jax.random.key(0))


def test_no_token_is_dropped_when_all_pick_one_expert():
    moe = ExpertShareMoE(16, 8, 8, k=1, select_bias=True, scale=2.446)
    params = moe.init(jax.random.key(2))
    params["select_bias"] = jnp.zeros(8).at[5].set(10.0)   # all pick 5
    x = jax.random.normal(jax.random.key(3), (40, 16))
    out, st = moe(params, x, return_stats=True)
    assert st["sizes"].tolist() == [0, 0, 0, 0, 0, 40, 0, 0]
    h = jax.nn.silu(x @ params["wg"][5]) * (x @ params["wi"][5])
    np.testing.assert_allclose(out, 2.446 * (h @ params["wo"][5]),
                               atol=1e-6)


def test_kernel_experts_equal_ragged_dot_all_held(ragged_dot_experts,
                                                  grouped_form):
    """All 8 experts held (Kimi's layer): the Pallas grouped call on
    the aligned layout — fused (one call that adds its weighted rows
    into the tokens') and split (three calls and the gather back) —
    against three ``ragged_dot`` calls on the dense sorted rows — plain,
    and as a ``StackedLeaf`` with a traced layer inside a scan, as the
    serving step hands the weights over. The host's counters say which
    form the lane ran and the rows of the tiles it visited."""
    from hetu_tpu import telemetry
    from hetu_tpu.nn.module import StackedLeaf
    from hetu_tpu.ops.grouped_matmul_pallas import grouped_rows_computed
    moe = ExpertShareMoE(16, 8, 8, k=3, select_bias=True, scale=2.446)
    params = moe.init(jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (45, 16))
    want = ragged_dot_experts(moe, params, x)
    assert float(jnp.abs(want).max()) > 1e-3
    out, st = jax.jit(lambda p, x: moe(p, x, return_stats=True))(
        params, x)
    assert list(st) == ["sizes"]                 # no group limit here
    assert int(st["sizes"].sum()) == 45 * 3 and moe.tile_rows(45 * 3) == 32
    assert moe.grouped_form(45 * 3) == grouped_form
    np.testing.assert_allclose(out, want, atol=1e-6)

    telemetry.enable(True)
    try:
        reg = telemetry.get_registry()

        def read():
            return ({f: reg.counter("moe_grouped_form_calls_total").value(
                form=f) for f in ("fused", "split")},
                reg.counter("moe_grouped_rows_total").value(
                    kind="computed"))
        forms0, rows0 = read()
        # one scan of two layer calls at these sizes, a lane of 45 rows
        moe.count_share(np.stack([st["sizes"]] * 2), tokens=45)
        forms, rows = read()
    finally:
        telemetry.enable(False)
    other = {"fused": "split", "split": "fused"}[grouped_form]
    assert forms[grouped_form] - forms0[grouped_form] == 2
    assert forms[other] == forms0[other]
    assert rows - rows0 == 2 * grouped_rows_computed(
        np.asarray(st["sizes"]), 32)

    # three layers stacked, layer 1 is this one's, the others' differ
    stack = {n: jnp.stack([params[n] * 0 + 1, params[n], params[n] * 2])
             for n in ("wg", "wi", "wo")}

    def body(carry, layer):
        p = {**params, **{n: StackedLeaf(stack[n], layer) for n in stack}}
        return carry, moe(p, x)

    _, outs = jax.jit(lambda: jax.lax.scan(body, 0, jnp.arange(3)))()
    np.testing.assert_allclose(outs[1], want, atol=1e-6)
    assert float(jnp.abs(outs[0] - want).max()) > 1e-3


def test_a_crowded_share_walks_two_windows_in_both_forms(
        ragged_dot_experts, grouped_form):
    """A share of 2 of 16 experts that every token picks both of: 160
    live pairs walk the window loop in TWO windows of 128 rows — the
    fused form adds each window's rows into the tokens', the split form
    gathers each window's back — against ``ragged_dot`` over all the
    sorted rows at once."""
    moe = ExpertShareMoE(16, 8, 16, k=4, local_experts=(4, 2))
    params = moe.init(jax.random.key(0), dtype=jnp.float32)
    bias = jnp.full((16,), -9.0).at[jnp.asarray([0, 1, 4, 5])].set(
        jnp.asarray([3., 2., 6., 5.]))
    x = jnp.concatenate([jax.random.normal(jax.random.key(1), (80, 15)),
                         jnp.ones((80, 1))], axis=-1)
    params = {**params, "router": params["router"].at[15].set(bias)}
    assert moe._window_rows(80 * 4) == 128
    assert moe.grouped_form(80 * 4) == grouped_form
    out, st = jax.jit(lambda p, x: moe(p, x, return_stats=True))(
        params, x)
    assert st["sizes"].tolist() == [80, 80]      # two windows of 128
    want = ragged_dot_experts(moe, params, x)
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(out, want, atol=1e-6)


# -- (e) the arena: one latent leaf -----------------------------------------

def test_arena_is_one_leaf_of_stored_rows(tiny):
    from hetu_tpu import telemetry
    from hetu_tpu.serving import ServingEngine
    _, model, params = tiny
    cfg = model.cfg
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, max_len=64, prefill_chunk=16,
                            block_size=8, slots=2, kv_blocks=20,
                            cache_dtype=jnp.bfloat16)
        row = cfg.stored_row or 40
        leaf, = eng.pool.caches
        assert leaf.shape == (cfg.num_hidden_layers, 20, 8, row)
        assert eng.pool.nbytes() == 20 * 8 * row * 2 * cfg.num_hidden_layers
        assert not eng.pool.quantized
        g = telemetry.get_registry().gauge("kv_row_bytes")
        assert g.value(kind="stored") == row * 2
        assert g.value(kind="needed") == 40 * 2
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_what_needs_per_head_kv_refuses_by_name(tiny):
    from hetu_tpu.serving import ServingEngine
    _, model, params = tiny
    kw = dict(max_len=64, prefill_chunk=16, block_size=8, slots=2,
              kv_blocks=20)
    for bad in (dict(cache_dtype=jnp.int8), dict(long_max_len=128),
                dict(w8a8="on", cache_dtype=jnp.int8),
                dict(draft_model=model, draft_params=params,
                     spec_depth=2)):
        with pytest.raises(LatentKVNotSupported):
            ServingEngine(model, params, **kw, **bad)
    with pytest.raises(LatentKVNotSupported):
        model.blocks.prefill(params["blocks"], None)
    with pytest.raises(LatentKVNotSupported):        # no dense cache
        from hetu_tpu.models.generation import generate
        generate(model, params, _ids(8), max_new_tokens=2)


def test_prefix_sharing_spill_and_speculation_carry_the_leaf(tiny):
    """Prefix sharing with copy-on-write, preemption with spill and
    resume, and n-gram speculation are written over the arena's leaves,
    whatever they are: each gives the tokens of an undisturbed run."""
    from hetu_tpu.serving import SamplingParams, ServingEngine
    _, model, params = tiny
    kw = dict(max_len=64, prefill_chunk=16, block_size=8, kv_blocks=24)
    ids = [int(t) for t in _ids(30, seed=4)[0]]
    a, b = ids[:21], ids[:19] + ids[25:30]      # 19 shared: 2 pages + 3
    plain = ServingEngine(model, params, slots=2, prefix_cache=False,
                          **kw).generate_many(
        [a, b], SamplingParams(max_tokens=5))
    shared = ServingEngine(model, params, slots=2, **kw)
    got = [shared.generate_many([p], SamplingParams(max_tokens=5))[0]
           for p in (a, b)]
    assert got == plain
    assert shared.prefix_cache is not None \
        and shared.blocks.blocks_in_use > 0
    spec = ServingEngine(model, params, slots=2, spec_depth=2,
                         prefix_cache=False, **kw)
    assert spec.generate_many([a, b],
                              SamplingParams(max_tokens=5)) == plain
    one = ServingEngine(model, params, slots=1, prefix_cache=False, **kw)
    lo = one.submit(a, SamplingParams(max_tokens=5, priority=2))
    for _ in range(4):
        one.step()
    hi = one.submit(b, SamplingParams(max_tokens=5, priority=0))
    one.run_until_drained()
    assert lo.preemptions == 1 and lo.resumed_blocks == lo.spilled_blocks
    assert [list(lo.tokens), list(hi.tokens)] == plain


# -- (f) the latent paged call ----------------------------------------------

def _latent_case(seed=0, *, S=5, R=1, hq=16, d=72, L=3, nb=48, bs=4, W=8):
    rng = np.random.default_rng(seed)
    arena = jnp.asarray(rng.normal(size=(L, nb, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, R, hq, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(np.arange(1, nb))[:S * W]
                      .reshape(S, W), jnp.int32)
    return arena, q, tbl


@pytest.mark.parametrize("rows", [1, 3])
def test_latent_paged_call_list_form(rows):
    """ONE key head for a group of 16 query heads, a 72-wide key whose
    first 64 columns are the value, the scale given; against the gather
    reference on every live slot; a dead slot's rows are zeros."""
    arena, q, tbl = _latent_case(R=rows)
    off = jnp.asarray([5, 17, 0, 28, 11], jnp.int32)
    live = jnp.asarray([True, True, False, True, True])
    for layer in (0, 2):
        out, lse = paged_attention_pallas(
            q, arena, None, tbl, off, layer=jnp.int32(layer), scale=0.11,
            v_width=64, interpret=True, return_lse=True, live=live,
            pages_per_step=2)
        ref, lref = paged_attention_reference(
            q, arena[layer], None, tbl, off, scale=0.11, v_width=64,
            return_lse=True)
        assert out.shape == (5, rows, 16, 64)
        m = np.asarray(live)
        # float32 online softmax against a one-pass softmax
        np.testing.assert_allclose(np.asarray(out)[m], np.asarray(ref)[m],
                                   atol=2e-6)
        np.testing.assert_allclose(np.asarray(lse)[m],
                                   np.asarray(lref)[m], atol=2e-6)
        assert not np.asarray(out)[~m].any()
    with pytest.raises(ValueError, match="v_width"):
        paged_attention_pallas(q, arena, None, tbl, off,
                               layer=jnp.int32(0), v_width=64)   # no scale
    with pytest.raises(ValueError, match="v_width"):
        paged_attention_pallas(q, arena, arena, tbl, off,
                               layer=jnp.int32(0), scale=1., v_width=64)


def test_latent_paged_call_tile_form():
    """The prefill lane's history read over the latent leaf: a pack of
    three runs (one without history) in tiles of 4 tokens, against one
    gathered row a token."""
    arena, _, tbl = _latent_case(seed=1)
    C, hq, d = 14, 16, 72
    q = jnp.asarray(np.random.default_rng(2).normal(size=(C, hq, d)),
                    jnp.float32)
    runs = [(0, 0, 6, 9), (1, 6, 3, 0), (3, 9, 5, 14)]
    tiles, counts = pack_history_tiles(runs, tile_rows=4, n_tiles=8)
    assert counts[0] > 0 and counts[1] > 0
    hist = np.zeros(C, np.int32)
    slot = np.zeros(C, np.int32)
    for s, first, n, h in runs:
        hist[first:first + n], slot[first:first + n] = h, s
    out, lse = paged_history_attention(
        q, arena, None, jnp.take(tbl, tiles[0], axis=0),
        jnp.asarray(hist), tiles, tile_rows=4, layer=jnp.int32(1),
        scale=0.11, v_width=64, interpret=True)
    ref, lref = paged_attention_reference(
        q[:, None], arena[1], None, jnp.take(tbl, slot, axis=0),
        jnp.asarray(hist) - 1, scale=0.11, v_width=64, return_lse=True)
    has = hist > 0
    np.testing.assert_allclose(np.asarray(out)[has],
                               np.asarray(ref)[has, 0], atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse)[has],
                               np.asarray(lref)[has, :, 0], atol=2e-6)
    assert not np.asarray(out)[~has].any()
