"""The KDA / MLA / group-limited-expert decoder on the CPU at a tiny
size (ISSUE 41): the chunk form and the one-token update against the
token recurrence; the convolution across packs against one pass; the
model against the plain reference; chunked prefill then decode through
the latent arena, the states and the tails against the reference's ONE
forward pass (logits), a reused slot included; the group-limited route
against the reference's and against the ungrouped one to the bit; the
eight shares of an expert layer against the uncut layer; the refusals,
by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served
from served import KDA_D as D, KDA_H as H, REFUSED, ServedArchContract
from served import kda_draw as _draw, kda_pack as _pack
from benchmark.reference import kda_mla_moe as reference
from hetu_tpu import telemetry
from hetu_tpu.models import generation
from hetu_tpu.nn.moe import ExpertShareMoE
from hetu_tpu.nn.parallel import LatentKVNotSupported
from hetu_tpu.ops import kda


@pytest.fixture(scope="module")
def tiny():
    return served.tiny("ling", 41)


@pytest.mark.parametrize("runs,C,layer", [
    ([(0, 0, 50)], 50, None),                     # one run, padded chunk
    ([(1, 64, 130)], 130, None),                  # continuing, 3 chunks
    # three slots' runs: one from 0, one continuing, chunk boundaries
    # inside each; a short one; pad lanes behind
    ([(2, 0, 70), (0, 37, 100), (1, 0, 5)], 200, None),
    ([(2, 0, 70), (0, 37, 100), (1, 0, 5)], 200, 1),
], ids=["one-run", "continuing", "three-slots", "three-slots-stacked"])
def test_kda_scan_equals_the_recurrence(runs, C, layer):
    ops, state0, where, want_o, want_s, used = _pack(
        runs, C, 4, jax.random.key(3))
    with jax.default_matmul_precision("highest"):
        if layer is None:
            o, st = jax.jit(kda.kda_scan)(*ops, state0, *where)
        else:
            buf = jnp.stack([state0 + 1.0, state0, state0 + 2.0])
            o, buf = jax.jit(lambda *a: kda.kda_scan(
                *a, layer=jnp.int32(layer)))(*ops, buf, *where)
            assert (buf[0] == state0 + 1.0).all() \
                and (buf[2] == state0 + 2.0).all()
            st = buf[1]
    np.testing.assert_allclose(o[:used], want_o, atol=5e-6)
    np.testing.assert_allclose(st, want_s, atol=1e-5)
    # a slot without a run keeps its state to the bit
    idle = sorted(set(range(4)) - {s for s, _, _ in runs})
    assert (np.asarray(st)[idle] == np.asarray(state0)[idle]).all()


def test_kda_scan_at_the_decay_bound_for_a_whole_chunk():
    """``g = -5`` for 64 tokens: ``e^{-G}`` alone would be ``e^{320}``."""
    x = _draw(jax.random.key(5), kda.CHUNK, at_bound=True)
    o, st = kda.kda_scan(
        *x, jnp.zeros((1, H, D, D)), jnp.zeros(kda.CHUNK, jnp.int32),
        jnp.arange(kda.CHUNK, dtype=jnp.int32),
        jnp.ones(kda.CHUNK, bool))
    want_o, want_s = kda.kda_recurrence(*x)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_allclose(st[0], want_s, atol=1e-6)


def test_kda_update_equals_one_step_of_the_recurrence():
    S = 4
    x = _draw(jax.random.key(6), S)
    state = jax.random.normal(jax.random.key(7), (S, H, D, D))
    live = jnp.array([True, False, True, True])
    fresh = jnp.array([False, False, True, False])
    o, new = jax.jit(kda.kda_update)(*x, state, live, fresh=fresh)
    for s in range(S):
        want_o, want_s = kda.kda_recurrence(
            *(a[s:s + 1] for a in x),
            state=None if fresh[s] else state[s])
        if live[s]:
            np.testing.assert_allclose(o[s], want_o[0], atol=1e-6)
            np.testing.assert_allclose(new[s], want_s, atol=1e-6)
        else:
            assert (new[s] == state[s]).all()


@pytest.mark.parametrize("cut", [1, 2, 3, 9])
def test_convolution_across_two_packs_equals_one_pass(cut):
    """A run cut after ``cut`` tokens (under the window too), beside
    another slot's run from position 0 whose tail held garbage."""
    N, K, T = 6, 4, 14
    taps = jax.random.normal(jax.random.key(1), (K, N))
    a = jax.random.normal(jax.random.key(2), (T, N))
    b = jax.random.normal(jax.random.key(3), (5, N))
    tail = jax.random.normal(jax.random.key(4), (3, K - 1, N))
    want_a, want_b = kda.conv_sequence(a, taps), kda.conv_sequence(b, taps)

    def pack(rows, slot, pos, C=20):
        n = rows.shape[0]
        return (jnp.pad(rows, ((0, C - n), (0, 0)), constant_values=3.0),
                jnp.pad(slot, (0, C - n)), jnp.pad(pos, (0, C - n)),
                jnp.arange(C) < n)

    rows, slot, pos, valid = pack(
        a[:cut], jnp.full((cut,), 2), jnp.arange(cut))
    y1, tail = kda.conv_pack(rows, taps, tail, slot, pos, valid)
    rest = T - cut
    rows, slot, pos, valid = pack(
        jnp.concatenate([a[cut:], b]),
        jnp.concatenate([jnp.full((rest,), 2), jnp.zeros((5,), jnp.int32)]),
        jnp.concatenate([jnp.arange(cut, T), jnp.arange(5)]))
    y2, tail2 = kda.conv_pack(rows, taps, tail, slot, pos, valid)
    np.testing.assert_allclose(y1[:cut], want_a[:cut], atol=1e-6)
    np.testing.assert_allclose(y2[:rest], want_a[cut:], atol=1e-6)
    np.testing.assert_allclose(y2[rest:rest + 5], want_b, atol=1e-6)
    np.testing.assert_allclose(tail2[2], a[-3:], atol=0)
    np.testing.assert_allclose(tail2[0], b[-3:], atol=0)
    # then a decode row a slot
    nxt = jax.random.normal(jax.random.key(5), (3, N))
    y3, tail3 = kda.conv_rows(nxt, taps, tail2,
                              jnp.array([True, False, True]))
    np.testing.assert_allclose(
        y3[2], kda.conv_sequence(jnp.concatenate([a, nxt[2:]]), taps)[-1],
        atol=1e-6)
    assert (tail3[1] == tail2[1]).all()


def test_caches_are_a_latent_arena_beside_two_slot_leaves(tiny):
    config, model, _ = tiny
    assert model.cfg.mixer_types == ("kda", "kda", "mla", "kda", "kda",
                                     "mla", "kda")
    assert model.blocks.run_kinds == ["kda", "mla", "kda", "mla", "kda"]
    assert model.blocks.layers_of == {"kda": 5, "mla": 2}
    latent, state, tail = generation.init_paged_caches(
        model, 9, 4, jnp.bfloat16, slots=3)
    assert latent.shape == (2, 9, 4, 48) and latent.dtype == jnp.bfloat16
    assert state.shape == (5, 3, 4, 16, 16) and state.dtype == jnp.float32
    assert tail.shape == (5, 3, 3, 3 * 64) and tail.dtype == jnp.float32
    got = model.blocks.cache_bytes(2)
    assert got["row"] == {"stored": 2 * 48 * 2, "needed": 2 * 40 * 2}
    assert got["state"] == {"slot": 5 * 4 * (64 * 16 + 3 * 192)}
    assert model.blocks.block.attn.kv_leaf_shapes() == ((1, 48),)
    assert model.blocks.dense[0].attn.kv_leaf_shapes() == ()


def _group_counters():
    reg = telemetry.get_registry()
    return [reg.counter("moe_group_held_total").value(),
            reg.counter("moe_group_tokens_total").value()]


class TestKDAMLAMoE(ServedArchContract):
    """The chunks cut the convolution's window and the scan's chunks; a
    reused slot's state AND its tail start from zeros, its pages are its
    own."""
    reference = reference
    forward_ids = jax.random.randint(jax.random.key(1), (2, 45), 1, 128)
    tol = token_tol = 1e-4
    controls = [{"no_erase": True}, {"no_conv": True}, {"head_decay": True},
                {"no_group_limit": True}, {"ignore_bias": True},
                {"operands": jnp.float8_e4m3fn}]
    control_ids = jax.random.randint(jax.random.key(1), (45,), 1, 128)
    control_moves = 0.01
    chunks = (10, 7)
    requests = (41, 1, 128, ((0, 23, 3), (1, 14, 4), (0, 17, 3)))
    serve_tol = 2e-4
    lanes = [dict()]
    counters = staticmethod(_group_counters)
    refused = REFUSED
    refuses_the_dense_cache = True
    new_modules = ("hetu_tpu.models.kda_mla_moe", "hetu_tpu.ops.kda")

    def engine_served(self, eng, model, lanes, counted):
        assert eng.prefix_cache is None and eng.preempt is False
        assert eng.prefill_attn == "flash"        # the pack as one row
        assert eng.pool.nbytes() == sum(c.nbytes for c in eng.pool.caches)
        assert len(eng.pool.caches) == 3
        held, tokens = counted
        # two of four groups stay: about half the tokens reach the held one
        assert 0 < held < tokens and 0.25 < held / tokens < 0.75
        assert telemetry.get_registry().gauge("kv_state_bytes").value(
            kind="slot") == model.blocks.cache_bytes(4)["state"]["slot"]

    def refusal(self, name):
        """The latent arena refuses some before the slot state is
        asked."""
        if name in ("long_max_len", "w8a8", "tenancy"):
            return LatentKVNotSupported, name
        return super().refusal(name)

    def test_dense_cache_and_cp_prefill_refuse_by_name(self, tiny):
        """... and the configuration's own refusals."""
        from hetu_tpu.models.kda_mla_moe import KDAMLAMoEConfig
        super().test_dense_cache_and_cp_prefill_refuse_by_name(tiny)
        with pytest.raises(NotImplementedError, match="q_lora_rank"):
            KDAMLAMoEConfig.tiny(q_lora_rank=64)
        with pytest.raises(ValueError, match="latent layer"):
            KDAMLAMoEConfig.tiny(num_hidden_layers=2)


def test_the_counter_counts_a_served_requests_steps(tiny):
    """``kda_scan_steps_total{kind}`` on a served request: computed =
    the grid steps run, live of them held a valid row. And
    ``kda_update_slots_total{kind}``: live = decode rows x KDA layers,
    stepped = slots x KDA layers a decode call — neither lane adds to
    the other's counter."""
    from hetu_tpu import telemetry
    from hetu_tpu.serving import SamplingParams, ServingEngine
    _, model, params = tiny
    telemetry.enable(True)
    try:
        reg = telemetry.get_registry()

        def read():
            c = reg.counter("kda_scan_steps_total")
            u = reg.counter("kda_update_slots_total")
            return [c.value(kind=k) for k in ("live", "computed")] + [
                u.value(kind=k) for k in ("live", "stepped")]
        before = read()
        eng = ServingEngine(model, params, max_len=64, prefill_chunk=8,
                            block_size=4, slots=2, kv_blocks=40, seed=0)
        eng.generate_many([list(range(1, 12))],
                          SamplingParams(max_tokens=3))
        live, computed, advanced, stepped = (
            a - b for a, b in zip(read(), before))
    finally:
        telemetry.enable(False)
    # 11 tokens in two packs of 8 rows, each one chunk: one live piece a
    # pack and KDA layer (all heads in one block), nothing else computed
    # — the decode rows run no scan
    kda_layers = model.blocks.layers_of["kda"]
    assert 0 < live <= computed
    assert live == computed == 2 * kda_layers
    # the second pack samples the first token; the other two are decode
    # rows of ONE live slot of the engine's two
    assert advanced == 2 * kda_layers
    assert stepped == 2 * advanced


def _moe(**kw):
    return ExpertShareMoE(32, 16, 16, k=3, select_bias=True, scale=2.5,
                          **kw)


def test_group_limited_route_equals_the_references():
    moe = _moe(n_group=8, topk_group=2)
    params = moe.init(jax.random.key(0))
    params["select_bias"] = 0.3 * jax.random.normal(jax.random.key(1), (16,))
    u = jax.random.normal(jax.random.key(2), (200, 32))
    idx, w, kept = moe.route(params, u, return_kept=True)
    config = dict(num_experts_per_tok=3, n_group=8, topk_group=2,
                  routed_scaling_factor=2.5, num_experts=16,
                  deployment={"expert_group": 0})
    with jax.default_matmul_precision("highest"):
        ridx, rw, margin = reference.route(params, u, config)
        free, _, _ = reference.route(params, u, config,
                                     no_group_limit=True)
    clear = np.asarray(margin) > 1e-4
    assert clear.mean() > 0.9
    order = np.argsort(np.asarray(idx), axis=1)
    rorder = np.argsort(np.asarray(ridx), axis=1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(idx), order, 1)[clear],
        np.take_along_axis(np.asarray(ridx), rorder, 1)[clear])
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1)[clear],
        np.take_along_axis(np.asarray(rw), rorder, 1)[clear], atol=1e-6)
    # every chosen expert stands in a kept group, two groups are kept,
    # and the limit binds: the free top-3 differs somewhere
    assert (np.asarray(kept).sum(1) == 2).all()
    assert np.take_along_axis(np.asarray(kept), np.asarray(idx) // 2,
                              1).all()
    assert (np.sort(np.asarray(free), 1)
            != np.sort(np.asarray(ridx), 1)).any()


def test_kernel_experts_equal_ragged_dot_under_the_group_limit(
        ragged_dot_experts, grouped_form):
    """``n_group`` 8 / ``topk_group`` 4, the held experts one group's
    pair (Ling's deployment in small): the Pallas grouped call, fused
    and split, against ``ragged_dot`` on the layer's own group-limited
    route."""
    moe = _moe(n_group=8, topk_group=4, local_experts=(6, 2))
    assert moe.grouped_form(200 * 3) == grouped_form
    params = moe.init(jax.random.key(0))
    params["select_bias"] = 0.3 * jax.random.normal(jax.random.key(1), (16,))
    u = jax.random.normal(jax.random.key(2), (200, 32))
    out, stats = jax.jit(lambda p, x: moe(p, x, return_stats=True))(
        params, u)
    assert 0 < int(stats["sizes"].sum()) < 200 * 3
    want = ragged_dot_experts(moe, params, u)
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(out, want, atol=1e-6)


@pytest.mark.parametrize("bias", [True, False])
def test_one_group_is_the_ungrouped_route_to_the_bit(bias):
    moe = ExpertShareMoE(32, 16, 16, k=3, select_bias=bias, scale=2.5,
                         n_group=1, topk_group=1)
    params = moe.init(jax.random.key(0))
    u = jax.random.normal(jax.random.key(2), (200, 32))
    idx, w = jax.jit(moe.route)(params, u)

    @jax.jit
    def old(params, x):        # the route as it stood before the groups
        z = jnp.matmul(x.astype(jnp.float32),
                       params["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        if bias:
            s = jax.nn.sigmoid(z)
            _, idx = jax.lax.top_k(
                s + params["select_bias"].astype(jnp.float32), 3)
            top = jnp.take_along_axis(s, idx, axis=-1)
        else:
            top, idx = jax.lax.top_k(jax.nn.sigmoid(z), 3)
        return idx.astype(jnp.int32), top / top.sum(-1, keepdims=True) * 2.5

    oidx, ow = old(params, u)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(oidx))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(ow))
    with pytest.raises(ValueError, match="groups"):
        ExpertShareMoE(32, 16, 16, k=3, n_group=3)
    with pytest.raises(ValueError, match="groups"):
        ExpertShareMoE(32, 16, 16, k=9, n_group=8, topk_group=4)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed sums of the eight chips of a deployment (a group of
    two experts each), and the shared expert counted ONCE, against the
    reference's layer with every expert held."""
    whole = _moe(n_group=8, topk_group=4)
    params = whole.init(jax.random.key(0))
    params["select_bias"] = 0.3 * jax.random.normal(jax.random.key(1), (16,))
    u = jax.random.normal(jax.random.key(2), (60, 32))
    total, sizes, held = jnp.zeros_like(u), [], 0
    for g in range(8):
        share = _moe(n_group=8, topk_group=4, local_experts=(2 * g, 2))
        part = {**params, **{n: params[n][2 * g:2 * g + 2]
                             for n in ("wg", "wi", "wo")}}
        out, st = share(part, u, return_stats=True)
        total = total + out
        sizes.append(int(st["sizes"].sum()))
        assert int(st["group_held"][1]) == 60
        held += int(st["group_held"][0])
    assert sum(sizes) == 60 * 3               # every chosen pair, once
    assert held == 60 * 4                     # a token reaches 4 chips
    shared = {n: {"weight": 0.1 * jax.random.normal(
        jax.random.key(i), shape)} for i, (n, shape) in enumerate(
            [("gate_proj", (32, 16)), ("up_proj", (32, 16)),
             ("fc_out", (16, 32))])}
    config = dict(num_experts_per_tok=3, n_group=8, topk_group=4,
                  routed_scaling_factor=2.5, num_experts=16,
                  deployment={"expert_group": 0})
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_ffn({"moe": params, "shared": shared},
                                       u, config)
        got = total + reference.gated(shared, u)
        np.testing.assert_allclose(got, want, atol=2e-6)
        np.testing.assert_allclose(whole(params, u), total, atol=2e-6)
