"""Qwen3-Next (ISSUE 59) at ``Qwen3NextConfig.tiny``-like sizes on the
CPU: the delta-rule oracles and the two Pallas kernels (interpreted) at
ONE decay a head and grouped key heads against a token recurrence
written here, with decays far beyond Kimi Delta Attention's bound; the
system's prefill-then-decode through the engine against the float32
reference's full forward (logits and the slot states); partial rotary
and the output gate against the reference one layer at a time; the
share test of the ``model-configs`` guide's section 4; the planted
controls; the counters and the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served
from served import ServedArchContract, counted, top_token_gaps
from benchmark.reference import qwen3_next as reference
from benchmark.runners.serve_arch import load_arch
from hetu_tpu import telemetry
from hetu_tpu.ops import kda
from hetu_tpu.ops.kda_pallas import hetu_kda_scan, hetu_kda_update

HK, H, D = 2, 4, 16            # key heads under value heads of D


# -- the delta rule at one decay a head, grouped key heads ---------------------
def _token_rule(q, k, v, g, beta, state):
    """Gated DeltaNet's recurrence in numpy, float64: ``q``, ``k`` ``(T,
    HK, D)``, ``v`` ``(T, H, D)``, ``g``, ``beta`` ``(T, H)``; value
    head ``j`` reads key head ``j // (H / HK)``."""
    q, k, v, g, beta = (np.asarray(x, np.float64)
                        for x in (q, k, v, g, beta))
    S = np.zeros((H, D, D)) if state is None else np.array(state, np.float64)
    out = np.zeros(v.shape)
    for t in range(len(q)):
        for j in range(H):
            kj, qj = k[t, j // (H // HK)], q[t, j // (H // HK)]
            S[j] = np.exp(g[t, j]) * S[j]
            r = v[t, j] - kj @ S[j]
            S[j] = S[j] + beta[t, j] * np.outer(kj, r)
            out[t, j] = qj @ S[j]
    return out, S


def _draw(key, T, steep=False):
    """Operands as the mixer makes them: unit q, k a KEY head; ``g (T,
    H)`` in (-4, 0), or ``steep``: a token in five decays by e^-20 to
    e^-60 (no lower bound: ``g = -A softplus(.)``)."""
    ks = jax.random.split(key, 6)
    q, k = (jax.random.normal(ks[i], (T, HK, D)) for i in range(2))
    v = jax.random.normal(ks[2], (T, H, D))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -4.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (T, H)))
    if steep:
        g = jnp.where(jax.random.uniform(ks[5], (T, H)) < 0.2,
                      -20.0 - 40.0 * jax.random.uniform(ks[5], (T, H)), g)
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))


def _rule(x, state):
    return _token_rule(*x, state)


_rule.state_shape = (H, D, D)


def _pack(runs, C, slots, key, steep):
    """:func:`served.delta_rule_pack` of :func:`_draw` against
    :func:`_token_rule`."""
    return served.delta_rule_pack(
        runs, C, slots, key, lambda k, n: _draw(k, n, steep), _rule)


THREE = [(2, 0, 70), (0, 37, 100), (1, 0, 5)]
PACKS = {
    "one-run": ([(0, 0, 50)], 50, None, False),
    "three-slots": (THREE, 200, None, False),
    "three-slots-stacked-steep": (THREE, 200, 1, True),
    "shared-chunk-restart-steep": ([(1, 9, 20), (3, 0, 30), (0, 5, 90)],
                                   192, 2, True),
    "short-16": ([(0, 0, 6), (2, 5, 4)], 10, None, True),
}


@pytest.mark.parametrize("form", ["kernel", "chunk_form"])
@pytest.mark.parametrize("name", list(PACKS))
def test_scan_at_one_decay_a_head_equals_the_token_rule(name, form):
    """``hetu_kda_scan`` (interpreted) and its ``jax.numpy`` oracle on
    Gated DeltaNet's operands — ``g (C, H)``, two key heads under four
    value heads — against the token rule; ``steep`` packs decay by up
    to e^-60 a token, where the per-channel form's reference row would
    overflow (the pair factors are formed directly here)."""
    runs, C, layer, steep = PACKS[name]
    ops, state0, where, want_o, want_s, used = _pack(
        runs, C, 4, jax.random.key(11), steep)
    fn = hetu_kda_scan if form == "kernel" else kda.kda_scan
    with jax.default_matmul_precision("highest"):
        if layer is None:
            o, st = jax.jit(fn)(*ops, state0, *where)
        else:
            buf0 = jnp.stack([state0 + float(i + 1) for i in range(3)]) \
                .at[layer].set(state0)
            o, buf = jax.jit(lambda *a: fn(*a, layer=jnp.int32(layer)))(
                *ops, buf0, *where)
            others = [i for i in range(3) if i != layer]
            assert (np.asarray(buf)[others]
                    == np.asarray(buf0)[others]).all()
            st = buf[layer]
    assert np.isfinite(np.asarray(o)).all()
    # (a steep pack's running sum of g reaches -700 inside a chunk: a
    # float32 step there is 6e-5, and a pair's factor is its exponential)
    np.testing.assert_allclose(o[:used], want_o,
                               atol=5e-5 if steep else 1e-5)
    np.testing.assert_allclose(st, want_s, atol=5e-5 if steep else 2e-5)
    idle = sorted(set(range(4)) - {s for s, _, _ in runs})
    assert (np.asarray(st)[idle] == np.asarray(state0)[idle]).all()


@pytest.mark.parametrize("form", ["kernel", "gather_form"])
@pytest.mark.parametrize("steep", [False, True], ids=["mild", "steep"])
def test_update_at_one_decay_a_head_equals_the_token_rule(steep, form):
    rows = 6
    x = _draw(jax.random.key(6), rows, steep)
    state0 = jax.random.normal(jax.random.key(7), (rows, H, D, D))
    live = jnp.asarray([True, False, True, True, False, True])
    fresh = jnp.asarray([False, True, True, False, False, False])
    fn = hetu_kda_update if form == "kernel" else kda.kda_update
    o, st = jax.jit(lambda *a: fn(*a, fresh=fresh))(*x, state0, live)
    for s in range(rows):
        if not live[s]:
            assert (np.asarray(st[s]) == np.asarray(state0[s])).all()
            continue
        want_o, want_s = _token_rule(
            *(a[s:s + 1] for a in x), None if fresh[s] else state0[s])
        np.testing.assert_allclose(o[s], want_o[0], atol=2e-6)
        np.testing.assert_allclose(st[s], want_s, atol=2e-6)


def test_recurrence_takes_both_forms_and_widen_leaves_kdas_alone():
    """``kda_recurrence(..., g (T, H))`` with grouped key heads is the
    token rule; operands already in Kimi Delta Attention's shapes pass
    ``widen`` as the SAME arrays (Ling's lowered kernels see no new
    operation), and head counts that do not divide are refused."""
    x = _draw(jax.random.key(2), 40, steep=True)
    o, st = kda.kda_recurrence(*x)
    want_o, want_s = _token_rule(*x, None)
    np.testing.assert_allclose(o, want_o, atol=2e-6)
    np.testing.assert_allclose(st, want_s, atol=2e-6)
    q, k, v, g, _ = x
    qw, kw, gw = kda.widen(q, k, v, g)
    assert qw.shape == kw.shape == gw.shape == v.shape
    np.testing.assert_array_equal(qw[:, 1], q[:, 0])    # j // 2, not j % 2
    np.testing.assert_array_equal(qw[:, 2], q[:, 1])
    again = kda.widen(qw, kw, v, gw)
    assert again[0] is qw and again[1] is kw and again[2] is gw
    with pytest.raises(ValueError, match="key heads under"):
        kda.widen(q[:, :1].repeat(3, 1), k[:, :1].repeat(3, 1), v, g)


# -- the model -----------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    """``tests/benchmark/configs/qwen3-next-tiny.json`` with EVERY
    expert held (the uncut model), its model and float32 weights."""
    return served.tiny("qwen3-next", 3, num_experts=16,
                       deployment={"expert_share": 0})


def test_tiny_is_the_issues(tiny):
    from hetu_tpu.models import Qwen3NextConfig
    from hetu_tpu.models.qwen3_next import ATTENTION, GDN
    cfg = Qwen3NextConfig.tiny()
    assert cfg.mixer_types == (GDN, GDN, GDN, ATTENTION) * 2
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim) == (4, 8, 16)
    assert (cfg.head_dim, cfg.rotary_dim) == (32, 8)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (16, 3)
    config, model, _ = tiny
    assert config["num_experts"] == config["published"]["num_experts"]
    assert model.blocks.layers_of == {GDN: 6, ATTENTION: 2}
    assert model.blocks.slot_state and model.blocks.paged


def _mixer_counters(mixer="gdn"):
    reg = telemetry.get_registry()
    c = reg.counter(f"{mixer}_scan_steps_total")
    u = reg.counter(f"{mixer}_update_slots_total")
    return [c.value(kind=k) for k in ("live", "computed")] + [
        u.value(kind=k) for k in ("live", "stepped")]


class TestQwen3Next(ServedArchContract):
    reference = reference
    forward_ids = jax.random.randint(jax.random.key(1), (2, 37), 1, 128)
    tol = 1e-3
    controls = [{"no_erase": True}, {"no_conv": True},
                {"tile_key_heads": True}, {"full_rotary": True},
                {"no_out_gate": True}, {"no_shared_gate": True},
                {"plain_gain": True}, {"sigmoid_router": True},
                {"operands": jnp.float8_e4m3fn},
                {"state_dtype": jnp.float8_e4m3fn}]
    control_ids = jax.random.randint(jax.random.key(1), (45,), 1, 128)
    control_from, control_moves = 16, 0.1
    lanes = [dict(), dict(attn_kernel="paged", prefill_attn="flash_pallas")]
    refused = [("prefix_cache", dict(prefix_cache=True)),
               ("preempt", dict(preempt=True)),
               ("spec_depth", dict(spec_depth=2))]

    def close(self, got, want, atol):
        assert float(jnp.abs(want).max()) > 1.0
        super().close(got, want, atol)

    def test_engine_serves_tokens_the_reference_puts_on_top(self, tiny,
                                                            lanes):
        """... and the STATES beside the tokens: over three requests
        through three slots, chunks that cut the convolution's window
        and the scan's pieces, the slot's state where the last chunk
        and where the last decoded token leave it is the token
        recurrence's, and the counters count what the kernels walked.
        ``paged_kernels``: the flash prefill and BOTH paged calls
        interpreted, at a head of 32."""
        from benchmark.runners import serve_arch_ssm
        from hetu_tpu.serving import ServingEngine
        config, model, params = tiny
        arch = load_arch("qwen3_next")
        kda_before = _mixer_counters("kda")
        with counted(_mixer_counters) as delta:
            eng = ServingEngine(model, params, **{**self.engine, **lanes})
            assert eng.prefix_cache is None and eng.preempt is False
            assert len(eng.pool.caches) == 4
            rng = np.random.default_rng(7)
            prompts = [rng.integers(1, 128, n) for n in (21, 13, 30)]
            recs = serve_arch_ssm.probe(arch, eng, prompts, 6)
            # (Ling's counters, which another engine of this process may
            # have fed, take nothing from this one)
            assert _mixer_counters("kda") == kda_before
        live, computed, advanced, stepped = delta
        assert eng.step_executables() == 1
        for r in recs:
            p, toks = len(r["prompt"]), r["tokens"]
            ids = np.zeros(64, np.int32)
            ids[:p + 6] = np.concatenate([r["prompt"], toks])
            lg, margin, states = arch.reference_rows(
                config, params, jnp.asarray(ids), jnp.int32(p - 1), 6)
            gap = top_token_gaps(lg, 1, toks)
            assert gap.max() <= 1e-3, (p, gap)
            assert np.isfinite(np.asarray(margin)).all()
            read_gap = arch.state_gap(config, params, r["states"], states)
            assert read_gap["gap"] < arch.state_tol(config), read_gap
            assert max(read_gap["whole_by_layer"]) < 1e-3, read_gap
        layers = model.blocks.layers_of["linear_attention"]
        assert 0 < live <= computed and live % layers == 0
        # three requests x five decode rows each (the first token is the
        # prefill's), every one a live slot of the three
        assert advanced == 3 * 5 * layers
        assert stepped % (3 * layers) == 0 and stepped >= advanced
        assert telemetry.get_registry().gauge("kv_state_bytes").value(
            kind="slot") == model.blocks.cache_bytes(4)["state"]["slot"]


def _attention_layer(tiny, **over):
    """The first gated attention layer's mixer alone, the program's and
    the reference's, on one random normed input."""
    from hetu_tpu.nn.parallel import ParallelAttention
    config, model, params = tiny
    a = jax.tree.map(lambda x: x[0], params["blocks"]["runs"]["1"]["attn"])
    u = jax.random.normal(jax.random.key(4), (1, 29, config["hidden_size"]))
    d = config["head_dim"]
    kw = dict(num_kv_heads=config["num_key_value_heads"], head_dim=d,
              bias=False, use_rope=True, rope_theta=config["rope_theta"],
              max_positions=64, rotary_dim=d // 4, qk_norm=True,
              qk_gain=2.0, zero_centered=True, out_gate=True)
    kw.update(over)
    attn = ParallelAttention(config["hidden_size"],
                             config["num_attention_heads"], **kw)
    got = attn(a, u, positions=jnp.arange(29)[None])[0]
    return got, lambda **c: reference.gated_attention(a, u[0], config, **c)


def test_partial_rotary_and_the_output_gate_one_layer_at_a_time(tiny):
    """The quarter rotary (8 of 32 numbers, as ONE head of 8) and the
    gate from the doubled ``q_proj`` against the reference's layer; a
    full rotary, a missing gate and a plain gain are each ANOTHER
    function, in the program and the reference alike."""
    got, ref = _attention_layer(tiny)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got, ref(), atol=2e-5)
        for over, control in ((dict(rotary_dim=None), "full_rotary"),
                              (dict(zero_centered=False, qk_gain=1.0),
                               "plain_gain")):
            moved, ref = _attention_layer(tiny, **over)
            assert float(jnp.abs(moved - got).max()) > 1e-2, control
            np.testing.assert_allclose(moved, ref(**{control: True}),
                                       atol=2e-5)
    with pytest.raises(ValueError, match="rotary_dim"):
        _attention_layer(tiny, rotary_dim=7)


def test_eight_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the routed sums of the eight chips of a
    deployment (two of the sixteen experts each, the weights normalised
    over ALL three chosen) and the gated shared expert counted ONCE add
    up to the reference's layer with every expert held."""
    from hetu_tpu.nn.moe import ExpertShareMoE
    config, _, params = tiny
    blk = jax.tree.map(lambda x: x[1], params["blocks"]["runs"]["0"])
    E = config["hidden_size"]
    u = jax.random.normal(jax.random.key(2), (60, E))
    total, sizes = jnp.zeros_like(u), []
    for g in range(8):
        share = ExpertShareMoE(E, config["moe_intermediate_size"], 16, k=3,
                               local_experts=(2 * g, 2), score="softmax")
        part = {"router": blk["moe"]["router"],
                **{n: blk["moe"][n][2 * g:2 * g + 2]
                   for n in ("wg", "wi", "wo")}}
        out, st = share(part, u, return_stats=True)
        total = total + out
        sizes.append(int(st["sizes"].sum()))
    assert sum(sizes) == 60 * 3               # every chosen pair, once
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_ffn(blk, u, config)
        shared = reference.swiglu(blk["shared"], u) * jax.nn.sigmoid(
            u @ blk["shared_gate"])
        np.testing.assert_allclose(total + shared, want, atol=2e-6)
        # ... and one share's layer is the reference's at that share
        one = {**config, "num_experts": 2, "deployment": {"expert_share": 5}}
        share = ExpertShareMoE(E, config["moe_intermediate_size"], 16, k=3,
                               local_experts=(10, 2), score="softmax")
        got = share({"router": blk["moe"]["router"],
                     **{n: blk["moe"][n][10:12]
                        for n in ("wg", "wi", "wo")}}, u)
        part = {**blk, "moe": {"router": blk["moe"]["router"],
                               **{n: blk["moe"][n][10:12]
                                  for n in ("wg", "wi", "wo")}}}
        np.testing.assert_allclose(
            got + shared, reference.expert_ffn(part, u, one)[0], atol=2e-6)


def test_share_in_both_forms_equals_ragged_dot_on_its_own_tile(
        ragged_dot_experts, grouped_form):
    """The pack lane's expert share in small — 8 of 64 experts under a
    softmax top-4, 640 tokens, so a window of 640 rows for the ~320
    expected: the fused call's tile holds one and a half times the rows
    an expert expects (64 for 40, as the cell's 64 for 40), the split
    calls' the window's share (128) — against three ``ragged_dot``
    calls over the dense sorted rows."""
    from hetu_tpu.nn.moe import ExpertShareMoE
    moe = ExpertShareMoE(32, 16, 64, k=4, local_experts=(8, 8),
                         score="softmax")
    params = moe.init(jax.random.key(3))
    u = jax.random.normal(jax.random.key(4), (640, 32))
    assert moe._window_rows(640 * 4) == 640
    assert (moe.grouped_form(640 * 4), moe.tile_rows(640 * 4)) \
        == (grouped_form, {"fused": 64, "split": 128}[grouped_form])
    out, st = jax.jit(lambda p, x: moe(p, x, return_stats=True))(params, u)
    assert 0 < int(st["sizes"].sum()) <= 640
    want = ragged_dot_experts(moe, params, u)
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(out, want, atol=2e-6)


def test_a_steep_decay_survives_the_chunked_lanes():
    """Weights drawn so that heads decay by e^-16 a token and more (the
    tiny preset's ``dt_range`` up to 1): chunked prefill then decode
    still serve the full forward's tokens — the per-channel chunk form
    returns garbage there (its reference row's exponent passes 88)."""
    from hetu_tpu.models.qwen3_next import (
        Qwen3NextConfig, Qwen3NextForCausalLM,
    )
    from hetu_tpu.serving import SamplingParams, ServingEngine
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny(init_std=0.16))
    params = model.init(jax.random.key(7))
    ids = np.random.default_rng(0).integers(1, 127, 40)
    eng = ServingEngine(model, params, max_len=64, prefill_chunk=8,
                        block_size=4, slots=2, kv_blocks=40)
    (toks,) = eng.generate_many([ids.tolist()], SamplingParams(max_tokens=8))
    lg = np.asarray(model(params, jnp.asarray(
        np.concatenate([ids, toks]))[None])[0])[39:47]
    assert (lg.max(-1) - lg[np.arange(8), toks]).max() <= 1e-3


def test_the_config_refuses_what_is_not_built():
    from hetu_tpu.models import Qwen3NextConfig
    with pytest.raises(NotImplementedError, match="tied head"):
        Qwen3NextConfig.tiny(tie_word_embeddings=True)
    with pytest.raises(NotImplementedError, match="differ in size"):
        Qwen3NextConfig.tiny(linear_value_head_dim=32)
    with pytest.raises(ValueError, match="at least one attention"):
        Qwen3NextConfig.tiny(num_hidden_layers=3)
