"""Brumby (``hetu_tpu/models/brumby.py``): power retention of degree 2
(``ops/retention.py``, ``ops/retention_pallas.py``) in a stack that
keeps NO token rows — the feature map, the ``jax.numpy`` forms against
the token recurrence, both kernels (interpreted) against those, the
model against the plain quadratic reference
(``benchmark/reference/brumby.py``), chunked prefill then decoding
through the state against the reference's one forward pass (logits), a
reused slot included; a stack of per-slot kinds alone builds, serves and
holds no arena leaf; admission by slots; what is refused over a slot
state is refused by name; the other stacks' leaves as before."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from served import (
    PAGE_MOVERS, REFUSED, ROOT, ServedArchContract, counted,
    top_token_gaps,
)
from served import pack_slices as _pack
from benchmark.reference import brumby as reference
from hetu_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
from hetu_tpu.nn.parallel import SlotStateNotSupported
from hetu_tpu.ops import retention as R
from hetu_tpu.ops import retention_pallas as P

EPS = 1e-6
H, HKV, D = 4, 2, 16


def _draw(T, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (T, H, D)),
            jax.random.normal(ks[1], (T, HKV, D)),
            jax.random.normal(ks[2], (T, HKV, D)),
            jax.nn.log_sigmoid(2 * jax.random.normal(ks[3], (T, HKV)) + 2))


def _zeros(slots):
    return jnp.zeros((slots, HKV, R.feature_rows(D), R.value_rows(D), D))


def test_phi_is_the_second_tensor_power_in_both_layouts():
    x, y = (jax.random.normal(jax.random.key(i), (7, D)) for i in (0, 1))
    want = jnp.sum(x * y, -1) ** 2
    assert R.phi(x).shape == (7, D * (D + 1) // 2)
    np.testing.assert_allclose((R.phi(x) * R.phi(y)).sum(-1), want,
                               rtol=1e-4, atol=1e-5)
    tiles = R.phi_tiles(x)
    assert tiles.shape == (7, D // 2 + 1, D)
    np.testing.assert_allclose((tiles * R.phi_tiles(y)).sum((-1, -2)),
                               want, rtol=1e-4, atol=1e-5)
    # the same multiset of features: D / 2 spare places are zero
    assert int((np.asarray(tiles[0]) == 0).sum()) == D // 2
    np.testing.assert_allclose(np.sort(np.abs(tiles[0]).ravel())[D // 2:],
                               np.sort(np.abs(R.phi(x)[0])), rtol=1e-6)
    # 8,256 features in 8,320 places, 129 value rows in 136
    assert (R.feature_rows(128) * 128, R.value_rows(128)) == (8320, 136)
    with pytest.raises(ValueError, match="even"):
        R.feature_rows(15)


def test_the_recurrence_is_the_quadratic_form():
    T = 40
    q, k, v, lg = _draw(T)
    y, _ = R.retention_recurrence(q, k, v, lg, eps=EPS)
    G = jnp.cumsum(lg, 0)
    s = jnp.einsum("qhgd,khd->hgqk", q.reshape(T, HKV, H // HKV, D), k) \
        / D ** 0.5
    w = jnp.where((jnp.arange(T)[None] <= jnp.arange(T)[:, None])[None, None],
                  s * s * jnp.exp(G.T[:, :, None] - G.T[:, None, :])[:, None],
                  0.0)
    want = jnp.einsum("hgqk,khd->qhgd", w, v) \
        / (jnp.moveaxis(w.sum(-1), 2, 0)[..., None] + EPS)
    np.testing.assert_allclose(y, want.reshape(T, H, D), atol=2e-5)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_scan_equals_the_recurrence_over_packs_of_several_slots(form):
    """A run cut over two packs, a second slot's run beside it, a slot
    without a token keeping its state to the bit, and a slot TAKEN AGAIN
    at position 0 starting from zeros whatever it held."""
    scan = (lambda *a: R.retention_scan(*a, eps=EPS, block=16)) \
        if form == "jnp" else (lambda *a: P.hetu_retention_scan(*a, eps=EPS))
    a, b = _draw(40, 0), _draw(40, 1)
    ya, Sa = R.retention_recurrence(*a, eps=EPS)
    yb, Sb = R.retention_recurrence(*b, eps=EPS)
    st = _zeros(3).at[0].set(1.0).at[1].set(7.0)   # slot 1 holds rubbish
    ops, where = _pack([(1, (0, 24), a)], 32)
    o1, st = scan(*ops, st, *where)
    np.testing.assert_allclose(o1[:24], ya[:24], atol=2e-5)
    assert not np.asarray(o1[24:]).any()
    ops, where = _pack([(1, (24, 40), a), (2, (0, 8), b)], 32)
    o2, st = scan(*ops, st, *where)
    np.testing.assert_allclose(o2[:16], ya[24:], atol=2e-5)
    np.testing.assert_allclose(o2[16:24], yb[:8], atol=2e-5)
    np.testing.assert_allclose(st[1], R.state_tiles(Sa), atol=1e-5)
    assert (np.asarray(st[0]) == 1.0).all()          # to the bit
    # pieces that end inside a chunk, two runs in one chunk, a pad tail
    ops, where = _pack([(2, (8, 13), b), (0, (0, 6), a)], 16)
    o3, st = scan(*ops, st, *where)
    np.testing.assert_allclose(o3[:5], yb[8:13], atol=2e-5)
    np.testing.assert_allclose(o3[5:11], ya[:6], atol=2e-5)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
@pytest.mark.parametrize("live", [(True, False, True), (False, False, False),
                                  (False, True, False)])
def test_update_equals_one_step_of_the_recurrence(form, live):
    update = R.retention_update if form == "jnp" \
        else P.hetu_retention_update
    seqs = [_draw(12, i) for i in range(3)]
    before = [R.retention_recurrence(*(x[:11] for x in s), eps=EPS)[1]
              for s in seqs]
    after = [R.retention_recurrence(*s, eps=EPS) for s in seqs]
    st = jnp.stack([R.state_tiles(S) for S in before])
    q, k, v, lg = (jnp.stack([s[i][11] for s in seqs]) for i in range(4))
    y, new = update(q, k, v, lg, st, jnp.asarray(live), eps=EPS)
    for s, on in enumerate(live):
        if on:
            np.testing.assert_allclose(y[s], after[s][0][11], atol=2e-5)
            np.testing.assert_allclose(new[s], R.state_tiles(after[s][1]),
                                       atol=1e-5)
        else:           # not live: the state to the bit, zeros out
            assert (np.asarray(new[s]) == np.asarray(st[s])).all()
            assert not np.asarray(y[s]).any()


def test_kernels_address_the_stacked_leaf_in_place_at_a_layer():
    a = _draw(24)
    ops, where = _pack([(1, (0, 24), a)], 24)
    st = _zeros(3).at[0].set(1.0)
    leaf = jnp.stack([st, 2 * st, 3 * st])
    want = R.retention_scan(*ops, 2 * st, *where, eps=EPS)
    y, got, runs = P.hetu_retention_scan(
        *ops, leaf, *where, eps=EPS, layer=jnp.int32(1), return_runs=True)
    assert int(runs) == 1
    np.testing.assert_allclose(y, want[0], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    assert (np.asarray(got[0]) == np.asarray(st)).all()
    assert (np.asarray(got[2]) == np.asarray(3 * st)).all()
    q, k, v, lg = (x[:3] for x in a)
    live = jnp.asarray([False, True, True])
    want = R.retention_update(q, k, v, lg, got[1], live, eps=EPS)
    y, new = P.hetu_retention_update(q, k, v, lg, got, live, eps=EPS,
                                     layer=jnp.int32(1))
    np.testing.assert_allclose(y, want[0], atol=2e-4)
    np.testing.assert_allclose(new[1], want[1], atol=1e-4)
    assert (np.asarray(new[0]) == np.asarray(got[0])).all()
    assert (np.asarray(new[1, 0]) == np.asarray(got[1, 0])).all()


def test_bf16_operands_stay_close_to_the_float32_form():
    """The operands the cell serves in: the MXU's products take bf16
    ``phi``, state copy and weights (float32 accumulation), so the scan
    differs from the float32 form by the operands' rounding — 2^-9 a
    factor, a few of them a product: under 3 % of the values' size."""
    a = _draw(32)
    ops, where = _pack([(0, (0, 32), a)], 32)
    want, _ = R.retention_scan(*ops, _zeros(1), *where, eps=EPS)
    got, _ = P.hetu_retention_scan(
        *(x.astype(jnp.bfloat16) for x in ops[:3]), ops[3], _zeros(1),
        *where, eps=EPS)
    assert float(jnp.abs(got - want).max()) < 0.03 * float(
        jnp.abs(want).max())
    assert P.scan_chunk(2048) == 256 and P.scan_chunk(12) == 16
    assert P.update_rows(65) == 13 and P.update_rows(9) == 9
    ids, n = P.live_list(jnp.asarray([False, True, False, True]))
    assert ids.tolist()[:2] == [1, 3] and int(n[0]) == 2


# -- the model ---------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    model = BrumbyForCausalLM(BrumbyConfig.tiny(init_std=0.25))
    config = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  rms_norm_eps=1e-6, rope_theta=1e6,
                  assumed={"retention_eps": 1e-6})
    return config, model, model.init(jax.random.key(1))


class TestBrumby(ServedArchContract):
    """The program's forward (the token recurrence on the natural
    features; the kernels interpreted) against the quadratic reference,
    float32 both: they differ by rounding alone — most at a row's first
    positions, where the normaliser is one small weight. The chunked
    case's prompts cross chunk and piece edges (a pack of 8 holds one
    run's tail and the next one's head)."""
    reference = reference
    ref_kw = {"q_block": 16}
    forward_ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 96, (2, 50)), jnp.int32)
    tol = 5e-3
    controls = [dict(no_gate=True), dict(reset_every=16),
                dict(diag_only=True), dict(operands=jnp.float8_e4m3fn)]
    control_ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 96, 50), jnp.int32)
    control_from, control_moves = 20, 0.1
    chunks = (8,)
    requests = (2, 0, 96, ((0, 21, 6), (1, 13, 4), (0, 18, 5)))
    serve = dict(slots=2)
    lanes = [dict()]
    refused = REFUSED
    page_movers = tuple(PAGE_MOVERS)
    small_engine = dict(max_len=64, prefill_chunk=8, slots=2, kv_blocks=0)
    refuses_the_dense_cache = True
    new_modules = ("hetu_tpu.models.brumby", "hetu_tpu.ops.retention",
                   "hetu_tpu.ops.retention_pallas")

    def close(self, got, want, atol):
        np.testing.assert_allclose(got[4:], want[4:], atol=atol / 10)
        super().close(got, want, atol)

    def test_engine_serves_tokens_the_reference_puts_on_top(self, tiny,
                                                            lanes):
        """... WITHOUT AN ARENA, admitting by slots: no arena leaf, no
        table, no ledger for the scheduler; five requests through three
        slots (a free slot is the whole price), one trace and one
        executable."""
        from hetu_tpu import telemetry
        from hetu_tpu.engine import trace_counts
        from hetu_tpu.serving import ServingEngine
        from hetu_tpu.serving.kv_pool import NoBlocks
        from hetu_tpu.serving.scheduler import SamplingParams
        _, model, params = tiny
        before = trace_counts().get("serving_step", 0)
        reg = telemetry.get_registry()

        def read():      # (the counters are the process's: deltas)
            got = {n: reg.get(n) for n in ("retention_rows_total",
                                           "retention_runs_total",
                                           "serving_attn_kernel_total")}
            return [0 if c is None else c.value(**kw) for c, kw in (
                (got["retention_rows_total"], {"lane": "prefill"}),
                (got["retention_rows_total"], {"lane": "decode"}),
                (got["retention_runs_total"], {}),
                (got["serving_attn_kernel_total"], {"path": "none"}))]
        with counted(read) as delta:
            eng = ServingEngine(model, params, slots=3, max_len=64,
                                prefill_chunk=8, kv_blocks=0)
            assert not model.blocks.paged and model.blocks.slot_state
            assert [c.shape for c in eng.pool.caches] == [
                (3, 3, 2, 9, 24, 16)]
            assert eng.pool.caches[0].dtype == jnp.float32
            assert (eng.pool.n_blocks, eng.pool.table_width,
                    eng._bt.shape) == (0, 0, (3, 0))
            assert isinstance(eng.blocks, NoBlocks) \
                and eng.scheduler.blocks is None
            assert eng.attn_kernel == "none" and eng.prefix_cache is None
            rng = np.random.default_rng(3)
            prompts = [rng.integers(0, 96, n).tolist()
                       for n in (5, 19, 8, 13, 30)]
            reqs = [eng.submit(p, SamplingParams(max_tokens=6))
                    for p in prompts]
            assert all(eng.scheduler.blocks_needed(r) == 0 for r in reqs)
            eng.step()
            assert len(eng.scheduler.free) == 0 \
                and eng.scheduler.depth == 2
            eng.run_until_drained()
        assert trace_counts()["serving_step"] - before == 1
        assert eng.step_executables() == 1
        for p, r in zip(prompts, reqs):
            assert len(r.tokens) == 6
            gap = top_token_gaps(
                self.ref_logits(tiny, jnp.asarray(p + r.tokens)), len(p),
                r.tokens)
            assert gap.max() < 1e-3
        assert reg.get("kv_state_bytes").value(kind="slot") == \
            3 * 2 * 9 * 24 * 16 * 4
        assert reg.get("serving_slots").value(state="free") == 3
        prefill, decode, runs, iters = delta
        # every prompt token once a layer; every token but a request's
        # first is decoded
        assert prefill == 3 * sum(map(len, prompts))
        assert decode == 3 * 5 * 5
        assert runs >= 3 * 5 and iters > 0

    def test_dense_cache_and_cp_prefill_refuse_by_name(self, tiny):
        super().test_dense_cache_and_cp_prefill_refuse_by_name(tiny)
        _, model, params = tiny
        with pytest.raises(SlotStateNotSupported, match="return_kv"):
            model.blocks.block.attn(
                jax.tree.map(lambda x: x[0],
                             params["blocks"]["layers"]["attn"]),
                jnp.zeros((1, 4, 32)), return_kv=True)


def test_a_request_longer_than_any_page_budget_runs(tiny):
    """``max_len`` bounds positions only: a request that fills it runs
    in an engine with ``kv_blocks=0``; one beyond it is rejected by
    name; an arena is refused."""
    from hetu_tpu.serving import ServingEngine
    from hetu_tpu.serving.scheduler import SamplingParams
    _, model, params = tiny
    eng = ServingEngine(model, params, slots=1, max_len=200,
                        prefill_chunk=64, kv_blocks=0)
    rng = np.random.default_rng(4)
    (out,) = eng.generate_many([rng.integers(0, 96, 190).tolist()],
                               SamplingParams(max_tokens=10))
    assert len(out) == 10
    r = eng.submit(rng.integers(0, 96, 195).tolist(),
                   SamplingParams(max_tokens=10))
    assert r.status == "rejected"
    with pytest.raises(ValueError, match="no arena"):
        ServingEngine(model, params, slots=1, max_len=64, kv_blocks=40)
    with pytest.raises(ValueError, match="sized in slots"):
        ServingEngine(model, params, max_len=64, hbm_budget_bytes=1e9)


def test_other_stacks_build_the_leaves_they_built():
    """Shapes and order pinned: the paged kinds' leaves, then the slot
    leaves; every stack with a paged kind stays ``paged``."""
    from hetu_tpu.models.kda_mla_moe import (
        KDAMLAMoEConfig, KDAMLAMoEForCausalLM,
    )
    from hetu_tpu.models.minicpm_sala import (
        MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
    )
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel

    def leaves(model):
        return [(x.shape, x.dtype) for x in jax.eval_shape(
            lambda: model.blocks.init_paged_caches(
                11, 4, jnp.bfloat16, 3))]
    f32, bf16 = jnp.float32, jnp.bfloat16
    sala = MiniCPMSALAForCausalLM(MiniCPMSALAConfig.tiny())
    assert leaves(sala) == [
        ((2, 11, 8, 16), bf16), ((2, 11, 8, 16), bf16),
        ((2, 11, 128), bf16), ((3, 3, 4, 16, 16), f32)]
    assert sala.blocks.paged and sala.blocks.slot_state
    assert sala.blocks.cache_bytes(2)["state"] == {"slot": 3 * 4 * 16 * 16 * 4}
    ling = KDAMLAMoEForCausalLM(KDAMLAMoEConfig.tiny())
    got = leaves(ling)
    assert got == [((2, 11, 4, 40), bf16), ((5, 3, 4, 16, 16), f32),
                   ((5, 3, 3, 192), f32)]
    assert ling.blocks.paged and ling.blocks.slot_state
    gpt = GPTLMHeadModel(GPTConfig.tiny())
    assert gpt.blocks.paged and not gpt.blocks.slot_state
    brumby = BrumbyForCausalLM(BrumbyConfig.tiny())
    assert leaves(brumby) == [((3, 3, 2, 9, 24, 16), f32)]
    assert brumby.blocks.cache_bytes(4) == {
        "row": {}, "state": {"slot": 3 * 2 * 9 * 24 * 16 * 4}}


def test_published_widths_and_the_state_a_slot():
    with open(os.path.join(
            ROOT, "benchmark/configs/brumby-14b-pp4.json")) as f:
        c = json.load(f)
    from benchmark.runners.serve_arch import load_arch
    model = load_arch(c["arch"]).build(c)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    # the counted matrices, and the gains and the gate's bias on top
    assert 0 <= n - c["sizes"]["held_parameters"] < 2e5
    (leaf,) = jax.eval_shape(lambda: model.blocks.init_paged_caches(
        0, 1, jnp.float32, c["serve"]["slots"]))
    assert leaf.shape == (10, 14, 8, 65, 136, 128)
    assert model.blocks.cache_bytes(4)["state"]["slot"] == \
        c["sizes"]["state_bytes_a_slot"] == 362086400
    assert 14 * 362086400 == c["sizes"]["state_bytes"]
