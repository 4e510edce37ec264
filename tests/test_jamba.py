"""Jamba (``hetu_tpu/models/jamba.py``): Mamba-1 selective-scan layers
(``ops/selective_scan.py``, ``ops/selective_scan_pallas.py``) beside
multi-query attention layers with no positions — the ``jax.numpy``
forms against the token recurrence, both kernels (interpreted) against
those, the mixer cached against its whole-sequence forward, attention of
ONE kv head through both paged calls, the model against the plain
reference (``benchmark/reference/jamba.py``), chunked prefill then
decoding through the state, the tail and the arena against the
reference's one forward pass (logits), a reused slot included; the
counters; what is refused over a slot state is refused by name."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import served
from served import REFUSED, ROOT, ServedArchContract
from served import pack_slices as _pack
from benchmark.reference import jamba as reference
from benchmark.runners.serve_arch import load_arch
from hetu_tpu import telemetry
from hetu_tpu.models import generation
from hetu_tpu.nn.parallel import (
    MambaMixer, ParallelAttention, SlotStateNotSupported,
)
from hetu_tpu.ops import selective_scan as S
from hetu_tpu.ops import selective_scan_pallas as P

D, N = 256, 4


def _draw(T, seed=0):
    """``(x, dt, B, C)`` of one sequence."""
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (T, D)),
            jax.nn.softplus(jax.random.normal(ks[1], (T, D)) - 1.0),
            jax.random.normal(ks[2], (T, N)),
            jax.random.normal(ks[3], (T, N)))


A = -jnp.exp(0.5 * jax.random.normal(jax.random.key(9), (N, D)))


def _forms(form):
    """``(scan, update, to the form's state layout, back)``."""
    if form == "jnp":
        return (lambda ops, st, *w, **kw: S.selective_scan(
                    ops[0], ops[1], A, ops[2], ops[3], st, *w, **kw),
                lambda ops, st, *w, **kw: S.selective_update(
                    ops[0], ops[1], A, ops[2], ops[3], st, *w, **kw),
                lambda st: st, lambda st: st)
    tiles = P.state_tiles(D)
    return (lambda ops, st, *w, **kw: P.hetu_selective_scan(
                ops[0], ops[1], A, ops[2], ops[3], st, *w, **kw),
            lambda ops, st, *w, **kw: P.hetu_selective_update(
                ops[0], ops[1], A, ops[2], ops[3], st, *w, **kw),
            lambda st: st.reshape(st.shape[:-1] + tiles),
            lambda st: st.reshape(st.shape[:-2] + (D,)))


def test_state_tiles_are_whole_lane_rows():
    assert P.state_tiles(5120) == (40, 128)
    assert P.state_tiles(128) == (1, 128) and P.state_tiles(64) == (1, 64)
    with pytest.raises(ValueError, match="whole rows"):
        P.state_tiles(192)
    # compiled for a TPU: whole (8, 128) register tiles, by name
    x = jnp.zeros((8, 128))
    with pytest.raises(ValueError, match="whole register tiles"):
        P.hetu_selective_scan(
            x, x, jnp.zeros((4, 128)), x[:, :4], x[:, :4],
            jnp.zeros((2, 4, 1, 128)), jnp.zeros(8, jnp.int32),
            jnp.zeros(8, jnp.int32), jnp.ones(8, bool), interpret=False)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_scan_equals_the_recurrence_over_packs_of_several_slots(form):
    """A run cut over two packs, a second slot's run beside it, a slot
    without a token keeping its state to the bit, and a slot TAKEN AGAIN
    at position 0 starting from zeros whatever it held."""
    scan, _, lay, flat = _forms(form)
    a, b = _draw(40, 0), _draw(40, 1)
    ya, ha = S.selective_recurrence(a[0], a[1], A, a[2], a[3])
    yb, hb = S.selective_recurrence(b[0], b[1], A, b[2], b[3])
    st = lay(jnp.zeros((3, N, D)).at[0].set(1.0).at[1].set(7.0))
    ops, where = _pack([(1, (0, 24), a)], 32)     # slot 1 holds rubbish
    o1, st = scan(ops, st, *where)
    np.testing.assert_allclose(o1[:24], ya[:24], atol=2e-5)
    assert not np.asarray(o1[24:]).any()
    ops, where = _pack([(1, (24, 40), a), (2, (0, 8), b)], 32)
    o2, st = scan(ops, st, *where)
    np.testing.assert_allclose(o2[:16], ya[24:], atol=2e-5)
    np.testing.assert_allclose(o2[16:24], yb[:8], atol=2e-5)
    np.testing.assert_allclose(flat(st)[1], ha, atol=2e-5)
    assert (np.asarray(flat(st)[0]) == 1.0).all()    # no token: untouched
    # slot 1 taken again by another request
    ops, where = _pack([(1, (0, 10), b), (2, (8, 20), b)], 32)
    o3, st = scan(ops, st, *where)
    np.testing.assert_allclose(o3[:10], yb[:10], atol=2e-5)
    np.testing.assert_allclose(o3[10:22], yb[8:20], atol=2e-5)


@pytest.mark.parametrize("cut", [1, 7, 8, 9, 16, 31])
@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_a_run_split_across_packs_at_any_boundary(form, cut):
    """The state carried across the boundary: two packs equal one
    pass, wherever the cut falls against the kernel's chunks of 8."""
    scan, _, lay, flat = _forms(form)
    a = _draw(32, 3)
    want, h = S.selective_recurrence(a[0], a[1], A, a[2], a[3])
    st = lay(jnp.zeros((2, N, D)))
    kw = {} if form == "jnp" else {"chunk": 8}
    ops, where = _pack([(1, (0, cut), a)], 32)
    o1, st = scan(ops, st, *where, **kw)
    ops, where = _pack([(1, (cut, 32), a)], 32)
    o2, st = scan(ops, st, *where, **kw)
    np.testing.assert_allclose(o1[:cut], want[:cut], atol=2e-5)
    np.testing.assert_allclose(o2[:32 - cut], want[cut:], atol=2e-5)
    np.testing.assert_allclose(flat(st)[1], h, atol=2e-5)
    assert not np.asarray(flat(st)[0]).any()


@pytest.mark.parametrize("live", [(True, False, True), (False,) * 3,
                                  (True,) * 3],
                         ids=["some", "none", "all"])
@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_update_equals_one_step_of_the_recurrence(form, live):
    _, update, lay, flat = _forms(form)
    a = _draw(3, 5)
    st0 = jax.random.normal(jax.random.key(6), (3, N, D))
    fresh = jnp.asarray([False, False, True])
    live = jnp.asarray(live)
    y, st = update(a, lay(st0), live, fresh=fresh)
    for s in range(3):
        if not live[s]:
            assert (np.asarray(flat(st)[s]) == np.asarray(st0[s])).all()
            assert not np.asarray(y[s]).any()
            continue
        want, h = S.selective_recurrence(
            a[0][s:s + 1], a[1][s:s + 1], A, a[2][s:s + 1], a[3][s:s + 1],
            jnp.zeros((N, D)) if fresh[s] else st0[s])
        np.testing.assert_allclose(y[s], want[0], atol=1e-5)
        np.testing.assert_allclose(flat(st)[s], h, atol=1e-5)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_forms_address_the_stacked_leaf_in_place_at_a_layer(form):
    """``layer=``: only ``[layer, slot]`` of a live slot or run moves;
    every other layer and slot keeps its bits."""
    scan, update, lay, flat = _forms(form)
    a = _draw(12, 7)
    st0 = jax.random.normal(jax.random.key(8), (3, 2, N, D))
    ops, where = _pack([(1, (4, 16), _draw(16, 7))], 16)
    y, st = scan(ops, lay(st0), *where, layer=jnp.int32(2))
    st = np.asarray(flat(st))
    assert (st[:2] == np.asarray(st0[:2])).all()
    assert (st[2, 0] == np.asarray(st0[2, 0])).all()
    want, h = S.selective_recurrence(
        ops[0][:12], ops[1][:12], A, ops[2][:12], ops[3][:12], st0[2, 1])
    np.testing.assert_allclose(y[:12], want, atol=2e-5)
    np.testing.assert_allclose(st[2, 1], h, atol=2e-5)
    y, st = update(tuple(t[:2] for t in a), lay(st0),
                   jnp.asarray([False, True]), layer=jnp.int32(1))
    st = np.asarray(flat(st))
    assert (st[0] == np.asarray(st0[0])).all()
    assert (st[2] == np.asarray(st0[2])).all()
    assert (st[1, 0] == np.asarray(st0[1, 0])).all()
    assert np.abs(st[1, 1] - np.asarray(st0[1, 1])).max() > 1e-3


def test_kernels_report_their_steps():
    a = _draw(20, 2)
    st = jnp.zeros((3, N) + P.state_tiles(D))
    ops, where = _pack([(0, (0, 12), a), (2, (0, 8), a)], 32)
    _, _, steps = P.hetu_selective_scan(
        ops[0], ops[1], A, ops[2], ops[3], st, *where, chunk=8,
        return_steps=True)
    # 32 rows in chunks of 8: pieces 0-7, 8-11 | 12-15 (a run opens
    # inside the chunk), 16-19, and a chunk without a valid row
    assert steps.tolist() == [4, 5]
    _, _, steps = P.hetu_selective_update(
        ops[0][:3], ops[1][:3], A, ops[2][:3], ops[3][:3], st,
        jnp.asarray([True, False, True]), return_steps=True)
    assert steps.tolist() == [2, 3]


# -- the mixer and the attention of one kv head --------------------------------
def test_mixer_cached_equals_its_whole_sequence_forward():
    """Two packs and a decode row through the two leaves against the
    forward without a cache (the oracle recurrence)."""
    mixer = MambaMixer(32, d_state=4, dt_rank=4, dt_range=(1e-2, 1.0),
                       init=lambda k, s, d: 0.3 * jax.random.normal(k, s, d))
    params = mixer.init(jax.random.key(0))
    assert mixer.kv_leaf_shapes() == () and mixer.cache_leaves == 2
    assert mixer.state_bytes() == 4 * 64 * (4 + 4)
    state, tail = mixer.init_leaves(2, 3)
    assert state.shape == (2, 3, 4, 1, 64) and tail.shape == (2, 3, 4, 64)
    assert state.dtype == tail.dtype == jnp.float32
    x = jax.random.normal(jax.random.key(1), (1, 21, 32))
    want = mixer(params, x)
    caches, out = (state, tail), []
    layer = jnp.int32(1)
    packed = jax.jit(lambda rows, pos, caches, pack: mixer(
        params, rows, positions=pos, kv_cache=(caches, layer), pack=pack))
    for lo, hi in ((0, 8), (8, 20)):       # ONE program, two packs
        n = hi - lo
        rows = jnp.pad(x[:, lo:hi], ((0, 0), (0, 12 - n), (0, 0)))
        pos = jnp.pad(jnp.arange(lo, hi), (0, 12 - n))[None]
        o, caches, st = packed(
            rows, pos, caches, {"valid": jnp.arange(12) < n,
                                "slot": jnp.full((12,), 2, jnp.int32)})
        out.append(o[0, :n])
        assert st["ssm_steps"].shape == (4,)
        assert st["ssm_steps"][2:].tolist() == [0, 0]
    rows = jnp.zeros((3, 1, 32)).at[2, 0].set(x[0, 20])
    o, caches, st = mixer(
        params, rows, positions=jnp.asarray([[0], [0], [20]]),
        kv_cache=(caches, layer),
        slot_mask=jnp.asarray([False, False, True]))
    out.append(o[2])
    assert st["ssm_steps"].tolist() == [0, 0, 1, 3]
    np.testing.assert_allclose(jnp.concatenate(out), want[0], atol=2e-5)
    assert not np.asarray(caches[0][0]).any()       # the other layer
    assert not np.asarray(caches[1][1, :2]).any()   # the other slots


@pytest.mark.parametrize("rows", [1, 3])
def test_paged_kernel_at_one_kv_head_of_128_under_twenty_query_heads(rows):
    """The arena row is ONE lane tile and a decode row's q tile 20
    heads — not whole sublane tiles: the paged call (interpreted)
    against the gather oracle."""
    from hetu_tpu.ops.paged_pallas import (
        paged_attention_pallas, paged_attention_reference,
    )
    S_, W, bs, hq, d = 3, 4, 8, 20, 128
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (S_, rows, hq, d))
    k = jax.random.normal(ks[1], (2, 1 + S_ * W, bs, d))
    v = jax.random.normal(ks[2], (2, 1 + S_ * W, bs, d))
    tbl = 1 + jnp.arange(S_ * W, dtype=jnp.int32).reshape(S_, W)
    off = jnp.asarray([5, 17, 28], jnp.int32)
    got = paged_attention_pallas(q, k, v, tbl, off, layer=jnp.int32(1),
                                 interpret=True)
    want = paged_attention_reference(q, k[1], v[1], tbl, off)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_attention_without_positions_paged_equals_the_reference():
    """``ParallelAttention(num_kv_heads=1, use_rope=False)``: its
    whole-sequence forward is ``attention_reference`` on q, k, v as
    projected — no rotation, no learned position."""
    from hetu_tpu.ops.attention import attention_reference
    attn = ParallelAttention(64, 4, num_kv_heads=1, head_dim=16,
                             bias=False, use_rope=False)
    params = attn.init(jax.random.key(0))
    assert attn.kv_leaf_shapes() == ((1, 16), (1, 16))
    x = jax.random.normal(jax.random.key(1), (2, 12, 64))
    q = (x @ params["q_proj"]["weight"]).reshape(2, 12, 4, 16)
    k = (x @ params["k_proj"]["weight"]).reshape(2, 12, 1, 16)
    v = (x @ params["v_proj"]["weight"]).reshape(2, 12, 1, 16)
    want = attention_reference(q, k, v, causal=True).reshape(2, 12, 64) \
        @ params["out_proj"]["weight"]
    np.testing.assert_allclose(attn(params, x), want, atol=2e-5)
    # a row's result does not depend on where the row stands
    np.testing.assert_allclose(
        attn(params, x, positions=jnp.arange(12)[None] + 50),
        attn(params, x), atol=1e-6)


# -- the model -----------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    return served.tiny("jamba", 55)


def _ssm_counters():
    reg = telemetry.get_registry()
    c = reg.counter("ssm_scan_steps_total")
    u = reg.counter("ssm_update_slots_total")
    return [c.value(kind=k) for k in ("live", "computed")] + [
        u.value(kind=k) for k in ("live", "stepped")]


class TestJamba(ServedArchContract):
    """Float32 both sides: the program (the oracle recurrence, the
    kernels interpreted) against the reference's token loop differs by
    rounding alone — 1e-3 on logits that span +-12 at the tiny
    configuration's init_std of 0.3 (1e-4 of their size, over 14
    layers). The chunks cut the convolution's window and the scan's
    pieces: a reused slot's state AND its tail start from zeros.
    ``paged_kernels``: the flash prefill and BOTH paged calls (the
    decode rows' and the pack's history tiles) interpreted, at ONE kv
    head."""
    reference = reference
    forward_ids = jax.random.randint(jax.random.key(1), (2, 45), 1, 128)
    tol = token_tol = 1e-3
    controls = [{"reset_every": 8}, {"drop_tail_every": 8},
                {"no_inner_norms": True}, {"no_skip": True},
                {"operands": jnp.float8_e4m3fn},
                {"state_dtype": jnp.float8_e4m3fn}]
    control_ids = jax.random.randint(jax.random.key(1), (45,), 1, 128)
    control_from, control_moves = 16, 0.3
    chunks = (10, 7, 8)
    requests = (55, 1, 128, ((0, 23, 3), (1, 14, 4), (0, 17, 3)))
    lanes = [dict(), dict(attn_kernel="paged", prefill_attn="flash_pallas")]
    counters = staticmethod(_ssm_counters)
    refused = REFUSED
    page_movers = tuple(served.PAGE_MOVERS)
    refuses_the_dense_cache = True
    new_modules = ("hetu_tpu.models.jamba", "hetu_tpu.ops.selective_scan",
                   "hetu_tpu.ops.selective_scan_pallas")

    def engine_served(self, eng, model, lanes, counted):
        """The counters count what the kernels walked."""
        assert eng.prefix_cache is None and eng.preempt is False
        assert len(eng.pool.caches) == 4
        assert eng.pool.nbytes() == sum(c.nbytes for c in eng.pool.caches)
        live, computed, advanced, stepped = counted
        layers = model.blocks.layers_of["mamba"]
        assert 0 < live <= computed and live % layers == 0
        # five requests x five decode rows each (the first token is the
        # prefill's), every one a live slot of the three
        assert advanced == 5 * 5 * layers
        assert stepped % (3 * layers) == 0 and stepped >= advanced
        assert telemetry.get_registry().gauge("kv_state_bytes").value(
            kind="slot") == model.blocks.cache_bytes(4)["state"]["slot"]

    def test_dense_cache_and_cp_prefill_refuse_by_name(self, tiny):
        """... and the mixer's own refusals, and the configuration's."""
        from hetu_tpu.models.jamba import JambaConfig
        super().test_dense_cache_and_cp_prefill_refuse_by_name(tiny)
        _, model, params = tiny
        mixer = model.blocks._runs[0].block.attn
        with pytest.raises(SlotStateNotSupported, match="return_kv"):
            mixer(jax.tree.map(lambda x: x[0],
                               params["blocks"]["runs"]["0"]["attn"]),
                  jnp.zeros((1, 4, 64)), return_kv=True)
        with pytest.raises(SlotStateNotSupported, match="verify lane"):
            mixer(jax.tree.map(lambda x: x[0],
                               params["blocks"]["runs"]["0"]["attn"]),
                  jnp.zeros((2, 3, 64)), positions=jnp.zeros((2, 3)),
                  kv_cache=((None, None), 0), slot_mask=jnp.ones(2, bool))
        with pytest.raises(NotImplementedError, match="num_experts=16"):
            JambaConfig(num_experts=16)
        with pytest.raises(ValueError, match="at least one attention"):
            JambaConfig(num_hidden_layers=6)


def test_caches_are_an_arena_of_two_leaves_beside_two_slot_leaves(tiny):
    config, model, _ = tiny
    assert model.cfg.mixer_types == ("mamba",) * 7 + ("attention",) \
        + ("mamba",) * 6
    assert model.blocks.run_kinds == ["mamba", "attention", "mamba"]
    assert model.blocks.layers_of == {"mamba": 13, "attention": 1}
    k, v, state, tail = generation.init_paged_caches(
        model, 9, 4, jnp.bfloat16, slots=3)
    assert k.shape == v.shape == (1, 9, 4, 16) and k.dtype == jnp.bfloat16
    assert state.shape == (13, 3, 4, 1, 128) and state.dtype == jnp.float32
    assert tail.shape == (13, 3, 4, 128) and tail.dtype == jnp.float32
    got = model.blocks.cache_bytes(2)
    assert got["state"] == {"slot": 13 * 4 * 128 * (4 + 4)}
    assert sum(got["row"].values()) >= 2 * 16 * 2
    assert model.blocks.paged and model.blocks.slot_state
    # the attention speaks for the arena: one kv head
    assert model.blocks.block.attn.num_kv_heads == 1
    assert "lm_head" not in model.init(jax.random.key(0))


def test_published_widths_and_what_a_slot_holds():
    with open(os.path.join(ROOT, "benchmark/configs/jamba2-3b.json")) as f:
        c = json.load(f)
    model = load_arch(c["arch"]).build(c)
    assert model.cfg.mixer_types.count("attention") == 2
    assert [i for i, k in enumerate(model.cfg.mixer_types)
            if k == "attention"] == [7, 21]
    assert [r.num_layers for r in model.blocks._runs] == [7, 1, 13, 1, 6]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    assert n == c["sizes"]["parameters"] == 3029337472
    s = c["serve"]
    k, v, state, tail = jax.eval_shape(
        lambda: model.blocks.init_paged_caches(
            s["kv_blocks"], s["block_size"], jnp.bfloat16, s["slots"]))
    assert k.shape == v.shape == (2, 9352, 64, 128)
    assert state.shape == (26, 18, 16, 40, 128)
    assert tail.shape == (26, 18, 4, 5120)
    got = model.blocks.cache_bytes(2)
    # (the model's own 10,117,120 with a tail of three rows: the
    # program keeps four, MambaMixer's note)
    assert got["state"]["slot"] == c["sizes"]["slot_bytes"] == 10649600
    assert c["sizes"]["model_slot_bytes"] == 10117120
    assert sum(got["row"].values()) >= c["sizes"]["cache_bytes_a_token"]
    assert c["sizes"]["cache_bytes_a_token"] == 1024
