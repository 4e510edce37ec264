"""The delta-rule scan kernel (``ops/kda_pallas.py``, ISSUE 46),
interpreted on the CPU: against the token recurrence AND against the
``jax.numpy`` chunk form at the tolerances that form met, over the four
packs of ``test_kda_scan_equals_the_recurrence`` and the edges of the
piece list — two runs in one chunk beside a run that restarts at
position 0 in a slot whose state is not zero, a whole chunk at the decay
bound, a pack with no valid row, a FULL piece list (the spare entry);
dead slots' states and the other layers of the stacked leaf to the bit;
the head-block rule (the counters on a served request are counted in
``test_kda_mla_moe.py``, beside the engine it already builds). And the
decode rows' update kernel (ISSUE 54) against ``kda.kda_update`` AND one
step of the recurrence: dead slots' states to the bit and their rows of
``o`` zeros, fresh slots over a state that is not zero, no live slot at
all, a full list, ``layer=`` on a stacked leaf, head blocks, the decay
bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from served import KDA_D as D, KDA_H as H
from served import kda_draw as _draw, kda_pack as _pack
from hetu_tpu.ops import kda
from hetu_tpu.ops import kda_pallas
from hetu_tpu.ops.kda_pallas import hetu_kda_scan, hetu_kda_update

SLOTS = 4
THREE = [(2, 0, 70), (0, 37, 100), (1, 0, 5)]
#: runs ``(slot, first position, tokens)``, pack rows, layer of a
#: stacked leaf (``None``: one layer's leaf), pad rows in FRONT
PACKS = {
    "one-run": ([(0, 0, 50)], 50, None, 0),
    "continuing": ([(1, 64, 130)], 130, None, 0),
    "three-slots": (THREE, 200, None, 0),
    "three-slots-stacked": (THREE, 200, 1, 0),
    # two runs share the first chunk; slot 3's state is NOT zero and its
    # run starts again at position 0
    "shared-chunk-restart": ([(1, 9, 20), (3, 0, 30), (0, 5, 90)], 192, 2,
                             0),
    # packs shorter than a chunk are ONE chunk of 16, 32 or 48 rows
    "short-16": ([(0, 0, 6), (2, 5, 4)], 10, None, 0),
    "short-32": ([(3, 7, 20)], 22, None, 2),
    "short-48": ([(1, 3, 25), (0, 0, 12)], 40, 1, 0),
    # a FULL piece list: every slot's run opens inside a chunk (rows 8,
    # 40, 100, 150 of three chunks) — chunks + slots pieces
    "full-list": ([(0, 3, 32), (1, 0, 60), (2, 7, 50), (3, 1, 42)], 192, 0,
                  8),
}


def _shifted_pack(name, key):
    """``_pack`` with the pack's last ``shift`` pad rows moved in front
    -> ``(ops, state0, where, want_o, want_s, first row, rows used)``."""
    runs, C, _, shift = PACKS[name]
    ops, state0, where, want_o, want_s, used = _pack(runs, C, SLOTS, key)
    assert shift <= C - used
    ops, where = ([jnp.roll(a, shift, axis=0) for a in x]
                  for x in (ops, where))
    return ops, state0, where, want_o, want_s, shift, used


def _run(ops, state0, where, layer, **kw):
    """The kernel on a pack -> ``(o, the layer's states, steps)``; the
    other layers of a stacked leaf are held to the bit here."""
    def scan(*a):
        return hetu_kda_scan(*a, return_steps=True, **kw)
    if layer is None:
        return jax.jit(scan)(*ops, state0, *where)
    buf0 = jnp.stack([state0 + float(i + 1) for i in range(3)]) \
        .at[layer].set(state0)
    kw["layer"] = jnp.int32(layer)
    o, buf, steps = jax.jit(scan)(*ops, buf0, *where)
    others = [i for i in range(3) if i != layer]
    assert (np.asarray(buf)[others] == np.asarray(buf0)[others]).all()
    return o, buf[layer], steps


@pytest.mark.parametrize("name", list(PACKS))
def test_kernel_equals_the_recurrence_and_the_chunk_form(name):
    runs, C, layer, _ = PACKS[name]
    ops, state0, where, want_o, want_s, at, used = _shifted_pack(
        name, jax.random.key(3))
    o, st, steps = _run(ops, state0, where, layer)
    np.testing.assert_allclose(o[at:at + used], want_o, atol=5e-6)
    np.testing.assert_allclose(st, want_s, atol=1e-5)
    # rows that are not valid come back as zeros, not as the pad's 7s
    assert (np.asarray(o)[:at] == 0).all() \
        and (np.asarray(o)[at + used:] == 0).all()
    # a slot without a run keeps its state to the bit
    idle = sorted(set(range(SLOTS)) - {s for s, _, _ in runs})
    assert (np.asarray(st)[idle] == np.asarray(state0)[idle]).all()
    with jax.default_matmul_precision("highest"):
        o2, st2 = jax.jit(kda.kda_scan)(*ops, state0, *where)
    np.testing.assert_allclose(o, o2, atol=5e-6)
    np.testing.assert_allclose(st, st2, atol=1e-5)
    # the steps: every chunk opens a piece, every run inside a chunk one
    # more; the live ones hold a valid row
    chunk = kda_pallas.scan_chunk(C)
    assert chunk == {10: 16, 22: 32, 40: 48}.get(C, kda.CHUNK)
    edges = {at}
    for _, _, n in runs:
        edges.add(max(edges) + n)
    pieces = set(range(0, -(-C // chunk) * chunk, chunk)) | {
        e for e in edges if e < at + used}
    live = sum(1 for p in pieces if at <= p < at + used)
    assert steps.tolist() == [live, len(pieces)]


def test_a_full_piece_list_uses_the_spare_entry():
    C = PACKS["full-list"][1]
    where = _shifted_pack("full-list", jax.random.key(3))[2]
    work = kda_pallas.scan_work_list(*where, slots=SLOTS)
    n_max = kda_pallas.scan_pieces_max(C, SLOTS)
    # chunks + slots + the spare one: the list is full, the spare is dead
    assert n_max == C // kda.CHUNK + SLOTS + 1
    assert int(work.n) == n_max - 1 and work.chunk.shape == (n_max,)
    assert int(work.hi[-1]) == int(work.lo[-1]) == int(work.flags[-1]) == 0
    assert int(work.chunk[-1]) == C // kda.CHUNK - 1
    # rows 0 (dead: the pad in front), 8, 40 | 64, 100 | 128, 150
    assert work.chunk[:7].tolist() == [0, 0, 0, 1, 1, 2, 2]
    assert work.lo[:7].tolist() == [0, 8, 40, 0, 36, 0, 22]
    assert work.hi[:7].tolist() == [0, 40, 64, 36, 64, 22, 64]
    assert work.slot[:7].tolist() == [0, 0, 1, 1, 2, 2, 3]
    flags = np.asarray(work.flags)[:7]
    assert (flags & 1).astype(bool).tolist() == [
        False, True, True, False, True, False, True]
    assert (flags & 4).astype(bool).tolist() == [
        False, True, False, True, False, True, True]
    # slot 1's run starts at position 0: from zeros
    assert (flags & 2).astype(bool).tolist() == [
        False, False, True, False, False, False, False]


def test_kernel_at_the_decay_bound_for_a_whole_chunk():
    """``g = -5`` for 64 tokens: ``e^{-G}`` alone would be ``e^{320}``."""
    x = _draw(jax.random.key(5), kda.CHUNK, at_bound=True)
    o, st = hetu_kda_scan(
        *x, jnp.zeros((1, H, D, D)), jnp.zeros(kda.CHUNK, jnp.int32),
        jnp.arange(kda.CHUNK, dtype=jnp.int32),
        jnp.ones(kda.CHUNK, bool))
    want_o, want_s = kda.kda_recurrence(*x)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, atol=1e-6)
    np.testing.assert_allclose(st[0], want_s, atol=1e-6)


def test_a_pack_with_no_valid_row_writes_zeros_and_no_state():
    C = 2 * kda.CHUNK
    ops = [jnp.full((C, H, D), 7.0)] * 4 + [jnp.full((C, H), 7.0)]
    state0 = jax.random.normal(jax.random.key(8), (3, SLOTS, H, D, D))
    o, buf, steps = jax.jit(lambda *a: hetu_kda_scan(
        *a, layer=jnp.int32(1), return_steps=True))(
            *ops, state0, jnp.zeros(C, jnp.int32),
            jnp.zeros(C, jnp.int32), jnp.zeros(C, bool))
    assert (np.asarray(o) == 0).all()
    assert (np.asarray(buf) == np.asarray(state0)).all()
    assert steps.tolist() == [0, 2]


@pytest.mark.parametrize("head_block", [1, 2])
def test_head_blocks_are_the_same_scan(head_block):
    layer = PACKS["shared-chunk-restart"][2]
    ops, state0, where, want_o, want_s, _, used = _shifted_pack(
        "shared-chunk-restart", jax.random.key(4))
    o, st, steps = _run(ops, state0, where, layer, head_block=head_block)
    np.testing.assert_allclose(o[:used], want_o, atol=5e-6)
    np.testing.assert_allclose(st, want_s, atol=1e-5)
    assert steps[1] == 5 * (H // head_block)


def test_head_block_rule_and_the_refusal_by_name():
    # Ling: 32 heads of 128 x 128 — 8 heads a step (0.5 MB of state)
    assert kda_pallas.kda_head_block(32, 128, 128) == 8
    assert kda_pallas.kda_head_block(H, D, D) == H
    assert kda_pallas.kda_head_block(12, 128, 128) == 6
    assert kda_pallas.kda_head_block(8, 256, 256) == 4
    x = _draw(jax.random.key(5), kda.CHUNK)
    where = (jnp.zeros(kda.CHUNK, jnp.int32),
             jnp.arange(kda.CHUNK, dtype=jnp.int32),
             jnp.ones(kda.CHUNK, bool))
    with pytest.raises(ValueError, match="whole lane tiles.*dk=16"):
        hetu_kda_scan(*x, jnp.zeros((1, H, D, D)), *where,
                      interpret=False)
    with pytest.raises(ValueError, match="does not divide"):
        hetu_kda_scan(*x, jnp.zeros((1, H, D, D)), *where, head_block=3)


#: the decode rows: ``(live, fresh, layer of a stacked leaf or None,
#: head block or None — the rule's —, g at the decay bound)`` over
#: ``ROWS`` slots
ROWS = 6
_T, _F = True, False
UPDATES = {
    "all-live": ([_T] * ROWS, [_F] * ROWS, None, None, False),
    "some-dead": ([_T, _F, _T, _T, _F, _T], [_F] * ROWS, None, None, False),
    # slots 2 and 5 start again from zeros over a state that is not zero;
    # slot 1 is fresh but NOT live: it keeps what it held
    "fresh": ([_T, _F, _T, _F, _T, _T], [_F, _T, _T, _F, _F, _T], None,
              None, False),
    "none-live": ([_F] * ROWS, [_F] * ROWS, 1, None, False),
    "none-live-one-layer": ([_F] * ROWS, [_T] * ROWS, None, None, False),
    # every slot live: the list is FULL, no step names a block again
    "full-list-stacked": ([_T] * ROWS, [_T] + [_F] * (ROWS - 1), 2, None,
                          False),
    "stacked-first-layer": ([_F, _T, _T, _F, _F, _T], [_F] * ROWS, 0, None,
                            False),
    "head-block-1": ([_T, _F, _T, _T, _F, _F], [_F, _F, _T, _F, _F, _F], 1,
                     1, False),
    "head-block-2": ([_F, _F, _T, _T, _T, _F], [_F] * ROWS, None, 2, False),
    "decay-bound": ([_T, _T, _F, _T, _T, _T], [_F] * ROWS, None, None,
                    True),
}


@pytest.mark.parametrize("name", list(UPDATES))
def test_update_kernel_equals_the_update_and_a_step_of_the_recurrence(name):
    live, fresh, layer, head_block, at_bound = UPDATES[name]
    x = _draw(jax.random.key(6), ROWS, at_bound=at_bound)
    state0 = jax.random.normal(jax.random.key(7), (ROWS, H, D, D))
    live, fresh = jnp.asarray(live), jnp.asarray(fresh)
    kw = dict(fresh=fresh, head_block=head_block, return_steps=True)
    if layer is None:
        o, st, steps = jax.jit(lambda *a: hetu_kda_update(*a, **kw))(
            *x, state0, live)
        o2, st2 = jax.jit(kda.kda_update)(*x, state0, live, fresh=fresh)
    else:
        buf0 = jnp.stack([state0 + float(i + 1) for i in range(3)]) \
            .at[layer].set(state0)
        at = jnp.int32(layer)
        o, buf, steps = jax.jit(lambda *a: hetu_kda_update(
            *a, layer=at, **kw))(*x, buf0, live)
        o2, buf2 = jax.jit(lambda *a: kda.kda_update(
            *a, layer=at, fresh=fresh))(*x, buf0, live)
        # the other layers of the leaf to the bit
        others = [i for i in range(3) if i != layer]
        assert (np.asarray(buf)[others] == np.asarray(buf0)[others]).all()
        st, st2 = buf[layer], buf2[layer]
    assert steps.tolist() == [int(live.sum()), ROWS]
    for s in range(ROWS):
        if not live[s]:
            # not live: the state to the bit (fresh or not), zeros of o
            assert (np.asarray(st[s]) == np.asarray(state0[s])).all()
            assert (np.asarray(o[s]) == 0).all()
            continue
        want_o, want_s = kda.kda_recurrence(
            *(a[s:s + 1] for a in x),
            state=None if fresh[s] else state0[s])
        assert np.isfinite(np.asarray(o[s])).all()
        np.testing.assert_allclose(o[s], want_o[0], atol=2e-6)
        np.testing.assert_allclose(st[s], want_s, atol=2e-6)
        np.testing.assert_allclose(o[s], o2[s], atol=2e-6)
    np.testing.assert_allclose(st, st2, atol=2e-6)


def test_update_kernel_refuses_by_name():
    x = _draw(jax.random.key(5), ROWS)
    state, live = jnp.zeros((ROWS, H, D, D)), jnp.ones(ROWS, bool)
    with pytest.raises(ValueError, match="hetu_kda_update.*whole lane "
                       "tiles.*dk=16"):
        hetu_kda_update(*x, state, live, interpret=False)
    with pytest.raises(ValueError, match="does not divide"):
        hetu_kda_update(*x, state, live, head_block=3)


def test_live_list_has_one_definition():
    """Both decode-row kernels walk the same list."""
    from hetu_tpu.ops import retention_pallas
    assert retention_pallas.live_list is kda_pallas.live_list
    ids, n = kda_pallas.live_list(jnp.asarray([_F, _T, _F, _T, _T]))
    assert ids.tolist() == [1, 3, 4, 0, 0] and n.tolist() == [3]


def test_every_dot_of_the_kernel_is_float32_at_the_highest_precision():
    """The configuration's precision, read from the kernels' jaxprs (the
    decode rows' update has no dot at all: it runs on the VPU)."""
    x = _draw(jax.random.key(5), kda.CHUNK)
    update = jax.make_jaxpr(lambda *a: hetu_kda_update(*a))(
        *_draw(jax.random.key(5), ROWS), jnp.zeros((ROWS, H, D, D)),
        jnp.ones(ROWS, bool))
    jaxpr = jax.make_jaxpr(lambda *a: hetu_kda_scan(*a))(
        *x, jnp.zeros((1, H, D, D)), jnp.zeros(kda.CHUNK, jnp.int32),
        jnp.arange(kda.CHUNK, dtype=jnp.int32), jnp.ones(kda.CHUNK, bool))
    dots = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(update.jaxpr)
    assert dots == []
    walk(jaxpr.jaxpr)
    # 4 row blocks, 10 of the solve, M [K e^G], M V, [W; Q e^G] S_0,
    # P U, S_C
    assert len(dots) == 19
    hi = jax.lax.Precision.HIGHEST
    for eqn in dots:
        assert all(v.aval.dtype == jnp.float32 for v in eqn.invars)
        assert eqn.params["precision"] in (hi, (hi, hi)), eqn.params
