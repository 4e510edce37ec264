"""Kernel plane (ISSUE 14): paged-attention decode kernel, flash in the
prefill lanes, W8A8 decode compute.

Acceptance discipline: the kernel plane changes HOW attention reads the
arena, never WHAT it computes — every path is pinned to the XLA-gather
reference (greedy-token identity end to end, fp-noise tolerance at the
op level) across fp32/int8 arenas, speculative verify rows,
preempt/resume churn and the packed flash prefill lane, with the
``record_trace("serving_step")`` 1-compile audit intact throughout.
Quick-tier tests run the Pallas kernels in interpret mode on tiny
shapes (host-cheap — satellite 6); engine-level parity matrices are
slow-tier.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import telemetry
from hetu_tpu.models import GPTConfig, GPTLMHeadModel, generate
from paged_cases import GARBAGE, TILE_FORMS, TQ
from paged_cases import _history_pack, _kernel_eqns, _pages
from hetu_tpu.ops.paged_pallas import (
    NEG_INF, combine_attention_lse, decode_work_list, history_tile_count,
    pack_history_tiles, paged_attention_auto,
    paged_attention_pallas, paged_attention_reference,
    paged_history_attention,
)

MAX_LEN = 32
CHUNK = 8
BLOCK = 8


@pytest.fixture(scope="module")
def gpt():
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, model, params


def _arena(rng, *, S=3, R=1, hq=4, hkv=2, d=16, n_blocks=9, bs=4, W=8,
           dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(S, R, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(n_blocks, bs, hkv * d)), dtype)
    v = jnp.asarray(rng.normal(size=(n_blocks, bs, hkv * d)), dtype)
    tbl = np.zeros((S, W), np.int32)
    for s in range(S):
        tbl[s] = np.concatenate(
            [rng.permutation(np.arange(1, n_blocks))[:W - 1], [0]])
    return q, k, v, jnp.asarray(tbl)


# ---------------------------------------------------------------------------
# quick tier: interpret-mode kernel units (host-cheap)
# ---------------------------------------------------------------------------

def test_paged_kernel_matches_reference_gqa_and_verify_rows():
    """The kernel == the XLA-gather oracle across GQA grouping, verify
    rows (R>1, the spec-decode shape), per-slot offsets and
    pages_per_step tilings — including a pages_per_step that does NOT
    divide the table width (the pad-lane path)."""
    rng = np.random.default_rng(0)
    q, k, v, tbl = _arena(rng, R=3)
    off = jnp.asarray([0, 5, 17], jnp.int32)
    ref, lse_r = paged_attention_reference(q, k, v, tbl, off,
                                           return_lse=True)
    for pages in (1, 3, 8):
        out, lse = paged_attention_pallas(q, k, v, tbl, off,
                                          pages_per_step=pages,
                                          return_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   atol=1e-5)


@pytest.mark.parametrize("window", [3, 4, 9, [2, 8, 30], 2 ** 30])
def test_paged_kernel_window_matches_reference(window):
    """A windowed call (row ``i`` of slot ``s`` sees keys ``> offset +
    i - window``; one scalar, or one per slot as the gather lane's
    per-token history read passes it) == the XLA-gather oracle with the same
    window: edges inside a page (3, 9) and on a page boundary (4, pages
    of 4), verify rows, every ``pages_per_step`` tiling — pages wholly
    below the window are skipped and their index maps name the window's
    first page, which must not change the result. ``2 ** 30`` is the
    full-attention layer of a model that mixes both kinds."""
    rng = np.random.default_rng(7)
    q, k, v, tbl = _arena(rng, R=3)
    off = jnp.asarray([0, 5, 17], jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    ref, lse_r = paged_attention_reference(q, k, v, tbl, off,
                                           return_lse=True, window=win)
    full = paged_attention_reference(q, k, v, tbl, off)
    if np.max(window) < 17:
        assert np.abs(np.asarray(ref) - np.asarray(full)).max() > 1e-2
    for pages in (1, 3, 8):
        out, lse = paged_attention_pallas(q, k, v, tbl, off, window=win,
                                          pages_per_step=pages,
                                          return_lse=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   atol=1e-5)


def test_paged_kernel_int8_arena_lane():
    """Int8 arenas stream quantized pages + fp32 scales and dequantize
    per tile — same numbers as gather-then-dequantize."""
    from hetu_tpu.ops.quantization import quantize_int8
    rng = np.random.default_rng(1)
    q, k, v, tbl = _arena(rng, R=2)
    off = jnp.asarray([3, 0, 9], jnp.int32)
    # per-(position, head) scales: quantize the heads view, store merged
    kq, ks = map(_pages, quantize_int8(k.reshape(9, 4, 2, 16), axis=-1))
    vq, vs = map(_pages, quantize_int8(v.reshape(9, 4, 2, 16), axis=-1))
    out = paged_attention_pallas(q, kq, vq, tbl, off,
                                 k_scale=ks, v_scale=vs)
    ref = paged_attention_reference(q, kq, vq, tbl, off,
                                    k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)


def _stacked_arena(rng, layers, *, dtype=jnp.float32, **kw):
    """``_arena`` with ``layers`` DIFFERENT layers stacked: what the
    layer scan carries. int8 → (q, kq, vq, tbl, scales dict)."""
    from hetu_tpu.ops.quantization import quantize_int8
    quant = dtype == jnp.int8
    q, _, _, tbl = _arena(rng, dtype=jnp.float32 if quant else dtype,
                          **kw)
    n_blocks, bs = kw.get("n_blocks", 9), kw.get("bs", 4)
    hkv, d = kw.get("hkv", 2), kw.get("d", 16)
    k, v = (jnp.asarray(rng.normal(size=(layers, n_blocks, bs, hkv, d)),
                        jnp.float32) for _ in range(2))
    if not quant:
        return (q, k.reshape(k.shape[:3] + (-1,)).astype(dtype),
                v.reshape(v.shape[:3] + (-1,)).astype(dtype), tbl, {})
    merge = lambda x: x.reshape(x.shape[:3] + (-1,))   # noqa: E731
    (kq, ks), (vq, vs) = (map(merge, quantize_int8(x, axis=-1))
                          for x in (k, v))
    return q, kq, vq, tbl, dict(k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("rows", [1, 3], ids=["decode", "verify3"])
@pytest.mark.parametrize("arena", ["bf16", "int8", "int8_layer_scales"])
def test_paged_kernel_layer_indexed_in_a_scan(arena, rows):
    """The stacked 4-D arena read at a TRACED layer inside a layer scan
    (how ``StackedBlocks.decode`` carries it) == the gather oracle on
    ``leaf[layer]``, layer by layer, on layers that differ — outputs
    and LSE. ``int8_layer_scales``: the scales as ONE layer's 3-D
    leaves beside the stacked int8 pages, the fused step's form."""
    layers = 3
    rng = np.random.default_rng(11)
    dtype = jnp.bfloat16 if arena == "bf16" else jnp.int8
    q, k, v, tbl, scales = _stacked_arena(rng, layers, dtype=dtype,
                                          R=rows)
    off = jnp.asarray([0, 5, 17], jnp.int32)

    def one(_, layer):
        sc = {n: jax.lax.dynamic_index_in_dim(x, layer, 0, False)
              for n, x in scales.items()} \
            if arena == "int8_layer_scales" else scales
        return None, paged_attention_pallas(q, k, v, tbl, off,
                                            layer=layer, return_lse=True,
                                            **sc)

    _, (outs, lses) = jax.jit(lambda: jax.lax.scan(
        one, None, jnp.arange(layers, dtype=jnp.int32)))()
    tol = 2e-2 if arena == "bf16" else 1e-5
    for l in range(layers):
        ref, lse_r = paged_attention_reference(
            q, k[l], v[l], tbl, off, return_lse=True,
            **{n: x[l] for n, x in scales.items()})
        np.testing.assert_allclose(
            np.asarray(outs[l], np.float32), np.asarray(ref, np.float32),
            atol=tol)
        np.testing.assert_allclose(np.asarray(lses[l]),
                                   np.asarray(lse_r), atol=tol)
    # the layers really differ: layer 0's answer is not layer 1's
    assert not np.allclose(np.asarray(outs[0], np.float32),
                           np.asarray(outs[1], np.float32), atol=tol)


def test_paged_kernel_one_layer_arena_is_the_stacked_call():
    """The 3-D arena without ``layer`` returns what it returned: the
    same numbers, bit for bit, as that layer of a stack read at
    ``layer`` — and the two forms do not mix."""
    rng = np.random.default_rng(12)
    q, k, v, tbl, _ = _stacked_arena(rng, 2, R=2)
    off = jnp.asarray([3, 0, 9], jnp.int32)
    for l in range(2):
        flat, lse_f = paged_attention_pallas(q, k[l], v[l], tbl, off,
                                             return_lse=True)
        stacked, lse_s = paged_attention_pallas(q, k, v, tbl, off,
                                                layer=l, return_lse=True)
        assert np.array_equal(np.asarray(flat), np.asarray(stacked))
        assert np.array_equal(np.asarray(lse_f), np.asarray(lse_s))
        ref = paged_attention_reference(q, k[l], v[l], tbl, off)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(ref),
                                   atol=1e-5)
    with pytest.raises(ValueError, match="layer="):
        paged_attention_pallas(q, k, v, tbl, off)          # no layer
    with pytest.raises(ValueError, match="layer="):
        paged_attention_pallas(q, k[0], v[0], tbl, off, layer=0)


def test_paged_attention_auto_stacked_arena_under_tp_mesh(gpt):
    """Under a tp=2 plan the wrapper shard_maps the kernel over the
    head axis with the STACKED arena's specs (leading layers dim, the
    layer replicated): each shard reads its head slice at (layer,
    page), and the result is the oracle's on ``leaf[layer]``."""
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan
    from hetu_tpu.parallel.strategy import Strategy
    _, model, _ = gpt
    plan = make_plan(model, optim.adamw(1e-3), Strategy(tp=2))
    rng = np.random.default_rng(13)
    q, k, v, tbl, _ = _stacked_arena(rng, 2, R=2)
    off = jnp.asarray([3, 0, 9], jnp.int32)

    @jax.jit
    def f(q, k, v, layer):
        return paged_attention_auto(q, k, v, tbl, off, layer=layer,
                                    return_lse=True)

    with plan.act:
        out, lse = f(q, k, v, jnp.asarray(1, jnp.int32))
        assert "shard_map" in str(jax.make_jaxpr(f)(
            q, k, v, jnp.asarray(1, jnp.int32)))
    ref, lse_r = paged_attention_reference(q, k[1], v[1], tbl, off,
                                           return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               atol=1e-5)
    # the prefill lane's history read under the same plan: the tile
    # map rides the shard_map replicated, not dropped
    qh, kh, vh, tblh, _, _, hist, runs, _ = _history_pack(
        rng, [(6, 9), (5, 0)])
    tiles, _ = pack_history_tiles(runs, tile_rows=TQ, n_tiles=5)
    tj = jnp.asarray(tiles)
    tables = jnp.take(tblh, tiles[0], axis=0)

    def h(q, k, v):
        return paged_history_attention(q, k, v, tables, hist, tj,
                                       tile_rows=TQ)

    want = h(qh, kh, vh)
    with plan.act:
        got = jax.jit(h)(qh, kh, vh)
        assert "shard_map" in str(jax.make_jaxpr(h)(qh, kh, vh))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


#: sha256(str(jaxpr))[:16] of the DECODE rows' call (``tiles=None``, two
#: pages a grid step; plain | under a window), at the decode (1 row a
#: slot) and verify (4) shapes, traced under this suite's configuration
#: (conftest sets the default matmul precision to highest, which the
#: jaxpr prints). Re-pinned ONCE by PR 60 from the values of commit
#: 9503357 (PR 41: da733211951e86ba, abf2819644e1ff84, ...), which PRs
#: 42-59 left: the one thing that differs is the work list's LENGTH —
#: ``decode_work_list``'s two arrays are 17 entries for 16 (``slots x
#: chunks`` + the spare entry), and with them the kernel's ``min(i + 1,
#: 16)`` for ``min(i + 1, 15)`` where it looks at the pair above; with
#: 16 and 17 written alike the two texts are equal line for line. A PR
#: that re-tiles the history read or adds a mask must leave the decode,
#: verify and sparse-read program as it is, operand for operand.
PARENT_DECODE_JAXPR = {
    (1, "bf16"): ("cb6eb5807f868b1f", "544c123b23c20030"),
    (1, "int8"): ("3d33296f8563e484", "62ea2080473d27aa"),
    (4, "bf16"): ("f7d66b97b785c48d", "5e7b58251690c2c3"),
    (4, "int8"): ("26f1f716ab28c14d", "a768f66a24b6e091")}

#: the same of the TILED call (``tiles=`` of zeros, ``return_lse``, two
#: pages a grid step; plain | under a window) at commit 428bd76 (PR 51),
#: the parent of the PR that gave the tiled call its banded causal mask
#: (``band=``, which only the block-sparse attention passes): without
#: the band the history read of every other model is the parent's
#: kernel — same grid, same scalar operands, same mask.
PARENT_TILED_JAXPR = {
    (1, "bf16"): ("d2df8ad7de5e205d", "5e6086b00a22c733"),
    (1, "int8"): ("534094dda382077e", "2f88d5b5fc6b2023"),
    (4, "bf16"): ("3681360f6791e2e0", "94c4f28c20824d70"),
    (4, "int8"): ("55c26ec2843d4090", "bdd0b78db86caf63")}


@pytest.mark.parametrize("rows,arena", list(PARENT_DECODE_JAXPR))
def test_decode_call_walks_a_work_list_and_tiled_call_is_the_parents(
        rows, arena):
    """The decode-lane and verify-lane calls walk the list of live
    (slot, chunk) pairs: ONE grid dimension whose bound is data, five
    scalar-prefetch operands (tables, offsets, layer, the pairs' slots
    and chunks; six under a window) — no static ``(S, n_steps)`` grid
    is left — and the program is the parent's to the character
    (``PARENT_DECODE_JAXPR``). The history read's tiled call keeps
    seven operands and two traced bounds."""
    import hashlib
    S, hq, hkv, d, L, nb, bs, W = 4, 4, 2, 16, 3, 9, 4, 8
    quant = arena == "int8"
    sds = jax.ShapeDtypeStruct
    page = sds((L, nb, bs, hkv * d), jnp.int8 if quant else jnp.bfloat16)
    args = (sds((S, rows, hq, d), jnp.bfloat16), page, page,
            sds((S, W), jnp.int32), sds((S,), jnp.int32),
            sds((), jnp.int32)) \
        + ((sds((L, nb, bs, hkv), jnp.float32),) * 2 if quant else ())

    def f(q, k, v, tbl, off, lyr, *scales, **kw):
        ks, vs = scales if scales else (None, None)
        return paged_attention_pallas(q, k, v, tbl, off, layer=lyr,
                                      k_scale=ks, v_scale=vs,
                                      interpret=True, **kw)

    def grid_mapping(jaxpr):
        eqn, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        return eqn.params["grid_mapping"]

    live = jnp.asarray([True, False, True, True])
    plain, windowed = PARENT_DECODE_JAXPR[rows, arena]
    for kw, operands, parents in (
            ({}, 5, plain), ({"live": live}, 5, None),
            ({"window": jnp.asarray(6, jnp.int32)}, 6, windowed)):
        jaxpr = jax.make_jaxpr(
            lambda *a: f(*a, pages_per_step=2, **kw))(*args)
        gm = grid_mapping(jaxpr.jaxpr)
        assert gm.num_index_operands == operands
        assert gm.num_dynamic_grid_bounds == 1 and len(gm.grid) == 1
        assert not any(isinstance(b, int) for b in gm.grid)
        assert parents in (None, hashlib.sha256(
            str(jaxpr).encode()).hexdigest()[:16])
    tiles = {n: jnp.zeros((S,), jnp.int32)
             for n in ("cap", "cell", "lo", "hi")}
    for pages in (1, 2):
        gm = grid_mapping(jax.make_jaxpr(
            lambda *a: f(*a, pages_per_step=pages, tiles=tiles))(
                *args).jaxpr)
        assert gm.num_index_operands == 7
        assert gm.num_dynamic_grid_bounds == 2
    # ... and is the parent's to the character where no band is asked
    # for; the band is the same grid and operands under another mask
    for kw, parents in zip(({}, {"window": jnp.asarray(6, jnp.int32)}),
                           PARENT_TILED_JAXPR[rows, arena]):
        jaxpr = jax.make_jaxpr(lambda *a: f(
            *a, pages_per_step=2, tiles=tiles, return_lse=True,
            **kw))(*args)
        assert parents == hashlib.sha256(
            str(jaxpr).encode()).hexdigest()[:16]
    if not quant:
        gm = grid_mapping(jax.make_jaxpr(lambda *a: f(
            *a, pages_per_step=2, tiles=tiles, band=(2, 1)))(*args).jaxpr)
        assert gm.num_index_operands == 7
        assert gm.num_dynamic_grid_bounds == 2
    with pytest.raises(ValueError, match="live="):
        f(*(jnp.zeros(a.shape, a.dtype) for a in args), tiles=tiles,
          live=live)
    for kw in ({}, {"tiles": tiles, "window": jnp.asarray(6, jnp.int32)}):
        with pytest.raises(ValueError, match="band="):
            f(*(jnp.zeros(a.shape, a.dtype) for a in args), band=(2, 1),
              **kw)


# -- the decode rows' work list: live (slot, chunk) pairs -------------------

def _pairs(off, live, *, rows, span, n_steps, window=None):
    """The live pairs in plain Python, from the definition."""
    want = []
    for s, o in enumerate(off):
        if live is not None and not live[s]:
            continue
        w = window if window is None or np.ndim(window) == 0 \
            else window[s]
        lo = 0 if w is None else max(o - int(w) + 1, 0) // span
        hi = min((o + rows - 1) // span, n_steps - 1)
        want += [(s, c) for c in range(min(lo, hi), hi + 1)]
    return want


WORK_LISTS = {
    "all_live": dict(off=[0, 5, 17, 31], live=None, rows=1),
    "live_none_is_all_true": dict(off=[0, 5, 17, 31],
                                  live=[True] * 4, rows=1),
    "dead_slots_at_stale_offsets": dict(
        off=[30, 5, 17, 31], live=[False, True, False, True], rows=1),
    "first_and_last_dead": dict(
        off=[9, 0, 12, 3], live=[False, True, True, False], rows=1),
    "verify_rows_cross_a_chunk": dict(
        off=[7, 6, 15, 28], live=[True, True, False, True], rows=4),
    "window": dict(off=[0, 9, 20, 31], live=[True, True, True, False],
                   rows=1, window=6),
    "window_per_slot": dict(off=[3, 9, 20, 31], live=None, rows=2,
                            window=[2, 30, 5, 9]),
    "full_layer_of_a_windowed_model": dict(
        off=[3, 9, 20, 31], live=[True, False, True, True], rows=1,
        window=2 ** 30),
    "all_dead": dict(off=[9, 0, 12, 3], live=[False] * 4, rows=1),
    "one_live": dict(off=[9, 0, 12, 3],
                     live=[False, False, True, False], rows=4),
}


@pytest.mark.parametrize("case", list(WORK_LISTS))
@pytest.mark.parametrize("span", [4, 8, 12])
def test_decode_work_list_is_the_live_pairs_in_slot_order(case, span):
    """``decode_work_list`` as a pure function: for given offsets /
    ``live`` / window / rows it lists exactly the live (slot, chunk)
    pairs — slot order, chunks ascending, from the chunk of the first
    key a slot's first row sees to the chunk of its last row, none for
    a dead slot whatever its offset says — and zeros after them."""
    kw = dict(WORK_LISTS[case])
    off, live = kw.pop("off"), kw.pop("live")
    n_steps = -(-32 // span)
    want = _pairs(off, live, span=span, n_steps=n_steps, **kw)
    if "window" in kw:
        kw["window"] = jnp.asarray(kw["window"], jnp.int32)
    slot, chunk, n = jax.jit(
        lambda o, lv: decode_work_list(o, lv, span=span, n_steps=n_steps,
                                       **kw))(
        jnp.asarray(off, jnp.int32),
        None if live is None else jnp.asarray(live))
    assert slot.shape == chunk.shape == (len(off) * n_steps + 1,)
    assert int(n) == len(want)
    got = list(zip(np.asarray(slot).tolist(), np.asarray(chunk).tolist()))
    assert got[:len(want)] == want
    assert got[len(want):] == [(0, 0)] * (len(got) - len(want))
    if case == "all_dead":
        assert not want
    if case == "live_none_is_all_true":
        assert want == _pairs(off, None, rows=1, span=span,
                              n_steps=n_steps)


def _poisoned_dead_slots(rng, live, *, R=1, dtype=jnp.float32, **kw):
    """An arena whose DEAD slots stand at stale offsets over garbage
    table rows (pages full of NaN): what a freed or prefilling slot
    leaves behind."""
    q, k, v, tbl = _arena(rng, S=len(live), R=R, dtype=dtype, **kw)
    k, v = (jnp.concatenate([x, jnp.full_like(x[:3], jnp.nan)])
            for x in (k, v))            # pages 9..11: nobody's
    tbl = np.asarray(tbl).copy()
    off = rng.integers(0, 28 - R, size=len(live)).astype(np.int32)
    for s, alive in enumerate(live):
        if not alive:
            tbl[s] = rng.integers(9, 12, size=tbl.shape[1])
            off[s] = 27 - R
    return q, k, v, jnp.asarray(tbl), jnp.asarray(off)


@pytest.mark.parametrize("live", [
    [True, False, True, False, False, True],
    [False, False, True, True, True, False],
    [False, True, False, False, False, False],
], ids=["interleaved", "dead_at_both_ends", "one_live"])
@pytest.mark.parametrize("rows,pages", [(1, 1), (1, 3), (4, 2), (4, 8)])
def test_paged_kernel_dead_slots_give_zero_rows(live, rows, pages):
    """A dead slot costs no grid step: its stale offset and garbage
    table (pages of NaN) are never read, its rows are zeros and its LSE
    ``NEG_INF`` — while every live slot equals the gather oracle,
    decode and verify rows, every tiling."""
    rng = np.random.default_rng(31)
    q, k, v, tbl, off = _poisoned_dead_slots(rng, live, R=rows)
    lv = np.asarray(live)
    out, lse = paged_attention_pallas(q, k, v, tbl, off, live=jnp.asarray(lv),
                                      pages_per_step=pages,
                                      return_lse=True)
    ref, lse_r = paged_attention_reference(q[lv], k, v, tbl[lv], off[lv],
                                           return_lse=True)
    np.testing.assert_allclose(np.asarray(out)[lv], np.asarray(ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse)[lv], np.asarray(lse_r),
                               atol=1e-5)
    assert not np.asarray(out)[~lv].any()
    assert (np.asarray(lse)[~lv] == NEG_INF).all()


@pytest.mark.parametrize("window", [3, 9, [2, 8, 30, 5, 5, 12], 2 ** 30])
def test_paged_kernel_window_layers_start_above_chunk_zero(window):
    """Under a window a slot's list starts at the chunk of the first
    key it sees (chunks below are no steps at all, not skipped ones):
    equal to the windowed oracle with dead slots beside, verify rows,
    and ``2 ** 30`` (the full layer of a mixed model) cuts nothing."""
    rng = np.random.default_rng(32)
    live = [True, True, False, True, False, True]
    q, k, v, tbl, off = _poisoned_dead_slots(rng, live, R=4)
    off = off.at[0].set(23)             # far above a window of 3
    lv = np.asarray(live)
    win = jnp.asarray(window, jnp.int32)
    for pages in (1, 2, 8):
        _, chunk, n = decode_work_list(off, jnp.asarray(lv), rows=4,
                                       span=pages * 4, n_steps=-(-8 // pages),
                                       window=win)
        if pages == 1 and np.max(window) < 10:
            assert int(chunk[0]) > 0
        out, lse = paged_attention_pallas(
            q, k, v, tbl, off, live=jnp.asarray(lv), window=win,
            pages_per_step=pages, return_lse=True)
        ref, lse_r = paged_attention_reference(
            q[lv], k, v, tbl[lv], off[lv], return_lse=True,
            window=win if win.ndim == 0 else win[lv])
        np.testing.assert_allclose(np.asarray(out)[lv], np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse)[lv], np.asarray(lse_r),
                                   atol=1e-5)
        assert not np.asarray(out)[~lv].any()


@pytest.mark.parametrize("rows", [1, 4], ids=["decode", "verify4"])
@pytest.mark.parametrize("arena", ["bf16", "int8"])
def test_paged_kernel_work_list_in_a_layer_scan(arena, rows):
    """The list inside a layer scan, as the fused step makes it: the
    stacked arena (int8: quantized pages and their scales) at a traced
    layer whose window is traced too, dead slots beside live ones."""
    layers = 3
    rng = np.random.default_rng(33)
    dtype = jnp.bfloat16 if arena == "bf16" else jnp.int8
    q, k, v, tbl, scales = _stacked_arena(rng, layers, dtype=dtype,
                                          R=rows, S=4)
    off = jnp.asarray([0, 27 - rows, 17, 9], jnp.int32)
    live = jnp.asarray([True, False, True, True])
    lv = np.asarray(live)
    windows = jnp.asarray([5, 2 ** 30, 11], jnp.int32)

    def one(_, x):
        layer, win = x
        return None, paged_attention_pallas(
            q, k, v, tbl, off, layer=layer, window=win, live=live,
            pages_per_step=2, return_lse=True, **scales)

    _, (outs, lses) = jax.jit(lambda: jax.lax.scan(
        one, None, (jnp.arange(layers, dtype=jnp.int32), windows)))()
    tol = 2e-2 if arena == "bf16" else 1e-5
    for l in range(layers):
        ref, lse_r = paged_attention_reference(
            q[lv], k[l], v[l], tbl[lv], off[lv], return_lse=True,
            window=windows[l], **{n: x[l] for n, x in scales.items()})
        np.testing.assert_allclose(
            np.asarray(outs[l], np.float32)[lv],
            np.asarray(ref, np.float32), atol=tol)
        np.testing.assert_allclose(np.asarray(lses[l])[lv],
                                   np.asarray(lse_r), atol=tol)
        assert not np.asarray(outs[l], np.float32)[~lv].any()
        assert (np.asarray(lses[l])[~lv] == NEG_INF).all()


@pytest.mark.parametrize("rows", [1, 4])
def test_paged_kernel_all_slots_dead_is_one_harmless_step(rows):
    """No live slot: the grid is one step (pair (0, 0), slot 0's stale
    rows) whose result the mask drops — all zeros, all ``NEG_INF``,
    nothing non-finite although every page the tables name is NaN."""
    rng = np.random.default_rng(34)
    q, k, v, tbl, off = _poisoned_dead_slots(rng, [False] * 3, R=rows)
    live = jnp.zeros((3,), bool)
    _, _, n = decode_work_list(off, live, rows=rows, span=8, n_steps=4)
    assert int(n) == 0
    out, lse = paged_attention_pallas(q, k, v, tbl, off, live=live,
                                      return_lse=True)
    assert not np.asarray(out).any()
    assert (np.asarray(lse) == NEG_INF).all()


@pytest.mark.parametrize("arena", ["f32", "int8"])
def test_paged_kernel_live_none_is_all_true(arena):
    """``live=None`` (the tuner, the AOT check, every older caller) is
    every slot live: the same numbers, bit for bit, as ``live`` all
    true."""
    from hetu_tpu.ops.quantization import quantize_int8
    rng = np.random.default_rng(35)
    q, k, v, tbl = _arena(rng, R=2)
    off = jnp.asarray([3, 0, 19], jnp.int32)
    kw = {}
    if arena == "int8":
        k, kw["k_scale"] = map(_pages, quantize_int8(
            k.reshape(9, 4, 2, 16), axis=-1))
        v, kw["v_scale"] = map(_pages, quantize_int8(
            v.reshape(9, 4, 2, 16), axis=-1))
    a, lse_a = paged_attention_pallas(q, k, v, tbl, off, return_lse=True,
                                      **kw)
    b, lse_b = paged_attention_pallas(q, k, v, tbl, off, return_lse=True,
                                      live=jnp.ones((3,), bool), **kw)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(lse_a), np.asarray(lse_b))
    ref = paged_attention_reference(q, k, v, tbl, off, **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=1e-5)


def test_paged_kernel_dead_lanes_inert():
    """Table lanes beyond the live context must not contribute even
    when they point at LIVE blocks full of garbage — the dead-lane
    skip and the position mask both have to hold (a reused block is
    never zeroed, so this is the no-stale-reads guarantee)."""
    rng = np.random.default_rng(2)
    q, k, v, tbl = _arena(rng)
    off = jnp.asarray([1, 2, 3], jnp.int32)
    base = paged_attention_pallas(q, k, v, tbl, off)
    poisoned = jnp.asarray(tbl).at[:, 2:].set(7)   # garbage mappings
    out = paged_attention_pallas(q, k, v, poisoned, off)
    ref = paged_attention_reference(q, k, v, poisoned, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    # positions < block 2 are unchanged by the poisoning at all
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=1e-5)


# -- the decode rows' tile: a chunk's pages joined, one update a head --------

#: last positions of the joined-tile cases' slots (pages of 4, a table
#: of 16 lanes): inside chunk 0, the last key of EVERY page of the upper
#: chunk of 8 pages (both pages of a chunk of 2), and mid-page
TILE_ENDS = [0, 6] + [4 * p + 3 for p in range(8, 16)] + [37, 50]


def _garbage_arena(rng, *, rows, hq, hkv, d, bs=4, W=16, v_width=None,
                   quant=False, ends=TILE_ENDS):
    """Slots whose last rows stand at ``TILE_ENDS`` over an arena that
    is GARBAGE wherever no row may look: every position above a slot's
    context (the rest of its last page, and the pages its table names
    above it) and every page no table names below a context."""
    from hetu_tpu.ops.quantization import quantize_int8
    S = len(ends)
    off = np.maximum(np.asarray(ends) - (rows - 1), 0).astype(np.int32)
    ends = off + rows - 1
    own = ends // bs + 1                 # a slot's pages with live keys
    n_blocks = 4 + int(own.sum())        # pages 0..3: nobody's
    k, v = (GARBAGE * rng.choice([-1.0, 1.0], size=(n_blocks, bs, hkv, d))
            for _ in range(2))
    tbl = rng.integers(0, 4, size=(S, W)).astype(np.int32)
    nxt = 4
    for s in range(S):
        tbl[s, :own[s]] = nxt + np.arange(own[s])
        for x in (k, v):
            live = x[nxt:nxt + own[s]].reshape(-1, hkv, d)
            live[:ends[s] + 1] = rng.normal(size=(ends[s] + 1, hkv, d))
        nxt += own[s]
    q = jnp.asarray(rng.normal(size=(S, rows, hq, d)), jnp.float32)
    kw = {}
    if v_width is not None:
        k, v = _pages(jnp.asarray(k, jnp.float32)), None
        kw = dict(v_width=v_width, scale=0.2)
    elif quant:
        (k, ks), (v, vs) = (map(_pages, quantize_int8(
            jnp.asarray(x, jnp.float32), axis=-1)) for x in (k, v))
        kw = dict(k_scale=ks, v_scale=vs)
    else:
        k, v = (_pages(jnp.asarray(x, jnp.float32)) for x in (k, v))
    return q, k, v, jnp.asarray(tbl), jnp.asarray(off), kw


@pytest.mark.parametrize("form,pages", [
    (f, p) for f in ("g1", "g4", "latent") for p in (1, 2, 8)]
    + [("int8", 8)])
@pytest.mark.parametrize("rows", [1, 4], ids=["decode", "verify4"])
def test_paged_kernel_joined_chunk_tile_matches_reference(form, pages,
                                                          rows):
    """One score tile, one mask and one online-softmax update a (head,
    chunk) == the gather oracle, outputs and LSE, whatever
    ``pages_per_step`` joins: contexts that end at every page of a
    chunk and mid-page (the pages above are MASKED now, not skipped:
    what they and the arena's unreferenced pages hold — large finite
    garbage — must not be seen), alone and under a window whose first
    key lies mid-chunk (13 keys: the pages below it are not fetched,
    their lanes name the window's first page again)."""
    rng = np.random.default_rng(51)
    q, k, v, tbl, off, kw = _garbage_arena(rng, rows=rows,
                                           **TILE_FORMS[form])
    for window in (None, jnp.asarray(13, jnp.int32)):
        ref, lse_r = paged_attention_reference(
            q, k, v, tbl, off, return_lse=True, window=window, **kw)
        out, lse = paged_attention_pallas(
            q, k, v, tbl, off, pages_per_step=pages, return_lse=True,
            window=window, **kw)
        assert np.abs(np.asarray(ref)).max() < 10     # no garbage in it
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   atol=1e-5)


KB = 1024


@pytest.mark.parametrize("block_size,width,lane_bytes,pages,steps", [
    # pages under 64 keys: the benchmark's cap, to the letter (at most
    # 128 keys from at most 3 pages, whatever the page's bytes)
    (16, 64, 0, 3, 22), (16, 64, 64 * KB, 3, 22), (16, 2, 0, 2, 1),
    (32, 64, 0, 3, 22), (8, 64, 0, 3, 22),
    # 64-key pages at the cells' table widths and page bytes (K + V, or
    # one latent page): SDAR 25 lanes, Command A+ 128, Kimi 256,
    # Jamba and Qwen3-Next 516 — 8 pages = 512 keys a step
    (64, 25, 128 * KB, 8, 4), (64, 128, 256 * KB, 8, 16),
    (64, 256, 80 * KB, 8, 32), (64, 516, 32 * KB, 8, 65),
    (64, 516, 128 * KB, 8, 65), (64, 128, 0, 8, 16), (64, 5, 0, 5, 1),
    # a 128-key page: 512 keys are 4 pages
    (128, 8, 0, 4, 2), (128, 64, 512 * KB, 4, 16), (256, 8, 0, 2, 4),
    # pages wide enough that VMEM lowers the count (32 kv heads of 128:
    # 1 MB a lane, double buffered under 8 MiB), down to one
    (64, 128, 512 * KB, 8, 16), (64, 128, 1024 * KB, 4, 32),
    (64, 128, 2048 * KB, 2, 64), (64, 128, 8192 * KB, 1, 128)])
def test_default_tile_is_a_lane_tile_of_keys_from_at_most_three_pages(
        block_size, width, lane_bytes, pages, steps):
    """The rule behind ``pages_per_step=None``, from the page's keys
    and bytes. Under 64 keys a page: at most 128 keys a tile, at most 3
    pages — what the parent returned (the GPT-2 cells' half, which
    waits for a deeper backlog). From 64 keys a page: up to 8 pages and
    512 keys, fewer where the double-buffered pages of a table lane
    would not fit VMEM (never a compile error); a table narrower than
    the rule's pages is one chunk."""
    from hetu_tpu.ops.paged_pallas import table_chunks
    assert table_chunks(width, block_size, None, lane_bytes) \
        == (pages, steps)
    if block_size < 64:     # ... as the parent cut it
        assert pages == min(3, 128 // block_size, width)


def test_the_call_prices_a_lane_from_its_page_operands(monkeypatch):
    """The call hands the rule what ONE table lane brings into VMEM —
    the K and V pages (a latent arena: one page; an int8 arena: its
    scale pages too) — and the engine's counters price the same lane
    from the attention's leaf shapes."""
    import types
    from hetu_tpu.ops import paged_pallas
    from hetu_tpu.serving import ServingEngine
    seen = []

    def rule(block_size, lane_bytes=0):
        seen.append((block_size, lane_bytes))
        return 2

    monkeypatch.setattr(paged_pallas, "default_pages_per_step", rule)
    rng = np.random.default_rng(7)
    for form, want in (("g4", 2 * 4 * 2 * 16 * 4),
                       ("latent", 4 * 32 * 4),
                       ("int8", 2 * 4 * 2 * (16 + 4))):
        q, k, v, tbl, off, kw = _garbage_arena(rng, rows=1,
                                               **TILE_FORMS[form])
        paged_attention_pallas(q, k, v, tbl, off, **kw)
        assert seen.pop() == (4, want)
        # ... and the engine, from its pool's leaf and the attention
        f = TILE_FORMS[form]
        eng = types.SimpleNamespace(
            pool=types.SimpleNamespace(block_size=4, caches=[
                jax.ShapeDtypeStruct((2,) + k.shape, k.dtype)]),
            prefill_chunk=8, _fin_cap=2, attn_kernel="paged")
        attn = types.SimpleNamespace(
            num_heads=f["hq"], num_kv_heads=f["hkv"], head_dim=f["d"],
            latent="v_width" in f, history_tiles=True)
        ServingEngine._size_history_tiles(eng, attn, 16, "flash")
        assert seen.pop() == (4, want)
        assert (eng._chunk_span, eng._chunk_steps) == (8, 8)


#: the cells' forms of the decode rows' call at pages of 64 keys, where
#: the rule joins 8 pages = 512 keys a grid step (a table of 20 lanes is
#: 3 chunks, the last of 4 pages): SDAR's block lane (8 rows a slot
#: under the block bound), Command A+'s window, the latent arena, one
#: kv head (Jamba), heads of 256 (Qwen3-Next), the verify rows, int8
CELL_FORMS = {
    "block4_rows8": dict(rows=8, hq=8, hkv=2, d=16, block=4),
    "window": dict(rows=1, hq=8, hkv=2, d=16, window=300),
    "latent": dict(rows=1, hq=4, hkv=1, d=32, v_width=24),
    "one_kv_head": dict(rows=1, hq=5, hkv=1, d=16),
    "d256": dict(rows=1, hq=4, hkv=2, d=256),
    "verify4": dict(rows=4, hq=4, hkv=2, d=16),
    "int8": dict(rows=2, hq=4, hkv=2, d=16, quant=True)}
#: last positions of their slots: inside chunk 0, at both edges of the
#: chunks of 512 keys, mid-page in each chunk, the table's last key
CELL_ENDS = [40, 511, 512, 700, 1023, 1024, 1100, 1279]


@pytest.mark.parametrize("form", list(CELL_FORMS))
def test_decode_call_at_eight_pages_a_step_matches_reference(form):
    """``pages_per_step=None`` at 64-key pages IS 8 pages a step: the
    call equals the gather oracle, outputs and LSE, in every form a
    serving cell runs it, over an arena that is garbage wherever no row
    may look (the up to 7 masked pages of a slot's last chunk
    included), with a dead slot beside the live ones."""
    from hetu_tpu.ops.paged_pallas import table_chunks
    kw = dict(CELL_FORMS[form])
    window, block = kw.pop("window", None), kw.pop("block", 1)
    rng = np.random.default_rng(60)
    q, k, v, tbl, off, arena = _garbage_arena(
        rng, bs=64, W=20, ends=CELL_ENDS, **kw)
    if block != 1:          # a block lane's rows start at a block
        off = off - off % block
    assert table_chunks(20, 64, None, 64 * k.shape[-1]) == (8, 3)
    live = jnp.asarray([True] * 3 + [False] + [True] * 4)
    lv = np.asarray(live)
    more = {} if window is None else {
        "window": jnp.asarray(window, jnp.int32)}
    if block != 1:
        more["block"] = block
    ref, lse_r = paged_attention_reference(
        q[lv], k, v, tbl[lv], off[lv], return_lse=True, **more, **arena)
    out, lse = paged_attention_pallas(
        q, k, v, tbl, off, live=live, return_lse=True, **more, **arena)
    assert np.abs(np.asarray(ref)).max() < 10         # no garbage in it
    np.testing.assert_allclose(np.asarray(out)[lv], np.asarray(ref),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse)[lv], np.asarray(lse_r),
                               atol=2e-5)
    assert not np.asarray(out)[~lv].any()


@pytest.mark.parametrize("rows,pages", [(1, 1), (1, 8), (4, 2), (8, 8)])
def test_full_work_list_has_a_spare_entry_and_reads_as_the_reference(
        rows, pages):
    """EVERY slot live and EVERY slot in its table's last chunk — what
    a long fixed context is at 8 pages a step for its whole decode: the
    list holds ``slots x chunks`` pairs, as many as there can be, and
    one entry more, (0, 0) — the pipeline's look one pair ahead stays
    inside the list (past it the chip read a page of nowhere and
    halted: PERF.md section 6, PR 39) — and the call equals the
    oracle."""
    rng = np.random.default_rng(61)
    S, W, bs = 3, 16, 4
    q, k, v, tbl, _, kw = _garbage_arena(
        rng, rows=rows, ends=[W * bs - 1, W * bs - 2, W * bs - 1],
        **TILE_FORMS["g4"])
    off = jnp.asarray([W * bs - rows, W * bs - 1 - rows, W * bs - rows],
                      jnp.int32)
    n_steps = -(-W // pages)
    slot, chunk, n = decode_work_list(off, None, rows=rows,
                                      span=pages * bs, n_steps=n_steps)
    assert int(n) == S * n_steps == slot.shape[0] - 1     # FULL
    assert (int(slot[-1]), int(chunk[-1])) == (0, 0)
    assert np.asarray(slot)[:-1].tolist() == sorted(
        list(range(S)) * n_steps)
    ref, lse_r = paged_attention_reference(q, k, v, tbl, off,
                                           return_lse=True, **kw)
    out, lse = paged_attention_pallas(q, k, v, tbl, off,
                                      pages_per_step=pages,
                                      return_lse=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                               atol=1e-5)


@pytest.mark.parametrize("pages", [1, 2, 8])
@pytest.mark.parametrize("form", ["g1", "g4", "latent", "int8"])
def test_decode_call_scores_a_chunk_per_head_in_one_tile(form, pages):
    """The kernel's jaxpr at ``pages_per_step`` pages a grid step holds
    ``hkv`` score products ``(rows, d) x (pages * bs, d)`` and ``hkv``
    value products — not ``pages x hkv`` of a page each — and one exp
    of a score tile a head; at one page there is nothing to join and no
    ``concatenate``."""
    rng = np.random.default_rng(52)
    form = TILE_FORMS[form]
    hkv, d, bs = form["hkv"], form["d"], 4
    q, k, v, tbl, off, kw = _garbage_arena(rng, rows=1, **form)
    eqns = _kernel_eqns(jax.make_jaxpr(
        lambda q, k, v: paged_attention_pallas(
            q, k, v, tbl, off, pages_per_step=pages, interpret=True,
            **kw))(q, k, v).jaxpr)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    g, dv = form["hq"] // hkv, form.get("v_width", d)
    assert sorted((e.invars[1].aval.shape, e.outvars[0].aval.shape)
                  for e in dots) == sorted(
        [((pages * bs, d), (g, pages * bs))] * hkv         # scores
        + [((pages * bs, dv), (g, dv))] * hkv)             # values
    joins = [e for e in eqns if e.primitive.name == "concatenate"]
    per_head = (1 if "v_width" in form else 2)
    assert len(joins) == (0 if pages == 1 else per_head * hkv)
    assert sum(e.primitive.name == "exp"
               and e.outvars[0].aval.shape == (g, pages * bs)
               for e in eqns) == hkv


def test_combine_attention_lse_matches_joint_softmax():
    """Splitting the KV set and LSE-combining the partials must equal
    one joint softmax — including one side being fully masked."""
    from hetu_tpu.ops.attention import attention_reference
    rng = np.random.default_rng(3)
    b, sq, h, d, sk = 2, 3, 4, 16, 10
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)
    joint = attention_reference(q, k, v)
    o1, l1 = attention_reference(q, k[:, :6], v[:, :6], return_lse=True)
    o2, l2 = attention_reference(q, k[:, 6:], v[:, 6:], return_lse=True)
    out = combine_attention_lse(o1, l1, o2, l2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(joint),
                               atol=1e-5)
    # one side empty (all-masked ≈ NEG_INF lse): combine == other side
    from hetu_tpu.ops.paged_pallas import NEG_INF
    empty = jnp.zeros_like(o2)
    lse_e = jnp.full_like(l2, NEG_INF)
    out1 = combine_attention_lse(o1, l1, empty, lse_e)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(o1),
                               atol=1e-6)


def test_packed_flash_formulation_matches_per_token_gather():
    """Ops-level packed-prefill parity: intra-pack (segment-isolated
    flash PALLAS kernel, interpret) + arena-history (read per TILE of
    a run, ``paged_history_attention``), LSE-combined, ==
    the per-token union through the tables — and a token of request A
    is PROVABLY blind to request B's pack rows (segment isolation)."""
    from hetu_tpu.ops.attention import attention_with_lse
    rng = np.random.default_rng(4)
    hkv = hq = 4
    d, bs, W, n_req = 16, 4, 6, 2
    per_req, hist = 6, 5                    # 5 tokens already resident
    C = n_req * per_req
    n_blocks = 1 + n_req * W
    k_arena = rng.normal(size=(n_blocks, bs, hkv, d)).astype(np.float32)
    v_arena = rng.normal(size=(n_blocks, bs, hkv, d)).astype(np.float32)
    tbl = np.zeros((n_req, W), np.int32)
    for r in range(n_req):
        tbl[r] = 1 + r * W + np.arange(W)
    seg = np.repeat(np.arange(n_req), per_req).astype(np.int32)
    pos = np.concatenate([hist + np.arange(per_req)] * n_req
                         ).astype(np.int32)
    qp = rng.normal(size=(1, C, hq, d)).astype(np.float32)
    kp = rng.normal(size=(1, C, hkv, d)).astype(np.float32)
    vp = rng.normal(size=(1, C, hkv, d)).astype(np.float32)
    for t in range(C):                      # the shared scatter
        row = tbl[seg[t], pos[t] // bs] * bs + pos[t] % bs
        k_arena.reshape(-1, hkv, d)[row] = kp[0, t]
        v_arena.reshape(-1, hkv, d)[row] = vp[0, t]
    k_arena = _pages(jnp.asarray(k_arena))
    v_arena = _pages(jnp.asarray(v_arena))
    tbl_tok = jnp.asarray(tbl[seg])

    intra, lse_i = attention_with_lse(
        jnp.asarray(qp), jnp.asarray(kp), jnp.asarray(vp), causal=True,
        segment_ids=jnp.asarray(seg)[None, :], impl="pallas")
    # the history part as the lane reads it: tiles of TQ tokens, a run
    # of 6 is two of them, each one pass over the request's pages
    tiles, counts = pack_history_tiles(
        [(r, r * per_req, per_req, hist) for r in range(n_req)],
        tile_rows=TQ, n_tiles=history_tile_count(C, TQ, n_req))
    assert counts == (4, 0, C)          # rows 4-7 are two runs' cell
    hist_o, lse_h = paged_history_attention(
        jnp.asarray(qp)[0], k_arena, v_arena,
        jnp.take(jnp.asarray(tbl), tiles[0], axis=0),
        jnp.full((C,), hist, jnp.int32), jnp.asarray(tiles),
        tile_rows=TQ)
    hist_o, lse_h = hist_o[None], lse_h.T[None]
    out = combine_attention_lse(intra, lse_i, hist_o, lse_h)
    ref = paged_attention_reference(
        jnp.asarray(qp)[0][:, None], k_arena, v_arena, tbl_tok,
        jnp.asarray(pos))[:, 0][None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5)
    # segment isolation: corrupting request B's PACK rows leaves
    # request A's outputs bit-identical (no cross-document leakage)
    kp2 = kp.copy()
    kp2[0, per_req:] += 100.0
    intra2, lse_i2 = attention_with_lse(
        jnp.asarray(qp), jnp.asarray(kp2), jnp.asarray(vp), causal=True,
        segment_ids=jnp.asarray(seg)[None, :], impl="pallas")
    out2 = combine_attention_lse(intra2, lse_i2, hist_o, lse_h)
    assert np.array_equal(np.asarray(out2[:, :per_req]),
                          np.asarray(out[:, :per_req]))
    assert not np.allclose(np.asarray(out2[:, per_req:]),
                           np.asarray(out[:, per_req:]))


def test_w8a8_matmul_semantics_and_error_bound():
    from hetu_tpu.ops.quantization import int8_w8a8_matmul
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(7, 33)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(33, 19)) * 0.05, jnp.float32)
    out = int8_w8a8_matmul(x, w)
    ref = x @ w
    assert out.shape == ref.shape and out.dtype == ref.dtype
    rel = float(jnp.max(jnp.abs(out - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.05, rel
    # exact on values that quantize losslessly (scale = amax/127)
    xq = jnp.asarray(np.sign(rng.normal(size=(4, 8))) * 127.0)
    wq = jnp.asarray(np.sign(rng.normal(size=(8, 3))) * 127.0)
    np.testing.assert_allclose(np.asarray(int8_w8a8_matmul(xq, wq)),
                               np.asarray(xq @ wq), rtol=1e-6)


def test_resolve_decode_kernel_and_fallback_counter(monkeypatch):
    from hetu_tpu.ops.attention import (
        kernel_fallbacks, record_kernel_fallback, resolve_decode_kernel,
    )
    assert resolve_decode_kernel("auto") == "reference"   # CPU backend
    assert resolve_decode_kernel("reference") == "reference"
    # tp>1 holds "paged" when the shard_map head slice is provably even
    assert resolve_decode_kernel("paged", tp=2, num_heads=4,
                                 num_kv_heads=2) == "paged"
    with pytest.raises(ValueError, match="auto\\|paged\\|reference"):
        resolve_decode_kernel("fast")
    # unknown / tp-ragged head counts → loud fallback, counted
    telemetry.reset()
    telemetry.enable(True)
    try:
        before = kernel_fallbacks().get("t_site", 0)
        with pytest.warns(UserWarning, match="fell back"):
            assert resolve_decode_kernel("paged", tp=2,
                                         site="t_site") == "reference"
        assert kernel_fallbacks()["t_site"] == before + 1
        reg = telemetry.get_registry()
        assert reg.counter("attn_kernel_fallback_total").value(
            site="t_site") >= 1
        # warn-once: the second fallback (here a RAGGED head split)
        # counts but stays quiet
        resolve_decode_kernel("paged", tp=2, num_heads=3,
                              num_kv_heads=3, site="t_site")
        assert kernel_fallbacks()["t_site"] == before + 2
        # an AUTO-derived "paged" hits the same tp guard (a tp-sharded
        # TPU default must degrade when the split is unprovable — never
        # hand GSPMD a raw Mosaic call)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_decode_kernel("auto", tp=2,
                                     site="t_site") == "reference"
        assert kernel_fallbacks()["t_site"] == before + 3
        assert resolve_decode_kernel("auto", tp=2, num_heads=8,
                                     num_kv_heads=8) == "paged"
        assert resolve_decode_kernel("auto", tp=1) == "paged"
    finally:
        telemetry.enable(False)
        telemetry.reset()
    del record_kernel_fallback


def test_decode_attn_read_bytes_prices_the_gather_tax():
    """SATELLITE: the ledger prices the reference path by TABLE width
    (materialize + read back, +dequant pass on int8) and the kernel by
    LIVE pages — the analytic ratio bench --kernels reports."""
    from hetu_tpu.engine.memory import (
        decode_attn_read_bytes, kv_bytes_per_block,
    )
    cfg = GPTConfig.tiny()
    per_block = kv_bytes_per_block(cfg, block_size=16)
    paged = decode_attn_read_bytes(cfg, context_len=33, table_len=1024,
                                   block_size=16, kernel="paged")
    ref = decode_attn_read_bytes(cfg, context_len=33, table_len=1024,
                                 block_size=16, kernel="reference")
    assert paged == 3 * per_block            # ceil(33/16) live pages
    assert ref == 2 * kv_bytes_per_block(cfg, block_size=1024)
    assert ref / paged > 10                  # the long-table tax
    # int8: kernel reads int8 pages; reference pays the dequant pass
    p8 = decode_attn_read_bytes(cfg, context_len=33, table_len=1024,
                                block_size=16, cache_dtype="int8",
                                kernel="paged")
    r8 = decode_attn_read_bytes(cfg, context_len=33, table_len=1024,
                                block_size=16, cache_dtype="int8",
                                kernel="reference")
    assert p8 < paged and r8 > ref * 0.5
    with pytest.raises(ValueError, match="paged\\|reference"):
        decode_attn_read_bytes(cfg, context_len=1, table_len=16,
                               block_size=16, kernel="gather")


def test_engine_kernel_knob_validation(gpt):
    """Knob resolution is loud: bad names raise, W8A8 without the int8
    arena raises, CPU auto resolves to the reference path, and the
    per-layer W8A8 mask honors an index list."""
    from hetu_tpu.serving import ServingEngine
    cfg, model, params = gpt
    eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, block_size=BLOCK)
    assert eng.attn_kernel == "reference"       # CPU auto
    assert eng.prefill_attn == "reference"
    assert eng._w8a8_mask is None
    with pytest.raises(ValueError, match="auto\\|paged\\|reference"):
        ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                      attn_kernel="mosaic")
    with pytest.raises(ValueError, match="prefill_attn"):
        ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                      prefill_attn="turbo")
    with pytest.raises(ValueError, match="int8 arena"):
        ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                      w8a8="on")
    eng8 = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                         prefill_chunk=CHUNK, block_size=BLOCK,
                         cache_dtype=jnp.int8, w8a8=[0])
    assert np.asarray(eng8._w8a8_mask).tolist() == [True, False]
    # "auto" stays OFF on CPU even with the int8 arena
    eng_a = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                          prefill_chunk=CHUNK, block_size=BLOCK,
                          cache_dtype=jnp.int8, w8a8="auto")
    assert eng_a._w8a8_mask is None


# ---------------------------------------------------------------------------
# slow tier: engine-level parity matrices (compile-bearing)
# ---------------------------------------------------------------------------

def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (L,)).tolist() for L in lens]


def _ref(model, params, prompt, max_tokens, **kw):
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new_tokens=max_tokens, max_len=MAX_LEN, **kw)
    return np.asarray(out[0, len(prompt):]).tolist()


@pytest.mark.slow
def test_engine_paged_kernel_greedy_identical_with_spec_and_int8(gpt):
    """ACCEPTANCE: the paged kernel is greedy-token-identical to the
    reference path across fp32 and int8 arenas WITH spec-decode verify
    rows (depth 2) and arrival churn, at 1 fused-step compile per
    engine."""
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine
    cfg, model, params = gpt
    prompts = _prompts(cfg, (5, 11, 3, 7), seed=7)
    sp = SamplingParams(max_tokens=8)

    for dtype in (jnp.float32, jnp.int8):
        outs = {}
        for kern, depth in (("reference", 0), ("paged", 0),
                            ("paged", 2)):
            before = trace_counts().get("serving_step", 0)
            eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                                prefill_chunk=CHUNK, block_size=BLOCK,
                                cache_dtype=dtype, attn_kernel=kern,
                                spec_depth=depth)
            # churn: stagger arrivals across iterations
            reqs = [eng.submit(prompts[0], sp), eng.submit(prompts[1],
                                                           sp)]
            for _ in range(3):
                eng.step()
            reqs += [eng.submit(p, sp) for p in prompts[2:]]
            eng.run_until_drained()
            outs[(kern, depth)] = [list(r.tokens) for r in reqs]
            assert trace_counts().get("serving_step", 0) - before == 1
        assert outs[("paged", 0)] == outs[("reference", 0)], dtype
        assert outs[("paged", 2)] == outs[("reference", 0)], dtype


@pytest.mark.slow
def test_engine_paged_kernel_preempt_resume_identity(gpt):
    """ACCEPTANCE: preempt→spill→resume churn on the PAGED kernel path
    stays token-identical to the one-shot oracle."""
    from hetu_tpu.serving import SamplingParams, ServingEngine
    cfg, model, params = gpt
    rng = np.random.default_rng(11)
    lo_p = rng.integers(1, cfg.vocab_size, (10,)).tolist()
    hi_p = rng.integers(1, cfg.vocab_size, (8,)).tolist()
    eng = ServingEngine(model, params, slots=1, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, attn_kernel="paged")
    lo = eng.submit(lo_p, SamplingParams(max_tokens=16, priority=2))
    for _ in range(6):
        eng.step()
    hi = eng.submit(hi_p, SamplingParams(max_tokens=4, priority=0))
    eng.run_until_drained()
    assert lo.preemptions == 1 and lo.resumed_blocks >= 1
    assert list(hi.tokens) == _ref(model, params, hi_p, 4)
    assert list(lo.tokens) == _ref(model, params, lo_p, 16)


@pytest.mark.slow
def test_engine_packed_flash_prefill_identity_and_isolation(gpt):
    """ACCEPTANCE: the packed flash prefill lane (pallas intra kernel,
    interpret) + paged kernel decode is greedy-identical to the
    reference engine; co-packed requests match their SOLO runs (no
    cross-document leakage through the pack); prefill KV matches the
    reference lane's arena at 1e-6 (fp reassociation across the two
    formulations)."""
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.serving import SamplingParams, ServingEngine
    cfg, model, params = gpt
    prompts = _prompts(cfg, (3, 4, 9), seed=13)   # first two co-pack
    sp = SamplingParams(max_tokens=6)

    def build(**kw):
        return ServingEngine(model, params, slots=3, max_len=MAX_LEN,
                             prefill_chunk=CHUNK, block_size=BLOCK,
                             **kw)

    ref_eng = build()
    ref_out = ref_eng.generate_many(prompts, sp)
    before = trace_counts().get("serving_step", 0)
    fl_eng = build(prefill_attn="flash_pallas", attn_kernel="paged")
    fl_out = fl_eng.generate_many(prompts, sp)
    assert trace_counts().get("serving_step", 0) - before == 1
    assert fl_out == ref_out
    # solo runs (nothing co-packed) — identical tokens
    for p, toks in zip(prompts[:2], fl_out[:2]):
        solo = build(prefill_attn="flash_pallas").generate_many(
            [p], sp)[0]
        assert solo == toks
    # a prompt of three chunks shares its packs with short requests
    # (its 2nd and 3rd runs have history, theirs none; tiles of 4 cut
    # its runs in two): every request's tokens are its SOLO run's
    from hetu_tpu.ops import paged_pallas
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_pallas, "history_tile_rows",
                   lambda *a, **kw: 4)
        mixed = _prompts(cfg, (2 * CHUNK + 5, 3, 4, 2), seed=17)
        eng = build(prefill_attn="flash_pallas", attn_kernel="paged")
        assert eng._hist_tile == 4 and eng._hist_tiles == 2 + 3 - 1
        reqs = [eng.submit(mixed[0], sp)]
        eng.step()                      # chunk 1 of the long prompt
        reqs += [eng.submit(p, sp) for p in mixed[1:]]
        eng.run_until_drained()
        got = [r.tokens for r in reqs]
        assert got == ref_eng.generate_many(mixed, sp)
        for p, toks in zip(mixed, got):
            assert build(prefill_attn="flash_pallas",
                         attn_kernel="paged").generate_many(
                [p], sp)[0] == toks
    # prefill KV parity: a single max_tokens=1 request writes ONLY
    # prefill rows — the two lanes' arenas must agree to fp noise
    one = SamplingParams(max_tokens=1)
    e_r = build()
    e_f = build(prefill_attn="flash_pallas")
    e_r.generate_many([prompts[2]], one)
    e_f.generate_many([prompts[2]], one)
    for a, b in zip(jax.tree.leaves(e_r.pool.caches),
                    jax.tree.leaves(e_f.pool.caches)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-6)


def test_engine_counts_history_tiles_on_the_host(gpt, monkeypatch):
    """``serving_prefill_hist_tiles_total{state}`` and
    ``serving_prefill_hist_rows_total`` from a scripted sequence of
    packs (chunk 8 in cells of 4): a 19-token prompt takes three
    iterations beside two short ones that wait for room in the pack.
    Iteration 1: its first chunk, no history — 2 empty tiles. 2: rows
    8-15 over 8 resident tokens — 2 live tiles, 8 rows. 3: its last 3
    tokens (1 live tile, 3 rows) beside a 3-token run across both cells
    (2 empty) and a 2-token run (1 empty). Decode iterations add
    nothing; the tokens are the reference engine's.
    ``serving_prefill_hist_chunks_total{state, layer}`` and
    ``serving_prefill_hist_tile_keys_total{state, layer}`` beside them,
    at a key tile of 2 pages (16 keys) a grid step: iteration 2's two
    tiles stand under a cap of 7 (a step each, 8 of its 16 keys seen),
    iteration 3's under 15 (a step, every key). Then a pack of TWO
    runs with different histories (prefix hits of 24 and of 8 tokens:
    the deepest cap, 23, makes the grid two steps deep and the
    shallower run's two tiles walk a dead one each), counted again as
    a layer with a window of 6 sees it."""
    from hetu_tpu.ops import paged_pallas
    from hetu_tpu.serving import SamplingParams, ServingEngine
    cfg, model, params = gpt
    monkeypatch.setattr(paged_pallas, "history_tile_rows",
                        lambda *a, **kw: 4)
    monkeypatch.setattr(paged_pallas, "history_tile_pages",
                        lambda *a, **kw: 2)
    prompts = _prompts(cfg, (19, 3, 2), seed=29)
    sp = SamplingParams(max_tokens=2)
    kw = dict(slots=3, max_len=MAX_LEN, prefill_chunk=CHUNK,
              block_size=BLOCK)
    want = ServingEngine(model, params, **kw).generate_many(prompts, sp)
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, prefill_attn="flash_pallas",
                            attn_kernel="paged", **kw)
        assert eng.generate_many(prompts, sp) == want
        reg = telemetry.get_registry()
        tiles = reg.counter("serving_prefill_hist_tiles_total")
        assert tiles.value(state="live") == 3
        assert tiles.value(state="empty") == 5
        assert reg.counter("serving_prefill_hist_rows_total").value() == 11
        chunks = reg.counter("serving_prefill_hist_chunks_total")
        keys = reg.counter("serving_prefill_hist_tile_keys_total")

        def counts(layer):
            return [c.value(state=st, layer=layer) for c, st in (
                (chunks, "live"), (chunks, "dead"), (keys, "live"),
                (keys, "masked"))]
        assert eng._hist_span == 2 * BLOCK and eng._min_window is None
        assert counts("full") == [3, 0, 32, 16]
        assert counts("window") == [0, 0, 0, 0]
        # two runs with history in ONE pack: a served 27-token prompt
        # leaves three blocks in the prefix cache; D shares all three
        # (2 tokens over 24), E the first (3 tokens over 8, across two
        # cells) — tiles (D, cell 0), (E, cell 0), (E, cell 1)
        long_, = _prompts(cfg, (27,), seed=31)
        other = [t % (cfg.vocab_size - 1) + 1 for t in long_]
        assert eng.generate_many([long_], sp) \
            == ServingEngine(model, params, **kw).generate_many(
                [long_], sp)
        before = counts("full")
        eng._min_window = 6     # the host's count of a windowed layer
        pair = [long_[:24] + other[24:26], long_[:8] + other[8:11]]
        reqs = [eng.submit(p, sp) for p in pair]
        eng.run_until_drained()
        assert [r.tokens for r in reqs] == ServingEngine(
            model, params, **kw).generate_many(pair, sp)
        assert tiles.value(state="live") == 3 + 5 + 3
        # full: D computes both steps, E's tiles one and walk one dead
        assert list(np.subtract(counts("full"), before)) \
            == [4, 2, 24 + 8 + 8, 4 * 16 - 40]
        # window 6: D's rows start at 24 and see 19..23 (one step of
        # two), E's first tile's at 8 (3..7), its second's at 10 (5..7)
        assert counts("window") == [3, 3, 5 + 5 + 3, 3 * 16 - 13]
        # the gather lane cuts no tiles
        ref = ServingEngine(model, params, attn_kernel="paged", **kw)
        assert ref._hist_tiles == 0
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_engine_counts_decode_chunks_on_the_host(gpt, monkeypatch):
    """``serving_decode_chunks_total{state}``: per decoding iteration
    the live (slot, chunk) pairs of a full-attention layer against
    slots x chunks (3 x 4 at a page a step). Once the short request is
    finished its slot stands at its last position and is NOT counted:
    the next iteration's live pairs are the long request's alone, its
    chunks are ``skipped``. ``serving_decode_tile_keys_total{state}``
    beside it: the keys the active slots' rows see (``pos + 1`` each)
    are ``live``, the rest of the live pairs' tiles (a pair is BLOCK
    keys here) ``masked``."""
    from hetu_tpu.ops import paged_pallas
    from hetu_tpu.serving import SamplingParams, ServingEngine
    cfg, model, params = gpt
    monkeypatch.setattr(paged_pallas, "default_pages_per_step",
                        lambda block_size, lane_bytes=0: 1)
    short, long_ = _prompts(cfg, (11, 6), seed=41)
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=3, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, block_size=BLOCK,
                            attn_kernel="paged")
        chunks = telemetry.get_registry().counter(
            "serving_decode_chunks_total")

        keys = telemetry.get_registry().counter(
            "serving_decode_tile_keys_total")

        def counts():
            return (chunks.value(state="live"),
                    chunks.value(state="skipped"),
                    keys.value(state="live"),
                    keys.value(state="masked"))

        a = eng.submit(short, SamplingParams(max_tokens=2))
        b = eng.submit(long_, SamplingParams(max_tokens=14))
        while not a.done.is_set():
            # both decode: each iteration counts every active slot's
            # chunks up to its position's, 12 pairs in all
            before, pos = counts(), eng._pos.copy()
            act = eng._active.copy()
            eng.step()
            live, skipped, seen, masked = np.subtract(counts(), before)
            if act.any():
                assert live == (pos[act] // BLOCK + 1).sum()
                assert live + skipped == 12
                assert seen == (pos[act] + 1).sum()
                assert seen + masked == live * BLOCK
        slot_a = int(np.flatnonzero(~eng._active & (eng._pos > 0))[0])
        assert eng._pos[slot_a] >= len(short)    # stale, and left so
        while not b.done.is_set():
            before, pos_b = counts(), int(eng._pos[eng._active][0])
            eng.step()
            live, skipped, seen, masked = np.subtract(counts(), before)
            # the freed slot's two chunks are skipped, not live, and
            # its stale position's keys are nobody's
            assert live == pos_b // BLOCK + 1
            assert skipped == 12 - live
            assert seen == pos_b + 1
            assert masked == live * BLOCK - seen
        assert min(counts()) > 0
        # the gather path has no work list to count
        ref = ServingEngine(model, params, slots=3, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, block_size=BLOCK)
        assert ref._chunk_steps == 0
        want = ref.generate_many([short, long_], [
            SamplingParams(max_tokens=2), SamplingParams(max_tokens=14)])
        assert [a.tokens, b.tokens] == want
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_engine_w8a8_serves_and_counts(gpt):
    """W8A8 decode FFNs serve through the fused step (int8 arena gate,
    per-layer mask) with the kernel-path counters flowing."""
    from hetu_tpu.serving import SamplingParams, ServingEngine
    cfg, model, params = gpt
    prompts = _prompts(cfg, (5, 9), seed=17)
    telemetry.reset()
    telemetry.enable(True)
    try:
        eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, block_size=BLOCK,
                            cache_dtype=jnp.int8, attn_kernel="paged",
                            w8a8="on")
        out = eng.generate_many(prompts, SamplingParams(max_tokens=6))
        assert all(len(t) == 6 for t in out)
        reg = telemetry.get_registry()
        assert reg.counter("serving_attn_kernel_total").value(
            path="paged") > 0
        assert reg.counter("prefill_attn_kernel_total").value(
            path="reference") > 0
    finally:
        telemetry.enable(False)
        telemetry.reset()


@pytest.mark.slow
def test_tp2_paged_kernel_no_fallback_greedy_identical(gpt):
    """TENTPOLE ACCEPTANCE (tp lane, ISSUE 17): a tp=2 plan with
    divisible head counts runs the PAGED kernel — shard_map over the
    plan's tp axis, each shard streaming its local head slice — instead
    of degrading to the gather path. The serving-site fallback counter
    stays at zero and the tokens are identical to the single-device
    reference engine (and the one-shot oracle)."""
    from hetu_tpu import optim
    from hetu_tpu.engine import make_plan, trace_counts
    from hetu_tpu.ops.attention import kernel_fallbacks
    from hetu_tpu.parallel.sharding import shard_params
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.serving import SamplingParams, ServingEngine

    cfg, model, params = gpt
    prompts = _prompts(cfg, (5, 11, 3, 8), seed=23)
    sp = SamplingParams(max_tokens=6)
    ref_eng = ServingEngine(model, params, slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, block_size=BLOCK)
    want = ref_eng.generate_many(prompts, sp)

    plan = make_plan(model, optim.adamw(1e-3), Strategy(tp=2))
    sp_params = shard_params(params, plan.mesh, plan.param_specs)
    fb_before = kernel_fallbacks().get("serving_decode", 0)
    before = trace_counts().get("serving_step", 0)
    eng = ServingEngine(model, sp_params, slots=2, max_len=MAX_LEN,
                        prefill_chunk=CHUNK, block_size=BLOCK,
                        attn_kernel="paged", plan=plan)
    # divisible heads (4 q / 4 kv over tp=2): NO fallback at resolve
    assert eng.attn_kernel == "paged"
    assert kernel_fallbacks().get("serving_decode", 0) == fb_before
    assert eng.generate_many(prompts, sp) == want
    assert trace_counts().get("serving_step", 0) - before == 1
    assert eng.step_executables() == 1      # one trace AND one compile
    assert want == [_ref(model, params, p, 6) for p in prompts]


#: sha256(str(jaxpr))[:16] of the WHOLE fused serving step (CoW pass,
#: both lanes, sampling; ``ServingEngine._build_step``) of GPT-2 small
#: (148 slots) and GPT-2 large (32 slots) at the benchmark cells'
#: shapes, traced under this suite's configuration: the program of the
#: models WITHOUT an expert layer, which a PR of the expert layer or of
#: the 64-key cells must leave as it is. Re-pinned ONCE by PR 60 from
#: the values of commit e9e9b15 (PR 42: 6a47ef4078891afc,
#: 20ae0a9a6d9445d6): at 16-key pages the chunks are the parent's (3
#: pages, 22 a table) and the one thing that differs is the decode
#: call's work list — 705 entries for 704 at GPT-2 large (32 slots x 22
#: chunks + the spare entry; the kernel's ``min(i + 1, 704)`` for
#: ``703``), 3,257 for 3,256 at GPT-2 small — and ONE equation more in
#: the decode lane's layer body, the ``optimization_barrier`` that
#: hands the call's result on together with the arena's leaves
#: (``ParallelAttention._decode``; the equations behind it keep their
#: text and get the next names).
PARENT_STEP_JAXPR = {"gpt2-small": "739b5daf59e80ddd", "gpt2-large": "93c2171dad212180"}


@pytest.mark.parametrize("name", list(PARENT_STEP_JAXPR))
def test_fused_step_without_experts_is_the_parents(name):
    import hashlib
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.serving import ServingEngine
    cfg, slots = {
        "gpt2-small": (GPTConfig.small(), 148),
        "gpt2-large": (GPTConfig(
            vocab_size=50257, max_positions=1024, hidden_size=1280,
            num_layers=36, num_heads=20), 32)}[name]
    model = GPTLMHeadModel(cfg)
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                       jax.random.key(0)))
    eng = ServingEngine(model, params, max_len=1024, prefill_chunk=256,
                        cache_dtype=jnp.bfloat16, block_size=16,
                        slots=slots, kv_blocks=65, attn_kernel="paged",
                        prefill_attn="flash_pallas")

    class Recorded(Exception):
        pass

    def record(*args):
        raise Recorded(args)

    eng._fn = record
    eng.submit([1, 2, 3])
    with pytest.raises(Recorded) as seen:
        eng.step()
    args, = seen.value.args
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                       if not hasattr(x, "dtype")
                                       else x.dtype), args)
    jaxpr = jax.make_jaxpr(eng._build_step())(*sds)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] \
        == PARENT_STEP_JAXPR[name]
