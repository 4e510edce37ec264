"""The prefill lane's history read a TILE of a run at a time
(``ops.paged_pallas.paged_history_attention``), its banded form for a
block-sparse pack, and the shape rules that size a tile — interpreted on
the CPU against one reference slot a token. Split from
``tests/test_kernel_plane.py`` (PR 61: the driver hands a worker whole
files, and that file alone was the floor of tier 1's wall); the cases and
their assertions are unchanged."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops.paged_pallas import (
    NEG_INF, history_tile_count, history_tile_rows, pack_history_tiles,
    paged_attention_reference, paged_history_attention,
)
from paged_cases import GARBAGE, TILE_FORMS, TQ
from paged_cases import _history_pack, _kernel_eqns, _pages


# -- the prefill lane's history read: one pass per tile of a run ------------

#: runs of a pack as (tokens, hist); pack rows in order, pads after
HIST_CASES = {
    "run_1": [(1, 9)],
    "run_tq_minus_1": [(TQ - 1, 9)],
    "run_tq": [(TQ, 9)],
    "run_tq_plus_1": [(TQ + 1, 9)],
    "several_tiles": [(3 * TQ + 2, 6)],
    "runs_with_and_without_history": [(5, 0), (6, 11), (1, 0), (3, 4)],
    "every_run_with_history": [(2, 3), (7, 10), (5, 16)],
    "no_history_anywhere": [(9, 0), (3, 0)],
    "page_boundary_history": [(6, 8), (4, 4)],
}


def _assert_history_read_matches_per_token(case, *, window=None, tq=TQ,
                                           max_runs=4, **kw):
    """``paged_history_attention`` == one reference slot per token at
    ``hist - 1`` (outputs AND lse); tokens without history and pad
    lanes get the empty part exactly."""
    q, k, v, tbl, slot, pos, hist, runs, arena = case
    C = q.shape[0]
    G = history_tile_count(C, tq, max_runs)
    tiles, counts = pack_history_tiles(runs, tile_rows=tq, n_tiles=G)
    out, lse = paged_history_attention(
        q, k, v, jnp.take(tbl, tiles[0], axis=0), hist,
        jnp.asarray(tiles), tile_rows=tq, window=window, **arena, **kw)
    ref_kw = dict(arena)
    if window is not None:
        ref_kw["window"] = window - (pos - (hist - 1))
    ref, lse_r = paged_attention_reference(
        q[:, None], k, v, jnp.take(tbl, slot, axis=0), hist - 1,
        return_lse=True, **ref_kw)
    live = np.asarray(hist) > 0
    assert counts[0] == sum((f + n - 1) // tq - f // tq + 1
                            for _, f, n, h in runs if h)
    assert counts[2] == sum(n for _, _, n, h in runs if h)
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live, 0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse)[live],
                               np.asarray(lse_r)[live, :, 0], atol=1e-5)
    assert not np.asarray(out)[~live].any()
    assert (np.asarray(lse)[~live] == NEG_INF).all()
    return out, lse, ref


@pytest.mark.parametrize("case", list(HIST_CASES))
def test_history_read_tiles_match_per_token_reference(case):
    """Run lengths around the tile size, several tiles, packs of
    several runs with and without history, pad lanes, and a pack where
    nothing has history (every tile dead: out 0, lse NEG_INF)."""
    rng = np.random.default_rng(21)
    out, _, _ = _assert_history_read_matches_per_token(
        _history_pack(rng, HIST_CASES[case]))
    if not any(h for _, h in HIST_CASES[case]):
        assert not np.asarray(out).any()


@pytest.mark.parametrize("g", [1, 16])
def test_history_read_group_sizes(g):
    """``g`` = 1 (GPT-2: a tile is Tq rows per head) and ``g`` = 16
    (128 q heads over 8: Tq x 16 rows)."""
    rng = np.random.default_rng(22)
    case = _history_pack(rng, [(6, 11), (5, 0), (3, 7)], hq=2 * g,
                         hkv=2)
    _assert_history_read_matches_per_token(case)


@pytest.mark.parametrize("window", [3, 6, 8, 2 ** 30])
def test_history_read_window_applies_to_the_rows_own_position(window):
    """A windowed layer: the row sits at its TRUE position, so the
    layer's own window cuts its history — rows with history on both
    sides of it (a run of 7 over 12 resident tokens: window 6 leaves
    the deeper rows nothing, 8 cuts inside a page, 3 leaves only the
    first rows any) — and ``2 ** 30``, the full layer of a model that
    mixes both kinds, cuts nothing."""
    rng = np.random.default_rng(23)
    case = _history_pack(rng, [(7, 12), (4, 0), (5, 2)])
    w = jnp.asarray(window, jnp.int32)
    out, _, _ = _assert_history_read_matches_per_token(case, window=w)
    full, _, _ = _assert_history_read_matches_per_token(case)
    differs = np.abs(np.asarray(out) - np.asarray(full)).max() > 1e-3
    assert differs == (window < 2 ** 30)


def test_history_read_int8_arena():
    """The int8 arena's scales page the same way under the cap."""
    rng = np.random.default_rng(24)
    _assert_history_read_matches_per_token(
        _history_pack(rng, [(6, 9), (5, 0), (5, 14)], quant=True))


def test_history_read_in_a_layer_scan_and_dead_tiles_read_nothing():
    """The stacked arena at a traced layer, as the fused step calls it;
    and a dead tile's table may point anywhere (a pad tile carries slot
    0's): poisoning every table lane above the cap and every dead
    tile's row changes nothing."""
    rng = np.random.default_rng(25)
    q, k, v, tbl, slot, pos, hist, runs, _ = _history_pack(
        rng, [(6, 9), (5, 0), (5, 14)])
    C = q.shape[0]
    G = history_tile_count(C, TQ, 4)
    tiles, _ = pack_history_tiles(runs, tile_rows=TQ, n_tiles=G)
    tj = jnp.asarray(tiles)
    tables = np.asarray(jnp.take(tbl, tiles[0], axis=0)).copy()
    ks = jnp.stack([k * 0, k])
    vs = jnp.stack([v * 0, v])

    @jax.jit
    def f(tables, layer):
        return paged_history_attention(q, ks, vs, tables, hist, tj,
                                       tile_rows=TQ, layer=layer)
    base, lse = f(jnp.asarray(tables), jnp.asarray(1, jnp.int32))
    one, lse1 = paged_history_attention(q, k, v, jnp.asarray(tables),
                                        hist, tj, tile_rows=TQ)
    np.testing.assert_allclose(np.asarray(base), np.asarray(one),
                               atol=1e-6)
    for t in range(G):
        cap = tiles[-1][t]
        tables[t, (cap // 4 + 1 if cap >= 0 else 0):] = 0
    out, lse2 = f(jnp.asarray(tables), jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    np.testing.assert_array_equal(np.asarray(lse2), np.asarray(lse))


#: runs (tokens, hist) of the joined-tile cases' pack, over pages of 4
#: and tables of 16 lanes: histories that end inside chunk 0, at the
#: last key of EVERY page of the upper chunk of 8 pages and mid-page
#: (tiles of different caps in one call: the shallow ones walk dead
#: chunks under the deepest), runs that split cells of 4 rows between
#: them, and one without history
TILE_RUNS = [(3, 1), (5, 7), (2, 36), (4, 40), (1, 44), (6, 48), (2, 52),
             (3, 56), (3, 60), (5, 38), (2, 51), (2, 0)]


@pytest.mark.parametrize("form,pages", [
    (f, p) for f in ("g1", "g4", "latent") for p in (1, 2, 4, 8)]
    + [("int8", 8)])
def test_history_read_key_tile_of_several_pages_matches_per_token(
        form, pages):
    """The tiled call at ``pages`` pages a grid step == one reference
    slot a token, outputs and LSE: the chunk a cap ends in is masked
    above it (the pages the table names there are fetched at most as
    the cap's own), a window starting mid-chunk (13 keys) masks its
    first chunk from below, ``2 ** 30`` cuts nothing — and what no row
    may see (±3e4) is never seen."""
    for window in (None, 13, 2 ** 30):
        rng = np.random.default_rng(61)
        case = _history_pack(rng, TILE_RUNS, C=40, W=16, garbage=True,
                             window=window, **TILE_FORMS[form])
        w = None if window is None else jnp.asarray(window, jnp.int32)
        _, _, ref = _assert_history_read_matches_per_token(
            case, window=w, max_runs=len(TILE_RUNS),
            pages_per_step=pages)
        live = np.asarray(case[6]) > 0
        assert np.abs(np.asarray(ref)[live]).max() < 10   # no garbage


@pytest.mark.parametrize("pages", [1, 2, 8])
@pytest.mark.parametrize("form", ["g1", "g4", "latent", "int8"])
def test_history_read_scores_a_key_tile_per_head(form, pages):
    """The tiled call's kernel at ``pages`` pages a grid step: ``hkv``
    score products ``(rows, d) x (pages * bs, d)``, ``hkv`` value
    products and ``hkv`` exps of a score tile — not one a page — and
    the mask that is left: ONE select a head on the cap (a tile's rows
    stand above every key they read: no causal compare, no row
    positions), a second and the rows' positions only under a
    window."""
    rng = np.random.default_rng(62)
    form = TILE_FORMS[form]
    hkv, d, bs = form["hkv"], form["d"], 4
    q, k, v, tbl, _, _, hist, runs, arena = _history_pack(
        rng, [(6, 9), (5, 14)], W=16, **form)
    tiles, _ = pack_history_tiles(runs, tile_rows=TQ, n_tiles=5)
    rows, dv = TQ * form["hq"] // hkv, form.get("v_width", d)
    for window in (None, jnp.asarray(6, jnp.int32)):
        eqns = _kernel_eqns(jax.make_jaxpr(
            lambda q, k, v: paged_history_attention(
                q, k, v, jnp.take(tbl, tiles[0], axis=0), hist,
                jnp.asarray(tiles), tile_rows=TQ, pages_per_step=pages,
                window=window, interpret=True, **arena))(q, k, v).jaxpr)
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert sorted((e.invars[1].aval.shape, e.outvars[0].aval.shape)
                      for e in dots) == sorted(
            [((pages * bs, d), (rows, pages * bs))] * hkv
            + [((pages * bs, dv), (rows, dv))] * hkv)
        tile = (rows, pages * bs)

        def count(name):
            return sum(e.primitive.name == name
                       and e.outvars[0].aval.shape == tile for e in eqns)
        assert count("exp") == hkv
        # (under a window one more: the rows' positions floor-divide)
        assert count("select_n") == (hkv if window is None
                                     else 2 * hkv + 1)
        assert count("iota") == (1 if window is None else 2)


# -- the banded causal tiled call: a block-sparse pack's forced blocks ------

BAND, INIT = 2, 1   # blocks before a row's own, leading lanes (pages of 4)

#: runs of a pack as (tokens, first position); ``tq``: the tile's rows
BAND_CASES = {
    # the lower edge (floored to a block) with a row at a block's first
    # key, its last, and one past: the edge moves a whole block
    "edge_at_a_block_boundary": dict(runs=[(3, 15)]),
    "edge_one_key_below": dict(runs=[(3, 14)]),
    "edge_one_key_above": dict(runs=[(3, 16)]),
    # own <= BAND: the band reaches the first block (read once)
    "first_block_inside_the_band": dict(runs=[(9, 0)]),
    "band_meets_the_first_block": dict(runs=[(4, 10)]),
    "first_block_outside_the_band": dict(runs=[(7, 20)]),
    "tile_spans_two_blocks": dict(runs=[(4, 18)]),
    "tile_of_8_spans_three_blocks": dict(runs=[(8, 19)], tq=8),
    "run_starts_mid_cell": dict(runs=[(2, 5), (9, 17)]),
    "two_runs_share_a_cell_twice": dict(runs=[(5, 9), (6, 22), (3, 0)]),
    "a_dead_tile_and_pad_rows": dict(runs=[(6, 13)], max_runs=4),
}


def _band_pack(rng, runs, *, C=16, hq=4, hkv=2, d=16, bs=4, W=8):
    """A pack of ``runs`` (tokens, first position) whose keys are in
    the arena already; what no row of a run may see — positions above
    its last row, blocks between the leading lanes and its first
    row's band, the null block — is ±GARBAGE."""
    n_blocks = 1 + len(runs) * W
    k, v = (rng.normal(size=(n_blocks, bs, hkv, d)).astype(np.float32)
            for _ in range(2))
    tbl = 1 + np.arange(len(runs) * W, dtype=np.int32).reshape(-1, W)
    seen = np.zeros((n_blocks * bs,), bool)
    slot, pos = np.zeros(C, np.int32), np.zeros(C, np.int32)
    valid = np.zeros(C, bool)
    run_list, used = [], 0
    for s, (n, first) in enumerate(runs):
        base = (1 + s * W) * bs
        seen[base:base + INIT * bs] = True
        edge = max(first // bs - BAND, 0) * bs
        seen[base + edge:base + first + n] = True
        slot[used:used + n], valid[used:used + n] = s, True
        pos[used:used + n] = first + np.arange(n)
        run_list.append((s, used, n, first))
        used += n
    for x in (k, v):
        flat = x.reshape(-1, hkv, d)
        flat[~seen] = GARBAGE * rng.choice(
            [-1.0, 1.0], size=((~seen).sum(), hkv, d))
    q = jnp.asarray(rng.normal(size=(C, hq, d)), jnp.float32)
    return (q, _pages(jnp.asarray(k)), _pages(jnp.asarray(v)),
            jnp.asarray(tbl), slot, pos, valid, run_list)


def _band_reference(q, k, v, tbl, slot, pos, bs=4):
    """A token at a time: its leading and band blocks as a table of
    its own, read causally (``paged_attention_reference``)."""
    own = pos // bs
    first = np.maximum(own - BAND, INIT)
    lane = np.arange(INIT + BAND + 1)[None, :]
    blocks = np.where(lane < INIT, lane, first[:, None] + lane - INIT)
    n = np.minimum(own + 1, INIT + np.maximum(own - first + 1, 0))
    tables = np.where(lane < n[:, None],
                      np.asarray(tbl)[slot[:, None], np.minimum(blocks, 7)],
                      0)
    return paged_attention_reference(
        q[:, None], k, v, jnp.asarray(tables, jnp.int32),
        jnp.asarray((n - 1) * bs + pos % bs, jnp.int32), return_lse=True)


@pytest.mark.parametrize("pages", [1, 2, 8])
@pytest.mark.parametrize("case", list(BAND_CASES))
def test_banded_tiled_call_matches_per_token_reference(case, pages):
    """The tiled call under ``band=``: row ``t`` sees key ``j`` of its
    run's table iff ``j <= t`` and ``j`` lies in the BAND blocks before
    ``t``'s own, in its own, or in the INIT leading lanes — outputs and
    LSE of one reference slot a token, at one, two and every page a
    grid step; pad rows get the empty part; nothing a run's rows may
    not see (the keys above them, the blocks the band skips) is seen."""
    spec = BAND_CASES[case]
    tq = spec.get("tq", TQ)
    rng = np.random.default_rng(52)
    q, k, v, tbl, slot, pos, valid, runs = _band_pack(rng, spec["runs"])
    G = history_tile_count(q.shape[0], tq, spec.get("max_runs",
                                                    len(runs)))
    tiles, (live, _, rows) = pack_history_tiles(
        runs, tile_rows=tq, n_tiles=G, every_run=True)
    assert rows == valid.sum() and (tiles[-1][live:] == -1).all()
    # a live tile's last key is its last row's
    assert (tiles[-1][:live]
            == tiles[4][:live] + tiles[3][:live] - 1).all()
    out, lse = paged_history_attention(
        q, k, v, jnp.take(tbl, tiles[0], axis=0), jnp.asarray(valid),
        jnp.asarray(tiles), tile_rows=tq, band=(BAND, INIT),
        pages_per_step=pages)
    ref, lse_r = _band_reference(q, k, v, tbl, slot, pos)
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid, 0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse)[valid],
                               np.asarray(lse_r)[valid, :, 0], atol=1e-5)
    assert np.abs(np.asarray(ref)[valid]).max() < 10      # no garbage
    assert not np.asarray(out)[~valid].any()
    assert (np.asarray(lse)[~valid] == NEG_INF).all()


@pytest.mark.parametrize("g", [1, 4, 16])
def test_banded_tiled_call_group_sizes_in_a_layer_scan(g):
    """``g`` query heads a kv head (a tile is ``tq x g`` rows on the
    MXU) over the stacked arena at a traced layer; a dead tile's table
    and the lanes above a tile's last row may name any page."""
    rng = np.random.default_rng(53)
    q, k, v, tbl, slot, pos, valid, runs = _band_pack(
        rng, [(5, 9), (6, 22), (3, 0)], hq=2 * g)
    G = history_tile_count(q.shape[0], TQ, 6)
    tiles, (live, _, _) = pack_history_tiles(runs, tile_rows=TQ, n_tiles=G,
                                             every_run=True)
    tables = np.asarray(jnp.take(tbl, tiles[0], axis=0)).copy()
    tables[live:] = 0
    for t in range(live):
        tables[t, tiles[-1][t] // 4 + 1:] = 0
    ks, vs = jnp.stack([k * 0, k]), jnp.stack([v * 0, v])

    @jax.jit
    def f(layer):
        return paged_history_attention(
            q, ks, vs, jnp.asarray(tables), jnp.asarray(valid),
            jnp.asarray(tiles), tile_rows=TQ, band=(BAND, INIT),
            layer=layer)
    out, lse = f(jnp.asarray(1, jnp.int32))
    ref, lse_r = _band_reference(q, k, v, tbl, slot, pos)
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(ref)[valid, 0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse)[valid],
                               np.asarray(lse_r)[valid, :, 0], atol=1e-5)


def test_history_tile_pages_from_shapes():
    """The pages of a grid step's key tile are a function of shapes,
    rows first: 8 pages beside the latent cells (16 or 32 heads over
    one 640-wide row: 512 keys), 4 beside 128 q heads over 8 kv heads
    of 128 (8 would not fit beside the cell), 8 of GPT-2's 16-key
    pages (128 keys); never above 512 keys, never under one page."""
    from hetu_tpu.ops.paged_pallas import history_tile_pages
    for g, d, hkv, bs, latent, pages in (
            (1, 64, 12, 16, False, 8), (1, 64, 20, 16, False, 8),
            (16, 128, 8, 64, False, 4), (16, 640, 1, 64, True, 8),
            (32, 640, 1, 64, True, 8), (1, 64, 12, 128, False, 4),
            (1, 64, 12, 1024, False, 1), (20, 128, 1, 64, False, 8)):
        tq = history_tile_rows(g, d, hkv, bs)
        assert history_tile_pages(g, d, hkv, bs, tile_rows=tq,
                                  latent=latent) == pages


def test_history_tile_rows_from_shapes():
    """The tile size is a function of the head shapes: 128 tokens for
    GPT-2 (one row a token and head), 16 for 128 q heads over 8 kv
    heads of 128 (256 rows a kv head) — and the static tile count is
    the chunk's tiles plus one more for every run after the first."""
    assert history_tile_rows(1, 64, 12, 16) == 128
    assert history_tile_rows(1, 64, 20, 16) == 128
    assert history_tile_rows(16, 128, 8, 64) == 16
    assert history_tile_rows(16, 640, 1, 64) == 32      # (the latent
    assert history_tile_rows(32, 640, 1, 64) == 16      # cells)
    # a caller that bounds the rows of one kv head: the block-sparse
    # band's tile (a group of 16 over pages of ONE kv head) is 16
    # tokens at 256 rows, where the pricing alone says 128
    assert history_tile_rows(16, 128, 1, 64) == 128
    assert history_tile_rows(16, 128, 1, 64, head_rows=256) == 16
    assert history_tile_rows(16, 128, 8, 64, head_rows=1024) == 16
    # 20 query heads over ONE kv head of 128: the pricing alone says 128
    # tokens (2,560 rows), beside which ONE 64-key page fits — half a
    # lane tile of scores a step; the rows give way until a lane tile of
    # keys fits (PR 55)
    assert history_tile_rows(20, 128, 1, 64) == 64
    assert history_tile_count(256, 128, 32) == 33
    assert history_tile_count(512, 16, 48) == 79
    assert history_tile_count(8, 128, 3) == 3


#: (q heads a kv head, head width, kv heads a page row, page) of the
#: paged models the benchmark and the tests serve: GPT-2 small, large
#: and tiny; Command A+ and its tiny; the latent rows of Kimi and Ling
#: (576 padded to 640) and their tinies; MiniCPM-SALA's band over pages
#: of ONE kv head and over both, and its tiny; SDAR's group and its
#: block of 4 x 8 rows, and its tiny; Jamba's tiny; Llama-shaped
#: GQA; long pages
TILE_SHAPES_THE_RULE_LEAVES = (
    (1, 64, 12, 16), (1, 64, 20, 16), (1, 16, 4, 16),
    (16, 128, 8, 64), (4, 16, 2, 4),
    (16, 640, 1, 64), (32, 640, 1, 64), (4, 40, 1, 4), (4, 128, 1, 4),
    (16, 128, 1, 64), (16, 128, 2, 64), (2, 16, 1, 4), (2, 16, 2, 4),
    (8, 128, 4, 64), (32, 128, 4, 64),
    (4, 16, 1, 4),
    (4, 128, 8, 16), (8, 128, 8, 16), (2, 16, 2, 16),
    (1, 64, 12, 128), (1, 64, 12, 1024))


def _tile_rows_priced_alone(g, d, hkv, bs, kv_itemsize, head_rows):
    """:func:`history_tile_rows` as it stood before PR 55's rule: the
    blocks' VMEM price and the caller's bound, nothing else."""
    from hetu_tpu.ops import paged_pallas as pp
    pages = 2 * 2 * bs * hkv * d * kv_itemsize
    tq = 128
    while tq > 8 and (pp._tile_cell_bytes(tq * g, d, hkv) + pages
                      > pp._TILE_VMEM_BUDGET
                      or head_rows is not None and tq * g > head_rows):
        tq //= 2
    return tq


@pytest.mark.parametrize("shape", TILE_SHAPES_THE_RULE_LEAVES,
                         ids=lambda s: "x".join(map(str, s)))
def test_the_lane_tile_rule_leaves_every_other_models_tile(shape):
    """PR 55's rule in :func:`history_tile_rows` (the rows give way
    until a lane tile of keys fits beside them) was written for 20
    query heads over ONE kv head of 128; every shape another paged
    model lowers keeps the tile the pricing alone gave it, at every
    arena itemsize and under every bound a caller sets."""
    for kv_itemsize in (1, 2, 4):
        for head_rows in (None, 256, 1024):
            assert history_tile_rows(
                *shape, kv_itemsize=kv_itemsize, head_rows=head_rows) \
                == _tile_rows_priced_alone(*shape, kv_itemsize, head_rows)


def test_the_lane_tile_rule_moves_the_one_kv_head_of_twenty():
    """... and what it moves: Jamba's attention layers (128 tokens =
    2,560 rows beside ONE 64-key page, to 64 tokens beside 512 keys);
    a group of 5 over 8 kv heads of 128, which no model has; and 32 or
    40 kv heads of 128 with one query head each (the ``llama_7b`` /
    ``llama_13b`` presets: code that no cell and no test serves at that
    size — 64 tokens to 32, NOT measured)."""
    assert _tile_rows_priced_alone(20, 128, 1, 64, 2, None) == 128
    assert history_tile_rows(20, 128, 1, 64) == 64
    assert history_tile_rows(5, 128, 8, 64) \
        < _tile_rows_priced_alone(5, 128, 8, 64, 2, None)
    for hkv in (32, 40):
        assert _tile_rows_priced_alone(1, 128, hkv, 16, 2, None) == 64
        assert history_tile_rows(1, 128, hkv, 16) == 32
