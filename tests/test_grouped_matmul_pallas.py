"""The serving expert layer's grouped matmul (``ops/grouped_matmul_pallas``)
in interpret mode on the CPU: against ``jax.lax.ragged_dot`` and against a
per-group ``jnp.matmul`` loop, at the three expert cells' shapes cut to CPU
size with their awkward parts kept, and at the layout's edges. The tile
rule's values at the cells' two lanes are pinned, as ``history_tile_*``'s
are. The fused entry (``grouped_swiglu``: gate, up, SwiGLU and down of a
(row tile, expert) in one grid step and the weighted sum into the tokens'
rows) against the three-call path and the same loop, and the shape rule
that picks it at every benchmark lane."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.grouped_matmul_pallas import (
    grouped_block_cols, grouped_combine, grouped_layout, grouped_matmul,
    grouped_matmul_reference, grouped_padded_rows, grouped_rows_computed,
    grouped_swiglu, grouped_swiglu_fits, grouped_tile_rows,
)

CASES = {
    # Kimi's decode lane: 4.5 rows a group, a width of 11 x 128
    "rows_4p5_a_group_n_11x128": dict(
        sizes=[5, 4, 3, 6, 4, 5, 4, 5], rows=36, K=64, N=1408, tile=16),
    # Kimi's prefill lane: groups of a tile or two, uneven
    "hundreds_a_group": dict(
        sizes=[40, 9, 33, 46], rows=128, K=128, N=256, tile=32),
    # Command A+: 16 groups of a window with half the rows dead, the
    # matrix in column blocks
    "window_half_dead_column_blocks": dict(
        sizes=[5, 3, 6, 2, 4, 4, 7, 1, 4, 5, 3, 4, 6, 2, 4, 4],
        rows=128, K=128, N=512, tile=16, cols=128),
    # the layer scan's stacked leaf: a traced layer, every other
    # layer's groups empty
    "stacked_leaf_traced_layer": dict(
        sizes=[7, 0, 12, 5], rows=24, K=64, N=256, tile=8, layers=3,
        layer=1, cols=128),
    "gated_epilogue": dict(
        sizes=[7, 0, 12, 5], rows=24, K=64, N=256, tile=8, layers=2,
        layer=1, gated=True),
    "all_rows_in_one_group": dict(
        sizes=[0, 0, 40, 0], rows=40, K=64, N=128, tile=16),
    "some_groups_empty": dict(
        sizes=[0, 9, 0, 0, 17, 0], rows=32, K=64, N=128, tile=8),
    "zero_live_rows": dict(
        sizes=[0, 0, 0, 0], rows=16, K=64, N=256, tile=8, cols=128),
    # every group wastes tile - 1 rows and the live total is the rows:
    # the static bound's every tile is live (the full work list that
    # halted the paged call on the chip, PERF.md PR 39)
    "full_static_bound": dict(
        sizes=[9, 9, 9, 9], rows=36, K=64, N=256, tile=8, cols=128,
        full=True),
    "a_group_ends_on_a_tile_edge": dict(
        sizes=[16, 8, 24, 3], rows=56, K=64, N=128, tile=8),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_kernel_equals_ragged_dot_and_the_group_loop(case):
    sizes = np.asarray(case["sizes"], np.int32)
    rows, K, N, tile = (case[n] for n in ("rows", "K", "N", "tile"))
    layers, layer = case.get("layers"), case.get("layer")
    groups, live = len(sizes), int(sizes.sum())
    kx, kw, kg = jax.random.split(jax.random.key(len(sizes) + rows), 3)
    x = jax.random.normal(kx, (rows, K), jnp.bfloat16)
    w = (jax.random.normal(kw, (layers or 1, groups, K, N)) * 0.1) \
        .astype(jnp.bfloat16)
    gate = jax.random.normal(kg, (rows, N), jnp.float32) \
        if case.get("gated") else None

    @jax.jit
    def run(x, w, sizes, layer, gate):
        lay = grouped_layout(sizes, rows=rows, tile=tile)
        y = grouped_matmul(
            jnp.take(x, lay.src, axis=0), w if layers else w[0], lay,
            layer=layer if layers else None,
            gate=None if gate is None else jnp.take(gate, lay.src, axis=0),
            block_cols=case.get("cols"))
        return jnp.take(y, lay.dst, axis=0), lay.n_tiles

    got, n_tiles = run(x, w, jnp.asarray(sizes),
                       jnp.asarray(layer or 0, jnp.int32), gate)
    mine = w[layer or 0]
    ragged = jax.lax.ragged_dot(x, mine, jnp.asarray(sizes),
                                preferred_element_type=jnp.float32)
    loop = grouped_matmul_reference(x, mine, sizes)
    if gate is not None:
        ragged, loop = (jax.nn.silu(gate) * r for r in (ragged, loop))
    got = np.asarray(got)[:live]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(loop)[:live], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got, np.asarray(ragged)[:live], rtol=1e-5,
                               atol=1e-5)
    # the host's count of the rows the kernel visited is the device's
    assert int(n_tiles) * tile == grouped_rows_computed(sizes, tile)
    if case.get("full"):
        assert int(n_tiles) * tile == grouped_padded_rows(rows, groups,
                                                          tile)


def test_layout_puts_each_group_on_its_own_tiles():
    sizes = np.asarray([5, 0, 17, 8, 1], np.int32)
    rows, tile = 40, 8
    lay = grouped_layout(jnp.asarray(sizes), rows=rows, tile=tile)
    src, dst = np.asarray(lay.src), np.asarray(lay.dst)
    group_of_row = np.repeat(np.arange(5), sizes)
    live = int(sizes.sum())
    # a live sorted row comes back from where it went, on a tile whose
    # group is its own, and no two share a place
    assert (src[dst[:live]] == np.arange(live)).all()
    assert len(set(dst[:live].tolist())) == live
    assert (np.asarray(lay.tile_group)[dst[:live] // tile]
            == group_of_row).all()
    assert int(lay.n_tiles) == int(np.ceil(sizes / tile).sum())
    assert lay.rows == grouped_padded_rows(rows, 5, tile) == 80


def test_rows_computed_walks_a_share_in_windows():
    """A share's sorted rows are walked in windows, each laid out on
    its own: a group that straddles two windows rounds up in both."""
    sizes = [10, 30, 5]
    assert grouped_rows_computed(sizes, 16) == 16 + 32 + 16
    # windows of 32 rows: [10 + 22] and [8 + 5]
    assert grouped_rows_computed(sizes, 16, 32) == (16 + 32) + (16 + 16)
    assert grouped_rows_computed([0, 0], 16, 32) == 0


# rows a call, held experts, hidden, expert width: the three expert
# cells' decode and prefill lanes (Command A+ and Ling: the share's
# window, ``ExpertShareMoE._window_rows``)
LANES = {
    "kimi_decode": (288, 64, 2048, 1408),
    "kimi_prefill": (12288, 64, 2048, 1408),
    "command_a_decode": (128, 16, 4096, 4096),
    "command_a_prefill": (1024, 16, 4096, 4096),
    "ling_decode": (256, 64, 2560, 768),
    "ling_prefill": (4096, 64, 2560, 768),
}
PINNED = {          # tile rows, column block up | down
    "kimi_decode": (16, 1408, 2048),
    "kimi_prefill": (256, 1408, 2048),
    "command_a_decode": (16, 1024, 1024),
    "command_a_prefill": (64, 1024, 1024),
    "ling_decode": (16, 768, 2560),
    "ling_prefill": (64, 768, 2560),
}


@pytest.mark.parametrize("lane", LANES)
def test_tile_rule_at_the_cells_lanes(lane):
    rows, groups, hidden, width = LANES[lane]
    assert (grouped_tile_rows(rows, groups),
            grouped_block_cols(hidden, width),
            grouped_block_cols(width, hidden)) == PINNED[lane]


def test_refuses_operands_that_do_not_fit_the_layout():
    lay = grouped_layout(jnp.asarray([3, 4]), rows=8, tile=8)
    x = jnp.zeros((lay.rows, 16), jnp.bfloat16)
    w = jnp.zeros((2, 16, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="layer="):
        grouped_matmul(x, w, lay, layer=jnp.int32(0))
    with pytest.raises(ValueError, match="layout"):
        grouped_matmul(x[:8], w, lay)
    with pytest.raises(ValueError, match="block_cols"):
        grouped_matmul(x, w, lay, block_cols=96)


# -- the fused entry: an expert's gate, up, SwiGLU and down in one step ----
FUSED = {
    # Qwen3-Next's pack in small: a tile or so a group, uneven
    "a_tile_or_so_a_group": dict(
        sizes=[9, 3, 14, 6, 11, 5], rows=64, K=64, N=128, tile=16),
    # a group without a row has no tile: its matrices are NaN here and
    # the result is finite
    "empty_groups_never_fetched": dict(
        sizes=[0, 9, 0, 0, 17, 0], rows=32, K=64, N=128, tile=8,
        poison=[0, 2, 3, 5]),
    # every group wastes tile - 1 rows and the live total is the rows:
    # every tile of the static bound is a step
    "full_step_list": dict(
        sizes=[9, 9, 9, 9], rows=36, K=64, N=128, tile=8, full=True),
    "an_expert_over_several_tiles": dict(
        sizes=[3, 37, 0, 8], rows=56, K=64, N=128, tile=8),
    "zero_live_rows": dict(
        sizes=[0, 0, 0, 0], rows=16, K=64, N=128, tile=8),
    # the layer scan's stacked leaves at a traced layer, inside a scan
    "stacked_leaves_traced_layer_in_a_scan": dict(
        sizes=[7, 0, 12, 5], rows=24, K=64, N=128, tile=8, layers=3),
    # bf16 operands: h is rounded to bf16 where the split path's second
    # call rounds it, so the two agree to the BIT
    "h_rounded_to_the_compute_dtype": dict(
        sizes=[5, 11, 8], rows=24, K=128, N=256, tile=16,
        dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", FUSED.values(), ids=FUSED.keys())
def test_fused_swiglu_equals_three_calls_and_the_group_loop(case):
    sizes = np.asarray(case["sizes"], np.int32)
    rows, K, N, tile = (case[n] for n in ("rows", "K", "N", "tile"))
    layers, dt = case.get("layers"), case.get("dtype", jnp.float32)
    groups, live, tokens = len(sizes), int(sizes.sum()), 13
    ks = jax.random.split(jax.random.key(rows + groups), 6)
    x = jax.random.normal(ks[0], (rows, K)).astype(dt)
    wg, wi = ((jax.random.normal(k, (layers or 1, groups, K, N)) * 0.1)
              .astype(dt) for k in ks[1:3])
    wo = (jax.random.normal(ks[3], (layers or 1, groups, N, K)) * 0.1) \
        .astype(dt)
    for g in case.get("poison", ()):
        wg, wi, wo = (w.at[:, g].set(jnp.nan) for w in (wg, wi, wo))
    token = jax.random.randint(ks[4], (rows,), 0, tokens)
    weight = jax.random.uniform(ks[5], (rows,), jnp.float32, 0.1, 1.0)

    def one(ws, layer, x, sizes):
        lay = grouped_layout(sizes, rows=rows, tile=tile)
        xl = jnp.take(x, lay.src, axis=0)
        kw = dict(layer=layer)
        # every laid-out row a token of its own at weight 1: the rows'
        # results as they are (0 + 1 * y is y to the bit)
        fused = grouped_swiglu(xl, *ws, lay, grouped_combine(
            lay, sizes, jnp.arange(lay.rows), jnp.ones((lay.rows,))),
            tokens=lay.rows, **kw)
        summed = grouped_swiglu(xl, *ws, lay, grouped_combine(
            lay, sizes, jnp.take(token, lay.src),
            jnp.take(weight, lay.src)), tokens=tokens, **kw)
        h = grouped_matmul(xl, ws[1], lay, **kw, out_dtype=dt,
                           gate=grouped_matmul(xl, ws[0], lay, **kw))
        three = grouped_matmul(h, ws[2], lay, **kw)
        return (jnp.take(fused, lay.dst, axis=0),
                jnp.take(three, lay.dst, axis=0), summed, lay.n_tiles)

    @jax.jit
    def run(ws, x, sizes):
        if not layers:
            return one([w[0] for w in ws], None, x, sizes)
        # every layer of the stack, the layer a traced scan index
        return jax.lax.scan(
            lambda c, layer: (c, one(ws, layer, x, sizes)), 0,
            jnp.arange(layers, dtype=jnp.int32))[1]

    got = run((wg, wi, wo), x, jnp.asarray(sizes))
    for layer in range(layers or 1):
        fused, three, summed, n_tiles = (
            np.asarray(a[layer] if layers else a, np.float32) for a in got)
        fused, three = fused[:live], three[:live]
        assert np.isfinite(fused).all() and np.isfinite(summed).all()
        # the same roundings in the same places: the three-call path's
        # to float32 rounding — to the bit where the products are bf16's
        if dt == jnp.bfloat16:
            assert (fused == three).all()
        np.testing.assert_allclose(fused, three, rtol=1e-5, atol=1e-5)
        w = [np.asarray(m[layer]) for m in (wg, wi, wo)]
        h = (jax.nn.silu(grouped_matmul_reference(x, w[0], sizes))
             * grouped_matmul_reference(x, w[1], sizes)).astype(dt)
        loop = np.asarray(grouped_matmul_reference(h, w[2], sizes))[:live]
        np.testing.assert_allclose(fused, loop, rtol=2e-5, atol=2e-5)
        # the combine form: each live row weighted into its token's row
        want = np.zeros((tokens, K), np.float32)
        np.add.at(want, np.asarray(token)[:live],
                  np.asarray(weight)[:live, None] * three)
        np.testing.assert_allclose(summed, want, rtol=1e-5, atol=1e-5)
        assert int(n_tiles) * tile == grouped_rows_computed(sizes, tile)
        if case.get("full"):
            assert int(n_tiles) * tile == grouped_padded_rows(
                rows, groups, tile)
    if dt == jnp.bfloat16:
        # ... and an h left in float32 is another result
        h32 = jax.nn.silu(grouped_matmul_reference(x, wg[0], sizes)) \
            * grouped_matmul_reference(x, wi[0], sizes)
        other = jnp.matmul(h32[:5], wo[0, 0].astype(jnp.float32))
        assert float(jnp.abs(other - fused[:5]).max()) > 1e-4


def test_fused_swiglu_refuses_operands_that_do_not_fit():
    lay = grouped_layout(jnp.asarray([3, 4]), rows=8, tile=8)
    x = jnp.zeros((lay.rows, 16), jnp.bfloat16)
    wg = jnp.zeros((2, 16, 256), jnp.bfloat16)
    wo = jnp.zeros((2, 256, 16), jnp.bfloat16)
    cmb = grouped_combine(lay, jnp.asarray([3, 4]), jnp.arange(lay.rows),
                          jnp.ones((lay.rows,)))
    with pytest.raises(ValueError, match="layer="):
        grouped_swiglu(x, wg, wg, wo, lay, cmb, tokens=8,
                       layer=jnp.int32(0))
    with pytest.raises(ValueError, match="layout"):
        grouped_swiglu(x[:8], wg, wg, wo, lay, cmb, tokens=8)
    with pytest.raises(ValueError, match="gate, up and down"):
        grouped_swiglu(x, wg, wg, wg, lay, cmb, tokens=8)
    with pytest.raises(ValueError, match="combine tables"):
        grouped_swiglu(x, wg, wg, wo, lay, cmb._replace(
            token=cmb.token[:8]), tokens=8)
    # Command A+'s experts: 201 MB double buffered
    big = jax.ShapeDtypeStruct((2, 4096, 4096), jnp.bfloat16)
    with pytest.raises(ValueError, match="three grouped_matmul calls"):
        jax.eval_shape(
            lambda x, w: grouped_swiglu(x, w, w, w, lay, cmb, tokens=8),
            jax.ShapeDtypeStruct((lay.rows, 4096), jnp.bfloat16), big)
    assert not grouped_swiglu_fits(4096, 4096, 8)
    # a result of more tokens than the kernel's limit holds beside them
    assert grouped_swiglu_fits(2048, 512, 2048)
    assert not grouped_swiglu_fits(2048, 512, 8192)


# features, hidden, experts, held, k, tokens a call: the five expert
# cells' pack and decode (SDAR: block) lanes, and what the shape rule
# makes of each: the form and the tile the call really uses
FORM_LANES = {
    "qwen3next_pack": ((2048, 512, 512, 64, 10, 2048), ("fused", 64)),
    "qwen3next_decode": ((2048, 512, 512, 64, 10, 18), ("fused", 16)),
    "sdar_pack": ((2048, 768, 128, 16, 8, 512), ("fused", 64)),
    "sdar_block": ((2048, 768, 128, 16, 8, 256), ("fused", 32)),
    "ling_pack": ((2560, 768, 512, 64, 8, 2048), ("fused", 64)),
    "ling_decode": ((2560, 768, 512, 64, 8, 72), ("fused", 16)),
    "kimi_pack": ((2048, 1408, 64, 64, 6, 2048), ("fused", 256)),
    "kimi_decode": ((2048, 1408, 64, 64, 6, 48), ("fused", 16)),
    "command_a_pack": ((4096, 4096, 128, 16, 8, 512), ("split", 64)),
    "command_a_decode": ((4096, 4096, 128, 16, 8, 48), ("split", 16)),
}


@pytest.mark.parametrize("lane", FORM_LANES)
def test_shape_rule_picks_the_form_and_its_tile_at_the_cells_lanes(lane):
    from hetu_tpu.core.dtypes import Policy, autocast
    from hetu_tpu.nn.moe import ExpertShareMoE
    (features, hidden, experts, held, k, tokens), want = FORM_LANES[lane]
    moe = ExpertShareMoE(features, hidden, experts, k=k,
                         local_experts=(0, held))
    with autocast(Policy(param_dtype=jnp.bfloat16,
                         compute_dtype=jnp.bfloat16)):
        assert (moe.grouped_form(tokens * k),
                moe.tile_rows(tokens * k)) == want


def test_host_counters_read_the_form_the_traced_lane_chose():
    """The lane is traced under its block's compute dtype (bf16: Kimi's
    experts and a 2,048-token result fit a step); the host thread that
    counts runs under the default float32 policy, where they would not
    — ``count_share`` reads the trace's choice, it does not make it
    again."""
    from hetu_tpu import telemetry
    from hetu_tpu.core.dtypes import Policy, autocast
    from hetu_tpu.nn.moe import ExpertShareMoE
    moe = ExpertShareMoE(2048, 1408, 64, k=6)
    pairs = 2048 * 6
    assert moe.grouped_form(pairs) == "split"        # float32, out here
    params = jax.eval_shape(
        lambda k: moe.init(k, dtype=jnp.bfloat16), jax.random.key(0))

    def traced(p, x):
        with autocast(Policy(param_dtype=jnp.bfloat16,
                             compute_dtype=jnp.bfloat16)):
            return moe(p, x)

    jax.eval_shape(traced, params,
                   jax.ShapeDtypeStruct((2048, 2048), jnp.float32))
    assert moe._lanes[pairs] == ("fused", 256)
    telemetry.enable(True)
    try:
        forms = telemetry.get_registry().counter(
            "moe_grouped_form_calls_total")
        before = {f: forms.value(form=f) for f in ("fused", "split")}
        moe.count_share(np.full((3, 64), 192), tokens=2048)
        after = {f: forms.value(form=f) for f in ("fused", "split")}
    finally:
        telemetry.enable(False)
    assert after["fused"] - before["fused"] == 3
    assert after["split"] == before["split"]
