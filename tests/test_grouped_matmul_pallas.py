"""The serving expert layer's grouped matmul (``ops/grouped_matmul_pallas``)
in interpret mode on the CPU: against ``jax.lax.ragged_dot`` and against a
per-group ``jnp.matmul`` loop, at the three expert cells' shapes cut to CPU
size with their awkward parts kept, and at the layout's edges. The tile
rule's values at the cells' two lanes are pinned, as ``history_tile_*``'s
are."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.grouped_matmul_pallas import (
    grouped_block_cols, grouped_layout, grouped_matmul,
    grouped_matmul_reference, grouped_padded_rows, grouped_rows_computed,
    grouped_tile_rows,
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
