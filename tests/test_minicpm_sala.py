"""MiniCPM-SALA on the CPU at a tiny size (ISSUE 39): the model against
the plain reference; chunked prefill then decode through the arena and
the slot states against the reference's ONE forward pass (logits); the
chunked lightning form against the recurrence; the selection against a
direct argsort; the refusals, by name; the paged call on a table that
selects ALL pages (the other cells' path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from served import (
    PAGE_MOVERS, REFUSED, ServedArchContract, serve_logits,
)
from benchmark.reference import minicpm_sala as reference
from hetu_tpu.models import generation
from hetu_tpu.models.minicpm_sala import (
    MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
)
from hetu_tpu.ops import linear_attention as la
from hetu_tpu.ops import sparse_select as ss


def ref_config(cfg: MiniCPMSALAConfig) -> dict:
    """``cfg`` under the published keys the reference reads."""
    return dict(
        mixer_types=list(cfg.mixer_types),
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, lightning_nh=cfg.lightning_nh,
        lightning_head_dim=cfg.lightning_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        scale_emb=cfg.scale_emb, scale_depth=cfg.scale_depth,
        dim_model_base=cfg.dim_model_base, hidden_size=cfg.hidden_size,
        published={"num_hidden_layers": cfg.published_depth},
        assumed=dict(kernel_size=cfg.kernel_size,
                     kernel_stride=cfg.kernel_stride,
                     block_size=cfg.block_size, topk=cfg.topk,
                     init_blocks=cfg.init_blocks,
                     window_size=cfg.window_size))


@pytest.fixture(scope="module")
def tiny():
    model = MiniCPMSALAForCausalLM(MiniCPMSALAConfig.tiny())
    return ref_config(model.cfg), model, model.init(jax.random.key(39))


def test_runs_and_caches_count_their_own_layers(tiny):
    _, model, params = tiny
    assert list(zip(model.blocks.run_kinds,
                    [r.num_layers for r in model.blocks.runs])) == [
        ("minicpm4", 1), ("lightning-attn", 2),
        ("minicpm4", 1), ("lightning-attn", 1)]
    assert model.blocks.layers_of == {"minicpm4": 2, "lightning-attn": 3}
    k, v, c, s = generation.init_paged_caches(model, 9, 4, jnp.float32,
                                              slots=3)
    # a page holds one kv head (head-minor in its block); 4 stride
    # means a page of 4 tokens at stride 1; a state a slot and layer
    assert k.shape == v.shape == (2, 9, 2 * 4, 16)
    assert c.shape == (2, 9, 4 * 2 * 16)
    assert s.shape == (3, 3, 4, 16, 16) and s.dtype == jnp.float32
    got = model.blocks.cache_bytes(2)
    assert got["row"] == {"k": 2 * 64, "v": 2 * 64, "compressed_k": 2 * 64}
    assert got["state"] == {"slot": 3 * 4 * 16 * 16 * 4}


class TestMiniCPMSALA(ServedArchContract):
    reference = reference
    tol = 2e-5
    token_tol = 1e-4
    requests = (39, 1, 128, ((0, 31, 5), (1, 22, 7), (0, 27, 6)))
    lanes = [dict(attn_kernel="auto"), dict(attn_kernel="paged")]
    refused = REFUSED
    page_movers = tuple(PAGE_MOVERS)
    refuses_the_dense_cache = True
    new_modules = ("hetu_tpu.models.minicpm_sala",
                   "hetu_tpu.ops.sparse_select",
                   "hetu_tpu.ops.linear_attention")

    def test_model_matches_the_reference(self, tiny):
        """... where selection DROPS blocks."""
        config, model, params = tiny
        ids = jax.random.randint(jax.random.key(1), (2, 45), 1, 128)
        got = model(params, ids)
        for b in range(2):
            want = reference.logits(params, ids[b], config, q_block=16)
            np.testing.assert_allclose(got[b], want, atol=5e-6)
        # 12 blocks of 4 at the last position, 4 chosen: blocks ARE
        # dropped, and the controls the benchmark's limits must refuse
        # move the logits
        _, margin = reference.hidden_states(params, ids[0], config,
                                            with_margins=True)
        assert np.isinf(np.asarray(margin[:16])).all()      # <= 4 visible
        assert np.isfinite(np.asarray(margin[16:])).all()
        base = reference.logits(params, ids[0], config)
        for control in ({"forced_only": True}, {"no_decay": True},
                        {"operands": jnp.float8_e4m3fn}):
            moved = reference.logits(params, ids[0], config, **control)
            assert float(jnp.abs(moved - base).max()) > 0.02, control

    @pytest.mark.parametrize("attn_kernel,looped,tile_rows", [
        ("reference", False, None), ("paged", False, None),
        ("reference", True, None), ("paged", False, 4), ("paged", True, 8)])
    def test_chunked_prefill_then_decode_equals_one_forward_pass(
            self, tiny, attn_kernel, looped, tile_rows, monkeypatch):
        """Chunks that cut strides and pages. ``looped``: at sizes
        under a pack's, so that the read goes call by call over padded
        rows and the scores and the scan block by block, as they do at
        the served size. ``tile_rows``: the pack carries the engine's
        tile map and reads its forced blocks a tile at a time, its free
        choices a token at a time (two runs a pack, a run that starts
        mid-cell, cells of one block and of two)."""
        if looped:
            from hetu_tpu.nn.parallel import (
                BlockSparseAttention, LightningAttention,
            )
            monkeypatch.setattr(BlockSparseAttention, "ROWS_PER_CALL", 8)
            monkeypatch.setattr(BlockSparseAttention, "SELECT_ROWS", 4)
            monkeypatch.setattr(LightningAttention, "SCAN_BLOCK", 4)
        _, model, params = tiny
        reqs = self.draw_requests()
        got = serve_logits(
            model, params, reqs, chunk=10, attn_kernel=attn_kernel,
            tile_rows=tile_rows, traced=("looped",) * looped, **self.serve)
        for i, (_, ids, _) in enumerate(reqs):
            self.close(got[i], self.ref_logits(tiny, jnp.asarray(ids)),
                       self.tol)

    def engine_served(self, eng, model, lanes, counted):
        """``paged``: the kernel path (interpret mode here) — the engine
        cuts every run of a pack into tiles for the block-sparse band (a
        pack holds the end of one prompt and the start of the next)."""
        from hetu_tpu import telemetry
        paged = lanes["attn_kernel"] == "paged"
        assert eng.prefix_cache is None and eng.preempt is False
        assert eng.prefill_attn == "flash"        # the pack as one row
        assert eng.attn_kernel == ("paged" if paged else "reference")
        # a tile map of every run, sized for pages of ONE kv head
        assert eng._hist_tiles == (1 + 3 - 1 if paged else 0)
        if telemetry.enabled():
            c = telemetry.get_registry().counter(
                "serving_sparse_pages_total")
            assert c.value(state="visible", lane="decode") >= \
                c.value(state="chosen", lane="decode") > 0

    def test_dense_cache_and_cp_prefill_refuse_by_name(self, tiny):
        super().test_dense_cache_and_cp_prefill_refuse_by_name(tiny)
        with pytest.raises(ValueError, match="block_size"):
            tiny[1].blocks.init_paged_caches(9, 8, jnp.float32, 2)


@pytest.fixture(scope="module")
def split_reads():
    """One 41-token prompt under top-6 (3 forced blocks of 4, up to 3
    free), prefilled in packs of 16: the logits of the split read (the
    band a tile of 8 at a time + the free lanes, joined), of the
    64-lane-style read a token at a time (no tile map: the parent's),
    and of the whole-sequence forward."""
    cfg = MiniCPMSALAConfig.tiny(topk=6)
    model = MiniCPMSALAForCausalLM(cfg)
    params = model.init(jax.random.key(52))
    ids = np.random.default_rng(52).integers(1, 128, 41)
    kw = dict(slots=1, chunk=16, block_size=4, n_blocks=16, max_len=48,
              attn_kernel="paged")
    return (serve_logits(model, params, [(0, ids, 1)], tile_rows=8,
                         **kw)[0],
            serve_logits(model, params, [(0, ids, 1)], **kw)[0],
            np.asarray(model(params, jnp.asarray(ids)[None])[0]))


@pytest.mark.parametrize("where,rows", [
    ("no_free_lane", slice(0, 12)), ("fewer_free_than_lanes", slice(12, 20)),
    ("every_free_lane", slice(20, 24)), ("past_topk_blocks", slice(24, 41))])
def test_split_read_is_the_per_token_read_and_the_forward(
        split_reads, where, rows):
    """Below position 12 every visible block is forced (the free part
    is the empty one), to 20 a token has 1 or 2 of its 3 free lanes, to
    24 all three and sees every block, past it the selection drops
    blocks: the band read a tile at a time joined with the free lanes
    is the read of the whole choice a token at a time, and the dense
    rule's."""
    split, per_token, forward = (x[rows] for x in split_reads)
    np.testing.assert_allclose(split, per_token, atol=2e-5)
    np.testing.assert_allclose(split, forward, atol=2e-5)


def test_chunk_scan_equals_the_recurrence_for_ragged_runs():
    """A chunk that does not divide the sequence; two slots' runs in one
    pack; a state carried in; a slot that starts at 0 over a stale
    state."""
    H, d, S = 3, 8, 4
    key = jax.random.split(jax.random.key(3), 8)
    slopes = la.decay_slopes(H)
    T0, T1 = 23, 9
    q, k, v = (jax.random.normal(kk, (T0 + T1, H, d)) for kk in key[:3])
    # slot 2 continues from position 11 with a state; slot 0 starts
    # at 0 and must ignore what its state held
    hist = 11
    qa, ka, va = (jax.random.normal(kk, (hist, H, d)) for kk in key[3:6])
    _, carried = la.linear_recurrence(qa, ka, va, slopes, scale=0.5)
    state = jnp.zeros((S, H, d, d)).at[2].set(carried) \
        .at[0].set(jax.random.normal(key[6], (H, d, d)))
    slot = jnp.concatenate([jnp.full(T0, 2), jnp.full(T1, 0)])
    pos = jnp.concatenate([hist + jnp.arange(T0), jnp.arange(T1)])
    valid = jnp.ones(T0 + T1, bool).at[-2:].set(False)      # pad lanes
    o, new = la.linear_scan(q, k, v, state, slot, pos, valid, slopes,
                            scale=0.5, block=5)
    w0, s2 = la.linear_recurrence(q[:T0], k[:T0], v[:T0], slopes,
                                  scale=0.5, state=carried)
    w1, s0 = la.linear_recurrence(q[T0:-2], k[T0:-2], v[T0:-2], slopes,
                                  scale=0.5)
    np.testing.assert_allclose(o[:T0], w0, atol=2e-5)
    np.testing.assert_allclose(o[T0:-2], w1, atol=2e-5)
    np.testing.assert_allclose(new[2], s2, atol=2e-5)
    np.testing.assert_allclose(new[0], s0, atol=2e-5)
    assert (np.asarray(new[1]) == 0).all() and (np.asarray(new[3]) == 0).all()
    # the decode rows' one-token update is the recurrence's step
    live = jnp.array([True, False, True, False])
    o1, st1 = la.linear_update(q[:S], k[:S], v[:S], new, live, slopes,
                               scale=0.5)
    w, s = la.linear_recurrence(q[2:3], k[2:3], v[2:3], slopes, scale=0.5,
                                state=new[2])
    np.testing.assert_allclose(o1[2], w[0], atol=2e-5)
    np.testing.assert_allclose(st1[2], s, atol=2e-5)
    np.testing.assert_array_equal(st1[1], new[1])          # not live


@pytest.mark.parametrize("topk", [4, 7, 64])
def test_selection_against_a_direct_argsort_ties_included(topk):
    """Scores drawn from a few values so that ties are the rule: the
    lowest index wins among equals, on both sides."""
    rng = np.random.default_rng(topk)
    B, st, ks, N, hkv, W = 4, 2, 4, 37, 2, 16
    J = W * (B // st)
    s = jnp.asarray(rng.integers(0, 5, (N, hkv, J)) / 4.0, jnp.float32)
    pos = jnp.asarray(rng.integers(0, W * B, N), jnp.int32)
    ids, n = ss.choose_blocks(s, pos, block_size=B, stride=st, kernel=ks,
                              topk=topk, init_blocks=1, window_blocks=1)
    s_np = np.asarray(s)
    for r in range(N):
        own = int(pos[r]) // B
        for g in range(hkv):
            score = np.full(W, -np.inf)
            for b in range(own + 1):
                over = [j for j in range(J)
                        if st * j < B * (b + 1) and st * j + ks > B * b]
                score[b] = max(s_np[r, g, j] for j in over)
                if b < 1 or own - 1 <= b <= own:
                    score[b] = np.inf
            want = np.argsort(-score, kind="stable")[:topk]
            want = sorted(int(b) for b in want if score[b] > -np.inf)
            got = [int(b) for b in np.asarray(ids[r, g]) if b < W]
            assert got == want, (r, g)
            assert int(n[r]) == len(want) == min(topk, own + 1)
            assert (np.asarray(ids[r, g])[len(want):] == W).all()


def test_window_scores_sum_to_the_group_size_over_visible_windows():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(5, 2, 3, 8)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(12, 2, 8)), jnp.float32)
    kbar = ss.compressed_keys(cm, 2)
    np.testing.assert_allclose(kbar[3], (cm[3] + cm[4]) / 2, atol=1e-6)
    pos = jnp.asarray([0, 1, 2, 7, 11], jnp.int32)
    s = ss.window_scores(q, kbar, pos, stride=1, kernel=2, scale=0.3)
    # window j is visible once j + 2 <= t + 1: none at t = 0
    assert float(jnp.abs(s[0]).max()) == 0.0
    for r, t in enumerate([1, 2, 7, 11]):
        np.testing.assert_allclose(s[r + 1].sum(-1), 3.0, atol=1e-5)
        assert float(jnp.abs(s[r + 1][:, t:]).max()) == 0.0


@pytest.mark.parametrize("d,bs,g", [(16, 4, 2), (128, 16, 4), (64, 8, 1)])
def test_paged_call_on_a_table_that_selects_all_pages(d, bs, g):
    """The sparse read IS the other cells' decode call: with every
    visible page chosen (ascending, the slot's own table) the virtual
    table's result equals the call on the slot's table and position —
    kernel and gather reference alike."""
    from hetu_tpu.ops.paged_pallas import (
        paged_attention_pallas, paged_attention_reference,
    )
    rng = np.random.default_rng(d)
    S, W, n_blk = 3, 6, 20
    k = jnp.asarray(rng.normal(size=(2, n_blk, bs, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, n_blk, bs, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, 1, g, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_blk))[:S * W]
                         .reshape(S, W), jnp.int32)
    pos = jnp.asarray([bs * W - 1, 2 * bs + 1, 0], jnp.int32)
    want = paged_attention_reference(q, k[1], v[1], tables, pos)
    # every visible block "chosen": ids ascending, W past the last
    own = pos // bs
    ids = jnp.where(jnp.arange(W)[None, :] <= own[:, None],
                    jnp.arange(W)[None, :], W)[:, None, :]
    vt, voff = ss.virtual_tables(ids, own + 1, tables, pos, block_size=bs)
    np.testing.assert_array_equal(voff, pos)
    got_ref = paged_attention_reference(q, k[1], v[1], vt, voff)
    got = paged_attention_pallas(q, k, v, vt, voff, layer=1,
                                 live=jnp.ones(S, bool), pages_per_step=4)
    np.testing.assert_allclose(got_ref, want, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_other_models_keep_their_defaults_and_their_programs():
    """``prefix_cache`` / ``preempt`` left at None are ON for a model
    without slot state, and its pack carries no slot operand."""
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.serving import ServingEngine
    model = GPTLMHeadModel(GPTConfig.tiny())
    eng = ServingEngine(model, model.init(jax.random.key(0)), max_len=32,
                        prefill_chunk=8, block_size=4, slots=2)
    assert eng.prefix_cache is not None and eng.preempt is True
    assert eng._slot_state is False and eng.pool.quantized is False
    assert len(eng.pool.caches) == 2
    # its tile map is of the runs WITH history, as it was (the tiled
    # call's program without the band is pinned beside the decode
    # call's: tests/test_kernel_plane.py, PARENT_TILED_JAXPR)
    assert eng._every_run is False
    assert type(model.blocks.block.attn).history_tiles is True


#: what ``ServingEngine._size_history_tiles`` gave the other serving
#: cells at commit 428bd76 (PR 51), the parent of the PR that made the
#: block-sparse class ask for a tile map: heads, kv heads, head dim,
#: latent, a page's width and dtype, block size, chunk, runs a pack,
#: table width -> tile rows, tiles, a table's steps, a step's keys
PARENT_TILE_SIZES = {
    "gpt2-small": ((12, 12, 64, False, 768, jnp.float32, 16, 256, 148,
                    65), (128, 149, 9, 128)),
    "gpt2-large": ((20, 20, 64, False, 1280, jnp.float32, 16, 256, 32,
                    65), (128, 33, 9, 128)),
    "command-a-plus-ep8": ((128, 8, 128, False, 1024, jnp.bfloat16, 64,
                            512, 48, 129), (16, 79, 33, 256)),
    "kimi-vl-a3b-pp4": ((16, 1, 640, True, 640, jnp.bfloat16, 64, 2048,
                         48, 257), (32, 111, 33, 512)),
    "ling-3.0-flash-vl-ep8": ((32, 1, 640, True, 640, jnp.bfloat16, 64,
                               2048, 72, 133), (16, 199, 17, 512)),
    # (this class: a page holds ONE of its 2 kv heads — 16 tokens x 16
    # group members a tile, 128 cells + 19 more runs, 8 pages a step)
    "minicpm-sala-pp2": ((32, 2, 128, False, 128, jnp.bfloat16, 64,
                          2048, 20, 520), (16, 147, 65, 512)),
}


@pytest.mark.parametrize("name", list(PARENT_TILE_SIZES))
def test_tile_map_is_sized_from_the_pages_shapes(name):
    """The engine sizes the prefill lane's tile map from what the paged
    call sees — the group, the head's width and the kv heads a PAGE
    holds — with no test of the model: the other cells' tile rows,
    tile counts and steps are the parent's to the number."""
    import types
    from hetu_tpu.serving import ServingEngine
    (h, hkv, d, latent, minor, dtype, bs, chunk, runs, W), want = \
        PARENT_TILE_SIZES[name]
    eng = types.SimpleNamespace(
        pool=types.SimpleNamespace(block_size=bs, caches=[
            jax.ShapeDtypeStruct((2, 9, bs, minor), dtype)]),
        prefill_chunk=chunk, _fin_cap=runs, attn_kernel="paged")
    attn = types.SimpleNamespace(
        num_heads=h, num_kv_heads=hkv, head_dim=d, latent=latent,
        **({"history_tiles": "every_run", "BAND_ROWS": 256}
           if name.startswith("minicpm") else {"history_tiles": True}))
    ServingEngine._size_history_tiles(eng, attn, W, "flash")
    assert (eng._hist_tile, eng._hist_tiles, eng._hist_steps,
            eng._hist_span) == want
    assert eng._every_run == name.startswith("minicpm")
    # the decode rows' chunk (PR 60): 3 pages of 16 keys as the parent
    # cut it, 8 pages of 64 = 512 keys
    assert eng._chunk_span == (48 if bs == 16 else 512)


def test_band_counter_is_the_forced_pages_of_the_packs_rows(tiny):
    """``serving_sparse_pages_total{state="band", lane="prefill"}``:
    the chosen pages of the pack's live rows that a TILE read — by
    hand, a token at ``t`` has ``min(t // 4 + 1, 3)`` forced blocks of
    its ``min(t // 4 + 1, 4)`` chosen and ``t // 4 + 1`` visible, a kv
    head and sparse layer (2 x 2). Without the tile map (the gather
    path) nothing is a tile's and ``chosen`` / ``visible`` read the
    same."""
    from hetu_tpu import telemetry
    from hetu_tpu.serving import SamplingParams, ServingEngine
    _, model, params = tiny
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 128, n).tolist() for n in (21, 13, 30)]
    blocks = np.concatenate([np.arange(len(p)) // 4 + 1 for p in prompts])
    was = telemetry.enabled()
    try:
        for kernel, band in (("paged", np.minimum(blocks, 3).sum() * 4),
                             ("reference", 0)):
            telemetry.reset()
            telemetry.enable(True)
            eng = ServingEngine(model, params, max_len=64, prefill_chunk=8,
                                block_size=4, slots=3, kv_blocks=40,
                                seed=0, attn_kernel=kernel)
            eng.generate_many(prompts, SamplingParams(max_tokens=2))
            c = telemetry.get_registry().counter(
                "serving_sparse_pages_total")
            assert c.value(state="band", lane="prefill") == band
            assert c.value(state="chosen", lane="prefill") \
                == np.minimum(blocks, 4).sum() * 4
            assert c.value(state="visible", lane="prefill") \
                == blocks.sum() * 4
            assert c.value(state="band", lane="decode") == 0
            assert c.value(state="chosen", lane="decode") > 0
    finally:
        telemetry.reset()
        telemetry.enable(was)
