"""What the one layer stack (``nn.parallel.LayerStack``), the one
pre-norm block and the one decoder shell must not move: the drawn
models' parameter trees IN ORDER (``Module.init`` splits the seed's key
over the flattened specs, so the order of attribute assignment decides
every weight a benchmark cell draws), their cache leaves, and a stack
built from a list no model file has.

The constants were taken at the parent of PR 44 (commit d648f3d, the
hand-written containers), before any edit.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.models import generation
from hetu_tpu.nn.module import _flatten_specs


def _model(name):
    import importlib
    mod = importlib.import_module(f"hetu_tpu.models.{name}")
    cfg = next(v for k, v in vars(mod).items() if k.endswith("Config"))
    lm = next(v for k, v in vars(mod).items() if k.endswith("ForCausalLM"))
    return lm(cfg.tiny())


#: model -> (sha256 of "path shape dtype" lines of the flattened specs
#: in order, how many, sum of the float64 sums of init(key(0))'s leaves)
PINS = {
    "cohere2_moe": (
        "cff83ff547283739666d066b9abe477e04ad621abe561295b0acad1caa5af36c",
        14, 590.8601716381272),
    "mla_moe": (
        "c78646f6b1788aaf63c03c0addc7679f665fce556702c3c4baf6c90bd86817ea",
        28, 696.6424668951086),
    "minicpm_sala": (
        "24263519b4585fc16449cb101a122324891103ff039434503b23a323dfb8e3a9",
        53, 1221.973154932847),
    "kda_mla_moe": (
        "752f0bc22659926368f7885130740ce4d963488feeff975c71ed1ca5559e9cb0",
        104, 592.5965917261366),
}


@pytest.mark.parametrize("name", list(PINS))
def test_parameter_paths_order_and_seed_0_weights_are_the_parents(name):
    sha, n, total = PINS[name]
    model = _model(name)
    flat = _flatten_specs(model.abstract_specs())
    text = "".join(f"{p} {tuple(s.shape)} {s.dtype}\n"
                   for p, s in flat.items())
    assert len(flat) == n
    assert hashlib.sha256(text.encode()).hexdigest() == sha, text
    assert list(model.abstract_specs())[:3] == [
        "wte", "blocks", "final_norm"]
    leaves = jax.tree.leaves(model.init(jax.random.key(0)))
    got = float(sum(np.asarray(x, np.float64).sum() for x in leaves))
    assert got == pytest.approx(total, rel=1e-9)


#: model -> (the leaves of init_paged_caches(10 blocks of 4, float32, 3
#: slots), the stack's run kinds or None where it is ONE scan)
CACHES = {
    "cohere2_moe": ([(8, 10, 4, 32)] * 2, None),
    "mla_moe": ([(4, 10, 4, 40)], ["mla"]),
    "minicpm_sala": (
        [(2, 10, 8, 16), (2, 10, 8, 16), (2, 10, 128), (3, 3, 4, 16, 16)],
        ["minicpm4", "lightning-attn", "minicpm4", "lightning-attn"]),
    "kda_mla_moe": (
        [(2, 10, 4, 40), (5, 3, 4, 16, 16), (5, 3, 3, 192)],
        ["kda", "mla", "kda", "mla", "kda"]),
}


@pytest.mark.parametrize("name", list(CACHES))
def test_cache_leaves_and_runs_are_the_parents(name):
    shapes, kinds = CACHES[name]
    model = _model(name)
    for leaves in (
            generation.init_paged_caches(model, 10, 4, jnp.float32,
                                         slots=3),
            model.blocks.init_paged_caches(10, 4, jnp.float32, 3)):
        assert [x.shape for x in leaves] == shapes
    assert model.blocks.slot_state == (len(shapes) > 2)
    if kinds is not None:
        assert model.blocks.run_kinds == kinds
        assert sum(model.blocks.layers_of.values()) \
            == model.blocks.num_layers == model.cfg.num_layers
    # what refuses nothing says so through the same call
    quiet = model.blocks.refuse_serving(prefix_cache=False)
    assert quiet is None
    if not model.blocks.slot_state:
        assert model.blocks.refuse_serving(prefix_cache=True) is None


def _new_hybrid():
    """A stack no model file has: a leading DENSE latent layer, a run
    of two lightning layers, a run of two latent EXPERT layers."""
    from hetu_tpu.nn.moe import ExpertShareMoE
    from hetu_tpu.nn.parallel import (
        LatentAttention, LayerStack, LightningAttention, ParallelMLP,
        PreNormBlock,
    )

    def make_block(kind, dense):
        attn = LatentAttention(
            32, 2, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
            max_positions=64) if kind == "latent" else \
            LightningAttention(32, 2, head_dim=8, max_positions=64)
        ffn = dict(mlp=ParallelMLP(32, 48, bias=False, gated=True)) \
            if dense or kind == "lightning" else dict(
                shared=ParallelMLP(32, 16, bias=False, gated=True),
                moe=ExpertShareMoE(32, 16, 4, k=2))
        return PreNormBlock(32, attn, eps=1e-6, **ffn)

    return LayerStack(
        ("latent", "lightning", "lightning", "latent", "latent"),
        make_block, n_dense=1)


def test_a_new_hybrid_costs_a_list_and_decodes_as_its_layers_one_by_one():
    from hetu_tpu.nn.parallel import LayerKV, SlotStateNotSupported
    stack = _new_hybrid()
    assert stack.run_kinds == ["lightning", "latent"]
    assert stack.layers_of == {"latent": 3, "lightning": 2}
    assert stack.slot_state and stack.block is stack.runs[1].block
    assert list(stack.abstract_specs()) == ["dense", "runs"]
    params = stack.init(jax.random.key(3))
    slots, bs, n_blocks = 3, 4, 7
    caches = stack.init_paged_caches(n_blocks, bs, jnp.float32, slots)
    # the paged kind's leaf first, then the per-slot kind's
    assert [c.shape for c in caches] == [
        (3, n_blocks, bs, 24), (2, slots, 2, 8, 8)]
    got = stack.cache_bytes(2)
    assert got == {"row": {"stored": 3 * 24 * 2, "needed": 3 * 24 * 2},
                   "state": {"slot": 2 * 2 * 8 * 8 * 4}}
    with pytest.raises(SlotStateNotSupported, match="prefix_cache"):
        stack.refuse_serving(prefix_cache=True, preempt=False)
    with pytest.raises(SlotStateNotSupported, match="CP-prefill"):
        stack.prefill(params, None)
    zeros = stack.layer_stats_zeros()
    assert {k: v.shape for k, v in zeros.items()} == {"moe_sizes": (2, 4)}

    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    mask = jnp.asarray([True, False, True])
    kw = dict(slot_mask=mask, block_tables=tables,
              attn_kernel="reference")
    want_caches = list(caches)
    # (the stack's call and a layer's call of each block compiled once,
    # not dispatched op by op at every step: the same arithmetic)
    decode = jax.jit(lambda p, x, c, pos: stack.decode(
        p, x, c, positions=pos, with_stats=True, **kw))
    one = {blk: jax.jit(lambda p, h, leaf, at, pos, blk=blk: blk(
        p, h, positions=pos, kv_cache=LayerKV((leaf,), at), **kw))
        for blk in (stack.dense[0], stack.runs[0].block,
                    stack.runs[1].block)}
    for step in range(3):                  # the state and the rows grow
        x = jax.random.normal(jax.random.key(10 + step), (slots, 1, 32))
        pos = jnp.full((slots, 1), step, jnp.int32)
        y, caches, stats = decode(params, x, caches, pos)
        # the layers one by one, each on its kind's leaves at ITS layer
        layers = [(stack.dense[0], params["dense"]["0"], 0, 0)] + [
            (run.block, jax.tree.map(lambda p: p[i], params["runs"][r]),
             leaf, run.first_layer + i)
            for r, run, leaf in (("0", stack.runs[0], 1),
                                 ("1", stack.runs[1], 0))
            for i in range(run.num_layers)]
        h, sizes = x, []
        for blk, p, leaf, at in layers:
            h, (want_caches[leaf],), *st = one[blk](
                p, h, want_caches[leaf], jnp.asarray(at, jnp.int32), pos)
            sizes += [s["moe_sizes"] for s in st]
        np.testing.assert_allclose(y, h, atol=1e-6)
        for a, b in zip(caches, want_caches):
            np.testing.assert_allclose(a, b, atol=1e-6)
        assert list(stats) == ["moe_sizes"]
        np.testing.assert_array_equal(stats["moe_sizes"], jnp.stack(sizes))
        assert int(stats["moe_sizes"].sum()) == 2 * slots * 2
    assert float(jnp.abs(caches[1][:, 1]).max()) == 0.0   # the dead slot
    assert float(jnp.abs(caches[1][:, 0]).min()) > 0.0
    # and without the stats: the same two results
    y2, caches2 = stack.decode(params, x, want_caches, positions=pos, **kw)
    assert y2.shape == y.shape and len(caches2) == 2
