"""What the paged-attention kernel tests share (a plain module, not
collected): the arena's page layout, the joined-tile cases' forms, a
kernel body's equations, and the prefill lane's history pack —
``tests/test_kernel_plane.py`` (the decode rows' call, the engine) and
``tests/test_history_tiles.py`` (the history read a tile at a time) both
build their cases from these."""

import jax
import jax.numpy as jnp
import numpy as np


def _pages(x):
    """(n_blocks, bs, hkv, w) → the arena's stored (n_blocks, bs, hkv*w)."""
    return x.reshape(x.shape[:2] + (-1,))


GARBAGE = 3e4       # large and finite: what a reused page may hold

TILE_FORMS = {"g1": dict(hq=2, hkv=2, d=16),
              "g4": dict(hq=8, hkv=2, d=16),
              "latent": dict(hq=4, hkv=1, d=32, v_width=24),
              "int8": dict(hq=4, hkv=2, d=16, quant=True)}


def _kernel_eqns(jaxpr):
    """Every equation of the paged call's kernel body, conditionals
    looked into."""
    def walk(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)
    call, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    return list(walk(call.params["jaxpr"]))


TQ = 4          # tile size of the cases below (a run of TQ is one tile)


def _history_pack(rng, runs, *, C=16, hq=4, hkv=2, d=16, bs=4, W=8,
                  quant=False, v_width=None, garbage=False, window=None):
    """A pack of ``runs`` over an arena whose history rows are random:
    the operands of the per-token formulation and of the tiles.
    ``v_width``: a latent arena (no value leaf). ``garbage``: whatever
    no row of a run may look at is ±GARBAGE — the null block, a
    request's positions from its history's end up and, under a
    ``window``, those below its FIRST token's."""
    from hetu_tpu.ops.quantization import quantize_int8
    n_blocks = 1 + len(runs) * W
    k, v = (rng.normal(size=(n_blocks, bs, hkv, d)).astype(np.float32)
            for _ in range(2))
    tbl = 1 + np.arange(len(runs) * W, dtype=np.int32).reshape(-1, W)
    if garbage:
        seen = np.zeros((n_blocks * bs,), bool)
        for s, (_, h) in enumerate(runs):
            first = 0 if window is None else max(h - int(window) + 1, 0)
            seen[(1 + s * W) * bs + first:(1 + s * W) * bs + h] = True
        for x in (k, v):
            flat = x.reshape(-1, hkv, d)
            flat[~seen] = GARBAGE * rng.choice(
                [-1.0, 1.0], size=((~seen).sum(), hkv, d))
    slot, pos, hist = (np.zeros(C, np.int32) for _ in range(3))
    run_list, used = [], 0
    for s, (n, h) in enumerate(runs):
        slot[used:used + n], hist[used:used + n] = s, h
        pos[used:used + n] = h + np.arange(n)
        run_list.append((s, used, n, h))
        used += n
    assert used <= C
    q = jnp.asarray(rng.normal(size=(C, hq, d)), jnp.float32)
    arena = {}
    if quant:
        (k, ks), (v, vs) = (map(_pages, quantize_int8(jnp.asarray(x),
                                                      axis=-1))
                            for x in (k, v))
        arena = dict(k_scale=ks, v_scale=vs)
    elif v_width is not None:
        k, v = _pages(jnp.asarray(k)), None
        arena = dict(v_width=v_width, scale=0.2)
    else:
        k, v = _pages(jnp.asarray(k)), _pages(jnp.asarray(v))
    return (q, k, v, jnp.asarray(tbl), jnp.asarray(slot),
            jnp.asarray(pos), jnp.asarray(hist), run_list, arena)
