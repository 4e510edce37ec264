"""Mosaic/XLA AOT compile checks for real TPU targets — no chip needed.

The Pallas kernels (flash attention, fused CE) normally only compile
for TPU inside a live window; everywhere else they run in interpret
mode, so a Mosaic-lowering regression (bad block shape, unsupported op,
VMEM overflow) stays invisible until scarce chip time is burned on it.
libtpu is local, so this workload AOT-compiles the REAL kernels — and
whole sharded train steps using them — for v5e topologies via
``jax.experimental.topologies`` with ``HETU_PALLAS_INTERPRET=0``:

- flash attention fwd+bwd: causal bench shape, GQA, packed segment
  ids, head_dim 128;
- fused streaming LM-head+CE fwd+bwd at the bench vocab;
- the dp2×tp2×cp2 ring-attention train step on a v5e:2x4 target
  (collectives + Pallas inside shard_map);
- the single-chip bench-winner step with per-device memory analysis
  (HBM headroom for the batch chain).

Usage: python workloads/aot_check.py [--quick]
Writes workloads/out/aot_check.json; one row per check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@contextlib.contextmanager
def _mosaic_aot_env():
    """Compile the REAL kernels from a CPU-backend process: force the
    interpret default off (restored on exit — a process-wide set would
    leak into importers, e.g. the test suite's interpret-mode kernel
    tests) and scope matmul precision to "default" (Mosaic rejects bf16
    dots under the global HIGHEST some harnesses set)."""
    prev = os.environ.get("HETU_PALLAS_INTERPRET")
    os.environ["HETU_PALLAS_INTERPRET"] = "0"
    try:
        with jax.default_matmul_precision("default"):
            yield
    finally:
        if prev is None:
            os.environ.pop("HETU_PALLAS_INTERPRET", None)
        else:
            os.environ["HETU_PALLAS_INTERPRET"] = prev


def _one_dev_mesh(devs):
    return Mesh(np.array(devs[:1]).reshape(1, 1), ("dp", "tp"))


def _sds(shape, dtype, mesh, spec=P()):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def check_flash(devs, *, shape=(4, 1024, 12, 64), kv_heads=None,
                seg=False, block_q=None, block_k=None,
                dropout_rate=0.0, lse=False, dtype=jnp.bfloat16):
    """The flash forward and both backward kernels (grad of a sum), or —
    ``lse`` — the forward that returns the LSE, as the serving prefill
    lane calls it on one packed row (``attention_with_lse``)."""
    from hetu_tpu.ops.flash_pallas import flash_attention_pallas as fa
    mesh = _one_dev_mesh(devs)
    b, s, h, d = shape
    q = _sds((b, s, h, d), dtype, mesh)
    kv = _sds((b, s, kv_heads or h, d), dtype, mesh)
    segs = _sds((b, s), jnp.int32, mesh) if seg else None
    if lse:
        from hetu_tpu.ops.attention import attention_with_lse
        return _compile_kernel(
            lambda q, k, v, *ids: attention_with_lse(
                q, k, v, causal=True, segment_ids=ids[0] if ids else None,
                impl="pallas", interpret=False),
            (q, kv, kv) + ((segs,) if seg else ()))
    # dropout: the SMEM seed operand + uint32 counter-RNG must lower in
    # Mosaic (interpret mode can never catch a Mosaic-only rejection)
    key = _sds((), jnp.uint32, mesh) if dropout_rate > 0 else None

    def loss(q, k, v, *extra):
        extra = list(extra)
        dkey = jax.random.wrap_key_data(
            jnp.broadcast_to(extra.pop().astype(jnp.uint32), (2,)),
            impl="threefry2x32") if dropout_rate > 0 else None
        out = fa(q, k, v, causal=True, interpret=False,
                 segment_ids=extra[0] if extra else None,
                 block_q=block_q, block_k=block_k,
                 dropout_rate=dropout_rate, dropout_key=dkey)
        return out.astype(jnp.float32).sum()

    args = (q, kv, kv) + ((segs,) if seg else ()) \
        + ((key,) if dropout_rate > 0 else ())
    f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    t0 = time.perf_counter()
    with _mosaic_aot_env():
        f.lower(*args).compile()
    return {"compile_s": round(time.perf_counter() - t0, 1)}


def _compile_kernel(f, args):
    """Compile ``f`` for the described target with the real Mosaic
    lowering; the kernel must be in the program."""
    t0 = time.perf_counter()
    with _mosaic_aot_env():
        hlo = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return {"compile_s": round(time.perf_counter() - t0, 1)}


def check_paged(devs, *, dtype=jnp.bfloat16, rows=1, return_lse=False,
                slots=8, n_blocks=2048, block_size=16, table_width=64,
                heads=12, kv_heads=None, head_dim=64, layers=12,
                windowed=False):
    """The paged decode kernel over an arena in its stored layout
    (``serving/kv_pool.py``: the stacked ``(layers, n_blocks,
    block_size, hkv*d)`` leaf read at a traced ``layer``, as the fused
    serving step calls it; int8 scales ``(..., hkv)``) — by default
    GPT-2 small's. ``layers=None`` is one layer's 3-D arena, the
    one-layer case of the same call. ``rows``: 1 = classic decode, k+1
    = the speculative verify lane. ``windowed``: the call with a
    per-slot window operand (a model with window layers)."""
    from hetu_tpu.ops.paged_pallas import paged_attention_pallas
    mesh = _one_dev_mesh(devs)
    hkv = kv_heads or heads
    quant = jnp.dtype(dtype) == jnp.int8
    lead = (n_blocks,) if layers is None else (layers, n_blocks)
    q = _sds((slots, rows, heads, head_dim),
             jnp.bfloat16 if quant else dtype, mesh)
    page = _sds(lead + (block_size, hkv * head_dim), dtype, mesh)
    args = [q, page, page, _sds((slots, table_width), jnp.int32, mesh),
            _sds((slots,), jnp.int32, mesh), _sds((), jnp.int32, mesh),
            _sds((slots,), jnp.int32, mesh)]
    if quant:
        args += [_sds(lead + (block_size, hkv), jnp.float32, mesh)] * 2

    def f(q, k, v, tbl, off, layer, window, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention_pallas(
            q, k, v, tbl, off, layer=None if layers is None else layer,
            k_scale=ks, v_scale=vs, interpret=False,
            return_lse=return_lse, window=window if windowed else None)

    return _compile_kernel(f, args)


def check_packed_prefill(devs, *, chunk=256, dtype=jnp.bfloat16, heads=12,
                         kv_heads=None, head_dim=64, layers=12,
                         n_blocks=2048, block_size=16, table_width=64,
                         slots=8, windowed=False, v_width=None):
    """The packed-prefill lane's attention as ``ParallelAttention.
    _decode_packed`` runs it: one ``(1, chunk)`` row of pack tokens
    through the flash forward kernel with segment ids, LSE-combined
    with each token's arena history — read once per TILE of a request's
    run (``paged_history_attention``: the paged kernel under a key cap,
    on a grid whose bounds are data) over the stacked arena at a traced
    ``layer``. Tile size, tile count and the pages of a grid step's
    key tile as the engine derives them from the head shapes and
    ``slots``. ``v_width``: a LATENT arena's history read (one key
    head ``head_dim`` wide whose first ``v_width`` columns are the
    value) — the read alone, the in-pack part is not a kernel."""
    from hetu_tpu.ops.attention import attention_with_lse
    from hetu_tpu.ops.paged_pallas import (
        TILE_FIELDS, combine_attention_lse, history_tile_count,
        history_tile_pages, history_tile_rows, paged_history_attention,
    )
    mesh = _one_dev_mesh(devs)
    hkv = kv_heads or heads
    tq = history_tile_rows(heads // hkv, head_dim, hkv, block_size)
    n_tiles = history_tile_count(chunk, tq, min(slots, chunk))
    pages = history_tile_pages(heads // hkv, head_dim, hkv, block_size,
                               tile_rows=tq, latent=v_width is not None)
    q = _sds((1, chunk, heads, head_dim), dtype, mesh)
    kv = _sds((1, chunk, hkv, head_dim), dtype, mesh)
    seg = _sds((1, chunk), jnp.int32, mesh)
    page = _sds((layers, n_blocks, block_size, hkv * head_dim),
                jnp.bfloat16, mesh)
    tiles = _sds((len(TILE_FIELDS), n_tiles), jnp.int32, mesh)

    def f(q, k, v, seg, ka, va, tbl, hist, tiles, layer, window):
        if v_width is not None:
            return paged_history_attention(
                q[0], ka, None, tbl, hist, tiles, tile_rows=tq,
                layer=layer, interpret=False, v_width=v_width,
                scale=head_dim ** -0.5)
        intra, lse_i = attention_with_lse(
            q, k, v, causal=True, segment_ids=seg, impl="pallas",
            interpret=False)
        past, lse_h = paged_history_attention(
            q[0], ka, va, tbl, hist, tiles, tile_rows=tq, layer=layer,
            interpret=False, window=window if windowed else None)
        return combine_attention_lse(intra, lse_i, past[None],
                                     lse_h.T[None])

    return dict(_compile_kernel(f, (
        q, kv, kv, seg, page, page,
        _sds((n_tiles, table_width), jnp.int32, mesh),
        _sds((chunk,), jnp.int32, mesh), tiles,
        _sds((), jnp.int32, mesh), _sds((), jnp.int32, mesh))),
        tile_rows=tq, tiles=n_tiles, pages=pages)


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?)\s([a-z][\w\-]*)\((.*)$")
_HLO_SHAPE = re.compile(r"[a-z]\w*\[([\d,]*)\]")


def arena_moves(hlo: str, leaf_elements: int) -> dict:
    """Instructions of an optimized HLO that MOVE at least one layer's
    leaf of the KV arena (``leaf_elements`` = n_blocks x block_size x
    hkv*d): name -> elements moved. The benchmark's rule
    (``benchmark/program_trace.py``: the name says copy or slice and
    the result holds a layer's leaf), with one refinement for a text
    that has the operands: an in-place ``dynamic-update-slice`` moves
    its UPDATE, not the buffer it returns (the CoW pass writes one
    block into the whole arena; the xs/ys layer scan wrote a layer's
    leaf into the stacked output)."""
    def elements(shapes: str) -> int:
        best = 0
        for dims in _HLO_SHAPE.findall(shapes):
            n = 1
            for d in dims.split(","):
                n *= int(d) if d else 1
            best = max(best, n)
        return best

    instrs = {}
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            name, shapes, _, rest = m.groups()
            instrs[name] = (elements(shapes), rest)
    moves = {}
    for name, (n, rest) in instrs.items():
        if not re.search(r"copy|slice", name):
            continue
        if "update" in name:
            ops = [instrs[o][0] for o in re.findall(r"%([\w.\-]+)",
                                                     rest.split("),")[0])
                   if o in instrs]
            n = max((e for e in ops if e < n), default=0)
        if n >= leaf_elements:
            moves[name] = n
    return moves


def kernel_calls(hlo: str) -> dict:
    """Kernel calls (``tpu_custom_call``: the Pallas calls and what the
    compiler made itself of a ``ragged_dot``; not its gather hints) of
    an optimized HLO by where they stand: ``"<lane>><innermost hetu.*
    scope>"`` (the benchmark's classifier, ``telemetry.device_scopes``)
    -> how many."""
    from hetu_tpu.telemetry import device_scopes
    where = device_scopes.describe(hlo)
    found: dict = {}
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if m and 'custom_call_target="tpu_custom_call"' in line \
                and m.group(1) in where:
            sc = where[m.group(1)]
            lane = next((p for p in sc.path if p.endswith("_lane")), "")
            key = f"{lane}>{sc.label}" if lane else sc.label
            found[key] = found.get(key, 0) + 1
    return found


def _serving_report(compiled, caches, t0, leaf_elements=None) -> dict:
    """Memory analysis and arena moves of a compiled serving program
    whose donated operand ``caches`` is the stacked arena (or the moves
    of any other leaf of ``leaf_elements`` elements a layer: a layer's
    experts), and its kernel calls by scope."""
    leaf = max(caches, key=lambda c: math.prod(c.shape))
    leaf_elements = leaf_elements or math.prod(leaf.shape[1:])
    ma = compiled.memory_analysis()
    return {
        "kernel_calls": kernel_calls(compiled.as_text()),
        "compile_s": round(time.perf_counter() - t0, 1),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes_est": int(ma.temp_size_in_bytes
                              + ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes),
        "arena_bytes": sum(math.prod(c.shape) * c.dtype.itemsize
                           for c in caches),
        "layer_leaf_bytes": leaf_elements * leaf.dtype.itemsize,
        "arena_moves": arena_moves(compiled.as_text(), leaf_elements),
    }


def check_serving_lane(devs, *, lane="decode", dtype=jnp.bfloat16,
                       n_blocks=2048, slots=8, chunk=256, block_size=16,
                       table_width=64):
    """One lane of the fused serving step — GPT-2 small's layer scan
    (``StackedBlocks.decode``) over a donated paged arena, under the
    ``cond`` the step wraps it in: ``lane="decode"`` is ``slots``
    one-token rows, ``"prefill"`` the packed flash chunk. Returns the
    program's temporaries and the instructions that still move a
    layer's leaf of the arena (none, while the scan carries it). The
    arena is abstract, so its size costs nothing here; at a few hundred
    blocks a whole int8 leaf fits the chip's fast memory and the
    compiler prefetches it there, a copy no serving arena sees."""
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.models.generation import init_paged_caches
    from hetu_tpu.ops.paged_pallas import (
        TILE_FIELDS, history_tile_count, history_tile_rows,
    )
    mesh = _one_dev_mesh(devs)
    model = GPTLMHeadModel(GPTConfig.small())
    embed = model.cfg.hidden_size

    def abstract(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, mesh), tree)

    blocks = abstract(jax.eval_shape(
        lambda k: model.init(k, dtype=jnp.bfloat16),
        jax.random.key(0))["blocks"])
    caches = abstract(jax.eval_shape(
        lambda: init_paged_caches(model, n_blocks, block_size, dtype)))
    n, shape = (slots, (slots, 1)) if lane == "decode" \
        else (chunk, (1, chunk))
    attn = model.blocks.block.attn
    tq = history_tile_rows(1, attn.head_dim, attn.num_kv_heads,
                           block_size)
    n_tiles = history_tile_count(chunk, tq, min(slots, chunk))
    tiles = {"map": _sds((len(TILE_FIELDS), n_tiles), jnp.int32, mesh),
             "tables": _sds((n_tiles, table_width), jnp.int32, mesh)}
    args = (_sds((), jnp.bool_, mesh), blocks, caches,
            # float32 activations over bf16 weights: the engine's loop
            # thread never enters autocast (PERF.md section 4)
            _sds(shape + (embed,), jnp.float32, mesh),
            _sds(shape, jnp.int32, mesh),
            _sds((n, table_width), jnp.int32, mesh),
            _sds((n,), jnp.bool_, mesh), _sds(shape, jnp.int32, mesh),
            _sds((n,), jnp.int32, mesh), tiles)

    def f(run, blocks, caches, h, pos, tbl, valid, seg, hist, tiles):
        def go(caches):
            kw = dict(slot_mask=valid) if lane == "decode" else dict(
                pack={"segment_ids": seg, "hist": hist, "valid": valid,
                      "impl": "pallas",
                      "tiles": dict(tiles, rows=tq)})
            return model.blocks.decode(blocks, h, caches, positions=pos,
                                       block_tables=tbl,
                                       attn_kernel="paged", **kw)
        return jax.lax.cond(run, go, lambda c: (h, c), caches)

    t0 = time.perf_counter()
    with _mosaic_aot_env():
        c = jax.jit(f, donate_argnums=(2,)).lower(*args).compile()
    return _serving_report(c, caches, t0)


def check_serving_step(devs, *, config="small", dtype=jnp.bfloat16,
                       slots=148, n_blocks=9473, max_len=1024, chunk=256,
                       model=None, block_size=16, leaf_elements=None,
                       with_text=False):
    """The REAL fused serving step (``ServingEngine._build_step``: CoW
    pass, decode lane, packed flash prefill lane, sampling) compiled
    for the target at a benchmark cell's sizes (defaults: GPT-2 small,
    148 slots, 9,473 blocks; ``config="large"`` with 32 / 2,049 is
    ``gpt2-large.backlog``). The engine is built on this host with a
    toy arena; the operands of its first dispatch are recorded, not
    run, and the step is lowered from their abstract shapes with the
    arena at ``n_blocks`` on the described device. ``model=`` is any
    other model with the engine's interface (its weights are zeros
    here: only their shapes reach the compiler). ``leaf_elements``:
    list the moves of leaves that large in place of the arena's;
    ``with_text``: the optimized HLO too, under ``"text"``."""
    from jax.sharding import SingleDeviceSharding
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.serving import ServingEngine

    if model is None:
        cfg = GPTConfig.small() if config == "small" else GPTConfig(
            vocab_size=50257, max_positions=1024, hidden_size=1280,
            num_layers=36, num_heads=20)
        model = GPTLMHeadModel(cfg)
        params = jax.jit(lambda k: model.init(k, dtype=jnp.bfloat16))(
            jax.random.key(0))
    else:
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                           jax.random.key(0)))
    eng = ServingEngine(model, params, max_len=max_len,
                        prefill_chunk=chunk, cache_dtype=dtype,
                        block_size=block_size, slots=slots,
                        # (a model without an arena takes none)
                        kv_blocks=max_len // block_size + 1
                        if getattr(model.blocks, "paged", True) else 0,
                        attn_kernel="paged", prefill_attn="flash_pallas")

    class Recorded(Exception):
        pass

    def record(*args):
        raise Recorded(args)

    eng._fn = record
    eng.submit([1, 2, 3])
    try:
        eng.step()
    except Recorded as r:
        args, = r.args
    # the step again, at home on the described device
    sh = SingleDeviceSharding(devs[0])
    eng._rep = eng._arena_sh = sh
    fn = eng._build_step()

    def abstract(x):
        x = x if hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    sds = list(jax.tree.map(abstract, args))
    # (a leaf over the SLOTS — a model's recurrent state — keeps its
    # own second axis: only the pages grow to the cell's arena)
    toy = eng.pool.n_blocks
    sds[1] = tuple(jax.ShapeDtypeStruct(
        (c.shape[0], n_blocks if c.shape[1] == toy else c.shape[1])
        + c.shape[2:], c.dtype, sharding=sh)
        for c in sds[1])
    t0 = time.perf_counter()
    with _mosaic_aot_env():
        c = fn.lower(*sds).compile()
    report = _serving_report(c, sds[1], t0, leaf_elements)
    if with_text:
        report["text"] = c.as_text()
    return report


def check_fused_ce(devs, *, n=4096, e=768, v=50257):
    from hetu_tpu.ops.fused_ce_pallas import fused_lm_ce
    mesh = _one_dev_mesh(devs)
    h = _sds((1, n, e), jnp.bfloat16, mesh)
    w = _sds((v, e), jnp.float32, mesh)
    lab = _sds((1, n), jnp.int32, mesh)

    def loss(h, w, lab):
        return fused_lm_ce(h, w, lab, interpret=False)

    f = jax.jit(jax.grad(loss, argnums=(0, 1)))
    t0 = time.perf_counter()
    with _mosaic_aot_env():
        f.lower(h, w, lab).compile()
    return {"compile_s": round(time.perf_counter() - t0, 1)}


def check_step(devs, strategy, *, batch, seq, cfgkw=None,
               attn_impl="pallas", ce="chunked", param_dtype="fp32"):
    """AOT-compile a full train step for the topology; memory rows.

    Sets (and restores) ``HETU_PALLAS_INTERPRET=0`` around the compile:
    inside the step the kernels take the interpret DEFAULT, which on
    this CPU-backend process would silently swap in the interpret
    lowering and validate nothing. Scoped here — a module-level set
    would leak into any process importing this file (e.g. the test
    suite, poisoning later interpret-mode kernel tests).
    ``ce="fused"`` compiles the streaming fused-CE Mosaic kernel the
    sweep can adopt (its GSPMD wrap is a distinct P0 surface)."""
    from workloads.pp_memory import analyze
    from hetu_tpu.core.dtypes import Policy
    from hetu_tpu.models import GPTConfig

    cfg = GPTConfig(vocab_size=50257, max_positions=seq, hidden_size=768,
                    num_layers=12, num_heads=12, **(cfgkw or {}))
    pol = Policy(param_dtype=jnp.bfloat16 if param_dtype == "bf16"
                 else jnp.float32, compute_dtype=jnp.bfloat16)
    # PIN the CE impl both ways: under _mosaic_aot_env the fused gate
    # fires on HETU_PALLAS_INTERPRET=0 too, so an ambient fused export
    # would silently flip rows labeled chunked (and the whole memory
    # calibration) onto the fused kernel
    prev_ce = os.environ.get("HETU_LM_LOSS_IMPL")
    if ce == "fused":
        os.environ["HETU_LM_LOSS_IMPL"] = "fused"
    else:
        os.environ.pop("HETU_LM_LOSS_IMPL", None)
    try:
        with _mosaic_aot_env():
            return analyze(cfg, strategy, devs, batch=batch, seq=seq,
                           policy=pol, attn_impl=attn_impl)
    finally:
        if prev_ce is None:
            os.environ.pop("HETU_LM_LOSS_IMPL", None)
        else:
            os.environ["HETU_LM_LOSS_IMPL"] = prev_ce


def check_decode(devs, *, batch=4, prompt=32, new=16):
    """AOT-compile the generation path (prefill + scan decode with a KV
    cache) for the TPU target — the inference surface's compile check."""
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.models.generation import generate

    mesh = _one_dev_mesh(devs)
    cfg = GPTConfig.small()
    model = GPTLMHeadModel(cfg)
    params_abs = jax.eval_shape(lambda k: model.init(k),
                                jax.random.key(0))
    sh = NamedSharding(mesh, P())
    p_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_abs)
    ids = jax.ShapeDtypeStruct((batch, prompt), jnp.int32, sharding=sh)
    f = jax.jit(lambda p, i: generate(
        model, p, i, max_new_tokens=new, max_len=prompt + 2 * new,
        cache_dtype=jnp.bfloat16))
    t0 = time.perf_counter()
    with _mosaic_aot_env():
        f.lower(p_abs, ids).compile()
    return {"compile_s": round(time.perf_counter() - t0, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="kernel checks only (skip whole-step compiles)")
    args = ap.parse_args()

    # script-entry only (a module-level set would flip the backend of
    # any process importing this file, e.g. the test suite): nothing
    # here executes on a device — only the AOT target is a TPU — so this
    # process never takes the chip
    jax.config.update("jax_platforms", "cpu")

    from jax.experimental import topologies

    from hetu_tpu.parallel.strategy import Strategy

    topo1 = topologies.get_topology_desc("v5e:2x2", "tpu")
    topo8 = topologies.get_topology_desc("v5e:2x4", "tpu")
    d1 = list(topo1.devices)
    d8 = list(topo8.devices)

    checks = [
        ("flash_causal_bench", lambda: check_flash(d1)),
        ("flash_gqa4", lambda: check_flash(d1, shape=(2, 1024, 8, 64),
                                           kv_heads=2)),
        ("flash_packed_segids", lambda: check_flash(d1, seg=True)),
        ("flash_d128", lambda: check_flash(d1, shape=(2, 1024, 8, 128))),
        # K and V of a head past the resident budget: major blocks
        ("flash_8k_major_blocks",
         lambda: check_flash(d1, shape=(1, 8192, 16, 64), seg=True)),
        # the serving packs' one row, the forward that returns the LSE
        ("flash_lse_gpt2_large_c256",
         lambda: check_flash(d1, shape=(1, 256, 20, 64), seg=True,
                             lse=True, dtype=jnp.float32)),
        ("flash_lse_gqa128x8_d128_c512",
         lambda: check_flash(d1, shape=(1, 512, 128, 128), kv_heads=8,
                             seg=True, lse=True)),
        ("fused_ce_bench_vocab", lambda: check_fused_ce(d1)),
        ("paged_decode_bf16", lambda: check_paged(d1)),
        ("paged_verify5_int8_lse",
         lambda: check_paged(d1, dtype=jnp.int8, rows=5,
                             return_lse=True)),
        ("paged_gqa32x8_d128",
         lambda: check_paged(d1, heads=32, kv_heads=8, head_dim=128)),
        ("paged_decode_one_layer_3d",
         lambda: check_paged(d1, layers=None)),
        ("packed_prefill_c256", lambda: check_packed_prefill(d1)),
        # the history read's tiles at the serving cells' head shapes
        ("packed_prefill_gpt2_large_c256",
         lambda: check_packed_prefill(
             d1, dtype=jnp.float32, heads=20, layers=36, slots=32,
             n_blocks=2049, table_width=65)),
        ("packed_prefill_gqa128x8_d128_c512_window",
         lambda: check_packed_prefill(
             d1, chunk=512, heads=128, kv_heads=8, head_dim=128,
             layers=4, slots=48, n_blocks=4250, block_size=64,
             table_width=129, windowed=True)),
        ("serving_lane_decode_bf16", lambda: check_serving_lane(d1)),
        ("serving_lane_prefill_int8",
         lambda: check_serving_lane(d1, lane="prefill", dtype=jnp.int8)),
    ]
    if not args.quick:
        checks += [
            ("step_dp2tp2cp2_ring_v5e8",
             lambda: check_step(d8, Strategy(dp=2, tp=2, cp=2,
                                             remat="selective"),
                                batch=8, seq=1024)),
            ("step_bench_winner_b32",
             lambda: check_step(d1[:1], Strategy(remat="selective",
                                                 unroll=True),
                                batch=32, seq=1024)),
            # the remaining dryrun strategy families, compiled for the
            # REAL v5e-8 target (the driver's dryrun only proves the
            # virtual CPU mesh): pipeline-in-manual-region and EP MoE
            ("step_dp2pp2tp2_v5e8",
             lambda: check_step(d8, Strategy(dp=2, pp=2, tp=2,
                                             num_microbatches=2,
                                             remat="selective"),
                                batch=8, seq=1024)),
            ("step_dp2pp2ep2_moe_v5e8",
             lambda: check_step(d8, Strategy(dp=2, pp=2, ep=2,
                                             num_microbatches=2,
                                             remat="selective"),
                                batch=8, seq=1024,
                                cfgkw={"num_experts": 4})),
            # ring attention per stage inside the pipeline region (the
            # hop kernels carry their own nested shard_map; the wrap
            # decision is captured at forward trace — see
            # parallel.sharding.manual_unbound_axes)
            ("step_dp2pp2cp2_ring_v5e8",
             lambda: check_step(d8, Strategy(dp=2, pp=2, cp=2,
                                             num_microbatches=2,
                                             remat="selective"),
                                batch=8, seq=1024)),
            # the fused-CE Mosaic kernel's GSPMD wraps: token-sharded
            # (dp) and token-REPLICATED multi-device (pp-only) meshes
            ("step_dp4_fusedce_v5e",
             lambda: check_step(d1, Strategy(dp=4, remat="selective"),
                                batch=8, seq=1024, ce="fused")),
            ("step_pp2_fusedce_v5e",
             lambda: check_step(d1[:2], Strategy(pp=2,
                                                 num_microbatches=2,
                                                 remat="selective"),
                                batch=8, seq=1024, ce="fused")),
            # activation offload to pinned host memory (never
            # TPU-compiled before r4 — 'degrades gracefully off-TPU'
            # was the only evidence)
            ("step_offload_v5e",
             lambda: check_step(d1[:1], Strategy(remat="offload"),
                                batch=8, seq=1024)),
            # inference: prefill + lax.scan KV-cache decode
            ("decode_kv_cache_v5e", lambda: check_decode(d1[:1])),
            # the fused serving step at the benchmark cells' sizes:
            # memory analysis and what still moves the arena
            ("serving_step_gpt2_small_chat",
             lambda: check_serving_step(d1[:1])),
            ("serving_step_gpt2_large_backlog",
             lambda: check_serving_step(d1[:1], config="large", slots=32,
                                        n_blocks=2049)),
        ]

    rows = []
    for name, fn in checks:
        try:
            r = fn()
        except Exception as e:
            r = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
        rows.append({"check": name, **r})
        status = r.get("error", f"ok {r.get('compile_s', '?')}s")
        extra = ""
        if "peak_bytes_est" in r:
            extra = f"  peak {r['peak_bytes_est'] / 1024**3:.2f} GiB"
        if "arena_moves" in r:
            extra += (f"  temp {r['temp_bytes'] / 1e6:.1f} MB"
                      f"  arena moves {sorted(r['arena_moves'])}")
        print(f"{name:>32}: {status}{extra}", flush=True)

    # --quick covers only the kernel rows: keep it out of the full
    # matrix's artifact so docs citing aot_check.json stay reproducible
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                        "aot_check_quick.json" if args.quick
                        else "aot_check.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"rows": rows}, f, indent=1)
    n_err = sum("error" in r for r in rows)
    print(f"{len(rows) - n_err}/{len(rows)} checks compiled; wrote {path}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
