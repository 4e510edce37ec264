"""Flash-attention microbench on the real TPU chip.

Role of the reference's ``examples/efficiency/profile_attn.py``: compile-check
every kernel variant (causal/GQA/segment-ids, seq 1k-8k) NON-interpret on the
TPU, validate numerics against the XLA oracle, then time fwd and fwd+bwd for
the Pallas kernel vs plain XLA attention.

Usage: python workloads/attn_bench.py [--quick]
Prints one JSON line per measurement and a summary table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.ops.attention import attention_reference
from hetu_tpu.ops.flash_pallas import flash_attention_pallas
from workloads._timing import scan_loop, scan_loop_grad, time_loop_ms


def _rand_qkv(key, b, s, hq, hkv, d, dtype=jnp.bfloat16):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype)
    return q, k, v


def _segments(b, s, n_seg=4):
    # packed batch: n_seg equal documents per row
    ids = np.repeat(np.arange(n_seg), s // n_seg)
    return jnp.asarray(np.broadcast_to(ids, (b, s)), jnp.int32)


N_ITERS = 32


def attn_flops(b, s, hq, d, causal):
    # 2 matmuls (QK^T and PV), 2*s*s*d MACs each -> 4*s*s*d*2 flops
    f = 4.0 * b * hq * s * s * d * 2
    return f / 2 if causal else f


def check_numerics(name, q, k, v, **kw):
    """fwd + grad parity: pallas (non-interpret) vs XLA oracle."""
    def loss_p(q, k, v):
        return flash_attention_pallas(q, k, v, interpret=False, **kw).astype(
            jnp.float32).sum()

    def loss_r(q, k, v):
        return attention_reference(q, k, v, **kw).astype(jnp.float32).sum()

    op = flash_attention_pallas(q, k, v, interpret=False, **kw)
    orf = attention_reference(q, k, v, **kw)
    err = float(jnp.max(jnp.abs(op.astype(jnp.float32)
                                - orf.astype(jnp.float32))))
    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(gp, gr))
    print(json.dumps({"check": name, "fwd_max_err": round(err, 4),
                      "grad_max_err": round(gerr, 4)}))
    # bf16 inputs, fp32 softmax: tolerances scale with seq len
    assert err < 0.15, f"{name}: fwd mismatch {err}"
    assert gerr < 16.0, f"{name}: grad mismatch {gerr}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        print(json.dumps({"error": "no TPU; this bench targets the chip"}))
        sys.exit(0)

    key = jax.random.key(0)

    # ---- compile-check + numerics on every variant (small sizes) ----
    q, k, v = _rand_qkv(key, 2, 1024, 8, 8, 64)
    check_numerics("causal_1k", q, k, v, causal=True)
    q, k, v = _rand_qkv(key, 2, 1024, 8, 2, 64)
    check_numerics("gqa4_causal_1k", q, k, v, causal=True)
    q, k, v = _rand_qkv(key, 2, 1024, 8, 8, 128)
    check_numerics("d128_causal_1k", q, k, v, causal=True)
    q, k, v = _rand_qkv(key, 2, 1024, 8, 8, 64)
    seg = _segments(2, 1024)
    check_numerics("packed_causal_1k", q, k, v, causal=True,
                   segment_ids=seg)
    check_numerics("packed_full_1k", q, k, v, causal=False,
                   segment_ids=seg)

    # ---- timing sweep: pallas vs XLA, fwd and fwd+bwd ----
    results = []
    seqs = [1024, 4096] if args.quick else [1024, 2048, 4096, 8192]
    for s in seqs:
        b = max(1, 8192 // s)  # constant token count
        hq, hkv, d = 16, 16, 64
        q, k, v = _rand_qkv(key, b, s, hq, hkv, d)

        # scan-looped inside one jit: per-call host dispatch
        # costs ~ms of host time and would swamp sub-ms kernels
        pallas_fwd = scan_loop(lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, interpret=False), N_ITERS)
        xla_fwd = scan_loop(lambda q, k, v: attention_reference(
            q, k, v, causal=True), N_ITERS)

        pallas_bwd = scan_loop_grad(lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, interpret=False), N_ITERS)
        xla_bwd = scan_loop_grad(lambda q, k, v: attention_reference(
            q, k, v, causal=True), N_ITERS)

        flops = attn_flops(b, s, hq, d, causal=True)
        for tag, fn, mult in (("fwd", pallas_fwd, 1.0),
                              ("fwd_xla", xla_fwd, 1.0),
                              ("bwd", pallas_bwd, 3.5),
                              ("bwd_xla", xla_bwd, 3.5)):
            dt = time_loop_ms(fn, (q, k, v), N_ITERS) / 1e3
            rec = {"seq": s, "batch": b, "op": tag,
                   "ms": round(dt * 1e3, 3),
                   "tflops": round(flops * mult / dt / 1e12, 2)}
            results.append(rec)
            print(json.dumps(rec))

    # summary: pallas speedup over XLA per seq
    print("\nseq   fwd pallas/xla   bwd pallas/xla")
    by = {(r["seq"], r["op"]): r["ms"] for r in results}
    for s in seqs:
        fs = by[(s, "fwd_xla")] / by[(s, "fwd")]
        bs = by[(s, "bwd_xla")] / by[(s, "bwd")]
        print(f"{s:5d}   {fs:10.2f}x   {bs:10.2f}x")


if __name__ == "__main__":
    main()
