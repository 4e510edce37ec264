"""int8/int4 weight-quantized matmul vs bf16, on-chip (VERDICT r4 weak #6).

The repo's quantization story (``ops/quantization.py``) is W8A16: weights
stored int8, dequantized into the consuming matmul — XLA fuses the
``q * scale`` into the operand stream, so the claimed win is HBM traffic
(1 byte/weight instead of 2), which should pay off exactly when the
matmul is memory-bound (small token count m) and wash out or lose when
it is compute-bound (large m, MXU-limited). Parity target: the
reference's bitsandbytes kernels (``hetu/impl/kernel/quantization.cu``),
which it ships for inference-time weight compression.

Measures, scan-looped inside one jit, tok/ms for x@W at transformer
shapes with m = tokens in flight:

- ``bf16``:  bf16 weights, bf16 matmul (baseline),
- ``int8``:  ``int8_matmul`` W8A16 (the adoption candidate),
- ``int4``:  dequantize-then-matmul packed int4 (storage-only today).

Writes per-shape rows + the regime verdict to
``workloads/out/quant_bench.json`` (flushed per row — a lost machine must
not lose completed rows).

Usage: python workloads/quant_bench.py          (on-chip timing)
       python workloads/quant_bench.py --aot    (offline compiler check)

``--aot`` needs NO chip: it compiles the same matmuls for the offline
v5e target and reads XLA's cost analysis. The W8A16 claim stands or
falls on whether the dequant is FUSED into the matmul's operand stream
(weights stream from HBM as 1 byte each) or materialized (a full bf16
copy is written+read, costing MORE than plain bf16): bytes-accessed
tells which, per shape, straight from the compiler.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from hetu_tpu.ops.quantization import (
    dequantize_int4, int8_matmul, quantize_int4, quantize_int8)

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                   "quant_bench.json")
ITERS = 30


def scan_mm(fn, n_iters):
    """One dispatch per n_iters matmuls.

    ``fn(x, eps, *operands)`` must mix the carry-derived scalar ``eps``
    into any otherwise loop-invariant prefix it wants timed per
    iteration — in the int8/int4 variants the dequant is exactly such a
    prefix (``dequantize(q, s)`` does not depend on ``x``, so LICM would
    legally hoist it and the loop would read pre-dequantized bf16
    weights, erasing the effect being measured). Perturbing the (1, n)
    scale by ``eps`` makes the dequant iteration-dependent at the cost
    of an O(n) add. Iterations chain through a scalar checksum of the
    output (cannot be dead-coded, negligible arithmetic)."""

    def run(x, *operands):
        def body(carry, _):
            xc, acc = carry
            eps = 1e-30 * acc
            out = fn(xc, eps, *operands)
            s = out.astype(jnp.float32).sum()
            return (xc + (1e-30 * s).astype(xc.dtype), acc + s), None
        (_, acc), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), None,
                                   length=n_iters)
        return acc

    return jax.jit(run)


def time_ms(jitted, args):
    o = jitted(*args)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    o = jitted(*args)
    jax.block_until_ready(o)
    return (time.perf_counter() - t0) / ITERS * 1e3


def aot_main():
    """Offline fusion check: compile for the v5e topology, compare the
    compiler's bytes-accessed against the fused/materialized bounds."""
    jax.config.update("jax_platforms", "cpu")   # AOT only: no chip
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = Mesh(np.array(list(topo.devices)[:1]), ("x",))
    rep = NamedSharding(mesh, P())

    def compiled_bytes(fn, *avals):
        c = jax.jit(fn, out_shardings=rep).lower(*avals).compile()
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        return float(ca.get("bytes accessed", 0.0))

    rows = []
    for m, k, n in [(16, 4096, 4096), (256, 4096, 4096),
                    (16, 768, 3072)]:
        x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=rep)
        wb = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=rep)
        q8 = jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=rep)
        s8 = jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=rep)
        b_bf16 = compiled_bytes(jnp.matmul, x, wb)
        b_int8 = compiled_bytes(
            lambda x, q, s: int8_matmul(x, q, s, dtype=jnp.bfloat16),
            x, q8, s8)
        io = 2 * (m * k + m * n)
        fused = io + k * n + 4 * n        # int8 weights stream once
        mat = io + 3 * k * n + 4 * n      # bf16 copy written + read
        verdict = "fused" if abs(b_int8 - fused) < abs(b_int8 - mat) \
            else "materialized"
        rows.append({"m": m, "k": k, "n": n, "bf16_bytes": b_bf16,
                     "int8_bytes": b_int8, "fused_bound": fused,
                     "materialized_bound": mat, "verdict": verdict})
        print(f"m={m:>4} k={k} n={n}  bf16 {b_bf16/2**20:7.1f}MiB  "
              f"int8 {b_int8/2**20:7.1f}MiB  (fused bound "
              f"{fused/2**20:.1f}, materialized {mat/2**20:.1f}) "
              f"-> {verdict}", flush=True)
    out = OUT.replace("quant_bench.json", "quant_aot.json")
    with open(out, "w") as f:
        json.dump({"target": "v5e (offline AOT)", "rows": rows}, f,
                  indent=1)
    print(f"wrote {out}")


def main():
    if "--aot" in sys.argv:
        return aot_main()
    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "probe needs the TPU chip"}))
        return

    rows = []
    # m sweeps the memory-bound (decode-like, m small) to compute-bound
    # (prefill/train, m large) regimes at GPT-2-small and 4k widths.
    shapes = [(m, k, n)
              for (k, n) in ((768, 3072), (4096, 4096))
              for m in (16, 256, 4096)]
    for m, k, n in shapes:
        x = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.key(1), (k, n), jnp.float32) * 0.02
        wb = w.astype(jnp.bfloat16)
        q8, s8 = jax.jit(quantize_int8, static_argnums=1)(w, 0)
        q4, s4, orig = quantize_int4(w, axis=0)

        mm_bf16 = scan_mm(lambda x, eps, w: jnp.matmul(x, w), ITERS)
        mm_int8 = scan_mm(
            lambda x, eps, q, s: int8_matmul(x, q, s + eps,
                                             dtype=jnp.bfloat16), ITERS)
        mm_int4 = scan_mm(
            lambda x, eps, q, s: jnp.matmul(
                x, dequantize_int4(q, s + eps, orig, axis=0,
                                   dtype=jnp.bfloat16)),
            ITERS)

        row = {"m": m, "k": k, "n": n,
               "bf16_ms": time_ms(mm_bf16, (x, wb)),
               "int8_ms": time_ms(mm_int8, (x, q8, s8)),
               "int4_ms": time_ms(mm_int4, (x, q4, s4))}
        row["int8_speedup"] = row["bf16_ms"] / row["int8_ms"]
        row["int4_speedup"] = row["bf16_ms"] / row["int4_ms"]
        rows.append(row)
        print(f"m={m:>5} k={k} n={n}  bf16 {row['bf16_ms']:.3f}ms  "
              f"int8 {row['int8_ms']:.3f}ms ({row['int8_speedup']:.2f}x)  "
              f"int4 {row['int4_ms']:.3f}ms ({row['int4_speedup']:.2f}x)",
              flush=True)
        with open(OUT, "w") as f:
            json.dump({"backend": "tpu",
                       "device": jax.devices()[0].device_kind,
                       "iters": ITERS, "rows": rows}, f, indent=1)

    small = [r for r in rows if r["m"] <= 256]
    wins = sum(r["int8_speedup"] > 1.05 for r in small)
    verdict = ("int8 wins memory-bound (m<=256) cells"
               if wins >= len(small) // 2 + 1 else
               "int8 does not beat bf16 — keep it storage-only")
    print("VERDICT:", verdict)
    with open(OUT, "w") as f:
        json.dump({"backend": "tpu", "device": jax.devices()[0].device_kind,
                   "iters": ITERS, "rows": rows, "verdict": verdict},
                  f, indent=1)


if __name__ == "__main__":
    main()
