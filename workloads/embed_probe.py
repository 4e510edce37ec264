"""Embedding backward probe: scatter-add vs one-hot matmul, on-chip.

The embedding backward is a scatter-add of N token-rows into the (V, E)
table; XLA:TPU's scatter lowering is the wildcard — if it serializes,
the one-hot matmul formulation (2·N·V·E extra FLOPs but pure MXU) wins.
Measures, scan-looped inside one jit, at the bench shape:

- ``scatter``: plain ``jnp.take`` (XLA's native take-VJP backward),
- ``onehot``: ``ops.embedding.embedding_lookup(bwd="onehot")`` — gather
  forward, chunked one-hot-matmul backward (the real adoption candidate),
- ``onehot_fwd``: one-hot matmul in BOTH directions (diagnostic only).

Writes the scatter-vs-onehot winner to ``workloads/out/embed_bwd.json``;
``ops.embedding.preferred_embedding_bwd()`` (and so ``nn.Embedding`` with
``bwd="auto"``) adopts it on the next process start.

Usage: python workloads/embed_probe.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from hetu_tpu.ops.embedding import embedding_lookup
from workloads._timing import time_loop_ms

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                   "embed_bwd.json")


def main():
    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"error": "probe needs the TPU chip"}))
        return

    N, V, E = 32 * 1024, 50257, 768
    ids = jax.random.randint(jax.random.key(0), (N,), 0, V)
    w = jax.random.normal(jax.random.key(1), (V, E), jnp.float32) * 0.02
    g = jax.random.normal(jax.random.key(2), (N, E), jnp.bfloat16)

    def scatter_loss(w):
        h = jnp.take(w, ids, axis=0).astype(jnp.bfloat16)
        return (h * g).astype(jnp.float32).sum()

    def onehot_loss(w):
        h = embedding_lookup(w, ids, bwd="onehot").astype(jnp.bfloat16)
        return (h * g).astype(jnp.float32).sum()

    def onehot_fwd_loss(w):
        oh = jax.nn.one_hot(ids, V, dtype=jnp.bfloat16)
        h = oh @ w.astype(jnp.bfloat16)
        return (h * g).astype(jnp.float32).sum()

    iters = 16
    times = {}
    for name, loss in (("scatter", scatter_loss), ("onehot", onehot_loss),
                       ("onehot_fwd", onehot_fwd_loss)):
        grad = jax.grad(loss)

        # same 1e-30-carry chaining as _timing.scan_loop_grad, inlined
        # because the operand here is the single weight table, not (q,k,v)
        def run(w, grad=grad):
            def body(carry, _):
                return grad(w + 1e-30 * carry), None
            out, _ = jax.lax.scan(body, jnp.zeros_like(w), None,
                                  length=iters)
            return out

        try:
            ms = time_loop_ms(jax.jit(run), (w,), iters)
            times[name] = ms
            print(json.dumps({"impl": name, "fwd_bwd_ms": round(ms, 3)}),
                  flush=True)
        except Exception as e:
            print(json.dumps({"impl": name, "error": str(e)[:100]}),
                  flush=True)

    if "scatter" in times and "onehot" in times:
        winner = "onehot" if times["onehot"] < times["scatter"] else "scatter"
        rec = {"winner": winner, "backend": "tpu",
               "device": jax.devices()[0].device_kind,
               "shape": {"tokens": N, "vocab": V, "embed": E},
               "ms": {k: round(v, 3) for k, v in times.items()},
               "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        tmp = OUT + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, OUT)
        print(json.dumps({"winner": winner, "recorded": OUT}), flush=True)


if __name__ == "__main__":
    main()
