"""Dispatch-free op timing: loop the op inside ONE jit via ``lax.scan``.

Each dispatch costs host time, which swamps sub-ms kernels when timing
call-by-call. Chaining N iterations through a
negligible 1e-30 feedback term (so XLA can neither hoist nor dead-code
them) gives one dispatch per N device executions.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def scan_loop(fn, n_iters: int):
    """jit(run(q, k, v, *rest)) executing ``fn`` n_iters times,
    iterations chained through the first argument. ``fn(q, k, v, *rest)
    -> out`` with out broadcast-compatible to q."""

    def run(q, k, v, *rest):
        def body(carry, _):
            return fn(q + 1e-30 * carry, k, v, *rest), None
        out, _ = jax.lax.scan(body, jnp.zeros_like(q), None,
                              length=n_iters)
        return out

    return jax.jit(run)


def scan_loop_grad(fn, n_iters: int):
    """Same, for fwd+bwd: times grad of sum-loss wrt (q, k, v), chained
    through dq."""
    g = jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2))

    def run(q, k, v):
        def body(carry, _):
            dq, dk, dv = g(q + 1e-30 * carry, k, v)
            return dq, None
        out, _ = jax.lax.scan(body, jnp.zeros_like(q), None,
                              length=n_iters)
        return out

    return jax.jit(run)


def time_loop_ms(jitted, args, n_iters: int) -> float:
    """ms per iteration: one warmup dispatch (compile), one timed."""
    o = jitted(*args)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    o = jitted(*args)
    jax.block_until_ready(o)
    return (time.perf_counter() - t0) / n_iters * 1e3
