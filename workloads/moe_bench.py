"""EP / gate-zoo bench: step time per gate variant + capacity-drop stats.

CPU-mesh ratios are meaningful (flat vs hierarchical a2a, gate overhead);
absolute times only matter on TPU.

Reference: HetuMoE gate zoo (``hetu/v1/python/hetu/layers/*Gate.py``) and
its MoE examples (``hetu/v1/examples/moe/``).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    from hetu_tpu.nn.moe import MoEMLP, gate_drop_stats
    from hetu_tpu.parallel.sharding import (
        ActivationSharding, param_partition_specs, shard_params,
    )
    from hetu_tpu.parallel.strategy import Strategy
    from jax.sharding import NamedSharding

    n_dev = len(jax.devices())
    ep = min(args.experts, n_dev)
    dp = max(1, n_dev // ep)
    strat = Strategy(dp=dp, ep=ep)
    mesh = strat.build_mesh()
    act = ActivationSharding(mesh, batch=("dp", "ep"), seq="cp", tp="tp")
    T, d = args.tokens, args.dim
    x = jax.random.normal(jax.random.key(0), (dp * ep, T // (dp * ep), d))

    print(f"devices={n_dev} dp={dp} ep={ep} tokens={T} dim={d} "
          f"experts={args.experts}")
    for gate_type in ("topk", "ktop1", "sam", "balance"):
        kw = {"num_groups": max(1, args.experts // 2)} \
            if gate_type == "sam" else None
        moe = MoEMLP(d, args.hidden, args.experts, k=2,
                     capacity_factor=1.25, gate_type=gate_type,
                     gate_kwargs=kw)
        params = moe.init(jax.random.key(1), dtype=jnp.float32)
        sp = shard_params(params, mesh, param_partition_specs(
            moe, strat.axis_rules(), mesh))

        @jax.jit
        def f(p, x):
            with act:
                out, aux = moe(p, x)
            return out.sum(), aux

        xs = jax.device_put(x, NamedSharding(mesh, strat.data_spec(3)))
        f(sp, xs)[0].block_until_ready()          # compile
        t0 = time.perf_counter()
        for _ in range(args.steps):
            s, aux = f(sp, xs)
        s.block_until_ready()
        dt = (time.perf_counter() - t0) / args.steps * 1e3

        idx, wgt, _ = moe.gate(params["gate"], x.reshape(-1, d))
        stats = gate_drop_stats(idx, args.experts, moe.k, 1.25)
        print(f"{gate_type:8s} fwd {dt:8.2f} ms  "
              f"drop {float(stats['drop_frac']):.4f}  "
              f"imbalance {float(stats['load_imbalance']):.3f}  "
              f"aux {float(aux):.4f}")


if __name__ == "__main__":
    main()
