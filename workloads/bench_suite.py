"""BASELINE configs 1-5 benchmark suite, scaled to the available chip.

BASELINE.md's graduated configs:
1. MLP single-device smoke            (ref tests/test_cifar10.py)
2. GPT-2 small pretrain               (bench.py owns this; repeated here)
3. Llama auto-parallel                (Galvatron search + scaled measure)
4. GPT-MoE 8-expert                   (HetuMoE / v1 examples/moe)
5. 32k-context CP + remat             (lobra/efficiency long-context)

Each config prints ONE JSON line. Single-chip hardware runs configs at a
scaled size (model depth / batch trimmed to fit one v5e); the multi-chip
sharding of 3-5 is validated separately on the virtual CPU mesh
(__graft_entry__.dryrun_multichip). Run: python workloads/bench_suite.py
[--configs 1,3,4,5]
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

from hetu_tpu import optim
from hetu_tpu.core.dtypes import Policy, autocast
from hetu_tpu.engine import build_train_step, init_state, make_plan
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.utils.profiler import sync_result


def _bench_steps(step, state, batch, steps, warmup):
    """Timing loop (shared by the workload scripts). At least
    one warmup step always runs (compile must not land in the timed
    region) and ``steps`` is clamped to >= 1."""
    for _ in range(max(1, warmup)):
        state, m = step(state, batch)
    sync_result(m["loss"])
    steps = max(1, steps)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch)
    loss = float(jax.device_get(m["loss"]))
    dt = (time.perf_counter() - t0) / steps
    assert loss == loss, "NaN loss"
    return dt, loss


def _lm_bench(model, cfg, strategy, batch, seq, *, steps=10, warmup=2,
              policy=None):
    opt = optim.adamw(1e-4)
    import contextlib
    ctx = autocast(policy) if policy else contextlib.nullcontext()
    with ctx:
        plan = make_plan(model, opt, strategy)
        state = init_state(model, opt, plan, jax.random.key(0))
        step = build_train_step(model, opt, plan)
        ids = jax.random.randint(jax.random.key(1), (batch, seq + 1), 0,
                                 cfg.vocab_size)
        b = plan.shard_batch({"input_ids": ids[:, :-1],
                              "labels": ids[:, 1:]})
        dt, loss = _bench_steps(step, state, b, steps, warmup)
    n = sum(x.size for x in jax.tree.leaves(state.params))
    out = {"step_ms": round(dt * 1e3, 2),
           "tokens_per_sec": round(batch * seq / dt, 1),
           "params": n, "loss": round(loss, 3)}
    from hetu_tpu.utils.profiler import device_memory_stats
    mem = device_memory_stats()
    if mem.get("peak_bytes_in_use"):
        out["hbm_peak_gb"] = round(mem["peak_bytes_in_use"] / 1e9, 2)
    from bench import model_flops_per_token, peak_flops
    peak = peak_flops(jax.devices()[0])
    if peak:
        # PaLM appendix-B accounting via bench.py's shared formula, on
        # ACTIVE params: top-k MoE executes only k/E of each expert
        # tensor per token — charging all experts would inflate MoE MFU
        n_active = _active_params(state.params, cfg)
        fpt = model_flops_per_token(cfg, n_active, seq)
        out["mfu"] = round(fpt * out["tokens_per_sec"] / peak, 4)
    return out


_EXPERT_LEAVES = ("wi", "wg", "wo")   # MoEMLP expert tensors (nn/moe.py)


def _active_params(params, cfg) -> float:
    """Params touched per token: expert tensors count at k/E."""
    E = getattr(cfg, "num_experts", 0)
    k = getattr(cfg, "moe_top_k", 0)
    frac = (k / E) if E and k else 1.0
    from jax.tree_util import keystr, tree_flatten_with_path
    flat, _ = tree_flatten_with_path(params)
    total = 0.0
    for path, leaf in flat:
        name = keystr((path[-1],)).strip("[]'\"")
        total += leaf.size * (frac if name in _EXPERT_LEAVES else 1.0)
    return total


def config1_mlp():
    """Single-device MLP smoke (config 1): tiny classification train."""
    from hetu_tpu.models.vision import MLPClassifier

    model = MLPClassifier(256, 512, 10)
    params = model.init(jax.random.key(0))
    opt = optim.adamw(1e-3)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.key(1), (512, 256))
    y = jax.random.randint(jax.random.key(2), (512,), 0, 10)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = model(p, x)
            from hetu_tpu.ops.losses import cross_entropy_mean
            return cross_entropy_mean(logits, y)
        loss, g = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(g, opt_state, params)
        from hetu_tpu.optim.base import apply_updates
        return apply_updates(params, updates), opt_state, loss

    for _ in range(3):
        params, opt_state, loss = step(params, opt_state)
    sync_result(loss)
    t0 = time.perf_counter()
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state)
    l = float(jax.device_get(loss))
    dt = (time.perf_counter() - t0) / 20
    return {"config": 1, "metric": "mlp_smoke_step_ms",
            "value": round(dt * 1e3, 3), "unit": "ms", "loss": round(l, 3)}


def config3_llama_autoparallel(on_tpu):
    """Galvatron search for Llama-7B on a v5e-8 topology, then measured
    scaled-down Llama (7B dims, 4 layers) on the local chip."""
    from hetu_tpu.models import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.tools.galvatron import (
        ModelDims, TPUTopology, search_uniform,
    )
    dims = ModelDims.from_config(LlamaConfig.llama_7b(), seq_len=2048,
                                 global_batch=64)
    topo = TPUTopology.calibrated(8, peak_flops=197e12, hbm_bytes=16e9)
    cands = search_uniform(dims, topo)
    best = cands[0] if cands else None

    import dataclasses
    base = LlamaConfig.llama_7b()
    scaled = dataclasses.replace(base, num_layers=2,
                                 max_positions=2048)
    model = LlamaLMHeadModel(scaled)
    batch, seq = (4, 2048) if on_tpu else (2, 128)
    r = _lm_bench(model, scaled,
                  Strategy(remat="selective", unroll=True), batch, seq,
                  policy=Policy(param_dtype=jnp.bfloat16,
                                compute_dtype=jnp.bfloat16))
    return {"config": 3, "metric": "llama7b_dims_2layer_tokens_per_sec",
            "value": r["tokens_per_sec"], "unit": "tokens/sec",
            "searched_strategy": json.loads(best.strategy.to_json())
            if best else None,
            "predicted_step_ms": round(best.cost.step_time * 1e3, 1)
            if best else None, **r}


def config4_moe(on_tpu):
    """GPT-MoE 8 experts (config 4), single chip (EP all_to_all benched
    on the CPU mesh / dryrun)."""
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    cfg = GPTConfig.moe_8e() if on_tpu else GPTConfig.tiny_moe()
    if on_tpu:
        import dataclasses
        cfg = dataclasses.replace(cfg, num_layers=6)
    model = GPTLMHeadModel(cfg)
    batch, seq = (8, 1024) if on_tpu else (4, 64)
    r = _lm_bench(model, cfg, Strategy(unroll=True), batch, seq,
                  policy=Policy(param_dtype=jnp.float32,
                                compute_dtype=jnp.bfloat16))
    return {"config": 4, "metric": "gpt_moe8e_tokens_per_sec",
            "value": r["tokens_per_sec"], "unit": "tokens/sec", **r}


def config5_spec(seq: int = 32768):
    """(cfg, strategy, policy) of BASELINE config 5 — ONE definition
    shared with the AOT precheck (``aot_check.check_ctx32k``), so the
    feasibility number always describes the config the bench runs."""
    import dataclasses

    from hetu_tpu.models import LlamaConfig
    cfg = dataclasses.replace(LlamaConfig.tiny(), hidden_size=1024,
                              num_heads=8, num_kv_heads=8,
                              intermediate_size=2816, num_layers=4,
                              max_positions=seq, vocab_size=32000)
    return (cfg, Strategy(remat="full", unroll=True),
            Policy(param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16))


def config5_long_context(on_tpu):
    """32k-context CP+remat regime (config 5): single-chip flash path at
    the longest sequence that fits, remat full."""
    from hetu_tpu.models import LlamaLMHeadModel
    seq = 32768 if on_tpu else 512
    cfg, strategy, policy = config5_spec(seq)
    model = LlamaLMHeadModel(cfg)
    # AOT analysis (workloads/aot_check.py check_ctx32k) measured batch 2
    # at 10.76 GiB of 15.75 peak — try it first (~2x tokens/s); chain
    # down on OOM so the measurement is never lost to the attempt
    from bench import is_oom
    last = None
    for b in ((2, 1) if on_tpu else (1,)):
        try:
            r = _lm_bench(model, cfg, strategy, b, seq, steps=5,
                          warmup=2, policy=policy)
            return {"config": 5, "metric": "ctx32k_tokens_per_sec",
                    "value": r["tokens_per_sec"], "unit": "tokens/sec",
                    "seq_len": seq, "batch": b, **r}
        except Exception as e:
            if not is_oom(e):
                raise
            last = e
    raise last


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,3,4,5")
    args = ap.parse_args()
    want = {int(x) for x in args.configs.split(",")}

    # one process, the device JAX gives it (bench.bench_device: a TPU,
    # or the CPU smoke only under an explicit JAX_PLATFORMS=cpu)
    from bench import bench_device
    dev, on_tpu = bench_device()
    kind = getattr(dev, "device_kind", dev.platform)

    runners = {1: lambda: config1_mlp(),
               3: lambda: config3_llama_autoparallel(on_tpu),
               4: lambda: config4_moe(on_tpu),
               5: lambda: config5_long_context(on_tpu)}
    for c in sorted(want):
        if c not in runners:
            continue
        try:
            rec = runners[c]()
        except Exception as e:  # keep the suite going; record the failure
            rec = {"config": c, "error": f"{type(e).__name__}: {e}"[:200]}
        rec["device"] = kind
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
