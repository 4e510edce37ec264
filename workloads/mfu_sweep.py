"""Sweep batch x remat for the GPT-2 pretrain step on the local chip.

Finds the highest-MFU configuration for ``bench.py`` (BASELINE config 2).
MFU accounting counts model FLOPs only (PaLM appendix B), so remat must buy
a bigger batch than its recompute overhead costs to win.

Each config runs in its OWN subprocess with a per-config timeout: a hang
must cost one config's budget, not the whole sweep's. The parent never
touches a JAX backend, so each child in turn is the one process that
holds the chip.

Usage: python workloads/mfu_sweep.py [--steps 10]
"""

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_one(batch, remat, unroll, args, attn="auto"):
    """Measure a single config in THIS process; print one RESULT line."""
    if args.ce == "fused":
        os.environ["HETU_LM_LOSS_IMPL"] = "fused"
    import jax
    import jax.numpy as jnp

    from bench import peak_flops, model_flops_per_token
    from hetu_tpu.utils.profiler import sync_result
    from hetu_tpu import optim
    from hetu_tpu.core.dtypes import Policy, autocast
    from hetu_tpu.engine import make_plan, init_state, build_train_step
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy

    dev = jax.devices()[0]
    peak = peak_flops(dev)
    if not peak:
        raise SystemExit(f"no TPU (device {dev.device_kind!r})")
    cfg = GPTConfig.small()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-4, weight_decay=0.01)
    param_dt = jnp.float32 if args.param_dtype == "fp32" else jnp.bfloat16
    policy = Policy(param_dtype=param_dt, compute_dtype=jnp.bfloat16)
    seq = args.seq
    strategy = Strategy(remat=remat, unroll=unroll)
    with autocast(policy):
        plan = make_plan(model, opt, strategy)
        state = init_state(model, opt, plan, jax.random.key(0))
        step = build_train_step(model, opt, plan, attn_impl=attn)
        ids = jax.random.randint(jax.random.key(1),
                                 (batch, seq + 1), 0, cfg.vocab_size)
        b = plan.shard_batch({"input_ids": ids[:, :-1],
                              "labels": ids[:, 1:]})
        for _ in range(max(1, args.warmup)):
            state, m = step(state, b)
        sync_result(m["loss"])
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, m = step(state, b)
        sync_result(m["loss"])
        dt = (time.perf_counter() - t0) / args.steps
    n = sum(x.size for x in jax.tree.leaves(state.params))
    tps = batch * seq / dt
    mfu = model_flops_per_token(cfg, n, seq) * tps / peak
    print(f"RESULT {mfu:.4f} {batch} {remat} {int(unroll)} {attn} "
          f"{dt * 1e3:.1f} {tps:.0f} {dev.device_kind}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--param-dtype", choices=("fp32", "bf16"),
                    default="fp32",
                    help="bf16 halves param/grad HBM traffic (Adam "
                         "moments stay fp32)")
    ap.add_argument("--ce", choices=("chunked", "fused"), default="chunked",
                    help="LM-loss impl: XLA chunking or the fused "
                         "streaming Pallas kernel (ops/fused_ce_pallas)")
    ap.add_argument("--grid", default=None,
                    help="comma list of batch:remat:unroll[:attn] tuples, "
                         "e.g. 32:selective:1,32:selective:1:reference "
                         "(attn: auto|pallas|reference; default built-in)")
    ap.add_argument("--one", default=None, metavar="B:R:U[:A]",
                    help="internal: measure a single config in-process")
    ap.add_argument("--per-config-tmo", type=int, default=300,
                    help="seconds each config subprocess may take "
                         "(compile + measure)")
    args = ap.parse_args()

    if args.one:
        parts = args.one.split(":")
        b, r, u = parts[:3]
        attn = parts[3] if len(parts) > 3 else "auto"
        measure_one(int(b), r, bool(int(u)), args, attn=attn)
        return

    if args.grid:
        grid = []
        for item in args.grid.split(","):
            parts = item.split(":")
            b, r, u = parts[:3]
            attn = parts[3] if len(parts) > 3 else "auto"
            grid.append((int(b), r, bool(int(u)), attn))
    else:
        grid = [
            (8, "none", False, "auto"), (8, "none", True, "auto"),
            (16, "selective", True, "auto"),
            (32, "selective", False, "auto"),
            (32, "selective", True, "auto"),
            (48, "selective", True, "auto"),
            (64, "selective", True, "auto"),
            (32, "full", True, "auto"),
            # whole-step pallas-vs-XLA attention at the winning shape:
            # the decision comes from amortized step time
            (32, "selective", True, "reference"),
        ]
    print(f"seq={args.seq} params={args.param_dtype} "
          f"per_config_tmo={args.per_config_tmo}s")
    print(f"{'batch':>5} {'remat':>10} {'unroll':>6} {'attn':>9} "
          f"{'step_ms':>8} {'tok/s':>9} {'mfu':>6}")
    results = []
    infeasible = _load_infeasible(args.seq)
    for batch, remat, unroll, attn in grid:
        # offline AOT feasibility (aot_check.py --sweep-feasibility):
        # a config the compiler already refused for HBM must not burn
        # window minutes re-discovering that on the chip
        # the feasibility grid compiled pallas attention + chunked CE;
        # a fused-CE sweep uses LESS memory, so the skip would be wrong
        if attn in ("auto", "pallas") and args.ce == "chunked" and \
                feasibility_key(batch, remat, unroll,
                                args.param_dtype) in infeasible:
            print(f"{batch:>5} {remat:>10} {unroll!s:>6} {attn:>9}   "
                  f"SKIP (AOT: does not fit HBM)", flush=True)
            continue
        cmd = [sys.executable, os.path.abspath(__file__),
               "--one", f"{batch}:{remat}:{int(unroll)}:{attn}",
               "--steps", str(args.steps), "--warmup", str(args.warmup),
               "--seq", str(args.seq), "--param-dtype", args.param_dtype,
               "--ce", args.ce]
        try:
            r = subprocess.run(cmd, timeout=args.per_config_tmo,
                               capture_output=True, text=True)
            line = next((l for l in r.stdout.splitlines()
                         if l.startswith("RESULT ")), None)
        except subprocess.TimeoutExpired:
            r, line = None, None
            print(f"{batch:>5} {remat:>10} {unroll!s:>6} {attn:>9}   "
                  f"TIMEOUT ({args.per_config_tmo}s)", flush=True)
        if line:
            # maxsplit: device_kind has spaces ("TPU v5 lite")
            _, mfu, b_, r_, u_, a_, ms, tps, kind = line.split(maxsplit=8)
            print(f"{batch:>5} {remat:>10} {unroll!s:>6} {attn:>9} "
                  f"{float(ms):>8.1f} {float(tps):>9.0f} "
                  f"{float(mfu):>6.4f}", flush=True)
            results.append((float(mfu), batch, remat, unroll, attn, kind))
        else:
            # r is None on TIMEOUT (already reported above)
            if r is not None:
                msg = (r.stderr.strip().splitlines() or ["no output"])[-1][:80]
                print(f"{batch:>5} {remat:>10} {unroll!s:>6} {attn:>9}   "
                      f"FAIL {msg}", flush=True)
    if results:
        best = max(results)
        print(f"best: batch={best[1]} remat={best[2]} unroll={best[3]} "
              f"attn={best[4]} mfu={best[0]:.4f} on {best[5]}")
        _record_best(best, args.param_dtype, args.ce)


# sweep contenders at/above the current winner's batch — ONE definition
# shared with aot_check.sweep_feasibility so the offline feasibility keys
# always match what the sweep looks up
CONTENDER_GRID = ((32, "selective", True), (48, "selective", True),
                  (64, "selective", True))


def feasibility_key(batch, remat, unroll, param_dtype) -> str:
    return f"{batch}:{remat}:{int(unroll)}:{param_dtype}"


def _load_infeasible(seq: int, path: str = None) -> set:
    """Config keys ("batch:remat:unroll:param_dtype") the offline AOT
    pass recorded as NOT fitting HBM — only trusted at the same seq and
    for the pallas attention path the feasibility grid compiled."""
    import json
    path = path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "out",
        "sweep_feasible.json")
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("seq") != seq:
            return set()
        return {k for k, r in data.get("rows", {}).items()
                if r.get("fits") is False}
    except (OSError, ValueError, AttributeError):
        return set()


def _record_best(best, param_dtype, ce_impl="chunked"):
    """Persist the sweep winner (max-mfu wins
    across sweep variants — the bf16 sweep only overwrites the fp32
    entry when it actually measured higher)."""
    import json
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "out", "sweep_best.json")
    mfu, batch, remat, unroll, attn, kind = best
    entry = {"mfu": mfu, "batch": batch, "remat": remat,
             "unroll": bool(unroll), "attn": attn,
             "param_dtype": param_dtype, "ce": ce_impl, "device": kind,
             "seq": 1024}
    try:
        with open(path) as f:
            prev = json.load(f)
        if prev.get("mfu", 0.0) >= mfu:
            return
    except (OSError, ValueError):
        pass
    with open(path, "w") as f:
        json.dump(entry, f)
    print(f"recorded sweep winner to {path}")


if __name__ == "__main__":
    main()
