"""Compare pipeline schedules in the host-scheduled (hetero) executor:
GPipe vs 1F1B at increasing microbatch counts.

Both schedules share the same bubble fraction; 1F1B's win is *memory* —
at most ``pp`` microbatches of activations live at once instead of all
``nm`` (reference: ``GeneratePipedreamFlushSchedule``,
``executable_graph.cc:836`` vs the gpipe variant :803). On the virtual
CPU mesh we report wall-clock (sanity: comparable) and peak host RSS
delta as the memory proxy.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python workloads/pipeline_sched.py [--nm 8]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import time

from hetu_tpu import optim
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.hetero import (
    HeteroStrategy, HeteroTrainStep, StageSpec, init_hetero_state,
    make_hetero_plan,
)


def measure(schedule: str, nm: int, steps: int = 3, warmup: int = 1):
    n_dev = len(jax.devices())
    if n_dev < 4:
        raise SystemExit(
            f"needs >= 4 devices for pp x tp stages, have {n_dev} — run "
            "with XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "JAX_PLATFORMS=cpu")
    pp = 4
    cfg = GPTConfig(vocab_size=512, max_positions=128, hidden_size=128,
                    num_layers=pp * 2, num_heads=8)
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-3)
    strategy = HeteroStrategy(
        stages=tuple(StageSpec(layers=2, dp=1, tp=n_dev // pp)
                     for _ in range(pp)),
        num_microbatches=nm)
    plan = make_hetero_plan(model, strategy)
    state = init_hetero_state(model, opt, plan, jax.random.key(0))
    step = HeteroTrainStep(model, opt, plan, schedule=schedule)
    b = nm * 2
    ids = jax.random.randint(jax.random.key(1), (b, 65), 0, cfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    for _ in range(max(1, warmup)):
        state, m = step(state, batch)
    float(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch)
    loss = float(jax.device_get(m["loss"]))
    dt = (time.perf_counter() - t0) / steps
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return dt, loss, rss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nm", type=int, default=8)
    ap.add_argument("--schedule", default=None,
                    help="internal: run ONE schedule and print its JSON "
                         "(peak RSS is a process-wide high-water mark, so "
                         "each schedule must run in its own process)")
    args = ap.parse_args()
    if args.schedule:
        dt, loss, rss = measure(args.schedule, args.nm)
        print(json.dumps({"step_ms": round(dt * 1e3, 1),
                          "loss": round(loss, 4),
                          "peak_rss_mb": rss // 1024}))
        return
    import subprocess
    out = {"nm": args.nm,
           "device": getattr(jax.devices()[0], "device_kind",
                             jax.devices()[0].platform)}
    for schedule in ("gpipe", "1f1b"):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--nm", str(args.nm), "--schedule", schedule],
            capture_output=True, text=True, timeout=1200,
            env=dict(os.environ))
        if r.returncode != 0:
            out[f"{schedule}_error"] = r.stderr[-200:]
            continue
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        for k, v in rec.items():
            out[f"{schedule}_{k}"] = v
    if "gpipe_peak_rss_mb" in out and "1f1b_peak_rss_mb" in out:
        out["rss_saving_mb"] = out["gpipe_peak_rss_mb"] \
            - out["1f1b_peak_rss_mb"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
