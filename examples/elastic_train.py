"""Elastic multi-process training — the reference's ``examples/ampelos``
flow: launcher spawns workers, a worker dies, the pool restarts the
generation, training resumes from the last sharded checkpoint.

Run (CPU simulation, 2 workers, rank 1 dies once at step 2):
  python examples/elastic_train.py
The same file is both launcher (no HETU_RANK in env) and worker.
"""

import json
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def worker():
    os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU multi-process demo
    import jax
    import numpy as np
    from hetu_tpu import optim
    from hetu_tpu.engine import build_train_step, init_state, make_plan
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from hetu_tpu.parallel.strategy import Strategy
    from hetu_tpu.rpc.launcher import bootstrap_distributed
    from hetu_tpu.utils.dist_checkpoint import (
        load_checkpoint_distributed, save_checkpoint_distributed,
    )

    ctx = bootstrap_distributed()
    out = os.environ["HETU_OUT"]
    ckpt = os.path.join(out, "ckpt")
    cfg = GPTConfig.tiny()
    model = GPTLMHeadModel(cfg)
    opt = optim.adamw(1e-2)
    plan = make_plan(model, opt, Strategy(dp=ctx.num_processes))
    if ctx.generation > 0 and os.path.exists(
            os.path.join(ckpt, "meta.json")):
        state = load_checkpoint_distributed(ckpt, model, opt, plan=plan)
        print(f"[g{ctx.generation}/r{ctx.rank}] resumed at step "
              f"{int(jax.device_get(state.step))}", flush=True)
    else:
        state = init_state(model, opt, plan, jax.random.key(0))
    step_fn = build_train_step(model, opt, plan)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2 * ctx.num_processes, 65))
    batch = plan.shard_batch({"input_ids": ids[:, :-1],
                              "labels": ids[:, 1:]})
    for s in range(int(jax.device_get(state.step)), 6):
        state, m = step_fn(state, batch)
        save_checkpoint_distributed(ckpt, state)
        ctx.client.barrier(f"s{s}-g{ctx.generation}", ctx.num_processes,
                           f"w{ctx.rank}")
        print(f"[g{ctx.generation}/r{ctx.rank}] step {s} "
              f"loss {float(jax.device_get(m['loss'])):.4f}", flush=True)
        if ctx.generation == 0 and ctx.rank == 1 and s == 2:
            print(f"[g0/r1] simulating crash", flush=True)
            os._exit(1)
    ctx.shutdown()


def launcher():
    import tempfile
    from hetu_tpu.rpc.launcher import ElasticWorkerPool
    out = tempfile.mkdtemp(prefix="elastic_train_")
    with ElasticWorkerPool(os.path.abspath(__file__), 2, max_restarts=1,
                           env={"HETU_OUT": out},
                           log_dir=os.path.join(out, "logs")) as pool:
        summary = pool.run(timeout_s=600)
    print(json.dumps(summary))
    for f in sorted(os.listdir(os.path.join(out, "logs"))):
        print(f"--- {f}")
        with open(os.path.join(out, "logs", f)) as fh:
            print(fh.read().strip())


if __name__ == "__main__":
    if "HETU_RANK" in os.environ:
        worker()
    else:
        launcher()
