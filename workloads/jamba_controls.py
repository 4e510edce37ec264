"""The negative controls of the ``jamba2-3b`` cell, on the chip: one
run of the PROGRAM (the engine offline, the cell's configuration,
requests of the cell's lengths: chunked prefill, then decoding through
the state and the arena; the slot's state read where the last chunk and
where the last decoded token leave it), then the two comparisons of
``benchmark/runners/serve_arch_ssm.py`` — the emitted tokens and the
state — against the float32 token recurrence as it is and changed in
ONE way each (``benchmark/reference/jamba.py::CONTROL``): what the
runner's limits have to pass and to refuse (PERF.md section 6, PR
55).

    chiprun -- python3 workloads/jamba_controls.py [--seed N] [--config F]

Prints one JSON line a reading: ``correct`` as the runner's
comparisons decide it, the largest logit gap beside its limit and the
state's gap beside its limit.
(``--config tests/benchmark/configs/jamba-tiny.json --requests 4
--prompt 40 --outputs 12`` rehearses it on the CPU in seconds.)
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = {
    "none": {},
    "bfloat16_operands": {"operands": "bfloat16"},
    "float8_e4m3fn_operands": {"operands": "float8_e4m3fn"},
    "bfloat16_state": {"state_dtype": "bfloat16"},
    "state_reset_every_chunk": {"reset_every": "prefill_chunk"},
    "tail_dropped_every_chunk": {"drop_tail_every": "prefill_chunk"},
    "no_inner_norms": {"no_inner_norms": True},
    "no_skip": {"no_skip": True},
}


def main(controls=None, *, reference="jamba", seed=2155550251,
         config="benchmark/configs/jamba2-3b.json", extra=None):
    """``controls`` (default: this file's), the ``reference`` module's
    name under ``benchmark/reference/`` and the defaults of ``--seed``
    and ``--config`` are the cell's; ``extra(seen, recs, logits, limits)
    -> dict`` adds to a reading's line (``logits``: the reference's rows
    a compared request). ``workloads/qwen3_next_controls.py`` calls this
    with its own."""
    controls = CONTROLS if controls is None else controls
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=seed)
    ap.add_argument("--config", default=os.path.join(ROOT, config))
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=32768)
    ap.add_argument("--outputs", type=int, default=256)
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    import importlib
    import jax
    import jax.numpy as jnp
    from benchmark import traffic
    from benchmark.model import dtype
    from benchmark.runners import serve_arch, serve_arch_ssm, \
        serve_arch_ties
    reference = importlib.import_module(f"benchmark.reference.{reference}")
    from hetu_tpu.serving import ServingEngine

    with open(args.config) as f:
        config = json.load(f)
    serve = config["serve"]
    arch = serve_arch.load_arch(config["arch"])
    model = arch.build(config)
    params = jax.jit(lambda k: model.init(
        k, dtype=dtype(serve["param_dtype"])))(
            jax.random.key(traffic.jax_seed(args.seed)))
    eng = ServingEngine(
        model, params, max_len=serve["max_len"],
        prefill_chunk=serve["prefill_chunk"],
        cache_dtype=dtype(serve["cache_dtype"]),
        block_size=serve["block_size"], slots=serve["slots"],
        kv_blocks=serve["kv_blocks"])
    rng = traffic.rng_for(args.seed, "controls")
    t0 = time.perf_counter()
    recs = serve_arch_ssm.probe(
        arch, eng, [rng.integers(1, config["vocab_size"] - 1, args.prompt,
                                 dtype=np.int32)
                    for _ in range(args.requests)], args.outputs)
    print(json.dumps({"program_s": time.perf_counter() - t0,
                      "tokens": [len(r["tokens"]) for r in recs],
                      "device": jax.devices()[0].device_kind}), flush=True)
    eng.pool.caches = None
    limits = {n: float(getattr(arch, n)) for n in serve_arch_ties.LIMITS}
    for name, control in controls.items():
        if args.only and name not in args.only:
            continue
        reference.CONTROL.clear()
        reference.CONTROL.update({
            k: getattr(jnp, v) if k in ("operands", "state_dtype")
            else serve[v] if isinstance(v, str) else v
            for k, v in control.items()})
        t0 = time.perf_counter()
        rows = serve_arch.ReferenceRows(arch, config, params,
                                        serve["max_len"], args.outputs)
        logits = []

        def tokens_rows(*a):
            out = rows(*a)[:2]
            logits.append(np.asarray(out[0]))
            return out
        why, seen = serve_arch_ties.reference_check(
            limits, arch, config, tokens_rows, params, recs,
            serve["max_len"])
        more, state = serve_arch_ssm.state_check(
            arch, config, rows, params, recs, serve["max_len"])
        print(json.dumps({
            "control": name, "correct": not why + more,
            "tokens_correct": not why, "state_correct": not more,
            "limit": limits["LOGIT_TOL"],
            "compared": seen["compared_positions"],
            "max_gap": seen["max_logit_gap"],
            "random_token_gap": seen["median_logit_below_top"],
            "state_limit": state["state_tolerance"],
            "state_gap": state["state_gap"],
            "state_readings": state["state_readings"],
            **(extra(seen, recs, logits, limits) if extra else {}),
            "s": time.perf_counter() - t0}), flush=True)
    reference.CONTROL.clear()


if __name__ == "__main__":
    main()
