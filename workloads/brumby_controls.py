"""The negative controls of the ``brumby-14b-pp4`` cell, on the chip:
one run of the PROGRAM (the engine offline, the cell's configuration,
requests of the cell's lengths: chunked prefill, then decoding through
the state), then the comparison of
``benchmark/runners/serve_arch_state.py`` against the float32 quadratic
reference as it is and changed in ONE way each
(``benchmark/reference/brumby.py::CONTROL``): what the limit of
``benchmark/archs/brumby.py`` has to pass and to refuse (PERF.md section
6, PR 51).

    chiprun -- python3 workloads/brumby_controls.py [--seed N] [--config F]

Prints one JSON line a reading: ``correct`` as the runner's comparison
decides it, the largest gap, the gap's quantiles and the shares of
compared tokens more than 0.02 .. 1.0 below the reference's top logit.
(``--config tests/benchmark/configs/brumby-tiny.json --requests 4
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
    "no_gate": {"no_gate": True},
    "state_reset_every_chunk": {"reset_every": "prefill_chunk"},
    "phi_diagonal_only": {"diag_only": True},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147490251)
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark/configs/brumby-14b-pp4.json"))
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--prompt", type=int, default=16384)
    ap.add_argument("--outputs", type=int, default=96)
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmark import traffic
    from benchmark.model import dtype
    from benchmark.reference import brumby as reference
    from benchmark.runners import serve_arch, serve_arch_state
    from hetu_tpu.serving import ServingEngine
    from hetu_tpu.serving.scheduler import SamplingParams

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
    reqs = [eng.submit(rng.integers(1, config["vocab_size"] - 1,
                                    args.prompt, dtype=np.int32),
                       SamplingParams(max_tokens=args.outputs))
            for _ in range(args.requests)]
    iters = eng.run_until_drained()
    recs = [{"prompt": r.prompt, "tokens": r.tokens} for r in reqs]
    print(json.dumps({"program_s": time.perf_counter() - t0,
                      "iterations": iters,
                      "tokens": [len(r.tokens) for r in reqs],
                      "device": jax.devices()[0].device_kind}), flush=True)
    eng.pool.caches = None
    for name, control in CONTROLS.items():
        if args.only and name not in args.only:
            continue
        reference.CONTROL.clear()
        reference.CONTROL.update({
            k: getattr(jnp, v) if k == "operands"
            else serve[v] if k == "reset_every" else v
            for k, v in control.items()})
        t0 = time.perf_counter()
        rows = serve_arch.ReferenceRows(arch, config, params,
                                        serve["max_len"], args.outputs)
        got = serve_arch_state.gaps(arch, config, rows, params, recs,
                                    serve["max_len"])
        why, seen = serve_arch_state.judge(got, arch.LOGIT_TOL)
        gap = np.concatenate([g for g, _, _ in got])
        print(json.dumps({
            "control": name, "correct": not why,
            "limit": arch.LOGIT_TOL, "compared": len(gap),
            "max_gap": seen["max_logit_gap"],
            "random_token_gap": seen["median_logit_below_top"],
            "gap_q50_90_99": [float(x) for x in np.quantile(
                gap, [0.5, 0.9, 0.99])],
            "over": {str(t): float((gap > t).mean())
                     for t in (0.02, 0.05, 0.1, 0.2, 0.5, 1.0)},
            "largest": seen["largest_gaps"][:3],
            "s": time.perf_counter() - t0}), flush=True)
    reference.CONTROL.clear()


if __name__ == "__main__":
    main()
