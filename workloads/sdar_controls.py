"""The negative controls of the ``sdar-30b-a3b-ep8`` cell, on the chip:
one run of the PROGRAM (the engine offline, the cell's configuration,
eight requests of the cell's lengths), then the comparison of
``benchmark/runners/serve_arch_blocks.py`` against the float32
reference as it is and changed in ONE way each
(``benchmark/reference/sdar_moe.py::CONTROL``): what the limits of
``benchmark/archs/sdar_moe.py`` have to pass and to refuse (PERF.md
section 6, PR 45).

    chiprun -- python3 workloads/sdar_controls.py [--seed N] [--config F]

Prints one JSON line a reading: the shares of compared tokens more than
0.1 .. 1.0 below the reference's top logit, the gap's quantiles, the
shares of passes whose choice lies more than 2 .. 50 % from the
reference rule's in confidence.
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
    "causal_inside_a_block": {"intra": "causal"},
    "no_commit_pass": {"keys_from": "last_denoise"},
    "own_keys_left_out": {"intra": "none"},
    "left_to_right": {"order": "left_to_right"},
    "sigmoid_router": {"score": "sigmoid"},
    "no_qk_norm": {"qk_norm": False},
    "float8_e4m3fn_operands": {"operands": "float8_e4m3fn"},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147490201)
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark/configs/sdar-30b-a3b-ep8.json"))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--outputs", type=int, nargs=2, default=(512, 1024))
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmark import traffic
    from benchmark.model import dtype
    from benchmark.reference import sdar_moe as reference
    from benchmark.runners import serve_arch, serve_arch_blocks
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
    outs = np.linspace(*args.outputs, args.requests).astype(int)
    t0 = time.perf_counter()
    reqs = [eng.submit(rng.integers(1, config["vocab_size"] - 1,
                                    args.prompt, dtype=np.int32),
                       SamplingParams(max_tokens=int(n))) for n in outs]
    eng.run_until_drained()
    recs = [{"prompt": r.prompt, "tokens": r.tokens, "result": r.result()}
            for r in reqs]
    print(json.dumps({"program_s": time.perf_counter() - t0,
                      "tokens": [len(r.tokens) for r in reqs],
                      "device": jax.devices()[0].device_kind}), flush=True)
    eng.pool.caches = None
    for name, control in CONTROLS.items():
        if args.only and name not in args.only:
            continue
        reference.CONTROL.clear()
        reference.CONTROL.update({
            k: getattr(jnp, v) if k == "operands" else v
            for k, v in control.items()})
        t0 = time.perf_counter()
        why, gap, margin, below, swap, largest = \
            serve_arch_blocks.readings(arch, config, params, recs,
                                       serve["max_len"])
        print(json.dumps({
            "control": name, "audits": why, "compared": len(gap),
            "over": {str(t): float((gap > t).mean())
                     for t in (0.1, 0.2, 0.3, 0.5, 0.75, 1.0)},
            "gap_q50_90_99_max": [float(x) for x in np.quantile(
                gap, [0.5, 0.9, 0.99, 1.0])],
            "random_token_gap": float(below.mean()),
            "margin_q01_50_99": [float(x) for x in np.quantile(
                margin, [0.01, 0.5, 0.99])],
            "passes": len(swap),
            "swaps_over": {str(t): float((swap > t).mean())
                           for t in (0.0, 0.05, 0.1, 0.2, 0.3, 0.4, 0.5)},
            "largest": largest[:3], "s": time.perf_counter() - t0}),
            flush=True)
    reference.CONTROL.clear()


if __name__ == "__main__":
    main()
