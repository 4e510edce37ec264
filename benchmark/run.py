"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A new process per run. It takes the devices JAX gives it and exits
non-zero, printing no result, when they are not TPUs or fewer than the
cell asks for: there is no CPU fallback. Information lines come first;
the LAST line of stdout is the result object (see README.md).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)

    try:
        import jax
        t_jax = time.perf_counter()
        import hetu_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark: the system under test is not here: {e}",
              file=sys.stderr)
        return 1
    from benchmark import harness
    from hetu_tpu.utils.logging import get_logger
    # the program echoes one line a step at INFO; keep stderr for faults
    get_logger().setLevel("WARNING")

    manifest = harness.load_manifest(args.manifest)
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"benchmark: no workload {args.workload!r}",
              file=sys.stderr)
        return 1
    t_program = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU "
              f"chip(s), JAX gives {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        return 1

    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    cache = enable_persistent_compilation_cache(min_compile_seconds=0.0)
    harness.say(workload=args.workload, seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                device_kind=devices[0].device_kind,
                devices=len(devices), jax=jax.__version__,
                compile_cache=cache,
                # where set-up goes before the runner starts
                import_jax_s=t_jax - T_PROCESS,
                import_program_s=t_program - t_jax,
                devices_s=t_devices - t_program)
    out = harness.run_cell(
        manifest, ROOT, args.workload, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        on_chip=True, t_process=T_PROCESS)
    harness.say(info=out["info"])
    if out["why_incorrect"]:
        harness.say(why_incorrect=out["why_incorrect"])
    line = out["line"]
    if args.trace and not line["device"]["busy_s"] > 0:
        print("benchmark: the trace shows no operation on the device",
              file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
