"""Records the small trace the rehearsal test reduces
(``v5e_small.xplane.pb``). Run on the chip:

    chiprun -- python tests/benchmark/data/record_trace.py

and copy ``chiprun_out/v5e_small.xplane.pb`` next to this script; the
numbers the test holds are in the line it prints.
"""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from benchmark import trace as trace_mod

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    out = os.path.join(ROOT, "chiprun_out")
    tdir = os.path.join(out, "small_trace")
    shutil.rmtree(tdir, ignore_errors=True)

    @jax.jit
    def chain(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.01
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    chain(x).block_until_ready()
    # the Python tracer would put thousands of events in the file
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench:call"):
                chain(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = trace_mod.newest_xplane(tdir)
    dst = os.path.join(out, "v5e_small.xplane.pb")
    shutil.copy(src, dst)
    r = trace_mod.reduce_trace(dst)
    print(json.dumps({"bytes": os.path.getsize(dst),
                      "window_s": r["window_s"], "busy_s": r["busy_s"],
                      "op_calls": r["op_calls"],
                      "device_ops": r["device_ops"],
                      "idle_gaps": r["idle_gaps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
