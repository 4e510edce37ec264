"""One whole ``harness.run_cell`` at a tiny size on the CPU, held to the
STATE the serving tests compare — ``need`` requests finished in the
window, for the comparison to draw from — and not to what a window of
1.5 s holds on this machine at this moment: under the driver's six
workers a window finishes a fraction of what it does alone, and a test
that counted on eight finished requests (or on the share a control reads
over a few dozen positions) then failed for the machine's load. Where
fewer finished, the run is made again with the window doubled; the
result says which window it was (``window_asked_s``)."""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

#: the longest window tried: a machine on which this finishes fewer than
#: ``need`` is reported by the test's own asserts
LONGEST_S = 24.0


def run_cell(manifest: str, workload: str, *, seed: int, trace=False,
             seconds: float = 1.5, need: int = 8) -> dict:
    import jax
    while True:
        out = harness.run_cell(
            harness.load_manifest(manifest), ROOT, workload, seed=seed,
            seconds=seconds, trace=trace, devices=jax.devices(),
            on_chip=False, t_process=time.perf_counter())
        if out["info"]["n_finished"] >= need or seconds >= LONGEST_S:
            out["window_asked_s"] = seconds
            return out
        seconds *= 2
