"""Device self time per engine iteration in the packed prefill lane
(``hetu.prefill_lane``, its kernels included; arena moves and sampling
not) (``program_trace``)."""
NAME, UNIT = "step_prefill_ms.chat", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "prefill")
