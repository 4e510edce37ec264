"""Device self time per engine iteration under ``hetu.sample``
(logits adjustment, draws, speculative verify; ``program_trace``)."""
NAME, UNIT = "step_sample_ms.chat", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "sample")
