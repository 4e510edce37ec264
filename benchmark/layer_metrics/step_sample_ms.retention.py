"""Device self time per engine iteration under ``hetu.sample`` (both
lanes' sampling) (``program_trace``)."""
NAME, UNIT = "step_sample_ms.retention", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "sample")
