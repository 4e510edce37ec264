"""Device self time per engine iteration under ``hetu.sample``
(logits adjustment over 163,840 columns, draws, speculative verify)
(``program_trace``)."""
NAME, UNIT = "step_sample_ms.longdoc", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "sample")
