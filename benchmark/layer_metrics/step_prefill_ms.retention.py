"""Device self time per engine iteration in the packed prefill lane
(``hetu.prefill_lane``: the chunk's projections, the retention scan over
the pack's pieces and the SwiGLU MLP in ten layers; sampling not)
(``program_trace``)."""
NAME, UNIT = "step_prefill_ms.retention", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "prefill")
