"""Device self time per engine iteration in the decode lane
(``hetu.decode_lane``: the live rows' projections, the state update in
place and the MLP in ten layers, the head; sampling not)
(``program_trace``)."""
NAME, UNIT = "step_decode_ms.retention", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "decode")
