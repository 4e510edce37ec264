"""Device self time per engine iteration in the decode lane
(``hetu.decode_lane``: the rows' page selection and sparse read, their
state updates, the weights' matmuls; arena writes and sampling not)
(``program_trace``)."""
NAME, UNIT = "step_decode_ms.longctx", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "decode")
