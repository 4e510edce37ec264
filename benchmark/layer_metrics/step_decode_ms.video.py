"""Device self time per engine iteration in the decode lane
(``hetu.decode_lane``: the rows' projections, one-token convolution and
state update in ten layers, the latent paged call in two, the dense,
shared and expert matmuls) (``program_trace``)."""
NAME, UNIT = "step_decode_ms.video", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "decode")
