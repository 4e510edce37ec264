"""Device self time per engine iteration in the decode/verify lane
(``hetu.decode_lane``: its latent paged call, the absorbed products, its
expert and shared matmuls; arena writes and sampling not)
(``program_trace``)."""
NAME, UNIT = "step_decode_ms.longdoc", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "decode")
