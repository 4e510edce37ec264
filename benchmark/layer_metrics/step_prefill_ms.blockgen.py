"""Device self time per engine iteration in the packed prefill lane
(``hetu.prefill_lane``: a 512-token chunk of whole blocks through 48
layers, block-causal flash inside the pack; arena writes not; the lane
runs in the iterations that admit) (``program_trace``)."""
NAME, UNIT = "step_prefill_ms.blockgen", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "prefill")
