"""Device self time per engine iteration in the decode lane — here the
BLOCK lane (``hetu.decode_lane``: 4 q rows a slot through 48 layers,
its paged kernel, its expert matmuls, the head and, under
``hetu.diffusion_sample``, the sampler; arena writes not)
(``program_trace``)."""
NAME, UNIT = "step_decode_ms.blockgen", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "decode")
