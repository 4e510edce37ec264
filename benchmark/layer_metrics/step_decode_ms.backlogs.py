"""Device self time per engine iteration in the decode/verify lane
(``hetu.decode_lane``: whatever the configuration's layers do for the
live rows — the paged or latent call, a state's one-token update, the
expert, shared and dense matmuls, the head; in the block-diffusion
cell the BLOCK lane with its sampler — arena writes and, elsewhere,
sampling not) (``program_trace``). One reader for every cell that
reports ``serve_tokens_per_s`` and lists itself here."""
NAME, UNIT = "step_decode_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "decode")
