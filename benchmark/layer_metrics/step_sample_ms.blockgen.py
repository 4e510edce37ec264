"""Device self time per engine iteration under
``hetu.diffusion_sample``: the block lane's sampler — the mask id held
at -inf, the top token and its float32 softmax share at 128 rows of
18,992 columns, the rank of a block's confidences, the transfer
(``scopes``; ``program_trace`` counts it in the decode lane's
bucket)."""
NAME, UNIT = "step_sample_ms.blockgen", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.diffusion_sample")
