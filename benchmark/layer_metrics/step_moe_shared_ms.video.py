"""Device self time per engine iteration, both lanes and all ten expert
layers, in the shared expert (``hetu.moe_shared``) (``scopes``)."""
NAME, UNIT = "step_moe_shared_ms.video", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_shared")
