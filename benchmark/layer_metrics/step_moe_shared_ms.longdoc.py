"""Device self time per engine iteration, both lanes and all layers,
in the two summed shared experts' gated MLP (``hetu.moe_shared``) (``scopes``)."""
NAME, UNIT = "step_moe_shared_ms.longdoc", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_shared")
