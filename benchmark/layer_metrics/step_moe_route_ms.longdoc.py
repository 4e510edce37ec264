"""Device self time per engine iteration, both lanes and all layers,
in the router, the bias-corrected top-k, the sort by expert and the
row gather (``hetu.moe_route``) (``scopes``)."""
NAME, UNIT = "step_moe_route_ms.longdoc", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_route")
