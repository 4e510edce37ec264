"""Device self time per engine iteration, both lanes and all expert
layers, in the router, the (bias-corrected or group-limited) top-k,
the sort by local expert and the gather of the sorted rows
(``hetu.moe_route``) (``scopes``)."""
NAME, UNIT = "step_moe_route_ms.backlogs", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_route")
