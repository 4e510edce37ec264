"""Device self time per engine iteration, both lanes and all ten expert
layers, in the router 512 wide, the group scores and the two top-k,
the sort and the row gather (``hetu.moe_route``) (``scopes``)."""
NAME, UNIT = "step_moe_route_ms.video", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_route")
