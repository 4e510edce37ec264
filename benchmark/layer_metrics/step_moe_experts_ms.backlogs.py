"""Device self time per engine iteration, both lanes and all expert
layers, in the routed experts' grouped matmuls, their weighting and
the way back to token order (``hetu.moe_experts``) (``scopes``)."""
NAME, UNIT = "step_moe_experts_ms.backlogs", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_experts")
