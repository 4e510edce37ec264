"""Device self time per engine iteration, both lanes and all expert
layers, in the shared experts' gated MLP — averaged, summed or one
under its own gate, as the configuration has them
(``hetu.moe_shared``) (``scopes``)."""
NAME, UNIT = "step_moe_shared_ms.backlogs", "ms"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.moe_shared")
