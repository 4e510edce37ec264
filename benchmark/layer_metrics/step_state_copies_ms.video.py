"""Device self time per engine iteration in COPIES of the KDA layers'
slot leaves: instructions the compiler named ``copy*`` whose float32
result holds at least one layer's states (slots x 32 x 128 x 128) or
tails (slots x 3 x 12,288) of every slot and that stand under none of
the KDA scopes (``kda.state_copies_ms_per_step``); 0 where the leaves
are updated in place."""
NAME, UNIT = "step_state_copies_ms.video", "ms"
LAYER = "Kimi Delta Attention (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import kda
    return kda.state_copies_ms_per_step(run)
