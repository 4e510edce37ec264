"""Device self time per engine iteration under ``hetu.linear_update`` —
the decode rows' one-token state update, all
lightning layers (``longctx.ms_per_step``: the scope anywhere in an
instruction's path)."""
NAME, UNIT = "step_linear_update_ms.longctx", "ms"
LAYER = "lightning attention (nn/parallel.py, ops/linear_attention.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.linear_update")
