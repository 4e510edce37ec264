"""Device self time per engine iteration under ``hetu.kda_update`` —
the decode rows' one-token delta-rule update of the live slots' states
(their gather out of the leaf and scatter back included), ten layers
(``longctx.ms_per_step``)."""
NAME, UNIT = "step_kda_update_ms.video", "ms"
LAYER = "Kimi Delta Attention (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.kda_update")
