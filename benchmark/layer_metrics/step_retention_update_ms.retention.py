"""Device self time per engine iteration under ``hetu.retention_update``
— the decode rows' one-token update on the state leaf in place
(``hetu_retention_update``) and ``phi`` of the rows, ten layers
(``longctx.ms_per_step``)."""
NAME, UNIT = "step_retention_update_ms.retention", "ms"
LAYER = "power retention (nn/parallel.py, ops/retention_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.retention_update")
