"""Device self time per engine iteration under ``hetu.retention_scan`` —
the prefill pack's chunk form of power retention: the operands'
transposes, ``hetu_retention_scan`` (a run's state tiles in VMEM across
its pieces, ``phi`` a feature row at a time) and the division, ten
layers (``longctx.ms_per_step``)."""
NAME, UNIT = "step_retention_scan_ms.retention", "ms"
LAYER = "power retention (nn/parallel.py, ops/retention_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.retention_scan")
