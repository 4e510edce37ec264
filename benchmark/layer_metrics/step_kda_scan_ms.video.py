"""Device self time per engine iteration under ``hetu.kda_scan`` — the
prefill pack's chunk form of the gated delta rule (the triangular
solves, the piece loop over the runs' states), ten layers
(``longctx.ms_per_step``)."""
NAME, UNIT = "step_kda_scan_ms.video", "ms"
LAYER = "Kimi Delta Attention (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.kda_scan")
