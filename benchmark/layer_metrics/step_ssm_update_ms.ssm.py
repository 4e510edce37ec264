"""Device self time per engine iteration under ``hetu.ssm_update`` — the
decode rows' one-token update on the state leaf in place
(``hetu_selective_update``), 26 layers (``longctx.ms_per_step``)."""
NAME, UNIT = "step_ssm_update_ms.ssm", "ms"
LAYER = "selective scan (nn/parallel.py, ops/selective_scan_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import ssm
    return ssm.ms_per_step(run, "hetu.ssm_update")
