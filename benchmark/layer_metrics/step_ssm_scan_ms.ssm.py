"""Device self time per engine iteration under ``hetu.ssm_scan`` — the
prefill pack's selective scan: the operands' relayout to whole
registers, ``hetu_selective_scan`` (a run's state in registers across
a piece's tokens) and the result's way back, 26 layers
(``longctx.ms_per_step``)."""
NAME, UNIT = "step_ssm_scan_ms.ssm", "ms"
LAYER = "selective scan (nn/parallel.py, ops/selective_scan_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import ssm
    return ssm.ms_per_step(run, "hetu.ssm_scan")
