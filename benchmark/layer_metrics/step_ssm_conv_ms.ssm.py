"""Device self time per engine iteration under ``hetu.ssm_conv`` — the
short convolution over the mixer's inner channels with the reads and
writes of the slots' tails, both lanes, 26 layers
(``longctx.ms_per_step``: the scope anywhere in an instruction's
path)."""
NAME, UNIT = "step_ssm_conv_ms.ssm", "ms"
LAYER = "selective scan (nn/parallel.py, ops/selective_scan_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import ssm
    return ssm.ms_per_step(run, "hetu.ssm_conv")
