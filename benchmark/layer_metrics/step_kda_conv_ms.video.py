"""Device self time per engine iteration under ``hetu.kda_conv`` — the
short convolution over q, k and v before the activation, with the
reads and writes of the slots' tails, both lanes, ten layers
(``longctx.ms_per_step``: the scope anywhere in an instruction's
path)."""
NAME, UNIT = "step_kda_conv_ms.video", "ms"
LAYER = "Kimi Delta Attention (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.kda_conv")
