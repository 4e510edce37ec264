"""Device self time per engine iteration under ``hetu.gdn_conv`` — the
short convolution over q, k and v (8,192 channels) before the
activation, with the reads and writes of the slots' tails, both lanes,
nine layers (``gdn.ms_per_step``: the scope anywhere in an
instruction's path)."""
NAME, UNIT = "step_gdn_conv_ms.gdn", "ms"
LAYER = "Gated DeltaNet (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import gdn
    return gdn.ms_per_step(run, "hetu.gdn_conv")
