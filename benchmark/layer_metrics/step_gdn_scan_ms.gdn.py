"""Device self time per engine iteration under ``hetu.gdn_scan`` — the
prefill pack's chunk form of the gated delta rule, ONE Pallas call a
layer call (the Kimi Delta Attention kernel: the decay a head broadcast
over its channels, the key heads over their value heads) and the work
list beside it, nine layers (``gdn.ms_per_step``)."""
NAME, UNIT = "step_gdn_scan_ms.gdn", "ms"
LAYER = "Gated DeltaNet (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import gdn
    return gdn.ms_per_step(run, "hetu.gdn_scan")
