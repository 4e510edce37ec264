"""Device self time per engine iteration under ``hetu.gdn_update`` — the
decode rows' one-token update on the stacked state leaf in place, ONE
Pallas call a layer call, nine layers (``gdn.ms_per_step``)."""
NAME, UNIT = "step_gdn_update_ms.gdn", "ms"
LAYER = "Gated DeltaNet (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import gdn
    return gdn.ms_per_step(run, "hetu.gdn_update")
