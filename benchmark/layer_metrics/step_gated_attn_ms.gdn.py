"""Device self time per engine iteration under ``hetu.gated_attn`` — the
WHOLE mixer of the three gated softmax attention layers, both lanes: the
doubled query projection, the q and k norms, the quarter rotary, the
arena writes, the in-pack flash part and both paged calls at heads of
256, the output gate and projection (``gdn.ms_per_step``: the scope
anywhere in an instruction's path, so the kernels' own scopes inside it
count)."""
NAME, UNIT = "step_gated_attn_ms.gdn", "ms"
LAYER = "gated attention (nn/parallel.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import gdn
    return gdn.ms_per_step(run, "hetu.gated_attn")
