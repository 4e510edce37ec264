"""Device self time per engine iteration in COPIES of the Gated DeltaNet
layers' slot leaves: instructions the compiler named ``copy*`` whose
float32 result holds whole layers of every slot's state (slots x 32 x
128 x 128) or tail (slots x 3 x 8,192) and that stand under none of the
Gated DeltaNet scopes (``gdn.state_copies_ms_per_step``); 0 where the
leaves are updated in place, which is the design."""
NAME, UNIT = "step_state_copies_ms.gdn", "ms"
LAYER = "Gated DeltaNet (nn/parallel.py, ops/kda.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import gdn
    return gdn.state_copies_ms_per_step(run)
