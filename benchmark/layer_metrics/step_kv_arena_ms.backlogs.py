"""Device self time per engine iteration in the KV arena: the scope
``hetu.kv_arena`` (the paged writes of both lanes — K and V, and in
the block-sparse layers the stride means beside them — and CoW) plus
every instruction that moves the arena (named copy / slice /
dynamic-update-slice with a result of at least one layer's arena leaf:
XLA's per-layer slices and whole-arena copies) (``program_trace``). A
slot's recurrent state is no part of it: ``step_state_copies_ms.*``."""
NAME, UNIT = "step_kv_arena_ms.backlogs", "ms"
LAYER = "KV manager (serving/kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "kv_arena")
