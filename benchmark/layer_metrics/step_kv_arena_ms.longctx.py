"""Device self time per engine iteration in the KV arena: the scope
``hetu.kv_arena`` — here the paged writes of K, V and the stride means
of the four sparse layers, both lanes — plus every instruction that
moves the arena (``program_trace``; the lightning layers' slot states
are no part of it: ``step_state_copies_ms.longctx``)."""
NAME, UNIT = "step_kv_arena_ms.longctx", "ms"
LAYER = "KV manager (serving/kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "kv_arena")
