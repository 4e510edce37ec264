"""Device self time per engine iteration in COPIES of the lightning
layers' slot states: instructions the compiler named ``copy*`` whose
float32 result holds at least one layer's states of every slot (slots x
32 x 128 x 128) and that stand under none of the lightning scopes — XLA
keeps a run's state leaf in two layouts between the lanes' loops and
transposes it on the way (PERF.md section 5). They carry no scope of
the program's, so ``program_trace`` lists them as ``unscoped``; this
reader names them by what they move (``longctx.state_copy_seconds``)."""
NAME, UNIT = "step_state_copies_ms.longctx", "ms"
LAYER = "lightning attention (nn/parallel.py, ops/linear_attention.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.state_copies_ms_per_step(run)
