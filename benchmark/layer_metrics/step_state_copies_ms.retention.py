"""Device self time per engine iteration in COPIES of the retention
layers' state leaf: instructions the compiler named ``copy*`` whose
float32 result holds at least one layer of every slot's state
(``retention.state_copies_ms_per_step``); 0 where the leaf is updated in
place, which is the design."""
NAME, UNIT = "step_state_copies_ms.retention", "ms"
LAYER = "power retention (nn/parallel.py, ops/retention_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import retention
    return retention.state_copies_ms_per_step(run)
