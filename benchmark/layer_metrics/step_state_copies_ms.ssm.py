"""Device self time per engine iteration in COPIES of the Mamba layers'
slot leaves: instructions the compiler named ``copy*`` whose float32
result holds whole layers of every slot's state or tail
(``ssm.state_copies_ms_per_step``); 0 where the leaves are updated in
place, which is the design."""
NAME, UNIT = "step_state_copies_ms.ssm", "ms"
LAYER = "selective scan (nn/parallel.py, ops/selective_scan_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import ssm
    return ssm.state_copies_ms_per_step(run)
