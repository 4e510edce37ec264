"""Device self time per train step in the backward of the loss: everything
under ``transpose(`` and every remat recomputation, flash backward
kernels included (``program_trace``, rule of
``telemetry.device_scopes.classify``)."""
NAME, UNIT = "train_bwd_ms", "ms"
LAYER = "train step (engine/train_step.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "bwd")
