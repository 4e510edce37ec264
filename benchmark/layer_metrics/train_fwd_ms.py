"""Device self time per train step in the forward of the loss
(``hetu.loss`` outside ``transpose(``), flash forward kernel included
(``program_trace``, rule of ``telemetry.device_scopes.classify``)."""
NAME, UNIT = "train_fwd_ms", "ms"
LAYER = "train step (engine/train_step.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "fwd")
