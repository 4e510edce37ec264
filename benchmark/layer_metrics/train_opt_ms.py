"""Device self time per train step in the optimizer phase (``hetu.opt``:
gradient norm, clip, update, apply) (``program_trace``, rule of
``telemetry.device_scopes.classify``)."""
NAME, UNIT = "train_opt_ms", "ms"
LAYER = "train step (engine/train_step.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "opt")
