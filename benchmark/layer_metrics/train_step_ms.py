"""Median step time from ``Trainer``'s own records in the window (raw
tokens over its ``tokens_per_sec``, each step ended by the blocking
fetch of its loss)."""
NAME, UNIT = "train_step_ms", "ms"
LAYER = "train step (engine/train_step.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import stats
    m = stats.median(run.records["step_s"])
    return None if m is None else 1e3 * m
