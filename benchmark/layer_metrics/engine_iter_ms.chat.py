"""Milliseconds per engine iteration under open-loop chat load."""
NAME, UNIT = "engine_iter_ms.chat", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import readers
    return readers.engine_iter_ms(run)
