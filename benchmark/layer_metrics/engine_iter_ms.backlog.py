"""Milliseconds per engine iteration with every slot full."""
NAME, UNIT = "engine_iter_ms.backlog", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import readers
    return readers.engine_iter_ms(run)
