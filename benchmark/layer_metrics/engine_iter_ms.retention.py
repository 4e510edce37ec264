"""Milliseconds per engine iteration over the window (the window's
seconds over the increase of ``serving_attn_kernel_total``, which a
step without attention counts under ``path=none``)."""
NAME, UNIT = "engine_iter_ms.retention", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import readers
    return readers.engine_iter_ms(run)
