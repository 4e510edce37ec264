"""Milliseconds per engine iteration over the window, every slot full:
the window's seconds over the increase of ``serving_attn_kernel_total``
(which a step without attention counts under ``path=none``). In the
block-diffusion cell an iteration is one pass of every live slot's
block. One reader for every cell that reports ``serve_tokens_per_s``
and lists itself here; a configuration needs nothing of its own to be
read by it."""
NAME, UNIT = "engine_iter_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import readers
    return readers.engine_iter_ms(run)
