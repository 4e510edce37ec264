"""Device self time per engine iteration under ``hetu.sparse_select`` —
choosing every row's pages: the compressed-key
scores, the group sum, the block max, the forced blocks and the top-k,
both lanes and all sparse layers (``longctx.ms_per_step``: the scope anywhere in an
instruction's path)."""
NAME, UNIT = "step_sparse_select_ms.longctx", "ms"
LAYER = "block-sparse attention (nn/parallel.py, ops/sparse_select.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.sparse_select")
