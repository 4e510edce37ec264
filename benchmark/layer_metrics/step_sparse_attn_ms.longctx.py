"""Device self time per engine iteration under ``hetu.sparse_attn`` —
the read of the chosen pages: the paged call on
a table of each (row, kv head)'s own pages and its work list, both
lanes and all sparse layers (``longctx.ms_per_step``: the scope anywhere in an
instruction's path)."""
NAME, UNIT = "step_sparse_attn_ms.longctx", "ms"
LAYER = "block-sparse attention (nn/parallel.py, ops/sparse_select.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    return longctx.ms_per_step(run, "hetu.sparse_attn")
