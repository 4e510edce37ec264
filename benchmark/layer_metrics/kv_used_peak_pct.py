"""Peak share of the arena's blocks in use, sampled by the benchmark
each second of the window (``BlockManager.blocks_in_use`` over
``KVPool.n_blocks``)."""
NAME, UNIT = "kv_used_peak_pct", "%"
LAYER = "KV manager (serving/kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    used = run.records["kv_blocks_in_use"]
    return 100.0 * max(used) / run.records["kv_blocks"] if used else None
