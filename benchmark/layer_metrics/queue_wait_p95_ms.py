"""95th percentile of submit -> admit on the engine's clock
(``Request.timing()["queued_ms"]`` of each finished request)."""
NAME, UNIT = "queue_wait_p95_ms", "ms"
LAYER = "scheduler and admission (serving/scheduler.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import stats
    return stats.percentile([t["queued_ms"] for t in
                             run.records["timings"]
                             if "queued_ms" in t], 95)
