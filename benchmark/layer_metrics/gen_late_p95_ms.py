"""How late the load generator sent: send time minus due time, 95th
percentile over the window's requests. A starved generator must not be
read as a fast server."""
NAME, UNIT = "gen_late_p95_ms", "ms"
LAYER = "load generator (benchmark's own)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import readers, stats
    p = stats.percentile(stats.late_samples(readers.judged(run)), 95)
    return None if p is None else 1e3 * p
