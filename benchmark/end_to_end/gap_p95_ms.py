"""95th percentile over ALL gaps between successive tokens as the
streaming client received them (requests due in the window)."""
NAME, UNIT = "gap_p95_ms", "ms"


def read(run):
    from benchmark import readers, stats
    p = stats.percentile(stats.token_gaps(readers.judged(run)), 95)
    return None if p is None else 1e3 * p
