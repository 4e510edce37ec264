"""95th percentile of the time from a request's DUE time to the
client's receipt of its first token, over every request due in the
window; a failed or unfinished request counts as the drain deadline.

Per layer, not end to end: at 2 requests/s a window holds ~90 requests,
the 95th percentile is the fifth largest of them, and an engine
iteration (~190 ms) quantizes it; the driver's check read its runs 5-6 %
apart, more than half of the widest bound there is (PERF.md)."""
NAME, UNIT = "first_token_p95_ms", "ms"
LAYER = "serving front end (serving/server.py, rpc/stream.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import readers, stats
    p = stats.percentile(stats.ttft_samples(
        readers.judged(run), run.records["deadline"]), 95)
    return None if p is None else 1e3 * p
