"""Mean of the time from a request's DUE time to the client's receipt
of its first token, over every request due in the window (a failed or
unfinished request counts as the drain deadline): the steadier
statistic beside ``first_token_p95_ms``, for a later ``benchmark`` PR to
judge as an end-to-end metric once the ledger shows how it spreads."""
NAME, UNIT = "first_token_mean_ms", "ms"
LAYER = "serving front end (serving/server.py, rpc/stream.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import readers, stats
    xs = stats.ttft_samples(readers.judged(run), run.records["deadline"])
    return None if not xs else 1e3 * sum(xs) / len(xs)
