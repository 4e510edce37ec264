"""Output tokens received by clients in the window, over the window."""
NAME, UNIT = "serve_tokens_per_s", "tokens/s"


def read(run):
    from benchmark import readers, stats
    lo, hi = readers.window(run)
    return stats.tokens_in_window(run.records["requests"], lo, hi) \
        / (hi - lo)
