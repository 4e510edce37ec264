"""Mean milliseconds the loader took to produce a batch (the
benchmark's timer around ``next(batches)``, which runs in the
trainer's prefetch thread): below the step time it is hidden."""
NAME, UNIT = "data_wait_ms", "ms"
LAYER = "input pipeline (hetu_tpu/data)"
MOVES = "train_tokens_per_s"


def read(run):
    w = run.records["data_wait_s"]
    return 1e3 * sum(w) / len(w) if w else None
