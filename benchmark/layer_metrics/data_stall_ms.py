"""What the step loop WAITED for its batch, per step: mean self time of
the program's span ``train/next_batch`` over the ``train/step`` spans
wholly inside the traced slice — not what the loader took in its own
thread (that is ``data_wait_ms``)."""
NAME, UNIT = "data_stall_ms", "ms"
LAYER = "input pipeline (hetu_tpu/data)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import program_trace
    wait = program_trace.host_span(run, "train/next_batch")
    step = program_trace.host_span(run, "train/step")
    if not wait or not step:
        return None
    return 1e3 * wait["self_s"] / step["n"]
