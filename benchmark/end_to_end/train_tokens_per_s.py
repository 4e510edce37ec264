"""Tokens trained per second: tokens that carry a label (padding does
not) in the steps that finished in the window, over the time from the
window's start to the last of those steps' blocking loss fetch."""
NAME, UNIT = "train_tokens_per_s", "tokens/s"


def read(run):
    return sum(run.records["step_labelled_tokens"]) \
        / run.records["window_s"]
