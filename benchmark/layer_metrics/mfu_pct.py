"""Model FLOP/s utilization: trained tokens per second times the
operations a trained token requires (``flops.train_flops_per_token``:
6 per matmul parameter plus attention inside documents; recomputation
not counted) over chips times the peak. Padding earns nothing."""
NAME, UNIT = "mfu_pct", "%"
LAYER = "train step (engine/train_step.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import flops
    r = run.records
    if run.peaks is None or not r["step_s"]:
        return None
    tokens = sum(r["step_labelled_tokens"])
    # keys attended per trained token, from the documents really packed
    pairs = sum(r["step_pairs_per_row"]) * r["batch_rows"]
    per_token = flops.train_flops_per_token(run.config, pairs / tokens)
    rate = tokens / r["window_s"]
    return 100.0 * rate * per_token / (
        r["n_devices"] * run.peaks["bf16_flops_per_s"])
