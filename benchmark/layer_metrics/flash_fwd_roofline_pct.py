"""The flash forward kernel's share of its roofline: the least time the
chip could take for one layer's forward over the batch
(``flops.flash_train_call`` on the documents really packed, the larger
of operations over peak FLOP/s and bytes over peak HBM bytes/s) over
the device seconds one call under the scope ``hetu.flash_fwd`` (the
Pallas call) took (``program_trace``)."""
NAME, UNIT = "flash_fwd_roofline_pct", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import flops, program_trace
    r = run.records
    if run.peaks is None or not r.get("step_pairs_per_row"):
        return None
    # 1 Pallas call(s) make one layer's forward
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.flash_fwd", kernels_per_call=1)
    if not took:
        return None
    pairs = sum(r["step_pairs_per_row"]) / len(r["step_pairs_per_row"])
    call = flops.flash_train_call(run.config, r["batch_rows"],
                                  r["seq_len"], pairs)
    need = flops.roofline_seconds(call["fwd_flops"], call["fwd_bytes"],
                                  run.peaks)
    return 100.0 * need / took
