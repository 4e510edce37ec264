"""The flash backward kernels' share of their roofline: as
``flash_fwd_roofline_pct`` for the scope ``hetu.flash_bwd``; the dq and
the dk/dv call of a layer are summed (two Pallas calls make one layer's
backward)."""
NAME, UNIT = "flash_bwd_roofline_pct", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "train_tokens_per_s"


def read(run):
    from benchmark import flops, program_trace
    r = run.records
    if run.peaks is None or not r.get("step_pairs_per_row"):
        return None
    # 2 Pallas call(s) make one layer's backward
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.flash_bwd", kernels_per_call=2)
    if not took:
        return None
    pairs = sum(r["step_pairs_per_row"]) / len(r["step_pairs_per_row"])
    call = flops.flash_train_call(run.config, r["batch_rows"],
                                  r["seq_len"], pairs)
    need = flops.roofline_seconds(call["bwd_flops"], call["bwd_bytes"],
                                  run.peaks)
    return 100.0 * need / took
