"""The paged kernel's share of its roofline on the decode rows, the
mean over the window and full layers: the least time the chip could
take to read the pages a layer needs once
(``flops_cohere2_moe.paged_decode_call`` on the runner's mean
``live_pages`` and ``live_pages_window``: a window layer needs at most
window / block_size + 1 pages a slot) over the device seconds one call
under ``hetu.decode_lane`` -> ``hetu.paged_attn`` took
(``program_trace``)."""
NAME, UNIT = "paged_decode_roofline_pct.mixed", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops, flops_cohere2_moe, program_trace
    r = run.records
    if run.peaks is None or not r.get("live_pages_window"):
        return None
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.decode_lane>hetu.paged_attn")
    if not took:
        return None
    n = len(r["live_pages"])
    call = flops_cohere2_moe.paged_decode_call(
        run.config, sum(r["live_pages"]) / n,
        sum(r["live_pages_window"]) / n, r["block_size"])
    need = flops.roofline_seconds(call["flops"], call["bytes"],
                                  run.peaks)
    return 100.0 * need / took
