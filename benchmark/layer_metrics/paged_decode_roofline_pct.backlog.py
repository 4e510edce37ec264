"""The paged kernel's share of its roofline on the decode rows: the
least time the chip could take to read every live page's K and V once
(``flops.paged_decode_call`` on the runner's mean ``live_pages``) over
the device seconds one call under ``hetu.decode_lane`` ->
``hetu.paged_attn`` took (``program_trace``)."""
NAME, UNIT = "paged_decode_roofline_pct.backlog", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops, program_trace
    r = run.records
    if run.peaks is None or not r.get("live_pages"):
        return None
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.decode_lane>hetu.paged_attn")
    if not took:
        return None
    pages = sum(r["live_pages"]) / len(r["live_pages"])
    call = flops.paged_decode_call(run.config, pages, r["block_size"])
    need = flops.roofline_seconds(call["flops"], call["bytes"],
                                  run.peaks)
    return 100.0 * need / took
