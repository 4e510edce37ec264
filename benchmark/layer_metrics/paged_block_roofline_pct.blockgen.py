"""The paged kernel's share of its roofline on the BLOCK lane's rows,
the mean over the window: the least time the chip could take for one
layer's call — every live page's K and V read once a slot, 4 q rows a
slot against them (``flops_sdar_moe.paged_block_call`` on the runner's
mean ``live_pages``) — over the device seconds one call under
``hetu.decode_lane`` -> ``hetu.paged_attn`` took
(``program_trace``)."""
NAME, UNIT = "paged_block_roofline_pct.blockgen", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops, flops_sdar_moe, program_trace
    r = run.records
    if run.peaks is None or not r.get("live_pages"):
        return None
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.decode_lane>hetu.paged_attn")
    if not took:
        return None
    call = flops_sdar_moe.paged_block_call(
        run.config, sum(r["live_pages"]) / len(r["live_pages"]),
        r["block_size"])
    need = flops.roofline_seconds(call["flops"], call["bytes"],
                                  run.peaks)
    return 100.0 * need / took
