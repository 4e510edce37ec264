"""The decode rows' latent paged call against its roofline: the least
time the chip could take to read every live token's latent row ONCE
(576 numbers in bf16 — key and value are the same bytes, padding is
not needed bytes) and to spend heads x (2 x 576 + 2 x 512) operations
on it (``flops_mla_moe.mla_decode_call`` on the runner's mean
``live_pages``) over the device seconds one call under
``hetu.decode_lane`` -> ``hetu.paged_attn`` took (``program_trace``).
Nothing on a configuration without ``kv_lora_rank``."""
NAME, UNIT = "mla_decode_roofline_pct.backlogs", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops, flops_mla_moe, program_trace
    r = run.records
    if run.peaks is None or not r.get("live_pages") \
            or "kv_lora_rank" not in run.config:
        return None
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.decode_lane>hetu.paged_attn")
    if not took:
        return None
    call = flops_mla_moe.mla_decode_call(
        run.config, sum(r["live_pages"]) / len(r["live_pages"]),
        r["block_size"])
    need = flops.roofline_seconds(call["flops"], call["bytes"],
                                  run.peaks)
    return 100.0 * need / took
