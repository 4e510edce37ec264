"""The prefill pack's chunk scan against its roofline: q, k, v and the
log-decay read and o written once a token, a run's state read and
written once, the chunk form's operations at 64 rows a chunk
(``flops_kda_mla_moe.kda_scan_call`` on the prompt tokens an
iteration's pack held, all KDA layers), over the device seconds an
iteration spends under ``hetu.kda_scan`` (``kda.roofline_pct``). The
scope's matmuls are float32 at the highest precision against the bf16
peak: the share is low by construction."""
NAME, UNIT = "kda_scan_roofline_pct.video", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_kda_mla_moe as f, kda

    def need(cfg, units):
        call = f.kda_scan_call(cfg, units["prefill"])
        return {k: v * f.kda_layers(cfg) for k, v in call.items()}
    return kda.roofline_pct(run, "hetu.kda_scan", need)
