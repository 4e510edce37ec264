"""The prefill pack's chunk scan against its roofline, by Gated
DeltaNet's OWN work: q and k a key head (16) and v a value head (32)
read, one decay and one beta a value head read and o written once a
token, a run's state read and written once, the chunk form's operations
at 64 rows a chunk with the triangular products counted a KEY head
(``flops_qwen3_next.gdn_scan_call`` on the prompt tokens an iteration's
pack held, all Gated DeltaNet layers), over the device seconds an
iteration spends under ``hetu.gdn_scan`` (``gdn.roofline_pct``). The
scope's matmuls are float32 at the highest precision against the bf16
peak, and the kernel is the per-channel one fed broadcasts: the share
is low by construction."""
NAME, UNIT = "gdn_scan_roofline_pct.gdn", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_qwen3_next as f, gdn

    def need(cfg, units):
        call = f.gdn_scan_call(cfg, units["prefill"])
        return {k: v * f.gdn_layers(cfg) for k, v in call.items()}
    return gdn.roofline_pct(run, "hetu.gdn_scan", need)
