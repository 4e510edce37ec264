"""The decode rows' one-token update against its roofline: each live
slot's state (32 value heads x 128 x 128 float32) read and written
once, 7 d^2 operations a value head
(``flops_qwen3_next.gdn_update_call`` on the decode rows an iteration
held, all Gated DeltaNet layers), over the device seconds an iteration
spends under ``hetu.gdn_update`` (``gdn.roofline_pct``)."""
NAME, UNIT = "gdn_update_roofline_pct.gdn", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_qwen3_next as f, gdn

    def need(cfg, units):
        call = f.gdn_update_call(cfg, units["decode"])
        return {k: v * f.gdn_layers(cfg) for k, v in call.items()}
    return gdn.roofline_pct(run, "hetu.gdn_update", need)
