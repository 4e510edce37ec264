"""The decode rows' one-token update against its roofline: each live
slot's state read and written once, 7 d^2 operations a head
(``flops_kda_mla_moe.kda_update_call`` on the decode rows an iteration
held, all KDA layers), over the device seconds an iteration spends
under ``hetu.kda_update`` (``kda.roofline_pct``)."""
NAME, UNIT = "kda_update_roofline_pct.video", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_kda_mla_moe as f, kda

    def need(cfg, units):
        call = f.kda_update_call(cfg, units["decode"])
        return {k: v * f.kda_layers(cfg) for k, v in call.items()}
    return kda.roofline_pct(run, "hetu.kda_update", need)
