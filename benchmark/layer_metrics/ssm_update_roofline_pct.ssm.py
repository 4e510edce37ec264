"""The decode rows' one-token update against its roofline: each live
row's state — 16 x 5120 float32, the model's, whatever the kernel's
layout — read and written once (``flops_jamba.selective_update_call`` on
the decode rows an iteration held, all Mamba layers), over the device
seconds an iteration spends under ``hetu.ssm_update``
(``ssm.roofline_pct``)."""
NAME, UNIT = "ssm_update_roofline_pct.ssm", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_jamba as f, ssm

    def need(cfg, units):
        call = f.selective_update_call(cfg, units["decode"])
        return {k: v * f.mamba_layers(cfg) for k, v in call.items()}
    return ssm.roofline_pct(run, "hetu.ssm_update", need)
