"""The decode rows' one-token update against its roofline: each live
row's state — 8,256 x 129 float32 a kv head, the model's, whatever the
kernel's layout — read and written once
(``flops_brumby.retention_update_call`` on the decode rows an iteration
held, all layers), over the device seconds an iteration spends under
``hetu.retention_update`` (``retention.roofline_pct``)."""
NAME, UNIT = "retention_update_roofline_pct.retention", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_brumby as f, retention

    def need(cfg, units):
        call = f.retention_update_call(cfg, units["decode"])
        return {k: v * cfg["num_hidden_layers"] for k, v in call.items()}
    return retention.roofline_pct(run, "hetu.retention_update", need)
