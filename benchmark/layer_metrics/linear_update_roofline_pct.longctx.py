"""The decode rows' state update against its roofline: each live slot's
float32 state read and written once a lightning layer
(``flops_minicpm_sala.linear_update_call`` on the decode rows an
iteration held), over the device seconds an iteration spends under
``hetu.linear_update`` (``longctx``)."""
NAME, UNIT = "linear_update_roofline_pct.longctx", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_minicpm_sala as f, longctx

    def need(cfg, c):
        call = f.linear_update_call(cfg, c["decode"]["units"])
        return {k: v * f.layers(cfg)[1] for k, v in call.items()}
    return longctx.roofline_pct(run, "hetu.linear_update", need)
