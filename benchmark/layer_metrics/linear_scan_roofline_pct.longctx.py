"""The prefill pack's chunk scan against its roofline: q, k, v read and
o written once a token, a run's state read and written once, 4 d^2
operations a token and head (``flops_minicpm_sala.linear_scan_call`` on
the prompt tokens an iteration's pack held, all lightning layers), over
the device seconds an iteration spends under ``hetu.linear_scan``
(``longctx``)."""
NAME, UNIT = "linear_scan_roofline_pct.longctx", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_minicpm_sala as f, longctx

    def need(cfg, c):
        call = f.linear_scan_call(cfg, c["prefill"]["units"])
        return {k: v * f.layers(cfg)[1] for k, v in call.items()}
    return longctx.roofline_pct(run, "hetu.linear_scan", need)
