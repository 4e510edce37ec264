"""The prefill pack's retention scan against its roofline: by the
EQUATIONS, ``phi(q)^T S`` a query head, ``phi(k) [v, 1]^T`` a kv head
and the quadratic form inside a 256-token chunk, q, k, v read and y
written once a token, a run's state (8,256 x 129 float32 a kv head)
read and written once (``flops_brumby.retention_scan_call`` on the
prompt tokens an iteration's pack held, all layers), over the device
seconds an iteration spends under ``hetu.retention_scan``
(``retention.roofline_pct``)."""
NAME, UNIT = "retention_scan_roofline_pct.retention", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_brumby as f, retention

    def need(cfg, units):
        call = f.retention_scan_call(cfg, units["prefill"])
        return {k: v * cfg["num_hidden_layers"] for k, v in call.items()}
    return retention.roofline_pct(run, "hetu.retention_scan", need)
