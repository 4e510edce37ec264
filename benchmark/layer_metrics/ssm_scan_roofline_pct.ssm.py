"""The prefill pack's selective scan against its roofline: by the
EQUATIONS, 7 operations a (channel, state) pair and token, x and dt read
and y written once a token, a run's state (16 x 5120 float32) read and
written once (``flops_jamba.selective_scan_call`` on the prompt tokens
an iteration's pack held, all Mamba layers), over the device seconds an
iteration spends under ``hetu.ssm_scan`` (``ssm.roofline_pct``).

``benchmark/peaks.py`` has no VECTOR peak: the scan's operations are
vector-unit work (no step is a matrix product) and are held here
against the bytes and the MATRIX unit's operations, so the share reads
low by construction; a vector peak is a ``benchmark`` issue's to add."""
NAME, UNIT = "ssm_scan_roofline_pct.ssm", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_jamba as f, ssm

    def need(cfg, units):
        call = f.selective_scan_call(cfg, units["prefill"])
        return {k: v * f.mamba_layers(cfg) for k, v in call.items()}
    return ssm.roofline_pct(run, "hetu.ssm_scan", need)
