"""Device self time per engine iteration in the packed prefill lane
(``hetu.prefill_lane``: the chunk's matmuls, the in-pack attention and
the history read in tiles, or the chunk scan of a layer that keeps a
state, its kernels included; arena writes and sampling not)
(``program_trace``). One reader for every cell that reports
``serve_tokens_per_s`` and lists itself here."""
NAME, UNIT = "step_prefill_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "prefill")
