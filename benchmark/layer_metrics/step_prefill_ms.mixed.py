"""Device self time per engine iteration in the packed prefill lane
(``hetu.prefill_lane``: intra-pack flash, the history read through
the paged kernel, expert and shared matmuls) (``program_trace``)."""
NAME, UNIT = "step_prefill_ms.mixed", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "prefill")
