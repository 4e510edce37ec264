"""Device self time per engine iteration under ``hetu.sample`` (both
lanes: the greedy argmax over the held vocabulary and the key splits,
and where a live row asks for them the logits' adjustment, the draws
and the speculative verify) (``program_trace``). The block-diffusion
cell's sampler runs under ``hetu.diffusion_sample`` and has a reader
of its own (``step_sample_ms.blockgen``)."""
NAME, UNIT = "step_sample_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    return program_trace.device_ms_per_step(run, "sample")
