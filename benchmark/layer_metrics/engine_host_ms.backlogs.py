"""Host time of one engine iteration that is not the wait for the
device: mean per iteration of the span ``serve/step`` minus its child
``serve/device_wait``, over the iterations wholly inside the traced
slice (``program_trace``) — under the profiler's Python tracer, so
about twice what ``engine_host_cpu_ms.backlogs`` +
``engine_host_offcpu_ms.backlogs`` read of the untraced window."""
NAME, UNIT = "engine_host_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import program_trace
    step = program_trace.host_span(run, "serve/step")
    wait = program_trace.host_span(run, "serve/device_wait")
    if not step:
        return None
    return 1e3 * (step["total_s"]
                  - (wait["total_s"] if wait else 0.0)) / step["n"]
