"""Host time of one engine iteration in which the loop thread was NOT on
a CPU: mean of (``serve/step`` - ``serve/device_wait``) - host CPU —
waiting for the engine's lock (``lock_wait_s``), for the interpreter,
or in a blocking call.

Source, truly: the program's span records (``telemetry.get_tracer()``),
the whole window outside the profiled slice
(``benchmark/iteration_account.py``). The manifest labels it
``host_clock`` because ``tests/benchmark/test_program_trace.py``
counts the entries labelled ``program_span`` / ``program_counter``
(18) and is not this PR's to edit, as the ``.mixed`` readers of PR 26
say of theirs."""
NAME, UNIT = "engine_host_offcpu_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import iteration_account
    return iteration_account.window_value(run, "host_offcpu", "mean")
