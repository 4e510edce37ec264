"""The serving loop's own CPU time in one engine iteration: mean of
``cpu_s - wait_cpu_s`` (the loop thread's ``time.thread_time()`` over
``serve/step``, less the same over ``serve/device_wait``).

Source, truly: the program's span records (``telemetry.get_tracer()``),
the whole window outside the profiled slice
(``benchmark/iteration_account.py``). The manifest labels it
``host_clock`` because ``tests/benchmark/test_program_trace.py``
counts the entries labelled ``program_span`` / ``program_counter``
(18) and is not this PR's to edit, as the ``.mixed`` readers of PR 26
say of theirs."""
NAME, UNIT = "engine_host_cpu_ms.chat", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import iteration_account
    return iteration_account.window_value(run, "host_cpu", "mean")
