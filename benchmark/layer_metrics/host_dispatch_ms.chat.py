"""The span ``serve/dispatch`` (the call of the fused step: argument
handling, the upload of the host operands, the launch), mean per
iteration.

Source, truly: the program's span records (``telemetry.get_tracer()``),
the whole window outside the profiled slice
(``benchmark/iteration_account.py``). The manifest labels it
``host_clock`` because ``tests/benchmark/test_program_trace.py``
counts the entries labelled ``program_span`` / ``program_counter``
(18) and is not this PR's to edit, as the ``.mixed`` readers of PR 26
say of theirs."""
NAME, UNIT = "host_dispatch_ms.chat", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import iteration_account
    return iteration_account.window_value(
        run, "children", "serve/dispatch", "mean")
