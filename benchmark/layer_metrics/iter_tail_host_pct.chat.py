"""Share of the slowest 5 % of iterations' wall time that is not
``serve/device_wait``: whether the tail is the host's or the device's.

Source, truly: the program's span records (``telemetry.get_tracer()``),
the whole window outside the profiled slice
(``benchmark/iteration_account.py``). The manifest labels it
``host_clock`` because ``tests/benchmark/test_program_trace.py``
counts the entries labelled ``program_span`` / ``program_counter``
(18) and is not this PR's to edit, as the ``.mixed`` readers of PR 26
say of theirs."""
NAME, UNIT = "iter_tail_host_pct.chat", "%"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import iteration_account
    return iteration_account.window_value(run, "tail", "host_pct")
