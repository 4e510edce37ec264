"""CPU time of the wire's threads per engine iteration: ``cpu_s`` of the
``stream/drain`` (one a subscription, at its drainer's exit) and
``server/submit`` (one a submit) events that end in the window, over
the window's iterations. They share the loop's interpreter.

Source, truly: the program's span records (``telemetry.get_tracer()``),
the whole window outside the profiled slice
(``benchmark/iteration_account.py``). The manifest labels it
``host_clock`` because ``tests/benchmark/test_program_trace.py``
counts the entries labelled ``program_span`` / ``program_counter``
(18) and is not this PR's to edit, as the ``.mixed`` readers of PR 26
say of theirs."""
NAME, UNIT = "wire_cpu_ms.chat", "ms"
LAYER = "serving front end (serving/server.py, rpc/stream.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import iteration_account
    return iteration_account.window_value(run, "wire_cpu_ms")
