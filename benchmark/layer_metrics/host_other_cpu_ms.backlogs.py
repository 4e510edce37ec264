"""CPU time per engine iteration of everything in the process BUT the
loop inside its steps: the increase of ``process_cpu_seconds_total``
(``time.process_time()``: every thread, XLA's native ones too) between
the counter track's first and last sample of the counted window, less
the ``cpu_s`` of the ``serve/step`` events that start between them,
over their number. It holds ``wire_cpu_ms.*`` (the wire's threads);
what is above that no span owns, and the information line
``process_account`` splits it by who started the thread.

Source, truly: the program's counter tracks and span records
(``telemetry/process.py``, ``telemetry.get_tracer()``), the whole
window outside the profiled slice (``benchmark/process_account.py``).
The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit, as the ``.mixed`` readers of PR 26 say of theirs."""
NAME, UNIT = "host_other_cpu_ms.backlogs", "ms"
LAYER = "process beside the loop (telemetry/process.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import process_account
    return process_account.value(run, "window", "host_other_cpu_ms")
