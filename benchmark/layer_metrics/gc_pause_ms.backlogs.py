"""Seconds per engine iteration (in ms) the garbage collector held the
interpreter, all generations, all threads: the increase of
``gc_pause_seconds_total`` between the counter track's first and last
sample of the counted window, over the iterations that start between
them. The information line ``process_account`` gives the loop thread's
share, the longest pause, the count by generation and the spans the
collections ran under (the ``gc/collect`` events).

Source, truly: the program's counter tracks and span records
(``telemetry/process.py``, ``telemetry.get_tracer()``), the whole
window outside the profiled slice (``benchmark/process_account.py``).
The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit, as the ``.mixed`` readers of PR 26 say of theirs."""
NAME, UNIT = "gc_pause_ms.backlogs", "ms"
LAYER = "process beside the loop (telemetry/process.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import process_account
    return process_account.value(run, "window", "gc_pause_ms")
