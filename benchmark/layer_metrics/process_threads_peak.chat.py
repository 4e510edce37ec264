"""The largest number of live Python threads in the window: the largest
sample of the gauge ``process_threads`` (``threading.active_count()``,
sampled every 32 iterations) between the window's start and its end. A
drainer thread a QUEUED request makes it follow the queue's depth.

Source, truly: the program's counter tracks (``telemetry/process.py``;
``benchmark/process_account.py``).
The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit, as the ``.mixed`` readers of PR 26 say of theirs."""
NAME, UNIT = "process_threads_peak.chat", "count"
LAYER = "process beside the loop (telemetry/process.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import process_account
    return process_account.value(run, "window", "threads_peak")
