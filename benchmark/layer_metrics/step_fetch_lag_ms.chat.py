"""From the end of an iteration's program on the device (``XLA
Modules``) to ``serve/device_wait``'s end, median over the iterations
paired in the traced slice by the annotations' ``iter``.

Source, truly: the profiler's trace — the program's ``hetu:``
annotations beside the device plane
(``benchmark/iteration_account.py``). The manifest labels it
``device_trace`` because ``tests/benchmark/test_program_trace.py``
counts the entries labelled ``program_span`` / ``program_counter``
(18) and is not this PR's to edit, as the ``.mixed`` readers of PR 26
say of theirs."""
NAME, UNIT = "step_fetch_lag_ms.chat", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import iteration_account
    return iteration_account.lag_value(run, "fetch_ms")
