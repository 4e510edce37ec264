"""The device's idle time per engine iteration under the LOOP's own host
phases: ``program_trace``'s ``idle_by_span`` under every span of the
loop thread other than ``serve/dispatch`` and ``serve/device_wait``
(``serve/admit``, ``pack``, ``commit``, ``pump``, ``account``,
``gc/collect``, the step's own) and under no span between two steps,
over the whole step spans the traced slice recorded. With
``step_launch_lag_ms`` and ``step_fetch_lag_ms`` it is an iteration's
idle by what the loop was doing; ``breakdown.idle_gaps`` names the
innermost call of ANY thread instead.

Source, truly: the profiler's trace — the program's ``hetu:`` annotations
beside the device plane (``benchmark/program_trace.py``,
``benchmark/process_account.py``).
The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit, as the ``.mixed`` readers of PR 26 say of theirs."""
NAME, UNIT = "idle_host_phases_ms.backlogs", "ms"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import process_account
    return process_account.value(run, "idle", "idle_host_phases_ms")
