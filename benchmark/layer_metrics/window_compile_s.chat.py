"""Seconds JAX spent tracing, lowering and compiling INSIDE the measured
window, on any thread: the program's ``telemetry.compile_events()``
stamped between the window's start and its end (the information line
``process_account`` names the functions and their threads). 0 is the
design: nothing compiles in a window.

Source, truly: the program's compile events (a ``jax.monitoring`` listener,
``telemetry/compile_events.py``; ``benchmark/process_account.py``).
The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit, as the ``.mixed`` readers of PR 26 say of theirs."""
NAME, UNIT = "window_compile_s.chat", "s"
LAYER = "compile (engine/precompile.py, the engine's jit)"
MOVES = "gap_p95_ms"


def read(run):
    from benchmark import process_account
    return process_account.value(run, "compile", "window_s")
