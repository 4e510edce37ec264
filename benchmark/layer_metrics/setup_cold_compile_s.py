"""How much of set-up was COLD compilation: the ``compile``-stage seconds
of the program's ``telemetry.compile_events()`` stamped before the
window opened whose ``cache`` is not ``hit`` — a ``miss`` (compiled and
written), ``uncached`` (asked, neither found nor written: under the
cache's thresholds) or ``off``. ~0 where every program came from the
persistent cache; beside ``setup_compile_s`` it says whether this side
compiled cold.

Source, truly: the program's compile events (a ``jax.monitoring`` listener,
``telemetry/compile_events.py``; ``benchmark/process_account.py``).
The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit, as the ``.mixed`` readers of PR 26 say of theirs."""
NAME, UNIT = "setup_cold_compile_s", "s"
LAYER = "compile (engine/precompile.py, the engine's jit)"
MOVES = "setup_s"


def read(run):
    from benchmark import process_account
    return process_account.value(run, "compile", "cold_s")
