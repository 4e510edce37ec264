"""How much of set-up JAX spent tracing, lowering and compiling (a
persistent-cache read is inside the compile stage): the program's
``telemetry.compile_events()`` stamped before the window opened, summed
over the stages trace, lower and compile. The stages, the cache reads
among them, are apart on the ``program_trace`` information line. The
serve runner records the window's start on the events' clock
(``perf_counter``); the train runner records none, so the train cells
do not report this."""
NAME, UNIT = "setup_compile_s", "s"
LAYER = "compile (engine/precompile.py, the engine's jit)"
MOVES = "setup_s"


def read(run):
    from benchmark import program_trace
    got = program_trace.read(run)["compile"]
    if got is None:
        return None
    return sum(got["seconds"].get(k, 0.0)
               for k in ("trace", "lower", "compile"))
