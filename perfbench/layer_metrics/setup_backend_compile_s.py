"""Seconds the process spent in the backend (the compiler, or
the persistent cache's read in its place),
as JAX reports them to the program's compile listener:
``bf_compile_seconds_total{stage="backend"}`` at the end of the run.
Nothing compiles inside a warmed window, so every compile is the
set-up's or the reference's (which ``setup_s`` and ``compile_s`` leave
out, and which this counter cannot tell apart)."""

from perfbench.harness import program_trace as pt

STAGE = "backend"


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.counter_value("bf_compile_seconds_total", stage=STAGE)
