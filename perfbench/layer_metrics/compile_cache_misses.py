"""``bf_compile_cache_misses_total`` at the end of the run: executables
the persistent compilation cache did not hold (a warm run reads 0)."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip() or pt.counter_value("bf_compiles_total") is None:
        return None     # (or the program does not count its compiles)
    return pt.counter_value("bf_compile_cache_misses_total") or 0.0
