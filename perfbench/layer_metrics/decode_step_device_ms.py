"""Median device time of one call of the engine's resident decode
program, from its executions in the traced stretch."""

from perfbench.harness import clocks, trace as tr

MODULE = r"decode_step"


def reduce(trace, spans, ctx):
    if "serve" not in ctx:
        return None
    calls = tr.module_calls(trace, MODULE)
    if not calls:
        return None
    return 1e3 * clocks.median(calls)
