"""Median host time of one train step in the timed window, each sample
a run of consecutive steps at least 250 ms long, ended by
``block_until_ready``."""

from perfbench.harness import clocks


def reduce(trace, spans, ctx):
    stamps = ctx.get("stamps")
    if not stamps or len(stamps) < 2:
        return None
    return 1e3 * clocks.grouped_step_seconds(stamps)
