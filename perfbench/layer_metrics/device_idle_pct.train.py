"""Share of the traced stretch of train steps in which no operation
ran on the device (1 - union of op intervals / window, mean over the
chips; a collective in flight counts as busy)."""

from perfbench.harness import trace as tr


def reduce(trace, spans, ctx):
    if "stamps" not in ctx:
        return None
    return tr.idle_pct(trace)
