"""Share of the traced stretch of the serving window in which no
operation ran on the device; ``breakdown`` gives the gaps to the host
span open in them."""

from perfbench.harness import trace as tr


def reduce(trace, spans, ctx):
    if "serve" not in ctx:
        return None
    return tr.idle_pct(trace)
