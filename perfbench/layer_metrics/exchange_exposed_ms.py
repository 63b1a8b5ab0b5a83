"""The part of ``exchange_ms`` during which no compute runs on that
chip: what an overlap of exchange and compute could still hide."""

from perfbench.harness import trace as tr


def reduce(trace, spans, ctx):
    if ctx.get("chips", 1) < 2:
        return None
    in_flight, exposed = tr.exchange_seconds(trace)
    steps = len(tr.module_calls(trace))
    if in_flight <= 0 or steps == 0:
        return None
    return 1e3 * exposed / steps
