"""95th percentile of the time from a request's due time to the end of
the first ``engine.step()`` after which it holds a slot."""

from perfbench.harness import clocks


def reduce(trace, spans, ctx):
    waits = ctx.get("serve", {}).get("queue_wait_ms")
    if waits is None or len(waits) == 0:
        return None
    return clocks.percentile(waits, 95)
