"""95th percentile of how late the load generator submitted a request
against its due time: a starved generator must not read as a fast
server."""

from perfbench.harness import clocks


def reduce(trace, spans, ctx):
    late = ctx.get("serve", {}).get("late_ms")
    if late is None or len(late) == 0:
        return None
    return clocks.percentile(late, 95)
