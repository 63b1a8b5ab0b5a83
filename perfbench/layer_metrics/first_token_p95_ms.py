"""95th percentile of the time to the first token, each request timed
from when it was DUE, over every request of the window: what
``ttft_p95_ms`` is end to end, reported per layer in a cell where it
repeats too loosely for any bound the contract allows (it is a handful
of engine steps there, and where a due time falls in the engine's own
step moves it by most of one: PERF.md section 2)."""

from perfbench.harness import clocks


def reduce(trace, spans, ctx):
    ttft = ctx.get("serve", {}).get("ttft_ms")
    if ttft is None or len(ttft) == 0:
        return None
    return clocks.percentile(ttft, 95)
