"""Median host time of one ``engine.step()`` (the benchmark's span
around the call), each sample a run of consecutive steps at least
250 ms long."""

from perfbench.harness import clocks


def reduce(trace, spans, ctx):
    steps = ctx.get("engine_steps")
    if not steps:
        return None
    samples, acc, k = [], 0.0, 0
    for s, e in steps:
        acc, k = acc + (e - s), k + 1
        if acc >= 0.25:
            samples.append(acc / k)
            acc, k = 0.0, 0
    if not samples:
        samples = [sum(e - s for s, e in steps) / len(steps)]
    return 1e3 * clocks.median(samples)
