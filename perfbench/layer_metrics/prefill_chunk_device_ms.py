"""Median device time of one call of the engine's prefill-chunk program,
from its executions in the traced stretch (a stretch with no prompt in
prefill holds none, and the metric is left out).  On the chip it also
prints the program's device time by the model's own ``bf.<layer>.<part>``
scopes, a chunk, as ``harness/decode_scopes.py`` prints the decode
program's; a program that writes no such scope prints no table."""

import re

from perfbench.harness import clocks, decode_scopes, program_trace as pt
from perfbench.harness import trace as tr

MODULE = re.compile(r"prefill_chunk")


def say(text: str) -> None:
    print(f"[prefill_chunk] {text}", flush=True)


def executions(trace):
    """``[(start, end)]`` of the prefill-chunk program's executions
    wholly inside the window, on the first chip."""
    lo, hi = trace.window
    return [(s, e) for name, s, e in trace.devices[0].modules
            if MODULE.search(name) and s >= lo and e <= hi]


def print_scopes(trace, runs) -> None:
    run = pt.for_run(__file__) if pt.on_chip() else None
    if run is None:
        return
    scopes = decode_scopes.by_scope(trace, run.tf_ops, runs)
    if not any(k is not None for k in scopes):
        return
    n = len(runs)
    say(f"device time of the prefill-chunk program by scope over {n} "
        "executions, ms a chunk; a fusion is billed whole to the scope "
        "its tf_op names, loops are left out and their bodies counted:")
    for key in sorted(scopes, key=lambda k: (k is None, k)):
        ops = scopes[key]
        say(f"  {key or '(no scope)':16s} "
            f"{1e-6 * sum(ops.values()) / n:9.3f}  " + ", ".join(
                f"{k} {1e-6 * v / n:.3f}" for k, v in tr.top(ops, 5)))


def reduce(trace, spans, ctx):
    if "serve" not in ctx or not trace.devices:
        return None
    runs = executions(trace)
    if not runs:
        return None
    print_scopes(trace, runs)
    return 1e-6 * clocks.median([e - s for s, e in runs])
