"""Device time a train step of the operations whose ``tf_op`` lies
under ``bf.forward_backward`` outside JAX's ``transpose(...)``,
mean over the chips.  One event has one ``tf_op``: a fusion is billed
whole to the scope it names (``program_trace.scope_seconds``); the
reader prints every scope, its five heaviest operations, and the time
under no scope.  An executable that the persistent cache
served from before the scopes is reported as stale and split by
``jvp(`` and ``transpose(`` alone."""

from perfbench.harness import program_trace as pt

SCOPE = "forward"


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.train_scope_ms(__file__, trace, SCOPE)
