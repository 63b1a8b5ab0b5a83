"""Programs the device executes an engine step: executions on ``XLA
Modules`` that begin inside a ``bf.engine.step`` span of the traced
stretch, over those spans."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.device_launches_per_step(__file__, trace)
