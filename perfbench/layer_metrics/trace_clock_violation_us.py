"""How far the device line of the run's xplane must move for every
paired execution to start after its ``bf.engine.decode_dispatch`` began
(after the runtime enqueued it, where the host plane says when) and to
end before its ``bf.engine.device_wait`` returned (before the runtime
learnt of it): 0 on a causal trace.  The reader prints the window of
offsets causality allows, where the xplane's own alignment lies in it,
launch + completion latency, and ``engine_idle_ms.token_fetch`` at both
ends of the window: the error bar of every metric that intersects the
two lines."""

from perfbench.harness import program_trace as pt, step_timeline as st


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return st.clock_violation_us(__file__, trace)
