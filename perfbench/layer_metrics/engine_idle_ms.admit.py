"""Device idle time inside the engine's ``admit`` phase, per engine step
of the traced stretch (mean): the gaps of ``device_idle_pct.serve`` put
down to what the host was doing in them; the reader prints every phase,
and the idle time under no ``bf.engine.*`` span."""

from perfbench.harness import program_trace as pt

PHASE = "admit"


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.engine_idle_ms(__file__, trace, PHASE)
