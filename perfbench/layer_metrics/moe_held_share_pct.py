"""Share of a decode step's token-to-expert assignments that fell on
experts this share holds: ``bf_moe_assignments_total{held="true"}`` over
both labels, over the whole process.  32 of 256 held is 12.5% in the
mean over seeds; a seed's router bias makes experts unequally
popular."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    held = pt.counter_value("bf_moe_assignments_total", held="true")
    absent = pt.counter_value("bf_moe_assignments_total", held="false")
    if not pt.on_chip() or held is None or absent is None \
            or held + absent <= 0:
        return None
    return 100.0 * held / (held + absent)
