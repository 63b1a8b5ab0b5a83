"""Host time of the engine's ``decode_inputs`` phase (the slot arrays, the per-slot ``PRNGKey`` and the host-to-device transfers)
summed within one ``bf.engine.step``, median over the steps of the
traced stretch in which it ran; the reader prints every phase, and the
step's self time (what no phase covers)."""

from perfbench.harness import program_trace as pt

PHASE = "decode_inputs"


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.engine_phase_ms(__file__, trace, PHASE)
