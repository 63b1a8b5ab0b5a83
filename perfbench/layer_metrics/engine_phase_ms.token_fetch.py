"""Host time of the engine's ``token_fetch`` phase (``np.asarray`` of the step's tokens: the wait for the device)
summed within one ``bf.engine.step``, median over the steps of the
traced stretch in which it ran; the reader prints every phase, and the
step's self time (what no phase covers)."""

from perfbench.harness import program_trace as pt

PHASE = "token_fetch"


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.engine_phase_ms(__file__, trace, PHASE)
