"""Host time between two decode programs: from one step's
``bf.engine.token_fetch`` returning to the next step's
``bf.engine.decode_dispatch`` starting (emit, the step's bookkeeping, the
load generator's own time between two ``engine.step()`` calls, admit,
``decode_inputs``), median over the decode cycles of the traced stretch
that hold no prefill chunk.  Host clock only: no reading of the device
line enters.  The reader prints the gap by phase, the cycles that hold a
chunk apart, and every other table of ``harness/step_timeline.py``."""

from perfbench.harness import program_trace as pt, step_timeline as st


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return st.median_ms(__file__, trace, st.host_gap_ns, need_device=False)
