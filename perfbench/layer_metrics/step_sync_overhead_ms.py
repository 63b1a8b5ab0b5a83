"""What a decode step's host sync costs beside the program itself:
``bf.engine.token_fetch``'s end less ``bf.engine.decode_dispatch``'s
start, on the host's clock, less the device time of the executions the
cycle launched, on the device's (paired with their spans by ``launch=``,
by order); median over the decode cycles of the traced stretch that hold
no prefill chunk.  Launch latency, completion latency and the copy; no
alignment of the two clocks enters.  With ``engine_host_gap_ms`` it is
the device's idle time a cycle (``harness/step_timeline.py`` prints the
sum beside ``device_idle_pct.serve``'s own figure)."""

from perfbench.harness import program_trace as pt, step_timeline as st


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return st.median_ms(__file__, trace, st.sync_overhead_ns,
                        need_device=True)
