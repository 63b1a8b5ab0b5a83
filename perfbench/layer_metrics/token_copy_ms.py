"""Median ``bf.engine.host_copy`` of the traced stretch: the transfer
and conversion of a decode step's tokens and of the ``stat_*`` leaves
that come with them, after the wait for the program has returned: the
part of ``step_sync_overhead_ms`` that is provably after the program.
The reader prints the leaves and bytes a step."""

from perfbench.harness import program_trace as pt, step_timeline as st


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return st.median_ms(__file__, trace, st.copy_ns, need_device=False)
