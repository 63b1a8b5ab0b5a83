"""Median ``bf.train.train_step`` span of the traced stretch: host
time to dispatch one train step, edge accounting included (the reader
prints the ``record_edges`` span's share)."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    return pt.train_dispatch_ms(__file__, trace)
