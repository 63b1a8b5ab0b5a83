"""Batch size of a decode step: ``bf_serving_decode_slots_total`` over
``bf_serving_decode_steps_total``, counted in the traced stretch where
the run traced one (the figure that stands beside the stretch's device
times), and over the whole process where it did not."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip():
        return None
    stretch, process = pt.decode_slots(ctx)
    return process if ctx.get("counter_window") is None else stretch
