"""Batch size of a decode step: ``bf_serving_decode_slots_total`` over
``bf_serving_decode_steps_total``, over the whole process."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    steps = pt.counter_value("bf_serving_decode_steps_total")
    slots = pt.counter_value("bf_serving_decode_slots_total")
    if not pt.on_chip() or not steps or slots is None:
        return None
    return slots / steps
