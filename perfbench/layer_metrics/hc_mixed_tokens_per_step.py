"""Live tokens times the sublayers their residual streams were mixed
around, an engine step: ``bf_hc_mixed_tokens_total`` (the valid tokens
of every prefill chunk and the slots of every decode step, times the
model's ``mixed_sublayers``) over ``bf_serving_steps_total``, over the
whole process; prints the gauge ``bf_hc_streams`` beside it.  Nothing
where the program counts no such tokens."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    mixed = pt.counter_value("bf_hc_mixed_tokens_total")
    steps = pt.counter_value("bf_serving_steps_total")
    if not pt.on_chip() or mixed is None or not steps:
        return None
    streams = pt.registry_metric("bf_hc_streams")
    shown = "absent" if streams is None else f"{float(streams.value):.0f}"
    print(f"[hc_mixed_tokens_per_step] {mixed:.0f} mixed tokens x "
          f"sublayers over {steps:.0f} engine steps; bf_hc_streams {shown}",
          flush=True)
    return mixed / steps
