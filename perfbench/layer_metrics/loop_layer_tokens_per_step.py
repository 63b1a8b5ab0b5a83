"""Layer applications an engine step asks of a looped model:
``bf_serving_loop_layer_tokens_total`` (the valid tokens of every
prefill chunk and the slots of every decode step, times the passes a
token makes, times the layers of a pass) over ``bf_serving_steps_total``,
over the whole process; prints the gauges ``bf_serving_loop_steps`` and
``bf_serving_exit_pass_mean`` (the pass the exit gate expects a token
to leave at; every token is served every pass) beside it.  Nothing
where the program counts no such tokens."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    looped = pt.counter_value("bf_serving_loop_layer_tokens_total")
    steps = pt.counter_value("bf_serving_steps_total")
    if not pt.on_chip() or looped is None or not steps:
        return None
    shown = []
    for name in ("bf_serving_loop_steps", "bf_serving_exit_pass_mean"):
        gauge = pt.registry_metric(name)
        shown.append(f"{name} " + ("absent" if gauge is None
                                   else f"{float(gauge.value):.4g}"))
    print(f"[loop_layer_tokens_per_step] {looped:.0f} live tokens x passes "
          f"x layers over {steps:.0f} engine steps; " + ", ".join(shown),
          flush=True)
    return looped / steps
