"""Share of the pool's reserved cache positions that a decode step of a
looped model fetches: ``bf_serving_streamed_positions_total{kind=
"full"}`` (every slot of the pool and every (pass, layer), worked out on
the host from the lengths it holds and the lowering's own block rule)
over ``bf_serving_decode_steps_total`` x capacity x ``max_len`` x
passes x layers, the passes read off the gauge ``bf_serving_loop_steps``,
over the whole process.  (``decode_cache_streamed_pct`` divides by the
configuration's layers alone and would read up to passes x 100 here.)
Nothing where the program sets no such gauge."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip() or "serve" not in ctx:
        return None
    passes = pt.registry_metric("bf_serving_loop_steps")
    steps = pt.counter_value("bf_serving_decode_steps_total")
    streamed = pt.counter_value("bf_serving_streamed_positions_total",
                                kind="full")
    if passes is None or not steps or streamed is None:
        return None
    engine = ctx["traffic"]["engine"]
    reserved = (engine["capacity"] * engine["max_len"]
                * float(passes.value) * ctx["sizes"]["num_hidden_layers"])
    print(f"[loop_cache_streamed_pct] {streamed / steps:.0f} positions "
          f"fetched a decode step of {reserved:.0f} reserved "
          f"({float(passes.value):.0f} passes), over {steps:.0f} steps",
          flush=True)
    return 100.0 * streamed / (steps * reserved)
