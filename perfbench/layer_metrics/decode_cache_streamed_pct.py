"""Share of the pool's reserved cache positions that a decode step
fetches: ``bf_serving_streamed_positions_total{kind="full"}`` (every
slot of the pool and every layer, worked out on the host from the
lengths it holds and the lowering's own block rule,
``parallel/pallas_decode.streamed_positions``) over
``bf_serving_decode_steps_total`` x capacity x ``max_len`` x layers, over
the whole process.  A lowering that reads every row behind a mask reads
100; a program that does not count reads nothing."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    steps = pt.counter_value("bf_serving_decode_steps_total")
    streamed = pt.counter_value("bf_serving_streamed_positions_total",
                                kind="full")
    if not pt.on_chip() or "serve" not in ctx or not steps \
            or streamed is None:
        return None
    engine = ctx["traffic"]["engine"]
    reserved = (engine["capacity"] * engine["max_len"]
                * ctx["sizes"]["num_hidden_layers"])
    print(f"[decode_cache_streamed_pct] {streamed / steps:.0f} positions "
          f"fetched a decode step of {reserved} reserved, over "
          f"{steps:.0f} steps", flush=True)
    return 100.0 * streamed / (steps * reserved)
