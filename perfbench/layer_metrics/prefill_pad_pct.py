"""Share of prefill positions that were padding: 100 x (1 - valid
prefill tokens / (chunks x chunk width)), over the whole process."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    chunks = pt.counter_value("bf_serving_prefill_chunks_total")
    tokens = pt.counter_value("bf_serving_prefill_tokens_total")
    if not pt.on_chip() or not chunks or tokens is None:
        return None
    width = ctx["traffic"]["engine"]["prefill_chunk"]
    return 100.0 * (1.0 - tokens / (chunks * width))
