"""Device time a prefill chunk of the operations under ``bf.loop`` (the
passes of ``bluefog_tpu.models.looped``), over the executions of the
prefill-chunk program in the traced stretch
(``harness/loop_scopes.py``); prints the attention under
``bf.loop.attn`` and the rest of the loop apart.  Nothing where the
program writes no such scope or the stretch holds no chunk."""

from perfbench.harness import loop_scopes


def reduce(trace, spans, ctx):
    return loop_scopes.loop_ms(__file__, trace, "chunk")
