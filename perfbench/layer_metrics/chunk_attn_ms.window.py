"""Device time a prefill chunk of the operations under
``bf.attn.window`` (the window layers' attention of a chunk's queries
over their rings: rotation, the write at the cache index, scores,
softmax, values), all window layers together, over the executions of
the prefill-chunk program in the traced stretch
(``harness/chunk_scopes.py``)."""

from perfbench.harness import chunk_scopes

SCOPE = "bf.attn.window"


def reduce(trace, spans, ctx):
    return chunk_scopes.scopes_ms(chunk_scopes.table(__file__, trace), SCOPE)
