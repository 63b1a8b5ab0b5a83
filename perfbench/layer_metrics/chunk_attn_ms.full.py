"""Device time a prefill chunk of the operations under ``bf.attn.full``
(the full-attention layers' attention of a chunk's queries over their
``max_len`` leaves: the write at the cache index, scores, softmax,
values), over the executions of the prefill-chunk program in the traced
stretch (``harness/chunk_scopes.py``)."""

from perfbench.harness import chunk_scopes

SCOPE = "bf.attn.full"


def reduce(trace, spans, ctx):
    return chunk_scopes.scopes_ms(chunk_scopes.table(__file__, trace), SCOPE)
