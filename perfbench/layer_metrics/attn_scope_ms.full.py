"""Device time a decode step of the operations under ``bf.attn.full``
(the full-attention layers over ``max_len`` positions)
(``harness/decode_scopes.py``)."""

from perfbench.harness import decode_scopes

SCOPE = "bf.attn.full"


def reduce(trace, spans, ctx):
    return decode_scopes.scope_ms(__file__, trace, SCOPE)
