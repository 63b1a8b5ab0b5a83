"""Device time a decode step of the operations under ``bf.attn.window``
(the window layers' attention over their rings: the write at the cache
index, scores, softmax, values), all window layers together
(``harness/decode_scopes.py``)."""

from perfbench.harness import decode_scopes

SCOPE = "bf.attn.window"


def reduce(trace, spans, ctx):
    return decode_scopes.scope_ms(__file__, trace, SCOPE)
