"""Device time a decode step of the operations under
``bf.moe.experts``: the routed experts this share holds, all expert
layers together; the reader's table has the router's and the shared
expert's rows beside it (``harness/decode_scopes.py``)."""

from perfbench.harness import decode_scopes

SCOPE = "bf.moe.experts"


def reduce(trace, spans, ctx):
    return decode_scopes.scope_ms(__file__, trace, SCOPE)
