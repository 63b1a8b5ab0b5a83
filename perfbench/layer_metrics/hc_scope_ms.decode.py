"""Device time a decode step of the operations under ``bf.hc.pre`` and
``bf.hc.post`` (the residual streams' mixing of
``bluefog_tpu.models.hyper_connections``: statistics, the coefficients'
projection, the Sinkhorn turns, the weighted sum a sublayer reads and
the streams it writes back), every sublayer of the step together
(``harness/decode_scopes.py``).  Nothing where the program writes no
such scope."""

from perfbench.harness import chunk_scopes, decode_scopes

SCOPE = "bf.hc."


def reduce(trace, spans, ctx):
    return chunk_scopes.scopes_ms(decode_scopes.table(__file__, trace),
                                  SCOPE)
