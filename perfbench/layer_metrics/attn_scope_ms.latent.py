"""Device time a decode step of the operations under ``bf.attn.latent``
(the latent attention of ``bluefog_tpu.models.mla_moe``: projections,
rotation, scores over the cached latent, values, output), the scopes
nested in it (``bf.attn.latent_absorb``, ``bf.attn.latent_expand``)
included (``harness/decode_scopes.py``)."""

from perfbench.harness import chunk_scopes, decode_scopes

SCOPE = "bf.attn.latent"


def reduce(trace, spans, ctx):
    return chunk_scopes.scopes_ms(decode_scopes.table(__file__, trace),
                                  SCOPE)
