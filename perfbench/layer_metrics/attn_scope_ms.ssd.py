"""Device time a decode step of the operations under ``bf.attn.ssd``
(the state-space mixer of ``bluefog_tpu.models.hybrid_ssm``: projection,
convolution, the step of the state, the gated norm and the output's
projection), the scope nested in it (``bf.attn.ssd_state``: the
single-token recurrence) included and printed apart
(``harness/decode_scopes.py``).  Nothing where the program writes no
such scope."""

from perfbench.harness import chunk_scopes, decode_scopes

SCOPE = "bf.attn.ssd"


def reduce(trace, spans, ctx):
    found = decode_scopes.table(__file__, trace)
    value = chunk_scopes.scopes_ms(found, SCOPE)
    if value is not None:
        print("[attn_scope_ms.ssd] " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(found[0].items())
            if k.startswith(SCOPE))
            + f" ms a decode step over {found[1]} executions", flush=True)
    return value
