"""Device time a decode step of the operations under ``bf.attn.kda``
(the recurrent mixer of ``bluefog_tpu.models.kda``: projections,
convolution, gates, the step of the state, the output's norm, gate and
projection), the scopes nested in it (``bf.attn.kda_state``,
``bf.attn.kda_conv``) included and printed apart
(``harness/decode_scopes.py``).  Nothing where the program writes no
such scope."""

from perfbench.harness import chunk_scopes, decode_scopes

SCOPE = "bf.attn.kda"


def reduce(trace, spans, ctx):
    found = decode_scopes.table(__file__, trace)
    value = chunk_scopes.scopes_ms(found, SCOPE)
    if value is not None:
        print("[attn_scope_ms.kda] " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(found[0].items())
            if k.startswith(SCOPE))
            + f" ms a decode step over {found[1]} executions", flush=True)
    return value
