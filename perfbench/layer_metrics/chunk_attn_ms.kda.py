"""Device time a prefill chunk of the operations under ``bf.attn.kda``
and the scopes nested in it (``bf.attn.kda_state``: the chunked form of
the recurrence; ``bf.attn.kda_conv``), over the executions of the
prefill-chunk program in the traced stretch
(``harness/chunk_scopes.py``).  Nothing where the program writes no such
scope or the stretch holds no chunk."""

from perfbench.harness import chunk_scopes

SCOPE = "bf.attn.kda"


def reduce(trace, spans, ctx):
    found = chunk_scopes.table(__file__, trace)
    value = chunk_scopes.scopes_ms(found, SCOPE)
    if value is not None:
        print("[chunk_attn_ms.kda] " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(found[0].items())
            if k.startswith(SCOPE))
            + f" ms a chunk over {found[1]} executions", flush=True)
    return value
