"""Device time a prefill chunk of the operations under ``bf.attn.ssd``
and the scope nested in it (``bf.attn.ssd_chunk``: the block form of
the recurrence), over the executions of the prefill-chunk program in
the traced stretch (``harness/chunk_scopes.py``).  Nothing where the
program writes no such scope or the stretch holds no chunk."""

from perfbench.harness import chunk_scopes

SCOPE = "bf.attn.ssd"


def reduce(trace, spans, ctx):
    found = chunk_scopes.table(__file__, trace)
    value = chunk_scopes.scopes_ms(found, SCOPE)
    if value is not None:
        print("[chunk_attn_ms.ssd] " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(found[0].items())
            if k.startswith(SCOPE))
            + f" ms a chunk over {found[1]} executions", flush=True)
    return value
