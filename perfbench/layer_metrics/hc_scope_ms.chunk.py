"""Device time a prefill chunk of the operations under ``bf.hc.pre``
and ``bf.hc.post`` (the residual streams' mixing), over the executions
of the prefill-chunk program in the traced stretch
(``harness/chunk_scopes.py``); prints the two scopes apart.  Nothing
where the program writes no such scope or the stretch holds no chunk."""

from perfbench.harness import chunk_scopes

SCOPE = "bf.hc."


def reduce(trace, spans, ctx):
    found = chunk_scopes.table(__file__, trace)
    value = chunk_scopes.scopes_ms(found, SCOPE)
    if value is not None:
        print("[hc_scope_ms.chunk] " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(found[0].items())
            if k.startswith(SCOPE))
            + f" ms a chunk over {found[1]} executions", flush=True)
    return value
