"""Device time a prefill chunk of the operations under
``bf.attn.latent`` and the scopes nested in it, over the executions of
the prefill-chunk program in the traced stretch
(``harness/chunk_scopes.py``): the chunk's attention over the cached
latent, which a long prompt pays once a chunk.  Beside the nested
scopes it prints what the time buys: the cached positions a layer whose
keys and values a chunk rebuilt from the latent
(``bf_serving_latent_expanded_positions_total`` over
``bf_serving_prefill_chunks_total``, whole process; nothing where the
program counts none)."""

from perfbench.harness import chunk_scopes, program_trace as pt

SCOPE = "bf.attn.latent"


def reduce(trace, spans, ctx):
    found = chunk_scopes.table(__file__, trace)
    value = chunk_scopes.scopes_ms(found, SCOPE)
    if value is not None:
        nested = {k: v for k, v in found[0].items() if k.startswith(SCOPE)}
        print("[chunk_attn_ms.latent] " + ", ".join(
            f"{k} {v:.3f}" for k, v in sorted(nested.items()))
            + f" ms a chunk over {found[1]} executions", flush=True)
        rebuilt = pt.counter_value(
            "bf_serving_latent_expanded_positions_total")
        chunks = pt.counter_value("bf_serving_prefill_chunks_total")
        if rebuilt and chunks:
            layers = ctx["sizes"]["num_hidden_layers"]
            print(f"[chunk_attn_ms.latent] {rebuilt / chunks / layers:.0f} "
                  "cached positions a layer a chunk rebuilt from the "
                  f"latent ({chunks:.0f} chunks)", flush=True)
    return value
