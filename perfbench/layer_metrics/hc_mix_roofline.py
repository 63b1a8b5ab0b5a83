"""The mixing's share of its HBM roofline in the prefill-chunk program:
the least bytes the residual streams' mixing of one chunk must move
(``references/<family>.hc_chunk_bytes``: the sublayers the chunk
program executes x the chunk's width x (3n + 1) streams' widths a token
a sublayer, whatever implements the mixing) over the HBM peak, over the
device time a chunk under ``bf.hc.*`` (``hc_scope_ms.chunk``'s
quantity, the same executions).  Nothing off the chip, for a reference
that states no such bytes, or where the program writes no such scope."""

from perfbench.harness import chunk_scopes, program_trace as pt
from perfbench.harness.peaks import share_pct

SCOPE = "bf.hc."


def reduce(trace, spans, ctx):
    ref = ctx.get("reference")
    if not pt.on_chip() or not ctx.get("peaks") \
            or not hasattr(ref, "hc_chunk_bytes"):
        return None
    found = chunk_scopes.table(__file__, trace)
    ms = chunk_scopes.scopes_ms(found, SCOPE)
    if not ms:
        return None
    width = ctx["traffic"]["engine"]["prefill_chunk"]
    nbytes = ref.hc_chunk_bytes(ctx["sizes"], 1, width)
    print(f"[hc_mix_roofline] {nbytes / 1e6:.1f} MB a chunk of {width} "
          f"tokens at the least; {ms:.3f} ms a chunk under {SCOPE}* over "
          f"{found[1]} executions: {nbytes / ms / 1e6:.1f} GB/s",
          flush=True)
    return share_pct(nbytes / ctx["peaks"]["hbm_bytes_per_s"], 1e-3 * ms,
                     "hc_mix_roofline")
