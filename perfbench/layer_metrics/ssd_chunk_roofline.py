"""The chunked state-space scan's share of its roofline in the
prefill-chunk program: the larger of the two least times of one chunk's
scan in every layer (``references/<family>.ssd_chunk_flops`` over the
bf16 peak, ``ssd_chunk_bytes`` over the HBM peak, a layer, at the
chunk's width, padding included: the device computes it too) over the
device time a chunk under ``bf.attn.ssd_chunk``
(``harness/chunk_scopes.py``).  Nothing off the chip, for a reference
that states no such operations, or where the program writes no such
scope."""

from perfbench.harness import chunk_scopes, program_trace as pt
from perfbench.harness.peaks import roofline_seconds, share_pct

SCOPE = "bf.attn.ssd_chunk"


def reduce(trace, spans, ctx):
    ref = ctx.get("reference")
    if not pt.on_chip() or not ctx.get("peaks") \
            or not hasattr(ref, "ssd_chunk_flops"):
        return None
    found = chunk_scopes.table(__file__, trace)
    ms = chunk_scopes.scopes_ms(found, SCOPE)
    if not ms:
        return None
    sz, width = ctx["sizes"], ctx["traffic"]["engine"]["prefill_chunk"]
    layers = ref.ssd_layers(sz)
    flops = layers * ref.ssd_chunk_flops(sz, width)
    nbytes = layers * ref.ssd_chunk_bytes(sz, width)
    least, bound = roofline_seconds(flops, nbytes, ctx["peaks"])
    print(f"[ssd_chunk_roofline] {layers} layers x {width} tokens: "
          f"{flops / 1e9:.2f} GFLOP and {nbytes / 1e6:.1f} MB a chunk at "
          f"the least ({bound} bound, {1e3 * least:.3f} ms); {ms:.3f} ms a "
          f"chunk under {SCOPE} over {found[1]} executions", flush=True)
    return share_pct(least, 1e-3 * ms, "ssd_chunk_roofline")
