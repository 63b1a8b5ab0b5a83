"""The decode program's share of its HBM roofline: the bytes one
decode step must read (every projection and the head once, and the
cache positions in use, ``references/dense_gqa_decoder.decode_step_bytes``)
over the HBM peak, over the program's median device time per call."""

from perfbench.harness import clocks, trace as tr
from perfbench.harness.peaks import share_pct

MODULE = r"decode_step"


def reduce(trace, spans, ctx):
    if "serve" not in ctx or not ctx.get("peaks") \
            or ctx.get("live_tokens_mean") is None:
        return None
    calls = tr.module_calls(trace, MODULE)
    if not calls:
        return None
    nbytes = ctx["reference"].decode_step_bytes(ctx["sizes"],
                                                ctx["live_tokens_mean"])
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return share_pct(least, clocks.median(calls), "decode_step_roofline")
