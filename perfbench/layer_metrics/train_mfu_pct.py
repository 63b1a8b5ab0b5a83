"""Model FLOP/s utilization: the forward and backward FLOPs an item
requires (``references/<family>.train_flops_per_item``, recomputation
not counted) times the window's items a second a chip, over the chip's
bf16 peak."""

from perfbench.harness.peaks import share_pct


def reduce(trace, spans, ctx):
    if not ctx.get("peaks") or "flops_per_item" not in ctx:
        return None
    achieved = ctx["flops_per_item"] * ctx["rate_per_chip"]
    return share_pct(achieved, ctx["peaks"]["bf16_flops_per_s"],
                     "train_mfu_pct")
