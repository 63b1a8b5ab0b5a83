"""The train attention kernel's share of its roofline: the least time
the chip could take for causal attention's forward and backward at the
cell's shapes (``references/dense_gqa_decoder.flash_kernel_cost``: the
larger of FLOPs over the bf16 peak and bytes over the HBM peak) over
the summed device time of the kernel's events in the trace."""

from perfbench.harness import trace as tr
from perfbench.harness.peaks import roofline_seconds, share_pct

# the train step's only Pallas kernels are the flash forward and its
# two backward kernels; the device trace shows each as a custom call
KERNEL = tr.PALLAS_KERNEL


def reduce(trace, spans, ctx):
    sz, traffic = ctx.get("sizes", {}), ctx.get("traffic", {})
    if sz.get("attn_impl") != "flash" or not ctx.get("peaks"):
        return None
    per_name = tr.op_seconds(trace, KERNEL)
    measured = sum(per_name.values())
    steps = len(tr.module_calls(trace))
    if measured <= 0 or steps == 0:
        return None
    flops, nbytes = ctx["reference"].flash_kernel_cost(
        sz, traffic["batch_per_chip"], traffic["seq_len"])
    least, bound = roofline_seconds(flops, nbytes, ctx["peaks"])
    least *= sz["num_hidden_layers"] * steps
    print(f"[flash_attention_roofline] bound: {bound}; events "
          f"{sorted(per_name)}", flush=True)
    return share_pct(least, measured, "flash_attention_roofline")
