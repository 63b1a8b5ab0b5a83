"""The decode program's share of its HBM roofline in a model with
expert and window layers: the bytes one decode step must read at the
least (``references/afmoe_decoder.moe_decode_step_bytes``: every
projection, the shared experts, the head's slice, the routed experts
that at least one token chose, and the keys and values of the positions
its queries attend) over the HBM peak, over the program's median device
time a call.  The experts hit are the mean of ``bf_moe_experts_hit_total
/ bf_moe_layer_steps_total`` and the attended positions the mean of
``bf_serving_attended_positions_total / bf_serving_decode_steps_total``,
both over the whole process (a counter has no window)."""

from perfbench.harness import clocks, program_trace as pt, trace as tr
from perfbench.harness.peaks import share_pct

MODULE = r"decode_step"


def mean_counts():
    """``(experts hit a layer a step, attended positions a step)`` or
    None where the program counts neither."""
    steps = pt.counter_value("bf_serving_decode_steps_total")
    layer_steps = pt.counter_value("bf_moe_layer_steps_total")
    hit = pt.counter_value("bf_moe_experts_hit_total")
    kinds = [pt.counter_value("bf_serving_attended_positions_total",
                              kind=k) for k in ("window", "full")]
    if not steps or not layer_steps or hit is None \
            or all(k is None for k in kinds):
        return None
    return hit / layer_steps, sum(k or 0.0 for k in kinds) / steps


def reduce(trace, spans, ctx):
    if not pt.on_chip() or not ctx.get("peaks") \
            or not hasattr(ctx.get("reference"), "moe_decode_step_bytes"):
        return None
    counts = mean_counts()
    calls = tr.module_calls(trace, MODULE)
    if counts is None or not calls:
        return None
    nbytes = ctx["reference"].moe_decode_step_bytes(ctx["sizes"], *counts)
    print(f"[moe_decode_step_roofline] {counts[0]:.2f} held experts hit a "
          f"layer a step, {counts[1]:.0f} attended positions a step: "
          f"{nbytes / 1e9:.3f} GB a step at the least; median device time "
          f"{1e3 * clocks.median(calls):.3f} ms over {len(calls)} calls",
          flush=True)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return share_pct(least, clocks.median(calls), "moe_decode_step_roofline")
