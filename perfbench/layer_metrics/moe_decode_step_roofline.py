"""The decode program's share of its HBM roofline in a model with
expert and window layers: the bytes one decode step must read at the
least (``references/afmoe_decoder.moe_decode_step_bytes``: every
projection, the shared experts, the head's slice, the routed experts
that at least one token chose, and the keys and values of the positions
its queries attend) over the HBM peak, over the program's median device
time a call.  The experts hit are the mean of ``bf_moe_experts_hit_total
/ bf_moe_layer_steps_total`` and the attended positions the mean of
``bf_serving_attended_positions_total / bf_serving_decode_steps_total``,
all four counted in the traced stretch that the calls are taken from
(``program_trace.counter_delta``)."""

from perfbench.harness import clocks, program_trace as pt, trace as tr
from perfbench.harness.peaks import share_pct

MODULE = r"decode_step"


def mean_counts(ctx):
    """``(experts hit a layer a step, attended positions a step)`` in
    the traced stretch and the same over the process, or None where the
    program counts neither or no stretch was traced."""
    hit = pt.stretch_and_process(ctx, "bf_moe_layer_steps_total",
                                 "bf_moe_experts_hit_total")
    kinds = [pt.stretch_and_process(
        ctx, pt.DECODE_STEPS, "bf_serving_attended_positions_total", kind=k)
        for k in ("window", "full")]
    if hit[0] is None or all(k[0] is None for k in kinds):
        return None
    attended = [sum(k[i] or 0.0 for k in kinds) for i in (0, 1)]
    return (hit[0], attended[0]), (hit[1], attended[1])


def reduce(trace, spans, ctx):
    if not pt.on_chip() or not ctx.get("peaks") \
            or not hasattr(ctx.get("reference"), "moe_decode_step_bytes"):
        return None
    counts = mean_counts(ctx)
    calls = tr.module_calls(trace, MODULE)
    if counts is None or not calls:
        return None
    here, process = counts
    nbytes = ctx["reference"].moe_decode_step_bytes(ctx["sizes"], *here)
    print(f"[moe_decode_step_roofline] {pt.slots_line(ctx)}; {here[0]:.2f} "
          f"held experts hit a layer a step and {here[1]:.0f} attended "
          f"positions a step there ({process[0]:.2f} and {process[1]:.0f} "
          f"over the process): {nbytes / 1e9:.3f} GB a step at the least; "
          f"median device time {1e3 * clocks.median(calls):.3f} ms over "
          f"{len(calls)} calls", flush=True)
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return share_pct(least, clocks.median(calls), "moe_decode_step_roofline")
