"""The state-space recurrence's share of its HBM roofline in the decode
program: the least bytes the step of the state must move
(``references/<family>.ssd_step_bytes``: every DECODING slot's state of
every layer read once and written once, whatever implements the step)
over the HBM peak, over the device time a decode step under
``bf.attn.ssd_state`` (``harness/decode_scopes.py``).  The decoding
slots times layers a step are ``bf_serving_state_steps_total`` over
``bf_serving_decode_steps_total``, both counted in the traced stretch
that the device time is taken over (``program_trace.counter_delta``):
a flash crowd's lull decodes half the slots a step that the process's
mean says.  Nothing off the chip, for a reference that states no such
bytes, or where the program counts or writes neither."""

from perfbench.harness import decode_scopes, program_trace as pt
from perfbench.harness.peaks import share_pct

SCOPE = "bf.attn.ssd_state"


def reduce(trace, spans, ctx):
    ref = ctx.get("reference")
    if not pt.on_chip() or not ctx.get("peaks") \
            or not hasattr(ref, "ssd_step_bytes"):
        return None
    state, _ = pt.stretch_and_process(
        ctx, pt.DECODE_STEPS, "bf_serving_state_steps_total")
    ms = decode_scopes.scope_ms(__file__, trace, SCOPE)
    if not state or not ms:
        return None
    nbytes = ref.ssd_step_bytes(ctx["sizes"], state)
    print(f"[ssd_state_roofline] {pt.slots_line(ctx)}; {state:.1f} "
          f"decoding slots x layers a step there: {nbytes / 1e6:.1f} MB a "
          f"step at the least; {ms:.3f} ms a step under {SCOPE}: "
          f"{nbytes / ms / 1e6:.1f} GB/s", flush=True)
    return share_pct(nbytes / ctx["peaks"]["hbm_bytes_per_s"], 1e-3 * ms,
                     "ssd_state_roofline")
