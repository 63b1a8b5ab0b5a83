"""MiB of recurrent state one slot holds, whatever its length: the
gauge ``bf_serving_state_bytes_per_slot`` (set when the pool is built,
from the ``state_*`` leaves themselves); prints what the reference
states beside it (``kda_state_bytes_per_slot``).  Nothing where the
program sets no such gauge."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip() or "serve" not in ctx:
        return None
    gauge = pt.registry_metric("bf_serving_state_bytes_per_slot")
    if gauge is None:
        return None
    ref = ctx.get("reference")
    stated = ref.kda_state_bytes_per_slot(ctx["sizes"]) \
        if hasattr(ref, "kda_state_bytes_per_slot") else None
    print(f"[state_mib_per_slot] {float(gauge.value):.0f} bytes a slot; "
          f"the reference states {stated}", flush=True)
    return float(gauge.value) / 2 ** 20
