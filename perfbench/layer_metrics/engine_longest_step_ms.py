"""The process's longest engine step that compiled nothing
(``bf_serving_longest_step_seconds{phase="step"}``): 150-230 ms is the
first decode step after a burst of prefill draining the chunks queued on
the device; seconds is a stall of the machine, and the reader prints the
phase that held it."""

from perfbench.harness import program_trace as pt

GAUGE = "bf_serving_longest_step_seconds"


def reduce(trace, spans, ctx):
    whole = pt.counter_value(GAUGE, phase="step")
    if not pt.on_chip() or whole is None:
        return None
    from bluefog_tpu.observe import get_registry

    phases = {labels["phase"]: metric.value for name, _, _, labels, metric
              in get_registry().collect()
              if name == GAUGE and labels["phase"] != "step"}
    pt.say(f"longest engine step {1e3 * whole:.1f} ms; by phase, ms: "
           + ", ".join(f"{k} {1e3 * v:.2f}" for k, v in
                       sorted(phases.items(), key=lambda kv: -kv[1])))
    return 1e3 * whole
