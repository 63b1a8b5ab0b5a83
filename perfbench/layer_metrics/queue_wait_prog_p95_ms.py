"""95th percentile of ``bf_serving_queue_wait_seconds``: submit to
slot, as the engine itself stamps it (``queue_wait_p95_ms`` runs from
the due time to the end of the step that gave the slot)."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    hist = pt.registry_metric("bf_serving_queue_wait_seconds")
    if not pt.on_chip() or hist is None or hist.count == 0:
        return None
    return 1e3 * hist.percentile(95)
