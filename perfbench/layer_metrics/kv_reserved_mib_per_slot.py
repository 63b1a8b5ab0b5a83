"""MiB of cache the slot pool reserves for one slot: the gauges
``bf_serving_cache_bytes{kind="window"}`` and ``{kind="full"}`` (set
when the pool is built) over the engine's capacity.  A window layer's
ring is about a window long whatever ``max_len`` is; the reader prints
both kinds."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip() or "serve" not in ctx:
        return None
    kinds = {k: pt.registry_metric("bf_serving_cache_bytes", kind=k)
             for k in ("window", "full")}
    found = {k: float(m.value) for k, m in kinds.items() if m is not None}
    if not found:
        return None
    capacity = ctx["traffic"]["engine"]["capacity"]
    print("[kv_reserved_mib_per_slot] " + ", ".join(
        f"{k} {v / capacity / 2 ** 20:.1f} MiB" for k, v in found.items())
        + f" a slot, {capacity} slots", flush=True)
    return sum(found.values()) / capacity / 2 ** 20
