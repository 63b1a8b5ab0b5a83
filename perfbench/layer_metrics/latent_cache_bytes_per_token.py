"""Bytes of cache one position of one layer reserves in a model whose
cache is a latent: the gauge ``bf_serving_cache_bytes{kind="full"}``
(set when the pool is built, from the leaves themselves) over capacity x
``max_len`` x layers.  It reads what the reference's
``cache_bytes_per_position`` states (640 at the published widths) while
no leaf holds an expanded key or value, and 16,384 if one ever does."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    if not pt.on_chip() or "serve" not in ctx \
            or "kv_lora_rank" not in ctx["sizes"]:
        return None
    full = pt.registry_metric("bf_serving_cache_bytes", kind="full")
    if full is None:
        return None
    engine = ctx["traffic"]["engine"]
    positions = engine["capacity"] * engine["max_len"] \
        * ctx["sizes"]["num_hidden_layers"]
    stated = ctx["reference"].cache_bytes_per_position(ctx["sizes"])
    print(f"[latent_cache_bytes_per_token] {float(full.value):.0f} bytes "
          f"over {positions} positions; the reference states {stated}",
          flush=True)
    return float(full.value) / positions
