"""Share of a slot's reserved cache rows that a prefill chunk reads to
attend: ``bf_serving_chunk_streamed_positions_total`` (both kinds of
leaf; worked out on the host from the chunk's start and width and the
model's own block rule, ``chunk_streamed_positions``) over
``bf_serving_prefill_chunks_total`` x the rows a chunk could read (full
layers x ``max_len`` + window layers x the ring: window + chunk), over
the whole process.  A lowering that reads every row behind a mask would
read 100; a program that does not count reads nothing."""

from perfbench.harness import program_trace as pt

NAME = "bf_serving_chunk_streamed_positions_total"


def reduce(trace, spans, ctx):
    chunks = pt.counter_value("bf_serving_prefill_chunks_total")
    streamed = {kind: pt.counter_value(NAME, kind=kind)
                for kind in ("window", "full")}
    if not pt.on_chip() or "serve" not in ctx or not chunks \
            or all(v is None for v in streamed.values()):
        return None
    streamed = {kind: v or 0.0 for kind, v in streamed.items()}
    sz, engine = ctx["sizes"], ctx["traffic"]["engine"]
    kept = sz.get("layers_kept") or range(sz["num_hidden_layers"])
    kinds = [sz["layer_types"][i] for i in kept]
    windows = sum(kind == "sliding_attention" for kind in kinds)
    rows = {"window": windows * (sz["sliding_window"]
                                 + engine["prefill_chunk"]),
            "full": (len(kinds) - windows) * engine["max_len"]}
    print("[chunk_cache_streamed_pct] " + ", ".join(
        f"{kind} {streamed[kind] / chunks:.0f} of {rows[kind]}"
        for kind in rows) + f" rows a chunk, over {chunks:.0f} chunks",
        flush=True)
    return 100.0 * sum(streamed.values()) / (chunks * sum(rows.values()))
