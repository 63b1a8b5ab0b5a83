"""How full the tiles of the expert loop are: held token-to-expert
assignments over the rows the held experts' matmuls computed,
``bf_moe_expert_assignments_total`` over ``bf_moe_expert_rows_total``,
over the whole process and over every call that wrote a slot (prefill
chunks and decode steps alike).  A loop that applies each hit expert to
every row of a call reads the share of rows that chose it; a program
that does not count reads nothing."""

from perfbench.harness import program_trace as pt


def reduce(trace, spans, ctx):
    rows = pt.counter_value("bf_moe_expert_rows_total")
    assigned = pt.counter_value("bf_moe_expert_assignments_total")
    if not pt.on_chip() or not rows or assigned is None:
        return None
    print(f"[moe_tile_fill_pct] {assigned:.0f} held assignments in "
          f"{rows:.0f} rows computed", flush=True)
    return 100.0 * assigned / rows
