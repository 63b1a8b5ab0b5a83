"""Device time of a resident program under the looped stack's scopes
(``bluefog_tpu.models.looped``): ``bf.loop`` around the passes
(embedding, head, gate and sampling lie outside it) and ``bf.loop.attn``
around a layer application's attention inside it.

``harness/decode_scopes.py`` names an operation by the innermost
``bf.<layer>.<part>`` of its ``tf_op``; the loop's own scope has one dot
and holds the other, so this module reads the same ``tf_op`` metadata
(``ProgramTrace.tf_ops``) with a rule of its own: an operation is
``attn`` where its ``tf_op`` holds ``bf.loop.attn``, ``rest`` where it
holds ``bf.loop`` alone, and ``outside`` otherwise.  An operation counts
where it runs inside an execution of the program that lies wholly in
the traced window; loops are left out and their bodies counted; the
sums are divided by the executions.

Where the program writes no such scope (the parent of the PR that added
it, any other model), or off the chip, ``table`` returns ``None``.
"""

from __future__ import annotations

import re

from perfbench.harness import (chunk_scopes, decode_scopes,
                               program_trace as pt, trace as tr)

LOOP = re.compile(r"bf\.loop(?![a-z_])")
ATTN = "bf.loop.attn"
PROGRAMS = {"decode": decode_scopes.decode_executions,
            "chunk": chunk_scopes.chunk_executions}


def part_of(tf_op) -> str:
    tf_op = tf_op or ""
    if ATTN in tf_op:
        return "attn"
    return "rest" if LOOP.search(tf_op) else "outside"


def by_part(trace, tf_ops: dict, runs) -> dict:
    """``{"attn" | "rest" | "outside": ns}`` of the first chip's
    operations that run inside one of ``runs`` (sorted, disjoint)."""
    dev = trace.devices[0]
    names = tf_ops.get(dev.index, {})
    out, j = {"attn": 0.0, "rest": 0.0, "outside": 0.0}, 0
    for name, s, e in dev.ops:
        while j < len(runs) and runs[j][1] <= s:
            j += 1
        if j == len(runs):
            break
        if s < runs[j][0] or e > runs[j][1] \
                or tr.CONTAINER.match(tr.short_name(name)):
            continue
        out[part_of(names.get(name))] += e - s
    return out


def table(reader_file: str, trace, program: str):
    """``({part: ms an execution}, executions)`` of ``program``
    (``decode`` or ``chunk``) in the run being reduced, printed once;
    ``None`` where there is nothing to read."""
    if not pt.on_chip() or not trace.devices:
        return None
    run = pt.for_run(reader_file)
    if run is None:
        return None

    def make():
        runs = PROGRAMS[program](trace)
        if not runs:
            return None
        parts = by_part(trace, run.tf_ops, runs)
        if not parts["attn"] + parts["rest"]:
            return None     # the program has no such scope
        n = len(runs)
        ms = {k: 1e-6 * v / n for k, v in parts.items()}
        print(f"[loop_scopes] {program} program over {n} executions, ms "
              f"an execution: under bf.loop {ms['attn'] + ms['rest']:.3f} "
              f"(bf.loop.attn {ms['attn']:.3f}, the rest of the loop "
              f"{ms['rest']:.3f}), outside it {ms['outside']:.3f}; the "
              f"program {1e-6 * sum(e - s for s, e in runs) / n:.3f}",
              flush=True)
        return ms, n

    return run.keep(f"loop_scopes.{program}", make)


def loop_ms(reader_file: str, trace, program: str):
    found = table(reader_file, trace, program)
    if found is None:
        return None
    return found[0]["attn"] + found[0]["rest"]
