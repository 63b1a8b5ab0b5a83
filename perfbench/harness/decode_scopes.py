"""Device time of the decode program's operations by the program's own
``bf.<layer>.<part>`` scopes (``bluefog_tpu.models.afmoe``:
``bf.attn.window``, ``bf.attn.full``, ``bf.moe.router``,
``bf.moe.shared``, ``bf.moe.experts``), a decode step.

``harness/program_trace.py`` tells a train step's scopes apart; its
pattern stops at the first dot.  This module reads the same ``tf_op``
metadata (through ``ProgramTrace.tf_ops``) for dotted scopes, over the
executions of the resident decode program alone: an operation counts
where it runs inside an execution of ``decode_step`` that lies wholly in
the traced window, and the sum is divided by those executions.  The
innermost ``bf.*.*`` scope of a ``tf_op`` names it; a fusion is billed
whole to the scope its ``tf_op`` names; containers are left out.

Where the program writes no such scope (the parent of the PR that added
them, a dense model), or off the chip, every function returns ``None``.
"""

from __future__ import annotations

import re

from perfbench.harness import program_trace as pt, trace as tr

MODULE = re.compile(r"decode_step")
DOTTED = re.compile(r"bf\.[a-z_]+\.[a-z_]+")


def say(text: str) -> None:
    print(f"[decode_scopes] {text}", flush=True)


def scope_of(tf_op) -> str | None:
    found = DOTTED.findall(tf_op or "")
    return found[-1] if found else None


def decode_executions(trace):
    """``[(start, end)]`` of the decode program's executions wholly
    inside the window, on the first chip."""
    lo, hi = trace.window
    return [(s, e) for name, s, e in trace.devices[0].modules
            if MODULE.search(name) and s >= lo and e <= hi]


def by_scope(trace, tf_ops: dict, runs) -> dict:
    """``{scope or None: {operation: ns}}`` of the first chip's
    operations that run inside one of ``runs`` (sorted, disjoint)."""
    dev = trace.devices[0]
    names = tf_ops.get(dev.index, {})
    out, j = {}, 0
    for name, s, e in dev.ops:
        while j < len(runs) and runs[j][1] <= s:
            j += 1
        if j == len(runs):
            break
        short = tr.short_name(name)
        if s < runs[j][0] or e > runs[j][1] or tr.CONTAINER.match(short):
            continue
        ops = out.setdefault(scope_of(names.get(name)), {})
        ops[short] = ops.get(short, 0.0) + e - s
    return out


def table(reader_file: str, trace):
    """``({scope: ms a decode step}, executions)`` of the run being
    reduced, printed once; ``None`` where there is nothing to read."""
    if not pt.on_chip() or not trace.devices:
        return None
    run = pt.for_run(reader_file)
    if run is None:
        return None

    def make():
        runs = decode_executions(trace)
        if not runs:
            return None
        scopes = by_scope(trace, run.tf_ops, runs)
        if not any(k is not None for k in scopes):
            return None     # the program has no such scopes
        n = len(runs)
        busy = sum(sum(ops.values()) for ops in scopes.values())
        say(f"device time of the decode program by scope over {n} "
            f"executions, ms a step (operations {1e-6 * busy / n:.3f}, "
            f"program {1e-6 * sum(e - s for s, e in runs) / n:.3f}); a "
            "fusion is billed whole to the scope its tf_op names:")
        for key in sorted(scopes, key=lambda k: (k is None, k)):
            ops = scopes[key]
            say(f"  {key or '(no scope)':16s} "
                f"{1e-6 * sum(ops.values()) / n:9.3f}  " + ", ".join(
                    f"{k} {1e-6 * v / n:.3f}" for k, v in tr.top(ops, 5)))
        return {k: 1e-6 * sum(v.values()) / n for k, v in scopes.items()
                if k is not None}, n

    return run.keep("decode_scopes", make)


def scope_ms(reader_file: str, trace, scope: str):
    found = table(reader_file, trace)
    if found is None:
        return None
    return found[0].get(scope)
