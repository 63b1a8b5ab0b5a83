"""Device time of the prefill-chunk program's operations by the
program's own ``bf.<layer>.<part>`` scopes, a chunk: the sibling of
``harness/decode_scopes.py`` for the engine's other resident program.

An operation counts where it runs inside an execution of
``prefill_chunk`` that lies wholly in the traced window; the sum is
divided by those executions (full chunks and a prompt's part-full last
chunk alike).  Scopes are told apart as ``decode_scopes`` tells them
(the innermost ``bf.*.*`` of a ``tf_op``; a fusion billed whole; loops
left out and their bodies counted).  ``layer_metrics/
prefill_chunk_device_ms.py`` prints the table; this module only hands
the numbers to the readers that name a scope.

Where the program writes no such scope, the stretch holds no chunk, or
off the chip, ``table`` returns ``None``.
"""

from __future__ import annotations

import re

from perfbench.harness import decode_scopes, program_trace as pt

MODULE = re.compile(r"prefill_chunk")


def chunk_executions(trace):
    """``[(start, end)]`` of the prefill-chunk program's executions
    wholly inside the window, on the first chip."""
    lo, hi = trace.window
    return [(s, e) for name, s, e in trace.devices[0].modules
            if MODULE.search(name) and s >= lo and e <= hi]


def table(reader_file: str, trace):
    """``({scope: ms a chunk}, executions)`` of the run being reduced."""
    if not pt.on_chip() or not trace.devices:
        return None
    run = pt.for_run(reader_file)
    if run is None:
        return None

    def make():
        runs = chunk_executions(trace)
        if not runs:
            return None
        scopes = decode_scopes.by_scope(trace, run.tf_ops, runs)
        if not any(k is not None for k in scopes):
            return None     # the program has no such scopes
        return {k: 1e-6 * sum(v.values()) / len(runs)
                for k, v in scopes.items() if k is not None}, len(runs)

    return run.keep("chunk_scopes", make)


def scopes_ms(found, prefix: str):
    """The sum of the scopes of a ``table`` that start with ``prefix``
    (a scope and the scopes nested in it), or ``None``."""
    if found is None:
        return None
    mine = [ms for scope, ms in found[0].items() if scope.startswith(prefix)]
    return sum(mine) if mine else None
