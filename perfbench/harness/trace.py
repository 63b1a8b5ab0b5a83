"""Reduction of a profiler trace (``*.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` gives planes, lines and events with a start
and a duration in nanoseconds.  On a TPU each chip is a plane
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per executed
operation and whose line ``XLA Modules`` one per executed program; the
host's threads are lines of ``/host:CPU``, and the benchmark's spans
(``jax.profiler.TraceAnnotation``, all named ``pb.*``) are events there,
on the same clock.  An operation's event is named by its whole HLO line
(``%fusion.91 = (f32[...]) fusion(...), kind=kOutput, ...``):
``short_name`` gives the part before `` = ``, and a pattern is searched
in the whole line, where a Pallas kernel shows as
``custom_call_target="tpu_custom_call"`` and a program (``XLA Modules``)
as ``jit_<function>(<id>)``.  Everything below works on plain tuples so
that the tests can hand it a trace built by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "pb."
WINDOW_SPAN = "pb.trace_window"
# operations that only hold others (a lax.switch over the schedule's
# rounds is a ``conditional`` whose interval spans the collectives and
# the mixing inside it): busy, but not compute of their own
CONTAINER = re.compile(r"^(conditional|while|call)(\.\d+)?$")
PALLAS_KERNEL = r'custom_call_target="tpu_custom_call"'
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")


def short_name(text: str) -> str:
    """``%fusion.91 = ... fusion(...)`` -> ``fusion.91``."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class DeviceTrace:
    index: int
    ops: list       # (name, start_ns, end_ns), by start
    modules: list   # (name, start_ns, end_ns), by start


@dataclasses.dataclass
class Trace:
    devices: list   # DeviceTrace, by index
    spans: list     # the benchmark's host spans (name, start_ns, end_ns)

    @property
    def window(self):
        """(start_ns, end_ns) of the traced window: the benchmark's
        ``pb.trace_window`` span, or else the device events' extent."""
        for name, s, e in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        starts = [o[1] for d in self.devices for o in d.ops]
        ends = [o[2] for d in self.devices for o in d.ops]
        if not starts:
            raise ValueError("the trace holds no device operation")
        return min(starts), max(ends)


def _events(line):
    out = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
           for e in line.events]
    out.sort(key=lambda t: t[1])
    return out


def from_profile_data(pd) -> Trace:
    devices, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d.index)
    spans.sort(key=lambda t: t[1])
    return Trace(devices, spans)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile_data(ProfileData.from_file(path))


# ------------------------------------------------------------------ #
# interval arithmetic, in nanoseconds
# ------------------------------------------------------------------ #
def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float):
    return subtract([(lo, hi)], busy)


# ------------------------------------------------------------------ #
# the reductions
# ------------------------------------------------------------------ #
def busy_intervals(dev: DeviceTrace, window):
    return union(clip([(s, e) for _, s, e in dev.ops], *window))


def busy_and_window_s(trace: Trace):
    """(``busy_s`` averaged over the chips, ``window_s``)."""
    lo, hi = trace.window
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    busy = [total(busy_intervals(d, (lo, hi))) for d in trace.devices]
    return sum(busy) / len(busy) * 1e-9, (hi - lo) * 1e-9


def idle_pct(trace: Trace) -> float:
    busy, window = busy_and_window_s(trace)
    return 100.0 * (1.0 - busy / window)


def op_seconds(trace: Trace, pattern: str | None = None):
    """Device seconds by operation (short name), summed over the window
    and averaged over the chips; ``pattern`` (a regex, searched in the
    operation's whole line) selects."""
    lo, hi = trace.window
    rx = re.compile(pattern) if pattern else None
    sums = {}
    for d in trace.devices:
        for name, s, e in d.ops:
            if (rx is None or rx.search(name)) and e > lo and s < hi:
                key = short_name(name)
                sums[key] = sums.get(key, 0.0) + (min(e, hi) - max(s, lo))
    n = max(len(trace.devices), 1)
    return {k: v / n * 1e-9 for k, v in sums.items()}


def module_calls(trace: Trace, pattern: str | None = None):
    """Durations (s) of the executions, wholly inside the window, of the
    programs whose name matches, on the first chip; with no pattern, of
    the program that took most of the window (a train step's)."""
    lo, hi = trace.window
    inside = [(name, (e - s) * 1e-9) for name, s, e
              in trace.devices[0].modules if s >= lo and e <= hi]
    if pattern is not None:
        rx = re.compile(pattern)
        return [d for name, d in inside if rx.search(name)]
    sums = {}
    for name, d in inside:
        sums[name] = sums.get(name, 0.0) + d
    if not sums:
        return []
    most = max(sums, key=sums.get)
    return [d for name, d in inside if name == most]


def top(sums: dict, n: int = 10):
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_span(trace: Trace):
    """Idle seconds of the first chip inside the window, given to the
    benchmark span that covers most of each gap (the innermost such
    span; ``(no span)`` where none does), summed by span name."""
    lo, hi = trace.window
    dev = trace.devices[0]
    spans = [(n, s, e) for n, s, e in trace.spans if n != WINDOW_SPAN]
    sums = {}
    for gs, ge in gaps(busy_intervals(dev, (lo, hi)), lo, hi):
        best, best_key = "(no span)", (0.0, 0.0)
        for name, s, e in spans:
            cover = min(e, ge) - max(s, gs)
            # most cover first, then the shorter (inner) span
            key = (cover, -(e - s))
            if cover > 0 and key > best_key:
                best, best_key = name, key
        sums[best] = sums.get(best, 0.0) + (ge - gs) * 1e-9
    return sums


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top(op_seconds(trace)),
            "idle_gaps": top(idle_gaps_by_span(trace))}


def collective_intervals(dev: DeviceTrace, window):
    """The intervals in which a collective is in flight on one chip: a
    synchronous collective's own event, and for an asynchronous pair the
    whole stretch from ``-start``'s begin to ``-done``'s end (paired by
    kind and order)."""
    lo, hi = window
    spans, open_starts = [], {}
    for name, s, e in dev.ops:
        m = COLLECTIVE.match(short_name(name))
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            open_starts.setdefault(kind, []).append(s)
        elif phase == "-done" and open_starts.get(kind):
            spans.append((open_starts[kind].pop(0), e))
        else:
            spans.append((s, e))
    return union(clip(spans, lo, hi))


def compute_intervals(dev: DeviceTrace, window):
    def computes(name):
        name = short_name(name)
        return not (COLLECTIVE.match(name) or CONTAINER.match(name))

    return union(clip([(s, e) for name, s, e in dev.ops if computes(name)],
                      *window))


def exchange_seconds_by_chip(trace: Trace):
    """For each chip (seconds with a collective in flight, the part of
    them with no compute running on that chip), over the window."""
    window = trace.window
    out = []
    for d in trace.devices:
        coll = collective_intervals(d, window)
        exposed = subtract(coll, compute_intervals(d, window))
        out.append((total(coll) * 1e-9, total(exposed) * 1e-9))
    return out


def exchange_seconds(trace: Trace):
    """``exchange_seconds_by_chip`` averaged over the chips."""
    by_chip = exchange_seconds_by_chip(trace)
    n = max(len(by_chip), 1)
    return (sum(a for a, _ in by_chip) / n, sum(b for _, b in by_chip) / n)


def describe(pd, limit: int = 6) -> str:
    """Planes, lines and a few events of each: what to read by hand
    before writing a reduction against a new kind of trace."""
    rows = []
    for plane in pd.planes:
        lines = list(plane.lines)
        rows.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:limit]:
                rows.append(f"    {e.name!r} start {e.start_ns:.0f} ns "
                            f"dur {e.duration_ns:.0f} ns")
    return "\n".join(rows)


if __name__ == "__main__":  # python3 perfbench/harness/trace.py <log dir>
    import sys

    from jax.profiler import ProfileData

    print(describe(ProfileData.from_file(find_xplane(sys.argv[1])),
                   int(sys.argv[2]) if len(sys.argv) > 2 else 6))
