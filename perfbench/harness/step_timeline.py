"""The engine step's timeline, read so that (almost) nothing leans on
the profiler's alignment of the host's clock with the device's.

``program_trace.idle_by_phase`` intersects the device's gaps with host
spans and trusts the two lines of an xplane to the microsecond.  They
are not that good: the device line of a v5e trace lies some tenths of a
millisecond early against the host line, and by 0.6-0.9 ms more in the
first profiled process of a machine's session (PERF.md section 3).  This module splits the idle time of an engine
step into terms that are each a difference on ONE clock:

* the **host gap** of a decode cycle: from ``bf.engine.token_fetch``'s
  end to the next ``bf.engine.decode_dispatch``'s start (host clock);
* its **sync overhead**: ``token_fetch``'s end less ``decode_dispatch``'s
  start (host clock) less the device time of the programs the cycle
  launched (device clock).  The executions are paired with the spans
  that dispatched them by ORDER: the span's ``launch=`` is the engine's
  running count of programs, and the k-th span is the k-th execution of
  an engine program on ``XLA Modules``;
* the **copy**: ``bf.engine.host_copy`` (host clock), the part of the
  overhead that is provably after the program.

Their sum is the device's idle time a cycle (the device is drained
whenever ``token_fetch`` returns).  What is left of the overhead once
the copy is out is launch latency plus completion latency; only the
split between these two needs a shared clock, and ``clock_window`` says
how far the device line may move before an execution would start before
it was dispatched or end after the host knew of it.  Where the host
plane holds the runtime's own events (``DoEnqueueProgram``: a program
goes onto the device's queue; ``tpu::System::Execute=>Done``: the host
learns that it ended), they stand in for the spans and narrow the
window, and they split the overhead further with no alignment at all
(``runtime_split``): the host's way to the enqueue, the device's side of
the launch and the completion together, the host's waking, the copy.

Everything below ``reduce`` works on plain tuples, so that the tests
hand it timelines built by hand.  Times are nanoseconds.
"""

from __future__ import annotations

import re
from statistics import fmean as _mean

import numpy as np

from perfbench.harness import clocks, program_trace as pt, trace as tr

STEP = pt.ENGINE_STEP
DISPATCH = "bf.engine.decode_dispatch"
FETCH = "bf.engine.token_fetch"
CHUNK = "bf.engine.prefill_chunk"
WAIT = "bf.engine.device_wait"
COPY = "bf.engine.host_copy"
# what the host does between two decode programs, by the span it is in
GAP_PHASES = {"bf.engine.emit": "emit", "bf.engine.admit": "admit",
              CHUNK: "prefill", "bf.engine.decode_inputs": "decode_inputs"}
# the engine's resident programs on ``XLA Modules``
ENGINE_PROGRAM = re.compile(r"jit__(decode_step|spec_step|prefill_chunk)_prog")
# the runtime's own events on the host plane (libtpu 0.0.34)
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
KINDS = ("plain", "chunk")


def say(text: str) -> None:
    print(f"[step_timeline] {text}", flush=True)


# ------------------------------------------------------------------ #
# the host line: decode cycles
# ------------------------------------------------------------------ #
def decode_cycles(spans, window):
    """One dict a ``decode_dispatch`` span wholly inside the window, in
    order: the cycle from the previous decode step's ``token_fetch`` end
    (``since``; None for the first) to this one's.  ``chunks``: the
    ``prefill_chunk`` spans in between; ``skipped``: engine steps in
    between that decoded nothing; ``between``: every span of the gap;
    ``prev``: the cycle before; ``kind``: ``plain``, ``chunk``, or
    ``broken`` (the first cycle, or one across a step that decoded
    nothing: the host was not driving the device there)."""
    lo, hi = window
    thread = next((th for name, _, _, _, th in spans if name == STEP), None)
    cycles, cur, holder, since, step = [], None, None, None, None
    steps, chunks, between = 0, [], []
    for name, s, e, args, th in spans:
        if th != thread or s < lo or e > hi \
                or (step is None and name != STEP):
            continue        # (a step the window's edge cuts is left out)
        if cur is None and holder is not None and name in (WAIT, COPY) \
                and e <= holder["fetch"][1]:
            # the children begin after their parent
            holder["wait" if name == WAIT else "copy"] = (s, e, args)
            continue
        between.append((name, s, e))
        if name == STEP:
            steps, step = steps + 1, (name, s, e)
        elif name == CHUNK:
            chunks.append((s, e, args))
        elif name == DISPATCH:
            cur = {"dispatch": (s, e), "launch": args.get("launch"),
                   "since": since, "chunks": chunks, "skipped": steps - 1,
                   "between": between, "wait": None, "copy": None,
                   "prev": cycles[-1] if cycles else None}
        elif name == FETCH and cur is not None:
            cur["fetch"] = (s, e)
            cur["kind"] = ("broken" if since is None or cur["skipped"]
                           else "chunk" if chunks else "plain")
            cycles.append(cur)
            holder, cur, since = cur, None, e
            steps, chunks, between = 0, [], [step] if step else []
    return cycles


def gap_parts(cycle):
    """``{part: ns}`` of the cycle's host gap by what the host was in:
    ``GAP_PHASES``, ``self`` (inside a ``bf.engine.step``, under no
    phase) and ``outside`` (between two ``engine.step()`` calls: the
    load generator's own time)."""
    a, b = cycle["since"], cycle["dispatch"][0]
    out = dict.fromkeys(GAP_PHASES.values(), 0.0)
    in_steps = 0.0
    for name, s, e in cycle["between"]:
        cover = min(e, b) - max(s, a)
        if cover <= 0:
            continue
        if name == STEP:
            in_steps += cover
        elif name in GAP_PHASES:
            out[GAP_PHASES[name]] += cover
    out["self"] = in_steps - sum(out.values())
    out["outside"] = (b - a) - in_steps
    return out


# ------------------------------------------------------------------ #
# the device line: executions, paired by order
# ------------------------------------------------------------------ #
def engine_executions(modules):
    """``[(kind, start, end, index)]`` of the engine's programs among
    ``modules`` (``(name, start, end)`` by start); ``index`` counts
    every execution of the line."""
    out = []
    for i, (name, s, e) in enumerate(modules):
        m = ENGINE_PROGRAM.match(name)
        if m:
            kind = "chunk" if m.group(1) == "prefill_chunk" else "decode"
            out.append((kind, s, e, i))
    return out


def host_launches(cycles):
    """The programs the cycles dispatched, in order:
    ``[(kind, cycle index, chunk index or None)]``, with ``(None, ...)``
    for a program some span's ``launch=`` says was dispatched under no
    span of the list (a draft model's chunk beside the target's)."""
    out, expect = [], None
    for k, c in enumerate(cycles):
        spans = [("chunk", a.get("launch"), i)
                 for i, (_, _, a) in enumerate(c["chunks"])]
        spans.append(("decode", c["launch"], None))
        for kind, launch, i in spans:
            if launch is not None and expect is not None:
                out.extend((None, k, None) for _ in range(launch - expect))
            out.append((kind, k, i))
            expect = None if launch is None else launch + 1
    return out


def pair_launches(cycles, executions, slack: int = 3):
    """Give every cycle ``dev`` (its decode execution, ``(start, end,
    index)``) and ``chunk_devs`` (its chunks'), pairing the dispatched
    programs with the executions BY ORDER.  The device is drained when a
    trace starts (starting the profiler stalls the loop for seconds), so
    the first dispatch of the stretch is the first execution; up to
    ``slack`` leading executions are let go where the kinds say so (a
    chunk dispatched just before the first whole step).  Returns the
    number let go, or None where no order fits."""
    host = host_launches(cycles)
    for c in cycles:
        c["dev"], c["chunk_devs"] = None, [None] * len(c["chunks"])
    for skip in range(slack + 1):
        dev = executions[skip:]
        n = min(len(host), len(dev))
        if n and all(h[0] in (None, d[0]) for h, d in zip(host, dev)):
            break
    else:
        return None
    for (kind, k, i), (_, s, e, index) in zip(host, dev):
        if kind == "decode":
            cycles[k]["dev"] = (s, e, index)
        elif kind == "chunk":
            cycles[k]["chunk_devs"][i] = (s, e, index)
    return skip


def paired(cycles):
    """The cycles whose every program has its execution."""
    return [c for c in cycles if c["dev"] is not None
            and all(d is not None for d in c["chunk_devs"])]


# ------------------------------------------------------------------ #
# the terms
# ------------------------------------------------------------------ #
def host_gap_ns(c) -> float:
    return c["dispatch"][0] - c["since"]


def device_ns(c) -> float:
    """Device time of the programs the cycle launched."""
    return (c["dev"][1] - c["dev"][0]
            + sum(e - s for s, e, _ in c["chunk_devs"]))


def sync_overhead_ns(c) -> float:
    return (c["fetch"][1] - c["dispatch"][0]) - device_ns(c)


def copy_ns(c):
    return None if c["copy"] is None else c["copy"][1] - c["copy"][0]


class Busy:
    """The device's busy intervals (disjoint, sorted), asked for the
    busy time inside any stretch."""

    def __init__(self, intervals):
        iv = np.asarray(intervals, np.float64).reshape(-1, 2)
        self.starts, self.ends = iv[:, 0], iv[:, 1]
        self.before = np.concatenate([[0.0], np.cumsum(iv[:, 1] - iv[:, 0])])

    def until(self, t: float) -> float:
        i = int(np.searchsorted(self.starts, t, side="right"))
        if i == 0:
            return 0.0
        return float(self.before[i - 1]
                     + min(t, self.ends[i - 1]) - self.starts[i - 1])

    def inside(self, a: float, b: float) -> float:
        return self.until(b) - self.until(a) if b > a else 0.0


def device_idle_ns(busy: Busy, c) -> float:
    """The device's idle time by its OWN line over the cycle: from the
    end of the previous cycle's decode execution to the end of this
    one's, less the operations in between (device clock only)."""
    a, b = c["prev"]["dev"][1], c["dev"][1]
    return (b - a) - busy.inside(a, b)


# ------------------------------------------------------------------ #
# the clock
# ------------------------------------------------------------------ #
def runtime_events(path: str):
    """``(enqueues, dones)``: the starts (ns, by start) of the host
    plane's ``DoEnqueueProgram`` and ``tpu::System::Execute=>Done``
    events, whatever thread wrote them."""
    from jax.profiler import ProfileData

    enq, done = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ENQUEUE:
                    enq.append(float(e.start_ns))
                elif e.name == DONE:
                    done.append(float(e.start_ns))
    return sorted(enq), sorted(done)


def pair_runtime(modules, enqueues, dones, slack: int = 2):
    """``{execution index: (enqueue, done)}``: the k-th execution of the
    line with the k-th enqueue and the k-th completion (up to ``slack``
    leading completions let go: of programs enqueued before the
    stretch), or None where the runtime wrote no such events or no such
    order holds: a program cannot take less between its enqueue and its
    completion, on the host's clock, than it ran on the device's."""
    if not (modules and enqueues and dones):
        return None
    for skip in range(slack + 1):
        rows = list(zip(modules, enqueues, dones[skip:]))
        if rows and all(d - q >= e - s for (_, s, e), q, d in rows):
            return {i: (q, d) for i, (_, q, d) in enumerate(rows)}
    return None


def clock_window(cycles, runtime=None):
    """``(lo, hi)``: the shifts of the device line (ns, added to every
    device stamp) that causality allows over the paired cycles: every
    execution starts after its dispatch began (after its enqueue, where
    ``runtime`` has it) and ends before the host's wait for it returned
    (before the runtime learnt of it).  0 is the xplane's own
    alignment."""
    los, his = [], []
    for c in paired(cycles):
        s, e, index = c["dev"]
        begin = c["dispatch"][0]
        until = (c["wait"] or c["fetch"])[1]
        if runtime and index in runtime:
            begin, until = max(begin, runtime[index][0]), \
                min(until, runtime[index][1])
        los.append(begin - s)
        his.append(until - e)
        for (cs, _, _), (ds, _, dindex) in zip(c["chunks"], c["chunk_devs"]):
            if runtime and dindex in runtime:
                cs = max(cs, runtime[dindex][0])
            los.append(cs - ds)
    if not los:
        return None
    return max(los), min(his)


def runtime_split(c, runtime):
    """The cycle's sync overhead in four terms that need no alignment,
    where ``runtime`` holds its decode execution: ``to_enqueue`` (host:
    ``decode_dispatch``'s start to the runtime's enqueue), ``device_side``
    (enqueue to completion on the host's clock less the execution on the
    device's: queueing before the program and the notice after it),
    ``wake`` (host: the completion to the wait's return) and ``after``
    (host: the rest of ``token_fetch``, the copy).  None without the
    events."""
    if not runtime or c["dev"][2] not in runtime:
        return None
    s, e, index = c["dev"]
    enqueue, done = runtime[index]
    woke = (c["wait"] or c["fetch"])[1]
    return {"to_enqueue": enqueue - c["dispatch"][0],
            "device_side": (done - enqueue) - (e - s),
            "wake": woke - done, "after": c["fetch"][1] - woke}


def violation_ns(window) -> float:
    """How far the device line must move to be causal: 0 inside the
    window, the excess outside it."""
    lo, hi = window
    return max(lo, 0.0) if lo > 0 else max(-hi, 0.0)


def fetch_idle_ms(busy: Busy, trace_window, fetches, n_steps: int,
                  shift: float) -> float:
    """``engine_idle_ms.token_fetch`` as ``program_trace`` reads it, with
    the device line moved by ``shift``."""
    lo, hi = trace_window
    idle = 0.0
    for a, b in fetches:
        a, b = max(a - shift, lo), min(b - shift, hi)
        if b > a:
            idle += (b - a) - busy.inside(a, b)
    return 1e-6 * idle / max(n_steps, 1)


# ------------------------------------------------------------------ #
# one run's reduction, shared by the readers
# ------------------------------------------------------------------ #
def reduction(spans, trace, events=None):
    """Everything the readers print and return, from the program's
    spans, the trace's first chip and the runtime's ``events``
    (``runtime_events``); None where the stretch holds no decode
    cycle."""
    window = trace.window
    cycles = decode_cycles(spans, window)
    if not cycles:
        return None
    out = {"cycles": cycles, "skip": None, "window": None, "runtime": None}
    if not trace.devices:
        return out
    dev = trace.devices[0]
    out["skip"] = pair_launches(cycles, engine_executions(dev.modules))
    if out["skip"] is None:
        return out
    if events is not None:
        out["runtime"] = pair_runtime(dev.modules, *events)
    out["span_window"] = clock_window(cycles)
    out["window"] = clock_window(cycles, out["runtime"])
    out["busy"] = Busy(tr.busy_intervals(dev, window))
    return out


def _ms(values, how=clocks.median):
    return 1e-6 * how(values) if values else float("nan")


def by_kind(cycles, kind):
    return [c for c in cycles if c["kind"] == kind]


def report(red, trace, steps) -> None:
    """The tables: the cycles by kind, the host gap by phase, the sum
    beside the device's own idle time, the copy, the clock."""
    cycles = red["cycles"]
    say(f"{len(cycles)} decode cycles in the traced stretch ("
        + ", ".join(f"{len(by_kind(cycles, k))} {k}"
                    for k in KINDS + ("broken",))
        + "); plain: no prefill chunk between two decode programs; "
        "broken: the stretch's first, or across a step that decoded "
        "nothing (left out)")
    if any(c["launch"] is None for c in cycles):
        say("the program's spans carry no launch=: executions paired by "
            "order alone, one program a span")
    for kind in KINDS:
        some = by_kind(cycles, kind)
        if not some:
            continue
        gaps = [host_gap_ns(c) for c in some]
        parts = [gap_parts(c) for c in some]
        say(f"{kind}: host gap (token_fetch end -> decode_dispatch start) "
            f"median {_ms(gaps):.3f} mean {_ms(gaps, _mean):.3f} ms over "
            f"{len(some)}; by phase, mean ms: " + ", ".join(
                f"{p} {1e-6 * _mean([part[p] for part in parts]):.3f}"
                for p in parts[0]))
    if red["skip"] is None:
        say("the executions on XLA Modules do not follow the order of the "
            "dispatch spans: nothing paired, no device term")
        return
    if red["skip"]:
        say(f"{red['skip']} leading executions let go (dispatched before "
            "the stretch's first whole step)")
    busy = red["busy"]
    for kind in KINDS:
        some = paired(by_kind(cycles, kind))
        if not some:
            continue
        sync = [sync_overhead_ns(c) for c in some]
        both = [host_gap_ns(c) + sync_overhead_ns(c) for c in some]
        say(f"{kind}: sync overhead (token_fetch end - decode_dispatch "
            f"start - device time of the programs launched) median "
            f"{_ms(sync):.3f} mean {_ms(sync, _mean):.3f} ms; device time "
            f"median {_ms([device_ns(c) for c in some]):.3f}; host gap + "
            f"sync overhead mean {_ms(both, _mean):.3f} ms a cycle")
        own = [c for c in some if c["prev"]["dev"] is not None]
        if own:
            by_dev = _mean([device_idle_ns(busy, c) for c in own])
            by_us = _mean([host_gap_ns(c) + sync_overhead_ns(c)
                           for c in own])
            inner = _mean([device_ns(c) - sum(
                busy.inside(s, e) for s, e, _ in [c["dev"]] + c["chunk_devs"])
                for c in own])
            say(f"{kind}: the device's own line, end of one decode "
                f"execution to the end of the next less its operations: "
                f"{1e-6 * by_dev:.3f} ms a cycle idle; the two clocks' "
                f"terms give {1e-6 * by_us:.3f} ({100 * (by_us / by_dev - 1):+.2f}%"
                f"), and {1e-6 * inner:.3f} ms of the difference lie "
                "between the operations INSIDE an execution")
    lo, hi = trace.window
    whole = paired([c for c in cycles if c["kind"] != "broken"])
    if whole and steps:
        idle_pct = tr.idle_pct(trace)
        total = sum(host_gap_ns(c) + sync_overhead_ns(c) for c in whole)
        span = sum(c["fetch"][1] - c["since"] for c in whole)
        say(f"all cycles: host gap + sync overhead {1e-6 * total:.1f} ms "
            f"over {1e-6 * span:.1f} ms of cycles = {100 * total / span:.2f}"
            f"% idle; device_idle_pct.serve reads {idle_pct:.2f}% of the "
            f"{1e-9 * (hi - lo):.2f} s stretch ({1e-6 * idle_pct / 100 * (hi - lo) / len(steps):.3f}"
            f" ms a step over {len(steps)} steps)")
    copies = [c for c in cycles if c["copy"] is not None]
    if copies:
        args = copies[-1]["copy"][2]
        waits = [c["wait"][1] - c["wait"][0] for c in copies if c["wait"]]
        say(f"bf.engine.host_copy median "
            f"{_ms([copy_ns(c) for c in copies]):.3f} ms over "
            f"{len(copies)} ({args.get('leaves')} leaves, "
            f"{args.get('bytes')} bytes); bf.engine.device_wait median "
            f"{_ms(waits):.3f} ms")
    report_clock(red, steps, trace)


def report_clock(red, steps, trace) -> None:
    window = red["window"]
    if window is None:
        return
    lo, hi = window
    slo, shi = red["span_window"]
    say(f"clock: the device line may move by {1e-3 * slo:+.0f} to "
        f"{1e-3 * shi:+.0f} us before an execution starts before its "
        "dispatch span began or ends after the wait for it returned "
        "(0 = the xplane's own alignment)")
    if red["runtime"]:
        say(f"clock: by the runtime's own events ({ENQUEUE}, {DONE}; "
            f"{len(red['runtime'])} executions paired by order) the window "
            f"narrows to {1e-3 * lo:+.0f} to {1e-3 * hi:+.0f} us")
    else:
        say("clock: the host plane holds no usable enqueue and completion "
            "events of the runtime; the window is the spans' own")
    where = ("inside it" if lo <= 0 <= hi else
             f"OUTSIDE it by {1e-3 * violation_ns(window):.0f} us")
    say(f"clock: the xplane's alignment (0) lies {where}"
        + ("" if lo <= hi else "; the window is EMPTY: the offset moved "
           "inside the stretch, or an execution is mispaired"))
    plain = paired(by_kind(red["cycles"], "plain"))
    if plain:
        rest = [sync_overhead_ns(c) - (copy_ns(c) or 0.0) for c in plain]
        say(f"plain: sync overhead less the copy = launch + completion "
            f"latency, median {_ms(rest):.3f} ms (the one part only a "
            "shared clock can split); at the window's ends launch latency "
            f"(decode_dispatch start -> execution start) reads median "
            f"{_ms([c['dev'][0] + lo - c['dispatch'][0] for c in plain]):.3f}"
            f" and {_ms([c['dev'][0] + hi - c['dispatch'][0] for c in plain]):.3f}"
            " ms")
    splits = [sp for sp in (runtime_split(c, red["runtime"]) for c in plain)
              if sp is not None]
    if splits:
        say("plain: by the runtime's events the sync overhead is, median ms "
            "(each a difference on one clock, or of two durations): "
            + ", ".join(f"{k} {_ms([sp[k] for sp in splits]):.3f}"
                        for k in splits[0])
            + " (to_enqueue: decode_dispatch start -> enqueue, host; "
            "device_side: enqueue -> completion less the execution; wake: "
            "completion -> the wait's return, host; after: the rest of "
            "token_fetch, host)")
    if steps:
        fetches = [iv for _, _, held in steps
                   for iv in held.get("token_fetch", [])]
        at = {shift: fetch_idle_ms(red["busy"], trace.window, fetches,
                                   len(steps), shift)
              for shift in (0.0, lo, hi)}
        say(f"clock: engine_idle_ms.token_fetch reads {at[0.0]:.3f} ms a "
            f"step as aligned, {at[lo]:.3f} at the window's low end and "
            f"{at[hi]:.3f} at its high end: its error bar")


def for_run(reader_file: str, trace):
    """``(reduction, ProgramTrace)`` of the run being reduced, computed
    and printed once; ``(None, None)`` where there is nothing to read."""
    run = pt.for_run(reader_file)
    if run is None:
        return None, None
    try:
        red = run.keep("step_timeline", lambda: reduction(
            run.spans, trace, runtime_events(run.path)))
        if red is not None and run.once("step_timeline"):
            report(red, trace, run.keep(
                "steps", lambda: pt.engine_steps(run.spans, trace.window)))
    except Exception:   # a reader reads nothing rather than fail a run
        import traceback

        say("no timeline: " + traceback.format_exc().replace("\n", " | "))
        run.kept["step_timeline"] = red = None
    return red, run


def median_ms(reader_file: str, trace, term, need_device: bool):
    """Median of ``term`` over the plain cycles of the run."""
    red, _ = for_run(reader_file, trace)
    if red is None:
        return None
    some = by_kind(red["cycles"], "plain")
    if need_device:
        some = paired(some)
    values = [v for v in map(term, some) if v is not None]
    return _ms(values) if values else None


def clock_violation_us(reader_file: str, trace):
    red, _ = for_run(reader_file, trace)
    if red is None or red.get("window") is None:
        return None
    return 1e-3 * violation_ns(red["window"])
