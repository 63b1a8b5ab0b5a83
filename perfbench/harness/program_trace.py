"""The program's own spans, scopes and counters, read for the per-layer
metrics that need more than ``harness/trace.py`` keeps.

The ``Trace`` handed to a reader has dropped every host event not named
``pb.*`` and every metadata stat, so this module opens the run's
``*.xplane.pb`` itself:

* the program's spans, ``bf.<track>.<name>`` on ``/host:CPU``
  (``bluefog_tpu.observe.tracer.Tracer.span`` writes each as a
  ``TraceAnnotation``), with their arguments, through
  ``jax.profiler.ProfileData``: on the clock of the device's ``XLA Ops``
  and of the benchmark's ``pb.*``;
* the ``tf_op`` stat of every device operation's *metadata* (the JAX op
  name, where a ``jax.named_scope`` shows).  ``ProfileData`` yields an
  event's own stats only, so the metadata is read from the protobuf
  wire format (``XSpace.planes[].event_metadata[].stats``), which needs
  nothing but the field numbers of ``xplane.proto``.

``run.py`` writes the trace to ``<root>/perfbench_out/trace/<cell>/``
after emptying that directory, and neither ``ctx`` nor ``Trace`` holds
the path: a reader passes its own ``__file__`` (``<root>/perfbench/
layer_metrics/<name>.py``) and gets the newest ``*.xplane.pb`` under
``<root>/perfbench_out/trace/``.

Where the program has no such span, scope or counter (the parent of the
PR that added them), every function here returns ``None``.  Off the
chip every reader built on this module reads nothing (``on_chip``): the
times would be a CPU's, and a traced line of the tests' CPU runs holds
what it held.  Span metrics are taken over the traced stretch
(``pb.trace_window``).  A counter is read over the whole process
(``counter_value``) where it is set against another counter, and over
the traced stretch (``counter_delta``) where it is set against the
stretch's device time.  Interval arithmetic is ``harness/trace.py``'s.
"""

from __future__ import annotations

import glob
import os
import re
from pathlib import Path

from perfbench.harness import clocks, trace as tr

PROGRAM_PREFIX = "bf."
ENGINE_STEP = "bf.engine.step"
TRAIN_STEP = "bf.train.train_step"
# metric suffix -> the span that is the phase
ENGINE_PHASES = {
    "admit": "bf.engine.admit",
    "prefill": "bf.engine.prefill_chunk",
    "decode_inputs": "bf.engine.decode_inputs",
    "decode_dispatch": "bf.engine.decode_dispatch",
    "token_fetch": "bf.engine.token_fetch",
    "emit": "bf.engine.emit",
}
SCOPE = re.compile(r"bf\.[a-z_]+")
FORWARD_BACKWARD = "bf.forward_backward"
# the parts of a train step that ``scope_of`` tells apart (the table
# that ``train_scope_ms`` prints has a row for each; ``forward`` and
# ``backward`` are metrics)
TRAIN_SCOPES = ("forward", "backward", "optimizer", "exchange")


def say(text: str) -> None:
    print(f"[program_trace] {text}", flush=True)


def on_chip() -> bool:
    """Whether this process runs on the chip.  The readers built on this
    module read nothing elsewhere: ``tests/perfbench/
    test_perfbench_runners.py`` holds the exact set of metrics of a
    traced CPU run (PERF.md section 7 asks a ``benchmark`` issue to
    relax it, and this gate to go)."""
    import jax

    return jax.default_backend() == "tpu"


# ------------------------------------------------------------------ #
# finding and reading the run's file
# ------------------------------------------------------------------ #
def run_xplane(reader_file: str):
    """The newest ``*.xplane.pb`` under ``<root>/perfbench_out/trace/``,
    ``<root>`` being two directories above the reader's own file; None
    where there is none."""
    root = Path(reader_file).resolve().parents[2]
    found = glob.glob(str(root / "perfbench_out" / "trace" / "*" / "plugins"
                          / "profile" / "*" / "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def program_spans_of(pd):
    """``[(name, start_ns, end_ns, args, thread)]`` of the program's
    spans in a ``ProfileData``, by start (a span before the spans it
    holds)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns),
                                dict(e.stats), line.name))
    out.sort(key=lambda t: (t[1], -t[2]))
    return out


def _varint(buf, i):
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, wire type, value)`` of one protobuf message; a
    length-delimited value is its ``(start, end)`` in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield number, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, plane, number):
    """The ``value`` messages of one ``map<int64, Message>`` field."""
    for f, wire, entry in _fields(buf, *plane):
        if f == number and wire == 2:
            for g, w, value in _fields(buf, *entry):
                if g == 2 and w == 2:
                    yield value


def tf_ops_of(buf) -> dict:
    """``{chip index: {operation's whole name: tf_op}}`` from the bytes
    of an ``XSpace``.  xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7 (a string kept as the name of another
    stat's metadata)."""
    buf = memoryview(buf)
    out = {}
    for f, wire, plane in _fields(buf, 0, len(buf)):
        if f != 1 or wire != 2:
            continue
        name = next((_text(buf, v) for g, w, v in _fields(buf, *plane)
                     if g == 2 and w == 2), "")
        m = tr.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_names = {}
        for meta in _map_values(buf, plane, 5):
            fields = {g: v for g, _, v in _fields(buf, *meta)}
            if 1 in fields and 2 in fields:
                stat_names[fields[1]] = _text(buf, fields[2])
        ops = out.setdefault(int(m.group(1)), {})
        for meta in _map_values(buf, plane, 4):
            op_name, tf_op = None, None
            for g, w, v in _fields(buf, *meta):
                if g == 2 and w == 2:
                    op_name = _text(buf, v)
                elif g == 5 and w == 2:
                    stat = {h: x for h, _, x in _fields(buf, *v)}
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        tf_op = _text(buf, stat[5])
                    elif 7 in stat:
                        tf_op = stat_names.get(stat[7])
            if op_name is not None and tf_op:
                ops[op_name] = tf_op
    return out


class ProgramTrace:
    """One run's file, read once: the program's spans at once, the
    operations' ``tf_op`` when a reader first asks."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        self.path = path
        self.spans = program_spans_of(ProfileData.from_file(path))
        self._tf_ops = None
        self.said = set()        # what has been printed once already
        self.kept = {}           # reductions that several readers share

    @property
    def tf_ops(self) -> dict:
        if self._tf_ops is None:
            with open(self.path, "rb") as fh:
                self._tf_ops = tf_ops_of(fh.read())
        return self._tf_ops

    def keep(self, key, make):
        """``make()``, computed once a run: the six readers of a table
        share one pass over the trace."""
        if key not in self.kept:
            self.kept[key] = make()
        return self.kept[key]

    def once(self, key: str) -> bool:
        """True the first time ``key`` is asked for."""
        if key in self.said:
            return False
        self.said.add(key)
        return True


_LOADED: dict = {}


def for_run(reader_file: str):
    """The ``ProgramTrace`` of the run that is being reduced, or None."""
    path = run_xplane(reader_file)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = ProgramTrace(path)
    return _LOADED[key]


# ------------------------------------------------------------------ #
# the serving engine's phases
# ------------------------------------------------------------------ #
def engine_steps(spans, window):
    """``[(start, end, {phase: [(start, end)]})]``: the ``bf.engine.step``
    spans wholly inside the window, each with the phase spans it holds
    on its thread."""
    lo, hi = window
    steps = [(s, e, th) for name, s, e, _, th in spans
             if name == ENGINE_STEP and s >= lo and e <= hi]
    by_span = {v: k for k, v in ENGINE_PHASES.items()}
    phases = [(by_span[name], s, e, th) for name, s, e, _, th in spans
              if name in by_span]
    out, j = [], 0
    for s, e, th in steps:
        held = {}
        while j < len(phases) and phases[j][1] < s:
            j += 1
        k = j
        while k < len(phases) and phases[k][1] < e:
            phase, ps, pe, pth = phases[k]
            if pth == th and pe <= e:
                held.setdefault(phase, []).append((ps, pe))
            k += 1
        out.append((s, e, held))
    return out


def phase_table(steps):
    """For each phase ``(steps in which it ran, median ms of its summed
    time in those steps, mean ms over all steps)``, and the same for
    ``self`` (what no phase covers) and ``step``."""
    rows = {}
    n = len(steps)
    sums = {p: [] for p in ENGINE_PHASES}
    selfs, whole = [], []
    for s, e, held in steps:
        covered = 0.0
        for phase, ivs in held.items():
            t = tr.total(ivs)
            sums[phase].append(t)
            covered += t
        selfs.append((e - s) - covered)
        whole.append(e - s)
    for phase, vals in {**sums, "self": selfs, "step": whole}.items():
        rows[phase] = ((len(vals), 1e-6 * clocks.median(vals),
                        1e-6 * sum(vals) / n) if vals else (0, None, 0.0))
    return rows


def _engine(reader_file, trace):
    pt = for_run(reader_file)
    if pt is None:
        return None, []
    return pt, pt.keep("steps",
                       lambda: engine_steps(pt.spans, trace.window))


def engine_phase_ms(reader_file: str, trace, phase: str):
    """Host time of ``phase`` summed within one ``bf.engine.step``,
    median over the steps of the traced stretch in which it ran."""
    pt, steps = _engine(reader_file, trace)
    if not steps:
        return None
    rows = pt.keep("phases", lambda: phase_table(steps))
    if pt.once("phase_table"):
        say(f"{len(steps)} bf.engine.step spans in the traced stretch; "
            "phase: steps in which it ran, median ms there, mean ms over "
            "all steps")
        for name, (k, med, mean) in rows.items():
            say(f"  {name:16s} {k:5d} "
                f"{'-' if med is None else format(med, '8.3f')} "
                f"{mean:8.3f}")
        say("  (self: the step's time that no phase covers)")
    return rows[phase][1]


def overlap(a, b) -> float:
    """Length of the part of the disjoint sorted intervals ``a`` that
    the disjoint sorted ``b`` cover."""
    return tr.total(a) - tr.total(tr.subtract(a, b))


def idle_by_phase(trace, steps):
    """Idle nanoseconds of the first chip inside the window, by what the
    engine was doing: each phase, ``self`` (inside a step, under no
    phase) and ``outside`` (under no ``bf.engine.step``)."""
    lo, hi = trace.window
    idle = tr.gaps(tr.busy_intervals(trace.devices[0], (lo, hi)), lo, hi)
    by_phase = {phase: tr.union([iv for _, _, held in steps
                                 for iv in held.get(phase, [])])
                for phase in ENGINE_PHASES}
    out = {phase: overlap(idle, ivs) for phase, ivs in by_phase.items()}
    in_steps = overlap(idle, tr.union([(s, e) for s, e, _ in steps]))
    in_phases = overlap(idle, tr.union(
        [iv for ivs in by_phase.values() for iv in ivs]))
    out.update(self=in_steps - in_phases,
               outside=tr.total(idle) - in_steps, all=tr.total(idle))
    return out


def engine_idle_ms(reader_file: str, trace, phase: str):
    """Device idle time inside ``phase``'s spans, per engine step of the
    traced stretch (mean)."""
    pt, steps = _engine(reader_file, trace)
    if not steps or not trace.devices:
        return None
    idle = pt.keep("idle", lambda: idle_by_phase(trace, steps))
    if pt.once("idle_table"):
        total = idle["all"] or 1.0
        say(f"device idle in the traced stretch {1e-6 * idle['all']:.1f} "
            f"ms over {len(steps)} engine steps; by phase, ms a step and "
            "share of all idle time:")
        for name in list(ENGINE_PHASES) + ["self", "outside"]:
            say(f"  {name:16s} {1e-6 * idle[name] / len(steps):8.3f} "
                f"{100 * idle[name] / total:6.2f}%")
        say("  (self: inside a step, under no phase; outside: under no "
            "bf.engine.* span)")
    return 1e-6 * idle[phase] / len(steps)


def launches_by_program(modules, steps) -> dict:
    """``{program: executions}`` of the ``XLA Modules`` events (by
    start) that begin inside one of ``steps``; a program is named by
    what precedes its ``(<id>)``."""
    names, j = {}, 0
    for s, e, _ in steps:
        while j < len(modules) and modules[j][1] < s:
            j += 1
        while j < len(modules) and modules[j][1] < e:
            key = modules[j][0].split("(")[0]
            names[key] = names.get(key, 0) + 1
            j += 1
    return names


def device_launches_per_step(reader_file: str, trace):
    """Executions on the first chip's ``XLA Modules`` that begin inside
    a ``bf.engine.step`` span of the traced stretch / those spans."""
    pt, steps = _engine(reader_file, trace)
    if not steps or not trace.devices:
        return None
    names = launches_by_program(trace.devices[0].modules, steps)
    if pt.once("launches"):
        say("programs launched inside engine steps, a step: " + ", ".join(
            f"{k} {v / len(steps):.2f}" for k, v in
            sorted(names.items(), key=lambda kv: -kv[1])[:8]))
    return sum(names.values()) / len(steps)


# ------------------------------------------------------------------ #
# the train step
# ------------------------------------------------------------------ #
def train_dispatch_ms(reader_file: str, trace):
    """Median ``bf.train.train_step`` span of the traced stretch: host
    time to dispatch one step, edge accounting included."""
    pt = for_run(reader_file)
    if pt is None:
        return None
    lo, hi = trace.window
    spans = [(s, e) for name, s, e, _, _ in pt.spans
             if name == TRAIN_STEP and s >= lo and e <= hi]
    if not spans:
        return None
    if pt.once("record_edges"):
        edges = [e - s for name, s, e, _, _ in pt.spans
                 if name == "bf.train.record_edges" and s >= lo and e <= hi]
        if edges:
            say(f"bf.train.record_edges: median "
                f"{1e-6 * clocks.median(edges):.3f} ms of the dispatch "
                f"over {len(edges)} steps")
    return 1e-6 * clocks.median([e - s for s, e in spans])


def scope_of(tf_op: str, bare: bool = False):
    """The part of the train step a ``tf_op`` path lies under:
    ``forward``, ``backward``, ``optimizer``, ``exchange``, or None.
    The innermost ``bf.*`` scope of the path names it; under
    ``bf.forward_backward`` JAX's own ``transpose(...)`` marks the
    backward pass.  ``bare``: the executable carries no ``bf.*`` scope
    at all (``stale``), and JAX's own ``jvp(`` is taken for
    ``bf.forward_backward``."""
    tf_op = tf_op or ""
    if bare:
        if "jvp(" not in tf_op:
            return None
        return "backward" if "transpose(" in tf_op else "forward"
    found = list(SCOPE.finditer(tf_op))
    if not found:
        return None
    last = found[-1]
    if last.group(0) == FORWARD_BACKWARD:
        return ("backward" if "transpose(" in tf_op[last.end():]
                else "forward")
    name = last.group(0)[len(PROGRAM_PREFIX):]
    return name if name in TRAIN_SCOPES else None


def stale(tf_ops: dict) -> bool:
    """Whether a program built with scopes runs an executable that
    shows none: the persistent compilation cache's key leaves metadata
    out, so an executable compiled before the scopes were written (or
    renamed) is served with its old ``tf_op`` names."""
    try:
        from bluefog_tpu.optim.functional import SCOPE_FORWARD_BACKWARD
    except ImportError:
        return False    # the program has no scopes to show
    return not any(SCOPE_FORWARD_BACKWARD in tf_op
                   for names in tf_ops.values() for tf_op in names.values())


def whole_steps(trace):
    """``(count, (start_ns, end_ns))`` of the executions, wholly inside
    the window, of the program that took most of it (a train step's) on
    the first chip: the stretch the scope times are taken over, so that
    a step the window's edge cuts is in neither the sum nor the count
    (host spans run ahead of the device, so the window rarely ends
    between two steps on the device)."""
    lo, hi = trace.window
    inside = [m for m in trace.devices[0].modules
              if m[1] >= lo and m[2] <= hi]
    sums = {}
    for name, s, e in inside:
        sums[name] = sums.get(name, 0.0) + e - s
    if not sums:
        return 0, (lo, hi)
    most = max(sums, key=sums.get)
    runs = [(s, e) for name, s, e in inside if name == most]
    return len(runs), (runs[0][0], runs[-1][1])


def scope_seconds(trace, tf_ops: dict, bare: bool = False, window=None):
    """``{scope: {operation: seconds}}`` over ``window`` (the trace's
    own by default), mean over the
    chips, with ``None`` for the time under no scope (``bare`` as in
    ``scope_of``).  One event has one
    ``tf_op``, and XLA fuses across scopes (the AdamW update into the
    weight-gradient matmuls): a fusion is billed whole to the scope its
    ``tf_op`` names.  Containers (``harness/trace.py:CONTAINER``) hold
    other operations and are left out."""
    lo, hi = window or trace.window
    n = max(len(trace.devices), 1)
    out = {}
    for d in trace.devices:
        names = tf_ops.get(d.index, {})
        for name, s, e in d.ops:
            short = tr.short_name(name)
            if e <= lo or s >= hi or tr.CONTAINER.match(short):
                continue
            ops = out.setdefault(scope_of(names.get(name), bare), {})
            ops[short] = ops.get(short, 0.0) + (
                min(e, hi) - max(s, lo)) * 1e-9 / n
    return out


def train_scope_ms(reader_file: str, trace, scope: str):
    """Device time a step of the operations under ``scope``."""
    pt = for_run(reader_file)
    if pt is None or not trace.devices:
        return None
    steps, stretch = whole_steps(trace)
    if steps == 0:
        return None
    bare = pt.keep("stale", lambda: stale(pt.tf_ops))
    by_scope = pt.keep(
        "scopes", lambda: scope_seconds(trace, pt.tf_ops, bare, stretch))
    if not any(k is not None for k in by_scope):
        return None     # the program has no scopes
    if pt.once("scope_table"):
        if bare:
            say("STALE EXECUTABLE: the program writes bf.* scopes and no "
                "operation of this trace carries one. The persistent "
                "compilation cache served an executable compiled before "
                "they were written (its key leaves metadata out). "
                "forward and backward are told apart by JAX's own jvp( "
                "and transpose( alone; bf.optimizer and bf.exchange "
                "cannot be seen. Clear the cache to read the scopes.")
        busy = 1e-9 * sum(tr.total(tr.busy_intervals(d, stretch))
                          for d in trace.devices) / len(trace.devices)
        say(f"device time by scope over {steps} whole steps, ms a step "
            f"(busy {1e3 * busy / steps:.3f}); a fusion is billed whole to "
            "the scope its tf_op names:")
        for key in list(TRAIN_SCOPES) + [None]:
            ops = by_scope.get(key, {})
            say(f"  {key or '(no scope)':12s} "
                f"{1e3 * sum(ops.values()) / steps:9.3f}  " + ", ".join(
                    f"{k} {1e3 * v / steps:.3f}" for k, v in tr.top(ops, 5)))
    ops = by_scope.get(scope)
    return 1e3 * sum(ops.values()) / steps if ops else None


# ------------------------------------------------------------------ #
# counters of the program's registry
# ------------------------------------------------------------------ #
def registry_metric(name: str, **labels):
    """The metric object ``name{labels}`` of the program's registry, or
    None where nothing has published it (asking the registry itself
    would create it)."""
    from bluefog_tpu.observe import get_registry

    want = {k: str(v) for k, v in labels.items()}
    for got, _, _, got_labels, metric in get_registry().collect():
        if got == name and got_labels == want:
            return metric
    return None


def counter_value(name: str, **labels):
    """The counter as it stands: its count over the whole process."""
    metric = registry_metric(name, **labels)
    return None if metric is None else float(metric.value)


DECODE_STEPS = "bf_serving_decode_steps_total"


def _key(name: str, labels: dict):
    return name, tuple(sorted((k, str(v)) for k, v in labels.items()))


def registry_values() -> dict:
    """``{(name, ((label, value), ...)): value}`` of every counter and
    gauge of the program's registry, as they stand."""
    from bluefog_tpu.observe import get_registry

    return {_key(name, labels): float(metric.value)
            for name, kind, _, labels, metric in get_registry().collect()
            if kind != "histogram"}


class CounterWindow:
    """The program's counters at the two edges of the traced stretch:
    ``runners/serve.py:trace_hooks`` calls ``open`` when the profiler
    has started and ``close`` just before it stops, and a run hands the
    object to the readers as ``ctx["counter_window"]``.

    The engine dispatches one decode program ahead of the one it blocks
    on, so the host's counters lead the device by one step at either
    edge: over the 250-450 steps of a stretch that is under half a
    percent, and it is left."""

    def __init__(self, opened=None, closed=None):
        self.opened, self.closed = opened, closed

    def open(self) -> None:
        self.opened = registry_values()

    def close(self) -> None:
        self.closed = registry_values()

    def delta(self, name: str, **labels):
        """What ``name{labels}`` grew by over the stretch; None where no
        stretch was traced or nothing had published the counter by its
        end."""
        key = _key(name, labels)
        if self.opened is None or self.closed is None \
                or key not in self.closed:
            return None
        return self.closed[key] - self.opened.get(key, 0.0)


def counter_delta(ctx: dict, name: str, **labels):
    """The counter's growth over the traced stretch of the run whose
    ``ctx`` this is; None where the run traced none (``CounterWindow``).
    What a reader divides by the stretch's device time is counted here:
    a flash crowd's lull holds half the decoding slots a step that the
    process's mean does."""
    window = ctx.get("counter_window")
    return None if window is None else window.delta(name, **labels)


def stretch_and_process(ctx: dict, per: str, name: str, **labels):
    """``(over the traced stretch, over the process)`` of the counter
    ``name{labels}`` a count of ``per``; either None where it cannot be
    formed."""
    def ratio(a, b):
        return a / b if a is not None and b else None

    return (ratio(counter_delta(ctx, name, **labels),
                  counter_delta(ctx, per)),
            ratio(counter_value(name, **labels), counter_value(per)))


def decode_slots(ctx: dict):
    """Decoding slots a decode step ``(in the traced stretch, over the
    process)``."""
    return stretch_and_process(ctx, DECODE_STEPS,
                               "bf_serving_decode_slots_total")


def slots_line(ctx: dict) -> str:
    """Both figures of ``decode_slots`` in words, for a reader's printed
    line: a log then shows by itself where the stretch is not the
    process."""
    here, process = (
        "no" if v is None else f"{v:.1f}" for v in decode_slots(ctx))
    return (f"{here} decoding slots a step in the traced stretch, "
            f"{process} over the process")
