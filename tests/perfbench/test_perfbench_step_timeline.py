"""The engine step's timeline (``perfbench/harness/step_timeline.py``,
PR 34): on a timeline built by hand whose device line is then moved
against the host's, on the pairing of dispatch spans with executions by
order, on the form the benchmark's lists keep under this PR's append,
and on a recording made on the chip with the new spans."""

import gzip
import importlib.util
import os

import pytest

from perfbench.harness import loader
from perfbench.harness import program_trace as pt
from perfbench.harness import step_timeline as st
from perfbench.harness import trace as tr

MS = 1e6  # nanoseconds
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SERVE_CELLS = ["mistral7b-serve-steady", "trinity-large-serve-mixed-len",
               "mistral-small4-serve-long-prompt",
               "mistral7b-serve-saturated", "xing4-serve-long-answer"]
NEW = ["engine_host_gap_ms", "step_sync_overhead_ms", "token_copy_ms",
       "trace_clock_violation_us", "engine_longest_step_ms"]
DECODE, CHUNK = "jit__decode_step_prog(5)", "jit__prefill_chunk_prog(3)"


def span(name, lo, hi, thread="main", **args):
    return ("bf.engine." + name, lo * MS, hi * MS, args, thread)


def step(lo, hi, dispatch, fetch_end, wait_end, launch, chunk=None,
         copy_ms=0.5):
    """One engine step that decodes: admit 0.5 ms, a chunk's dispatch
    (``(start, end, launch)``), decode_inputs up to the dispatch, the
    dispatch 1 ms, the fetch up to ``fetch_end`` with the wait and the
    copy inside it, emit 1 ms."""
    out = [span("step", lo, hi), span("admit", lo, lo + 0.5, admitted=0)]
    after = lo + 0.5
    if chunk:
        out.append(span("prefill_chunk", chunk[0], chunk[1], rid=1, slot=0,
                        start=512, launch=chunk[2], tokens=256))
        after = chunk[1]
    out += [span("decode_inputs", after, dispatch, slots=2),
            span("decode_dispatch", dispatch, dispatch + 1, launch=launch),
            span("token_fetch", dispatch + 1, fetch_end),
            span("device_wait", dispatch + 1.1, wait_end),
            span("host_copy", wait_end, wait_end + copy_ms, leaves=9,
                 bytes=1160),
            span("emit", fetch_end, fetch_end + 1, tokens=2)]
    return out


def hand_spans():
    """Six engine steps in a window of 200 ms.  A (10-40), B (41-70)
    and F (161-190) hold no chunk; C (71-110) dispatches one before its
    decode program; D (111-115) decodes nothing and dispatches a chunk;
    E (116-160) dispatches another and decodes.  Launches 7 to 14."""
    spans = (step(10, 40, 12, 38, 37, 7, copy_ms=0.8)
             + step(41, 70, 43, 68, 67, 8, copy_ms=0.6)
             + step(71, 110, 75, 108, 107, 10, chunk=(71.5, 73.5, 9))
             + [span("step", 111, 115), span("admit", 111, 111.5),
                span("prefill_chunk", 112, 114, rid=2, slot=1, start=0,
                     launch=11, tokens=256)]
             + step(116, 160, 120, 158, 157, 13, chunk=(116.5, 118.5, 12))
             + step(161, 190, 163, 188, 187.2, 14)
             + [span("emit", 60, 61, thread="other")])
    return sorted(spans, key=lambda t: (t[1], -t[2]))


EXECUTIONS = [(DECODE, 12.6, 36.5), (DECODE, 43.5, 66.4), (CHUNK, 72.2, 84.2),
              (DECODE, 84.3, 106.2), (CHUNK, 112.8, 124.8),
              (CHUNK, 124.9, 136.9), (DECODE, 137.0, 156.0),
              (DECODE, 163.4, 186.5)]


def hand_trace(shift_ms=0.0, executions=EXECUTIONS):
    """The device under ``hand_spans``, one operation an execution, its
    whole line moved by ``shift_ms``."""
    modules = [(name, (s + shift_ms) * MS, (e + shift_ms) * MS)
               for name, s, e in executions]
    ops = [(f"fusion.{i}", s, e) for i, (_, s, e) in enumerate(modules)]
    return tr.Trace([tr.DeviceTrace(0, ops, modules)],
                    [("pb.trace_window", 0.0, 200 * MS)])


def reduced(shift_ms=0.0):
    trace = hand_trace(shift_ms)
    return st.reduction(hand_spans(), trace), trace


def medians(red):
    plain = st.paired(st.by_kind(red["cycles"], "plain"))
    return {"gap": st._ms([st.host_gap_ns(c) for c in plain]),
            "sync": st._ms([st.sync_overhead_ns(c) for c in plain]),
            "copy": st._ms([st.copy_ns(c) for c in plain])}


# --------------------------------------------------------------------- #
# the cycles and their terms
# --------------------------------------------------------------------- #
def test_the_cycles_of_a_stretch_and_what_kind_each_is():
    cycles = st.decode_cycles(hand_spans(), (0.0, 200 * MS))
    assert [c["kind"] for c in cycles] == ["broken", "plain", "chunk",
                                           "broken", "plain"]
    assert [c["launch"] for c in cycles] == [7, 8, 10, 13, 14]
    assert [c["skipped"] for c in cycles] == [0, 0, 0, 1, 0]
    assert [len(c["chunks"]) for c in cycles] == [0, 0, 1, 2, 0]
    assert cycles[0]["since"] is None and cycles[1]["since"] == 38 * MS
    assert cycles[2]["prev"] is cycles[1]
    b = cycles[1]
    assert b["wait"][:2] == (44.1 * MS, 67 * MS)
    assert b["copy"][2] == {"leaves": 9, "bytes": 1160}
    # 38-43: emit 38-39, self 39-40, outside 40-41, admit, decode_inputs
    parts = {k: v / MS for k, v in st.gap_parts(b).items()}
    assert parts == pytest.approx({"emit": 1.0, "admit": 0.5, "prefill": 0.0,
                                   "decode_inputs": 1.5, "self": 1.0,
                                   "outside": 1.0})
    assert sum(parts.values()) == pytest.approx(st.host_gap_ns(b) / MS)
    # the cycle with a chunk: its dispatch is part of the gap
    assert st.gap_parts(cycles[2])["prefill"] == pytest.approx(2 * MS)
    # a window that cuts the first step leaves its cycle out
    assert len(st.decode_cycles(hand_spans(), (11 * MS, 200 * MS))) == 4


def test_the_terms_of_a_plain_cycle_and_of_one_with_a_chunk():
    red, _ = reduced()
    a, b, c, e, f = red["cycles"]
    assert red["skip"] == 0
    assert b["dev"] == (43.5 * MS, 66.4 * MS, 1)
    assert st.host_gap_ns(b) == pytest.approx(5.0 * MS)
    assert st.sync_overhead_ns(b) == pytest.approx((25 - 22.9) * MS)
    assert st.copy_ns(b) == pytest.approx(0.6 * MS)
    assert st.host_gap_ns(f) == pytest.approx(5.0 * MS)
    assert st.sync_overhead_ns(f) == pytest.approx((25 - 23.1) * MS)
    # with a chunk the cycle's device time is both programs', and the
    # sum of the two terms is still the device's idle time
    assert c["chunk_devs"] == [(72.2 * MS, 84.2 * MS, 2)]
    assert st.device_ns(c) == pytest.approx((12 + 21.9) * MS)
    assert st.host_gap_ns(c) + st.sync_overhead_ns(c) == \
        pytest.approx((40 - 33.9) * MS)
    # ... as the device's own line gives it: end of one decode execution
    # to the end of the next less the operations, but for how the
    # completion latency differs between the two ends (1.6 and 1.8 ms)
    assert st.device_idle_ns(red["busy"], c) == \
        pytest.approx((106.2 - 66.4 - 33.9) * MS)
    assert st.device_idle_ns(red["busy"], c) == pytest.approx(
        st.host_gap_ns(c) + st.sync_overhead_ns(c) - 0.2 * MS)
    assert medians(red) == pytest.approx({"gap": 5.0, "sync": 2.0,
                                          "copy": 0.55})


@pytest.mark.parametrize("shift_ms", [-1.0, 0.0, 0.3, 1.0])
def test_a_moved_device_line_moves_the_idle_split_and_not_the_terms(
        shift_ms):
    """The three terms hold to the digit wherever the device line lies;
    ``engine_idle_ms.token_fetch`` moves with it; the violation is 0
    inside the causal window and the excess outside it."""
    base, trace0 = reduced()
    red, trace = reduced(shift_ms)
    assert medians(red) == pytest.approx(medians(base), abs=1e-9)
    for c0, c in zip(base["cycles"], red["cycles"]):
        assert st.host_gap_ns(c) == st.host_gap_ns(c0) if c["since"] else True
        assert st.sync_overhead_ns(c) == pytest.approx(
            st.sync_overhead_ns(c0), abs=1e-3)
    # the spans allow -0.4 (F starts 0.4 after its dispatch) to +0.5 (A
    # ends 0.5 before its wait returns) on the line as recorded
    lo, hi = red["window"]
    assert (lo / MS, hi / MS) == pytest.approx((-0.4 - shift_ms,
                                                0.5 - shift_ms))
    want_us = {-1.0: 600.0, 0.0: 0.0, 0.3: 0.0, 1.0: 500.0}[shift_ms]
    assert 1e-3 * st.violation_ns(red["window"]) == pytest.approx(want_us)
    # the old reading, by the old reader, on both lines
    steps = pt.engine_steps(hand_spans(), trace.window)
    old = pt.idle_by_phase(trace, steps)["token_fetch"] / MS / len(steps)
    old0 = pt.idle_by_phase(trace0, steps)["token_fetch"] / MS / len(steps)
    if shift_ms:
        assert abs(old - old0) > 0.2
    # and this module reproduces it from ONE line and a shift
    fetches = [iv for _, _, held in steps
               for iv in held.get("token_fetch", [])]
    assert st.fetch_idle_ms(base["busy"], trace0.window, fetches,
                            len(steps), shift_ms * MS) == pytest.approx(old)
    assert st.fetch_idle_ms(red["busy"], trace.window, fetches, len(steps),
                            0.0) == pytest.approx(old)


def test_the_runtimes_own_events_narrow_the_window():
    """An enqueue 0.45 ms after each dispatch span began and a
    completion seen 0.3 ms before each wait returned, paired with the
    executions by order."""
    red, trace = reduced()
    modules = trace.devices[0].modules
    begins = {c["dev"][2]: c["dispatch"][0] for c in red["cycles"]}
    waits = {c["dev"][2]: c["wait"][1] for c in red["cycles"]}
    enq = [begins.get(i, s - 0.7 * MS) + 0.45 * MS
           for i, (_, s, _) in enumerate(modules)]
    done = [waits.get(i, e + 0.6 * MS) - 0.3 * MS
            for i, (_, _, e) in enumerate(modules)]
    runtime = st.pair_runtime(modules, enq, done)
    assert sorted(runtime) == list(range(8))
    lo, hi = st.clock_window(red["cycles"], runtime)
    assert (lo / MS, hi / MS) == pytest.approx((0.05, 0.2))
    assert 1e-3 * st.violation_ns((lo, hi)) == pytest.approx(50.0)
    # and split the overhead with no alignment: 0.45 to the enqueue, the
    # wait returns 0.3 after the completion, the copy and 0.5 of the
    # fetch's own after that; the rest lies on the device's side
    b = red["cycles"][1]
    split = st.runtime_split(b, runtime)
    assert {k: v / MS for k, v in split.items()} == pytest.approx(
        {"to_enqueue": 0.45, "device_side": 2.1 - 0.45 - 0.3 - 1.0,
         "wake": 0.3, "after": 1.0})
    assert sum(split.values()) == pytest.approx(st.sync_overhead_ns(b))
    assert st.runtime_split(b, None) is None
    # events that cannot be these executions' are not used
    assert st.pair_runtime(modules, enq, [d - 90 * MS for d in done]) is None
    assert st.pair_runtime(modules, [], []) is None
    # a completion of a program enqueued before the stretch is let go
    assert st.pair_runtime(modules, enq, [1.0 * MS] + done) == runtime
    assert st.clock_window(red["cycles"], None) == red["span_window"]


# --------------------------------------------------------------------- #
# pairing by order
# --------------------------------------------------------------------- #
def test_pairing_survives_a_chunk_and_a_step_that_decodes_nothing():
    red, _ = reduced()
    cycles = red["cycles"]
    assert [c["dev"][2] for c in cycles] == [0, 1, 3, 6, 7]
    assert [[d[2] for d in c["chunk_devs"]] for c in cycles] == [
        [], [], [2], [4, 5], []]
    assert [k for k, _, _ in st.host_launches(cycles)] == [
        "decode", "decode", "chunk", "decode", "chunk", "chunk", "decode",
        "decode"]


def test_pairing_follows_launch_over_a_program_no_span_shows():
    """The chunk of launch 11 dispatched under no span of the list (a
    draft model's chunk beside the target's is such a program): the
    count says an execution is to be passed over."""
    spans = [sp for sp in hand_spans() if sp[3].get("launch") != 11]
    red = st.reduction(spans, hand_trace())
    assert [c["dev"][2] for c in red["cycles"]] == [0, 1, 3, 6, 7]
    assert [[d[2] for d in c["chunk_devs"]] for c in red["cycles"]] == [
        [], [], [2], [5], []]
    # without the counts the order alone would be one execution off
    bare = [(n, s, e, {k: v for k, v in a.items() if k != "launch"}, th)
            for n, s, e, a, th in spans]
    off = st.reduction(bare, hand_trace())
    assert off["skip"] is None or \
        [c["dev"] and c["dev"][2] for c in off["cycles"]] != [0, 1, 3, 6, 7]


def test_pairing_lets_go_of_an_execution_dispatched_before_the_stretch():
    early = [(CHUNK, 1.0, 9.0)] + EXECUTIONS
    red = st.reduction(hand_spans(), hand_trace(executions=early))
    assert red["skip"] == 1
    assert [c["dev"][2] for c in red["cycles"]] == [1, 2, 4, 7, 8]
    # an order that fits nowhere pairs nothing, and no term needs it
    wrong = [(DECODE, s, e) for _, s, e in EXECUTIONS]
    red = st.reduction(hand_spans(), hand_trace(executions=wrong))
    assert red["skip"] is None and red["window"] is None
    assert st.paired(red["cycles"]) == []
    assert st._ms([st.host_gap_ns(c)
                   for c in st.by_kind(red["cycles"], "plain")]) == 5.0


def test_spans_of_the_parent_pair_by_order_and_read_no_copy():
    """The program before PR 34: no ``launch=``, nothing inside
    ``token_fetch``."""
    old = [(n, s, e, {k: v for k, v in a.items()
                      if k not in ("launch", "start")}, th)
           for n, s, e, a, th in hand_spans()
           if n not in (st.WAIT, st.COPY)]
    red = st.reduction(old, hand_trace())
    assert [c["dev"][2] for c in red["cycles"]] == [0, 1, 3, 6, 7]
    plain = st.by_kind(red["cycles"], "plain")
    assert [st.copy_ns(c) for c in plain] == [None, None]
    assert st._ms([st.sync_overhead_ns(c) for c in plain]) == \
        pytest.approx(2.0)
    # the window's upper end is the fetch's own end
    assert red["window"][1] / MS == pytest.approx(38 - 36.5)


# --------------------------------------------------------------------- #
# the readers
# --------------------------------------------------------------------- #
def reader(tmp_path, name):
    """A copy root with one reader file in it, as ``loader`` would load
    it, and the path of its (still missing) trace directory."""
    root = tmp_path / "root"
    (root / "perfbench" / "layer_metrics").mkdir(parents=True, exist_ok=True)
    src = os.path.join(REPO, "perfbench", "layer_metrics", name + ".py")
    dst = root / "perfbench" / "layer_metrics" / (name + ".py")
    dst.write_text(open(src).read())
    spec = importlib.util.spec_from_file_location("reader_under_test", dst)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, (root / "perfbench_out" / "trace" / "cell" / "plugins"
                    / "profile" / "run")


@pytest.fixture
def own_registry(monkeypatch):
    """A registry of this test's own as the process's."""
    from bluefog_tpu.observe import registry

    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    return registry.get_registry()


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_off_the_chip_or_without_a_file(
        tmp_path, monkeypatch, own_registry, name):
    """(For the longest step: without a gauge.)"""
    module, _ = reader(tmp_path, name)
    trace = hand_trace()
    assert module.reduce(trace, None, {}) is None           # the CPU
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    assert module.reduce(trace, None, {}) is None           # no file


def test_the_longest_step_comes_from_the_gauge(tmp_path, monkeypatch,
                                               own_registry, capsys):
    module, _ = reader(tmp_path, "engine_longest_step_ms")
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    reg = own_registry
    for phase, value in (("step", 2.5), ("device_wait", 2.25),
                         ("emit", 0.001)):
        reg.gauge("bf_serving_longest_step_seconds", "", phase=phase).set(
            value)
    assert module.reduce(None, None, {}) == pytest.approx(2500.0)
    out = capsys.readouterr().out
    assert "device_wait 2250.00, emit 1.00" in out


# --------------------------------------------------------------------- #
# a recording from the chip
# --------------------------------------------------------------------- #
RECORDING = "serve_timeline_v5e.xplane.pb.gz"
# the plain cycles' sync overhead by the runtime's events, median ms
SPLIT = {"to_enqueue": 1.021, "device_side": 0.536, "wake": 0.061,
         "after": 0.464}


@pytest.fixture(scope="module")
def recording(tmp_path_factory):
    """``(path, trace, spans)`` of eight engine steps of
    ``mistral7b-serve-steady`` around a prefill chunk, cut from one
    second traced on a TPU v5e with the engine of PR 34 (my chip run,
    PR 34; ``data/record_serve_timeline.py`` says how): the FIRST process
    of its machine's session.  The figures below were read off the file
    with the module's own functions and are pinned as the other
    recordings' are."""
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("recording") / RECORDING[:-3]
    with gzip.open(os.path.join(HERE, "data", RECORDING)) as src:
        path.write_bytes(src.read())
    trace = tr.load(str(path))
    spans = pt.program_spans_of(ProfileData.from_file(str(path)))
    return str(path), trace, spans


def test_the_recording_holds_the_new_spans_where_they_belong(recording):
    _, trace, spans = recording
    assert len(spans) == 65 and len(trace.devices[0].modules) == 15
    by_name = {}
    for name, s, e, args, _ in spans:
        by_name.setdefault(name.rsplit(".", 1)[1], []).append((s, e, args))
    assert {k: len(v) for k, v in by_name.items()} == {
        "step": 8, "admit": 8, "prefill_chunk": 1, "decode_inputs": 8,
        "decode_dispatch": 8, "token_fetch": 8, "device_wait": 8,
        "host_copy": 8, "emit": 8}
    for (s, e, _), (ws, we, _), (cs, ce, args) in zip(
            by_name["token_fetch"], by_name["device_wait"],
            by_name["host_copy"]):
        assert s < ws < we < cs < ce < e
        assert args == {"leaves": 1, "bytes": 128}   # [horizon 1, capacity 32]
        assert (ce - e) + (ws - s) + (cs - we) < 0.25 * MS   # the fetch IS its parts
    # every program once, in the order it was dispatched
    launches = sorted((s, args["launch"]) for name in
                      ("decode_dispatch", "prefill_chunk")
                      for s, _, args in by_name[name])
    assert [k for _, k in launches] == list(range(94, 103))
    assert by_name["prefill_chunk"][0][2] == {
        "rid": 6, "slot": 2, "start": 0, "launch": 96, "tokens": 249}


def test_the_recording_reduces_to_known_figures(recording):
    path, trace, spans = recording
    events = st.runtime_events(path)
    assert (len(events[0]), len(events[1])) == (15, 15)
    red = st.reduction(spans, trace, events)
    cycles = red["cycles"]
    # the window (the extent of the cut's operations) cuts the first
    # step and the last
    assert [c["kind"] for c in cycles] == ["broken", "chunk"] + ["plain"] * 4
    assert [c["launch"] for c in cycles] == [95, 97, 98, 99, 100, 101]
    # one decode execution before the stretch's first whole step is let
    # go; convert_element_type and reset_index_slot lie between the
    # engine's programs on the line and are passed over
    assert red["skip"] == 1
    assert [c["dev"][2] for c in cycles] == [1, 5, 8, 9, 10, 11]
    assert cycles[1]["chunk_devs"][0][2] == 4
    got = medians(red)
    assert got == pytest.approx({"gap": 2.1249, "sync": 2.0827,
                                 "copy": 0.3844}, abs=1e-4)
    parts = st.gap_parts(cycles[3])
    assert {k: round(v / MS, 3) for k, v in parts.items()} == {
        "emit": 0.052, "admit": 0.016, "prefill": 0.0,
        "decode_inputs": 1.832, "self": 0.197, "outside": 0.107}
    # the two clocks' terms are the device's own idle time a cycle
    plain = st.by_kind(cycles, "plain")
    ours = sum(st.host_gap_ns(c) + st.sync_overhead_ns(c) for c in plain)
    own = sum(st.device_idle_ns(red["busy"], c) for c in plain)
    assert ours / own == pytest.approx(1.0, abs=0.02)
    # the clock.  By the spans alone the device line of this file lies
    # at least 365 us EARLY: an execution starts before the span that
    # dispatched it began.  The runtime's own events narrow the window
    # to 1,214-1,679 us
    assert [round(1e-3 * v) for v in red["span_window"]] == [365, 1723]
    assert [round(1e-3 * v) for v in red["window"]] == [1214, 1679]
    assert len(red["runtime"]) == 15
    assert 1e-3 * st.violation_ns(red["window"]) == pytest.approx(1213.75)
    splits = [st.runtime_split(c, red["runtime"]) for c in plain]
    assert {k: round(st._ms([sp[k] for sp in splits]), 3)
            for k in splits[0]} == SPLIT
    assert all(sum(sp.values()) == pytest.approx(st.sync_overhead_ns(c))
               for sp, c in zip(splits, plain))
    early = cycles[0]
    assert early["dev"][0] < early["dispatch"][0]        # not causal
    # the old reading, as aligned and at the window's two ends
    steps = pt.engine_steps(spans, trace.window)
    fetches = [iv for _, _, held in steps
               for iv in held.get("token_fetch", [])]
    at = [st.fetch_idle_ms(red["busy"], trace.window, fetches, len(steps),
                           shift) for shift in (0.0, *red["window"])]
    assert at == pytest.approx([2.303, 1.099, 0.895], abs=1e-3)
    idle = pt.idle_by_phase(trace, steps)
    assert idle["token_fetch"] / MS / len(steps) == pytest.approx(at[0])
    assert idle["decode_dispatch"] / MS / len(steps) < 0.001


def test_the_recordings_device_line_moved_into_the_window_is_causal(
        recording):
    """The device line moved by the window's low end: the three terms
    stay to the digit, nothing is violated, and every execution starts
    after the span that dispatched it began."""
    path, trace, spans = recording
    events = st.runtime_events(path)
    base = st.reduction(spans, trace, events)
    shift = base["window"][0]
    dev = trace.devices[0]
    moved = tr.Trace(
        [tr.DeviceTrace(0, [(n, s + shift, e + shift) for n, s, e in dev.ops],
                        [(n, s + shift, e + shift) for n, s, e in dev.modules])],
        trace.spans)
    red = st.reduction(spans, moved, events)
    assert medians(red) == pytest.approx(medians(base), abs=1e-9)
    assert st.violation_ns(red["window"]) == pytest.approx(0.0, abs=1e-3)
    assert red["window"][1] - red["window"][0] == pytest.approx(
        base["window"][1] - base["window"][0])
    assert all(c["dev"][0] >= c["dispatch"][0] for c in red["cycles"])


def test_the_readers_read_the_recording(tmp_path, monkeypatch, recording,
                                        capsys):
    """The four readers of the timeline through the files the loader
    loads, on the recording laid where a run leaves its trace."""
    path, trace, _ = recording
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    got = {}
    for name in NEW[:4]:
        module, run_dir = reader(tmp_path, name)
        run_dir.mkdir(parents=True, exist_ok=True)
        target = run_dir / "recorded.xplane.pb"
        if not target.exists():
            target.write_bytes(open(path, "rb").read())
        got[name] = module.reduce(trace, None, {})
    assert got == pytest.approx({
        "engine_host_gap_ms": 2.1249, "step_sync_overhead_ms": 2.0827,
        "token_copy_ms": 0.3844, "trace_clock_violation_us": 1213.75},
        abs=1e-4)
    out = capsys.readouterr().out
    assert out.count("decode cycles in the traced stretch") == 1  # said once
    assert "(1 leaves, 128 bytes)" in out
    assert "lies OUTSIDE it by 1214 us" in out
    assert "narrows to +1214 to +1679 us" in out
    assert ", ".join(f"{k} {v:.3f}" for k, v in SPLIT.items()) in out


# --------------------------------------------------------------------- #
# the form of the append
# --------------------------------------------------------------------- #
CELLS = ["mistral7b-train-1chip", "resnet50-train-1chip",
         "mistral7b-serve-steady", "mistral7b-train-atc-4chip",
         "trinity-large-serve-mixed-len", "mistral-small4-serve-long-prompt",
         "mistral7b-serve-saturated", "xing4-serve-long-answer",
         "mistral7b-train-allreduce-4chip"]
# per_layer as the commit before this PR left it: each name with its
# cells, a digit a cell of CELLS (but that PR 48 took cell 7, whose
# first-token tail holds no bound, from the six lists that move
# ``ttft_p95_ms``: ``test_perfbench_mhc.py`` holds where it reads now)
BEFORE = [
    ("compile_s", "012345678"), ("step_ms.train", "0138"),
    ("train_mfu_pct", "0138"), ("flash_attention_roofline", "038"),
    ("exchange_ms", "3"), ("exchange_exposed_ms", "3"),
    ("device_idle_pct.train", "0138"), ("engine_step_ms", "2457"),
    ("queue_wait_p95_ms", "245"), ("decode_step_device_ms", "2457"),
    ("decode_step_roofline", "2"), ("device_idle_pct.serve", "24567"),
    ("loadgen_late_p95_ms", "245"), ("engine_phase_ms.admit", "245"),
    ("engine_phase_ms.prefill", "2457"),
    ("engine_phase_ms.decode_inputs", "2457"),
    ("engine_phase_ms.decode_dispatch", "2457"),
    ("engine_phase_ms.token_fetch", "2457"), ("engine_phase_ms.emit", "2457"),
    ("engine_idle_ms.admit", "24567"), ("engine_idle_ms.prefill", "24567"),
    ("engine_idle_ms.decode_inputs", "24567"),
    ("engine_idle_ms.decode_dispatch", "24567"),
    ("engine_idle_ms.token_fetch", "24567"), ("engine_idle_ms.emit", "24567"),
    ("device_launches_per_step", "2457"), ("queue_wait_prog_p95_ms", "245"),
    ("decode_slots_per_step", "2457"), ("prefill_pad_pct", "245"),
    ("train_dispatch_ms", "0138"), ("train_scope_ms.forward", "0138"),
    ("train_scope_ms.backward", "0138"), ("setup_trace_s", "012345678"),
    ("setup_lower_s", "012345678"), ("setup_backend_compile_s", "012345678"),
    ("compile_cache_misses", "012345678"), ("attn_scope_ms.window", "4"),
    ("attn_scope_ms.full", "4"), ("moe_experts_device_ms", "457"),
    ("moe_decode_step_roofline", "457"), ("moe_held_share_pct", "457"),
    ("kv_reserved_mib_per_slot", "457"), ("decode_cache_streamed_pct", "2"),
    ("prefill_chunk_device_ms", "2457"), ("moe_tile_fill_pct", "457"),
    ("attn_scope_ms.latent", "57"), ("chunk_attn_ms.latent", "5"),
    ("latent_cache_bytes_per_token", "57"), ("chunk_attn_ms.window", "4"),
    ("chunk_attn_ms.full", "4"), ("chunk_cache_streamed_pct", "4"),
    ("hc_scope_ms.decode", "7"), ("hc_scope_ms.chunk", "7"),
    ("hc_mix_roofline", "7"), ("hc_mixed_tokens_per_step", "7"),
]


def test_the_append_keeps_what_was_there():
    """What ``test_perfbench_mhc.py`` pinned as the LAST four names and
    a length of 55 is now a PREFIX: the 55 names in their order, every
    older ``workloads`` list as a prefix of what it is now, and this
    PR's five after them, each over the five serve cells (the older
    file's case is expected to fail from ``tests/conftest.py``, PERF.md
    section 7)."""
    bench = loader.load_benchmark(REPO)
    assert [w["name"] for w in bench["workloads"]][:len(CELLS)] == CELLS
    assert len(BEFORE) == 55
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    assert len(names) == len(set(names))
    assert names[:55] == [name for name, _ in BEFORE]
    for m, (_, cells) in zip(entries, BEFORE):
        was = [CELLS[int(c)] for c in cells]
        assert m["workloads"][:len(was)] == was, m["name"]
    assert names[55:60] == NEW
    for m in entries[55:60]:
        assert m["workloads"][:5] == SERVE_CELLS
        assert m["moves"] == "serve_tokens_per_s" and m["better"] == "lower"
        assert m["layer"] in ("serving engine", "device")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "layer_metrics", m["name"] + ".py"))
    assert {m["name"]: m["source"] for m in entries[55:60]} == {
        "engine_host_gap_ms": "program_span",
        "step_sync_overhead_ms": "device_trace",
        "token_copy_ms": "program_span",
        "trace_clock_violation_us": "device_trace",
        "engine_longest_step_ms": "program_counter"}
    # each of the five serve cells reports the five
    for cell in SERVE_CELLS:
        loaded = loader.load_cell(cell, REPO)
        assert set(NEW) <= {m["name"] for m in loaded.per_layer}
