"""The readers of the program's own spans, scopes and counters
(``perfbench/harness/program_trace.py``): on spans, gaps and launches
built by hand with known sums, on the wire format of the traces
recorded on the chip, and on a profile of the tiny engine made here."""

import gzip
import importlib.util
import os

import pytest

from perfbench.harness import program_trace as pt
from perfbench.harness import trace as tr

MS = 1e6  # nanoseconds
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def span(name, lo, hi, thread="main", **args):
    return (name, lo * MS, hi * MS, args, thread)


def hand_spans():
    """Two engine steps inside a 100 ms window and one cut by its end.
    Step 1, 10-50: admit 10-12, a chunk 12-16, decode_inputs 16-30,
    decode_dispatch 30-32, token_fetch 32-46, emit 46-49 (1 ms of self
    time).  Step 2, 55-90: admit 55-56, decode_inputs 56-70,
    decode_dispatch 70-72, token_fetch 72-86, emit 86-89 (1 ms self).
    Another thread's spans and a step that ends after the window are
    not counted."""
    return sorted([
        span("bf.engine.step", 10, 50),
        span("bf.engine.admit", 10, 12, admitted=1),
        span("bf.engine.prefill_chunk", 12, 16, rid=3, slot=0, tokens=200),
        span("bf.engine.decode_inputs", 16, 30, slots=2),
        span("bf.engine.decode_dispatch", 30, 32),
        span("bf.engine.token_fetch", 32, 46),
        span("bf.engine.emit", 46, 49, tokens=2),
        span("bf.engine.step", 55, 90),
        span("bf.engine.admit", 55, 56, admitted=0),
        span("bf.engine.decode_inputs", 56, 70, slots=2),
        span("bf.engine.decode_dispatch", 70, 72),
        span("bf.engine.token_fetch", 72, 86),
        span("bf.engine.emit", 86, 89, tokens=2),
        span("bf.engine.emit", 60, 61, thread="other"),
        span("bf.engine.step", 95, 120),
        span("bf.engine.admit", 95, 96),
    ], key=lambda t: (t[1], -t[2]))


def hand_trace():
    """The device under ``hand_spans``: a chunk 13-29, the decode
    program 33-45 and 73-85, a tiny program 20-21 (inside the chunk) and
    one at 60-61.  Idle: 0-13, 29-33, 45-60, 61-73, 85-100 = 59 ms."""
    ops = [("fusion.1", 13 * MS, 29 * MS), ("fusion.2", 33 * MS, 45 * MS),
           ("fusion.3", 60 * MS, 61 * MS), ("fusion.2", 73 * MS, 85 * MS)]
    modules = [("jit__prefill_chunk_prog(3)", 13 * MS, 29 * MS),
               ("jit__threefry_seed(9)", 20 * MS, 21 * MS),
               ("jit__decode_step_prog(5)", 33 * MS, 45 * MS),
               ("jit__threefry_seed(9)", 60 * MS, 61 * MS),
               ("jit__decode_step_prog(5)", 73 * MS, 85 * MS),
               ("jit__decode_step_prog(5)", 96 * MS, 99 * MS)]
    return tr.Trace([tr.DeviceTrace(0, ops, modules)],
                    [("pb.trace_window", 0.0, 100 * MS)])


def test_engine_steps_hold_their_own_threads_phases():
    steps = pt.engine_steps(hand_spans(), (0.0, 100 * MS))
    assert [(s, e) for s, e, _ in steps] == [(10 * MS, 50 * MS),
                                             (55 * MS, 90 * MS)]
    assert sorted(steps[0][2]) == sorted(pt.ENGINE_PHASES)
    assert "prefill" not in steps[1][2]
    assert steps[1][2]["emit"] == [(86 * MS, 89 * MS)]


def test_phase_table_medians_means_and_self_time():
    rows = pt.phase_table(pt.engine_steps(hand_spans(), (0.0, 100 * MS)))
    assert rows["prefill"] == (1, pytest.approx(4.0), pytest.approx(2.0))
    assert rows["decode_inputs"] == (2, pytest.approx(14.0),
                                     pytest.approx(14.0))
    assert rows["admit"] == (2, pytest.approx(1.5), pytest.approx(1.5))
    assert rows["self"] == (2, pytest.approx(1.0), pytest.approx(1.0))
    assert rows["step"] == (2, pytest.approx(37.5), pytest.approx(37.5))
    # the means over all steps add up to the mean step, self included
    assert sum(rows[p][2] for p in list(pt.ENGINE_PHASES) + ["self"]) == \
        pytest.approx(rows["step"][2])


def test_idle_time_goes_to_the_phase_that_covers_it():
    trace = hand_trace()
    steps = pt.engine_steps(hand_spans(), trace.window)
    idle = {k: v / MS for k, v in pt.idle_by_phase(trace, steps).items()}
    assert idle["all"] == pytest.approx(59.0)
    # 10-12; 12-13; 29-30 and 56-60, 61-70; 30-32, 70-72; 32-33 and
    # 45-46, 72-73 and 85-86; 46-49, 86-89
    assert idle["admit"] == pytest.approx(2.0 + 1.0)
    assert idle["prefill"] == pytest.approx(1.0)
    assert idle["decode_inputs"] == pytest.approx(1.0 + 4.0 + 9.0)
    assert idle["decode_dispatch"] == pytest.approx(4.0)
    assert idle["token_fetch"] == pytest.approx(4.0)
    assert idle["emit"] == pytest.approx(6.0)
    assert idle["self"] == pytest.approx(2.0)       # 49-50, 89-90
    assert idle["outside"] == pytest.approx(10.0 + 5.0 + 10.0)
    parts = list(pt.ENGINE_PHASES) + ["self", "outside"]
    assert sum(idle[k] for k in parts) == pytest.approx(idle["all"])


def test_launches_are_counted_by_where_they_begin():
    trace = hand_trace()
    steps = pt.engine_steps(hand_spans(), trace.window)
    names = pt.launches_by_program(trace.devices[0].modules, steps)
    assert names == {"jit__prefill_chunk_prog": 1, "jit__threefry_seed": 2,
                     "jit__decode_step_prog": 2}


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(wrapped)/shard_map/bf.forward_backward/jvp(Llama)/layer_1/"
     "attention/wq/dot_general:", "forward"),
    ("jit(wrapped)/shard_map/bf.forward_backward/transpose(jvp(Llama))/"
     "layer_0/mlp/dot_general:", "backward"),
    ("jit(wrapped)/shard_map/bf.optimizer/mul:", "optimizer"),
    ("jit(wrapped)/shard_map/bf.exchange/cond/branch_1_fun/ppermute:",
     "exchange"),
    # the bucketed engine applies a bucket's update inside the exchange:
    # the innermost scope names the work
    ("jit(wrapped)/shard_map/bf.exchange/cond/branch_0_fun/bf.optimizer/"
     "add:", "optimizer"),
    ("jit(wrapped)/jvp(Llama)/layer_1/attention/wq/dot_general:", None),
    ("jit(wrapped)/shard_map/bf.other/add:", None),
    ("", None), (None, None),
])
def test_the_innermost_scope_of_a_tf_op_names_the_part(tf_op, scope):
    assert pt.scope_of(tf_op) == scope


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(wrapped)/jvp(Llama)/layer_1/attention/wq/dot_general:",
     "forward"),
    ("jit(wrapped)/transpose(jvp(Llama))/layer_0/mlp/dot_general:",
     "backward"),
    ("jit(wrapped)/mul:", None), (None, None),
])
def test_a_stale_executable_is_split_by_jaxs_own_marks(tf_op, scope):
    """No ``bf.*`` scope in the whole executable (``bare``): ``jvp(``
    stands for ``bf.forward_backward``."""
    assert pt.scope_of(tf_op, bare=True) == scope


def test_scope_seconds_bill_each_operation_whole_and_skip_containers():
    ops = [("%fusion.1 = f32[] fusion()", 0, 10 * MS),
           ("%fusion.2 = f32[] fusion()", 10 * MS, 30 * MS),
           ("%conditional.1 = () conditional()", 30 * MS, 50 * MS),
           ("%collective-permute-start.1 = ()", 30 * MS, 31 * MS),
           ("%fusion.3 = f32[] fusion()", 31 * MS, 50 * MS),
           ("%copy.4 = f32[] copy()", 50 * MS, 52 * MS)]
    trace = tr.Trace([tr.DeviceTrace(0, ops, [])],
                     [("pb.trace_window", 0.0, 51 * MS)])
    tf_ops = {0: {
        ops[0][0]: "jit(f)/bf.forward_backward/jvp(M)/dot_general:",
        ops[1][0]: "jit(f)/bf.optimizer/add:",
        ops[3][0]: "jit(f)/bf.exchange/cond/branch_0_fun/ppermute:",
        ops[4][0]: "jit(f)/bf.exchange/cond/branch_0_fun/mul:"}}
    got = pt.scope_seconds(trace, tf_ops)
    assert got["forward"] == pytest.approx({"fusion.1": 0.010})
    assert got["optimizer"] == pytest.approx({"fusion.2": 0.020})
    assert got["exchange"] == pytest.approx(
        {"collective-permute-start.1": 0.001, "fusion.3": 0.019})
    assert got[None] == pytest.approx({"copy.4": 0.001})   # cut at 51
    busy, _ = tr.busy_and_window_s(trace)
    assert sum(sum(v.values()) for v in got.values()) == \
        pytest.approx(busy)


def test_scope_times_are_taken_over_whole_steps_only():
    """The window cuts the third step: two whole steps count, and only
    their operations are summed (a cut step would add its time to the
    sum and nothing to the count)."""
    step = [("jit_step", k * 10 * MS, (k + 1) * 10 * MS - 1)
            for k in range(3)]
    ops = [("%fusion.1 = f32[] fusion()", k * 10 * MS, k * 10 * MS + 4 * MS)
           for k in range(3)]
    tiny = [("jit_tiny", 2 * MS, 3 * MS)]
    trace = tr.Trace([tr.DeviceTrace(0, ops, step + tiny)],
                     [("pb.trace_window", 0.0, 25 * MS)])
    steps, stretch = pt.whole_steps(trace)
    assert steps == 2 and stretch == (0, 20 * MS - 1)
    tf_ops = {0: {ops[0][0]: "jit(f)/bf.forward_backward/jvp(M)/dot:"}}
    whole = pt.scope_seconds(trace, tf_ops, window=stretch)
    assert sum(whole["forward"].values()) / steps == pytest.approx(0.004)
    cut = pt.scope_seconds(trace, tf_ops)
    assert sum(cut["forward"].values()) / steps == pytest.approx(0.006)


def unpacked(tmp_path, name):
    path = tmp_path / name[:-3]
    with gzip.open(os.path.join(HERE, "data", name)) as src:
        path.write_bytes(src.read())
    return str(path)


def test_tf_ops_are_read_from_the_wire_format_of_a_chip_trace(tmp_path):
    """The train trace recorded by PR 23 predates the scopes: every
    operation has its ``tf_op``, none lies under a ``bf.*`` scope, and
    the scoped and unscoped time together are the device's busy time."""
    path = unpacked(tmp_path, "tiny_train_v5e.xplane.pb.gz")
    with open(path, "rb") as fh:
        tf_ops = pt.tf_ops_of(fh.read())
    assert list(tf_ops) == [0]
    by_short = {tr.short_name(k): v for k, v in tf_ops[0].items()}
    assert by_short["fusion.145"] == \
        "jit(wrapped)/jvp(Llama)/layer_1/attention/wq/dot_general:"
    assert sum("transpose(jvp(Llama))" in v for v in by_short.values()) > 50
    trace = tr.load(path)
    # what the compiler adds itself (the waits of asynchronous copies
    # above all) has no tf_op; the operations that have one hold most
    # of the device's time
    named = sum(e - s for name, s, e in trace.devices[0].ops
                if name in tf_ops[0])
    assert named > 0.75 * sum(e - s for _, s, e in trace.devices[0].ops)
    got = pt.scope_seconds(trace, tf_ops)
    assert list(got) == [None]
    busy, _ = tr.busy_and_window_s(trace)
    assert sum(got[None].values()) == pytest.approx(busy, rel=0.02)
    # to today's program, which writes scopes, this is what a stale
    # executable from the persistent cache looks like: forward and
    # backward are still told apart, and nothing is lost
    assert pt.stale(tf_ops)
    assert not pt.stale({0: {"%fusion.1": "jit(f)/bf.forward_backward/"
                                          "jvp(M)/dot_general:"}})
    bare = pt.scope_seconds(trace, tf_ops, bare=True)
    assert set(bare) == {"forward", "backward", None}
    assert sum(bare["backward"].values()) > sum(bare["forward"].values())
    assert sum(sum(v.values()) for v in bare.values()) == \
        pytest.approx(sum(got[None].values()))


def reader(tmp_path, name="engine_phase_ms.emit"):
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
    return module, root / "perfbench_out" / "trace" / "cell"


def test_a_reader_finds_the_runs_file_and_reads_nothing_off_the_chip(
        tmp_path, monkeypatch):
    """The tiny engine under the profiler, here on the CPU: the reader
    finds the newest xplane under its own root, its spans give every
    engine step its phases, and ``reduce`` returns nothing off the chip
    (and nothing where no file is)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu import models
    from bluefog_tpu.serving import Request, ServingEngine

    module, log = reader(tmp_path)
    assert pt.run_xplane(module.__file__) is None
    assert pt.for_run(module.__file__) is None
    assert not pt.on_chip()
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    empty = tr.Trace([], [("pb.trace_window", 0.0, 1.0)])
    assert module.reduce(empty, None, {}) is None

    cfg = models.LlamaConfig.tiny(dtype=jnp.float32)
    variables = models.Llama(cfg).init(jax.random.PRNGKey(1),
                                       jnp.zeros((2, 4), jnp.int32))
    eng = ServingEngine(variables, cfg, capacity=2, max_len=48,
                        prefill_chunk=4)
    rs = np.random.RandomState(3)
    eng.submit(Request(rs.randint(0, 256, (9,)).astype(np.int32), 3))
    eng.run()                                   # compiles
    for n in (6, 11):
        eng.submit(Request(rs.randint(0, 256, (n,)).astype(np.int32), 4))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    os.makedirs(log)
    jax.profiler.start_trace(str(log), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("pb.trace_window"):
            eng.run()
    finally:
        jax.profiler.stop_trace()
    run = pt.for_run(module.__file__)
    assert run is not None and run.path == tr.find_xplane(str(log))
    assert pt.for_run(module.__file__) is run       # read once
    trace = tr.load(run.path)                       # no device plane here
    steps = pt.engine_steps(run.spans, trace.window)
    assert len(steps) >= 6
    rows = pt.phase_table(steps)
    assert rows["admit"][0] == len(steps)
    assert rows["prefill"][0] == 2 + 3              # ceil(5/4) + ceil(10/4)
    assert rows["emit"][0] == rows["decode_inputs"][0] >= 4
    assert rows["self"][1] >= 0
    chunks = [args for name, _, _, args, _ in run.spans
              if name == "bf.engine.prefill_chunk"]
    assert sum(a["tokens"] for a in chunks) == 5 + 10
    assert module.reduce(trace, None, {}) == pytest.approx(rows["emit"][1])
    monkeypatch.undo()
    assert module.reduce(trace, None, {}) is None


def test_a_stale_executable_is_reported_loudly_and_still_read(
        tmp_path, monkeypatch, capsys):
    """The PR 23 train recording under today's program, through the
    reader: the scopes are missing though the program writes them, so
    the reader says so and splits forward from backward by JAX's own
    marks instead of returning nothing."""
    forward, log = reader(tmp_path, "train_scope_ms.forward")
    backward, _ = reader(tmp_path, "train_scope_ms.backward")
    path = log / "plugins" / "profile" / "run" / "t.xplane.pb"
    os.makedirs(path.parent)
    os.rename(unpacked(tmp_path, "tiny_train_v5e.xplane.pb.gz"), path)
    trace = tr.load(str(path))
    steps = len(tr.module_calls(trace))
    assert steps > 0
    assert forward.reduce(trace, None, {}) is None      # off the chip
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    fwd = forward.reduce(trace, None, {})
    bwd = backward.reduce(trace, None, {})
    out = capsys.readouterr().out
    assert out.count("STALE EXECUTABLE") == 1
    assert "(no scope)" in out
    busy, _ = tr.busy_and_window_s(trace)
    assert 0 < fwd < bwd and fwd + bwd < 1e3 * busy / steps


def test_counters_are_read_without_creating_them():
    from bluefog_tpu.observe import get_registry

    name = "bf_test_program_trace_total"
    assert pt.counter_value(name) is None
    assert pt.registry_metric(name) is None     # asking created nothing
    get_registry().counter(name, stage="a").inc(3)
    assert pt.counter_value(name) is None       # other labels
    assert pt.counter_value(name, stage="a") == 3.0


def test_the_set_up_readers_read_the_compile_counters(tmp_path,
                                                      monkeypatch):
    """``setup_*`` are ``bf_compile_seconds_total`` by stage as they
    stand at the end of the run; ``compile_cache_misses`` likewise."""
    import jax
    import jax.numpy as jnp

    readers = {stage: reader(tmp_path, name)[0] for stage, name in [
        ("trace", "setup_trace_s"), ("lower", "setup_lower_s"),
        ("backend", "setup_backend_compile_s")]}
    misses = reader(tmp_path, "compile_cache_misses")[0]
    assert all(r.reduce(None, None, {}) is None for r in readers.values())
    monkeypatch.setattr(pt, "on_chip", lambda: True)

    @jax.jit
    def a_function_only_this_test_compiles(x):
        return jnp.cos(x) - 2.0

    jax.block_until_ready(a_function_only_this_test_compiles(jnp.ones(3)))
    before = {s: r.reduce(None, None, {}) for s, r in readers.items()}
    for stage, seconds in before.items():
        assert seconds == pt.counter_value("bf_compile_seconds_total",
                                           stage=stage) > 0

    @jax.jit
    def another_function_only_this_test_compiles(x):
        return jnp.sin(x) + 2.0

    jax.block_until_ready(
        another_function_only_this_test_compiles(jnp.ones(3)))
    for stage, r in readers.items():
        assert r.reduce(None, None, {}) > before[stage]
    assert misses.reduce(None, None, {}) == (
        pt.counter_value("bf_compile_cache_misses_total") or 0.0)


def test_the_counters_come_through_the_command_with_the_gate_open(
        bench_copy, on_cpu, capsys, monkeypatch):
    """A traced run of the tiny serve cell through ``run.main``, the
    readers' look for the chip answered yes: the counters, which are
    exact on any device, are on the result line with what the
    registry holds (the batch of a decode step: with what it gained in
    the traced stretch)."""
    import json

    import jax

    from bluefog_tpu import config
    from perfbench import run as pbrun

    from conftest import TINY_DECODER, TINY_TRAFFIC, add_cell

    # the device's events lie a day after anything the CPU's profile holds
    ms, day = 1e6, 86400e9
    synthetic = tr.Trace(
        [tr.DeviceTrace(0, [("fusion.1", day + 1 * ms, day + 9 * ms)],
                        [("jit__decode_step_prog(5)", day + 1 * ms,
                          day + 9 * ms)])],
        [("pb.trace_window", day, day + 10 * ms)])
    monkeypatch.setattr(config, "configure_compilation_cache",
                        lambda: "/cache")
    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "load", lambda p: synthetic)
    monkeypatch.setattr(pt, "on_chip", lambda: True)
    traffic = TINY_TRAFFIC["tiny-serve"]
    add_cell(bench_copy, "cell", TINY_DECODER, "tiny-serve", traffic)
    windows = []

    class Kept(pt.CounterWindow):
        def __init__(self):
            super().__init__()
            windows.append(self)

    monkeypatch.setattr(pt, "CounterWindow", Kept)
    assert pbrun.main(["--workload", "cell", "--seed", str(2**31 + 7),
                       "--seconds", "1.0", "--trace", "1"],
                      root=bench_copy) == 0
    # the tiny schedule can end before the window does: the runner has
    # closed its profiler session all the same
    with pytest.raises(RuntimeError):
        jax.profiler.stop_trace()
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "metrics"]
    value = pt.counter_value
    # the batch of a decode step is counted in the traced stretch
    stretch, = windows
    assert got["decode_slots_per_step"]["value"] == pytest.approx(
        stretch.delta("bf_serving_decode_slots_total")
        / stretch.delta("bf_serving_decode_steps_total"))
    assert 0 < stretch.delta("bf_serving_decode_steps_total") \
        < value("bf_serving_decode_steps_total")
    assert 1.0 <= got["decode_slots_per_step"]["value"] <= \
        traffic["engine"]["capacity"]
    assert got["prefill_pad_pct"]["value"] == pytest.approx(100 * (
        1 - value("bf_serving_prefill_tokens_total")
        / (value("bf_serving_prefill_chunks_total")
           * traffic["engine"]["prefill_chunk"])))
    assert 0.0 <= got["prefill_pad_pct"]["value"] < 100.0
    assert got["queue_wait_prog_p95_ms"]["value"] >= 0.0
    assert got["setup_trace_s"]["value"] == value(
        "bf_compile_seconds_total", stage="trace")
    assert "compile_cache_misses" in got
    # the time-valued readers open the CPU's own profile (the runner
    # closed it), which has no engine step inside the synthetic window
    assert not any(k.startswith(("engine_phase_ms", "engine_idle_ms"))
                   for k in got)


def test_the_serve_recording_from_the_chip_reduces_to_known_figures(
        tmp_path):
    """Six engine steps of ``mistral7b-serve-steady`` at low load, cut
    from one second traced on a TPU v5e (my chip run, PR 24;
    ``data/record_serve_phases.py`` says how): five slots decoding, and
    in the third step one prefill chunk for request 6.  The figures
    were read off the file with this module's own functions and are
    pinned as the atc fixture's are."""
    from jax.profiler import ProfileData

    path = unpacked(tmp_path, "serve_phases_v5e.xplane.pb.gz")
    trace = tr.load(path)
    assert [d.index for d in trace.devices] == [0]
    assert len(trace.devices[0].ops) == 52057
    assert "pb.trace_window" not in {name for name, _, _ in trace.spans}
    spans = pt.program_spans_of(ProfileData.from_file(path))
    assert len(spans) == 37
    assert {th for _, _, _, _, th in spans} == {spans[0][4]}  # one thread
    steps = pt.engine_steps(spans, (0.0, float("inf")))
    assert len(steps) == 6
    assert ["prefill" in held for _, _, held in steps] == [
        False, False, True, False, False, False]
    chunk, = [args for name, _, _, args, _ in spans
              if name == "bf.engine.prefill_chunk"]
    assert chunk == {"rid": 6, "slot": 5, "tokens": 249}
    assert {args["slots"] for name, _, _, args, _ in spans
            if name == "bf.engine.decode_inputs"} == {5, 6}
    rows = pt.phase_table(steps)
    assert rows["step"][1:] == (pytest.approx(31.957, abs=1e-3),
                                pytest.approx(34.771, abs=1e-3))
    assert rows["prefill"] == (1, pytest.approx(2.262, abs=1e-3),
                               pytest.approx(0.377, abs=1e-3))
    assert rows["decode_inputs"][1] == pytest.approx(8.960, abs=1e-3)
    assert rows["token_fetch"][1] == pytest.approx(21.962, abs=1e-3)
    assert rows["decode_dispatch"][1] == pytest.approx(0.850, abs=1e-3)
    assert rows["emit"][1] == pytest.approx(0.0687, abs=1e-4)
    assert rows["admit"][1] == pytest.approx(0.0156, abs=1e-4)
    assert rows["self"][1] == pytest.approx(0.161, abs=1e-3)
    # six phases and the self time are the whole step
    assert sum(rows[p][2] for p in list(pt.ENGINE_PHASES) + ["self"]) == \
        pytest.approx(rows["step"][2])
    # the device: the window is the extent of the cut's operations,
    # which holds four of the steps whole
    inside = pt.engine_steps(spans, trace.window)
    assert len(inside) == 4
    idle = {k: v / MS for k, v in pt.idle_by_phase(trace, inside).items()}
    busy, window = tr.busy_and_window_s(trace)
    assert idle["all"] == pytest.approx(1e3 * (window - busy), rel=1e-9)
    assert idle["all"] == pytest.approx(64.792, abs=1e-3)
    assert idle["decode_inputs"] == pytest.approx(35.243, abs=1e-3)
    assert idle["token_fetch"] == pytest.approx(5.840, abs=1e-3)
    assert idle["outside"] == pytest.approx(19.516, abs=1e-3)
    assert sum(idle[k] for k in list(pt.ENGINE_PHASES)
               + ["self", "outside"]) == pytest.approx(idle["all"])
    # two tiny programs for every active slot, and the decode program
    assert pt.launches_by_program(trace.devices[0].modules, steps) == {
        "jit_convert_element_type": 36, "jit__threefry_seed": 34,
        "jit__decode_step_prog": 6, "jit__prefill_chunk_prog": 1}
    with open(path, "rb") as fh:
        tf_ops = pt.tf_ops_of(fh.read())[0]
    assert any(v.startswith("jit(_decode_step_prog)/")
               for v in tf_ops.values())
