"""Observability subsystem (bluefog_tpu/observe/).

Contracts under test:

* registry semantics — counter/gauge/histogram behavior, labeled
  families, one-kind-per-name, snapshot/reset;
* tracer — span nesting per track, instants, the sink protocol, the
  Chrome-trace round trip through the timeline file writer;
* step profiler — ``profile_step`` agrees with the ``benchutil``
  primitives it promotes (FLOPs = ``compiled_step_flops``, bytes =
  ``hlo_collective_bytes``) and, on the bucketed overlap step, its
  per-collective windows reproduce ``overlap_accounting``'s numbers
  exactly (the acceptance self-consistency bar);
* the zero-cost guarantee — enabling observability leaves compiled
  programs untouched: identical jit cache sizes and bit-identical
  train-step outputs with ``BLUEFOG_OBSERVE`` on vs off;
* ``BLUEFOG_OBSERVE=0`` stops every built-in publisher;
* the timeline drop contract — a saturated Python writer queue reports
  a nonzero drop count (and ``close()`` flushes it to the registry)
  instead of losing events silently;
* ``BLUEFOG_LOG_FORMAT=json`` emits parseable one-object-per-line logs.
"""

import json
import logging
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import benchutil as BU
from bluefog_tpu import observe
from bluefog_tpu.observe import (MetricsRegistry, Tracer, percentile,
                                 profile_step)

pytestmark = pytest.mark.observe

N = 8


@pytest.fixture
def registry():
    """A fresh, isolated registry (the global one keeps accumulating
    across the suite — tests that read the global assert deltas)."""
    return MetricsRegistry()


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #
def test_counter_gauge_histogram_semantics(registry):
    c = registry.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = registry.gauge("depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5.0

    h = registry.histogram("lat", window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        h.observe(v)
    # lifetime totals see everything; percentiles only the window
    assert h.count == 5 and h.sum == 110.0
    assert h.window_values == [2.0, 3.0, 4.0, 100.0]
    assert h.percentile(50) == percentile([2.0, 3.0, 4.0, 100.0], 50)


def test_labeled_families_and_kind_conflict(registry):
    a = registry.counter("ops", op="allreduce")
    b = registry.counter("ops", op="broadcast")
    assert a is not b
    assert registry.counter("ops", op="allreduce") is a  # same child
    with pytest.raises(ValueError):
        registry.gauge("ops")  # a name is bound to one kind
    a.inc(3)
    snap = registry.snapshot()
    assert {tuple(r["labels"].items()): r["value"]
            for r in snap["ops"]} == {(("op", "allreduce"),): 3.0,
                                      (("op", "broadcast"),): 0.0}
    registry.reset()
    assert registry.snapshot() == {}


def test_percentile_moved_and_reexported():
    """The promoted helper IS the serving module's percentile (backward
    compat for serving/metrics.py importers)."""
    from bluefog_tpu.serving.metrics import percentile as serving_pct

    assert serving_pct is percentile
    assert percentile([], 99) == 0.0
    assert percentile([1.0, None, 3.0], 50) == 2.0


# --------------------------------------------------------------------- #
# tracer
# --------------------------------------------------------------------- #
def test_tracer_span_nesting_and_instants():
    clock = iter(float(i) for i in range(100))
    tr = Tracer(clock=lambda: next(clock))
    tr.begin("t0", "outer")
    assert tr.open_depth("t0") == 1
    with tr.span("t0", "inner"):
        assert tr.open_depth("t0") == 2
        tr.instant("mark", track="t0")
    tr.end("t0")
    assert tr.open_depth("t0") == 0
    phases = [e[0] for e in tr.events()]
    assert phases == ["B", "B", "i", "E", "E"]
    ts = [e[3] for e in tr.events()]
    # microseconds since construction (t0 ate the clock's first tick),
    # strictly increasing under the injected clock
    assert ts == [1e6, 2e6, 3e6, 4e6, 5e6]


def test_tracer_per_thread_tracks():
    tr = Tracer()
    done = threading.Event()

    def worker():
        with tr.span(None, "work"):  # track = thread name
            done.set()

    t = threading.Thread(target=worker, name="worker-7")
    t.start()
    t.join()
    assert done.is_set()
    tracks = {e[2] for e in tr.events()}
    assert "worker-7" in tracks


def test_tracer_active_span_interleaved_tracks():
    """active_span survives non-LIFO begin/end interleavings (the
    eager op API ends concurrent in-flight handle spans out of order):
    no entry may leak in the thread-local stack."""
    tr = Tracer()
    tr.begin("op.1", "ENQUEUE")
    tr.begin("op.2", "ENQUEUE")
    assert tr.active_span() == ("op.2", "ENQUEUE")
    tr.end("op.1")  # out of order
    assert tr.active_span() == ("op.2", "ENQUEUE")
    tr.end("op.2")
    assert tr.active_span() is None  # nothing leaked
    tr.end("op.never-began")  # foreign end: no crash, no underflow
    assert tr.active_span() is None


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(max_events=8)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 8
    assert tr.dropped_events == 12
    assert tr.events()[0][1] == "e12"  # oldest fell off first


def test_span_arguments_ride_the_ring_and_the_chrome_trace():
    """``span(**args)`` / ``Span.set`` / ``instant(**args)`` keep their
    arguments as the event's fifth field and ``chrome_events`` writes
    them as ``args``; a span's two stamps give its duration; the sink
    protocol is unchanged (name, tid, phase)."""
    t = [0.0]
    tr = Tracer(clock=lambda: t[0])
    seen = []

    class Sink:
        def record(self, name, tid, phase):
            seen.append((name, tid, phase))

    tr.add_sink(Sink())
    with tr.span("engine", "step", rid=7) as sp:
        t[0] = 0.25
        sp.set(tokens=3)
    tr.instant("compile.f", "compile", cause="test")
    tr.begin("request.1", "admission")
    tr.end("request.1")
    assert sp.seconds == pytest.approx(0.25)
    ev = tr.events()
    assert [e[0] for e in ev] == ["B", "E", "i", "B", "E"]
    assert ev[0][4] == {"rid": 7, "tokens": 3} and not ev[1][4]
    assert ev[2][4] == {"cause": "test"} and not ev[3][4]
    chrome = tr.to_chrome_trace()
    assert chrome[0]["args"] == {"rid": 7, "tokens": 3}
    assert chrome[2]["args"] == {"cause": "test"}
    assert "args" not in chrome[1] and "args" not in chrome[3]
    assert seen[0] == ("step", "engine", "B") and len(seen) == 5
    assert '"args": {"rid": 7, "tokens": 3}' in \
        observe.jsonl_events(tr).splitlines()[0]


def test_spans_lie_in_the_profilers_trace_and_begin_end_do_not(tmp_path):
    """``Tracer.span`` run under ``jax.profiler.start_trace`` leaves
    ``bf.<track>.<name>`` events on the host plane of the xplane,
    nested as entered and with their arguments; ``begin``/``end`` pairs
    (the per-request tracks, the eager ops' handles) leave none."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tr.span("engine", "step"):
            with tr.span("engine", "admit") as sp:
                sp.set(admitted=2)
            with tr.span("engine", "prefill_chunk", rid=5, slot=1):
                pass
        tr.begin("request.5", "admission")
        tr.end("request.5")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if "admission" in e.name:
                    found[e.name] = None
                if e.name.startswith("bf."):
                    assert plane.name.startswith("/host:")
                    found[e.name] = (e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats))
    assert sorted(found) == ["bf.engine.admit", "bf.engine.prefill_chunk",
                             "bf.engine.step"]
    s0, s1, _ = found["bf.engine.step"]
    a0, a1, a_args = found["bf.engine.admit"]
    c0, c1, c_args = found["bf.engine.prefill_chunk"]
    assert s0 <= a0 <= a1 <= c0 <= c1 <= s1
    assert a_args == {"admitted": 2} and c_args == {"rid": 5, "slot": 1}


def test_chrome_trace_round_trip(tmp_path):
    """Spans published through a tracer stream to the timeline file
    sink AND serialize identically from the in-memory buffer — the
    thin-exporter contract timeline.py now has."""
    from bluefog_tpu.timeline import Timeline

    tl = Timeline(str(tmp_path / "tl"), rank=2, use_native=False)
    tl.tracer.begin("tensor_a", "ENQUEUE")
    tl.tracer.end("tensor_a")
    tl.tracer.instant("neighbor_allreduce")
    tl.close()
    file_events = json.loads((tmp_path / "tl2.json").read_text())
    mem_events = tl.tracer.to_chrome_trace()
    assert [e["ph"] for e in file_events] == [e["ph"] for e in mem_events]
    assert [e.get("name") for e in file_events] == \
        [e.get("name") for e in mem_events]
    # round trip: serialize the in-memory view, parse it back
    parsed = json.loads(json.dumps(mem_events))
    assert parsed[0] == {"name": "ENQUEUE", "cat": "tensor_a", "ph": "B",
                         "ts": parsed[0]["ts"], "pid": 2,
                         "tid": "tensor_a"}


def test_timeline_reports_saturated_queue_drops(tmp_path, monkeypatch):
    """A wedged/slow writer must surface as a DROP COUNT, not silent
    loss: block the file behind an event, saturate the bounded queue,
    and check dropped_events() plus the registry gauge close() flushes."""
    monkeypatch.setenv("BLUEFOG_TIMELINE_QUEUE_CAPACITY", "8")
    from bluefog_tpu.timeline import Timeline

    tl = Timeline(str(tmp_path / "sat"), rank=0, use_native=False)
    release = threading.Event()
    real_file = tl._writer._file

    class _BlockingFile:
        def write(self, s):
            release.wait(timeout=10.0)
            return real_file.write(s)

        def flush(self):
            real_file.flush()

        def close(self):
            real_file.close()

    tl._writer._file = _BlockingFile()
    for i in range(64):  # writer blocked -> queue (cap 8) must overflow
        tl.instant(f"burst{i}")
    assert tl.dropped_events() > 0
    release.set()
    observe.get_registry().reset()
    tl.close()
    gauge = observe.get_registry().gauge("bf_timeline_dropped_events",
                                         rank=0)
    assert gauge.value == tl.dropped_events() > 0


def test_timeline_flushes_drop_gauge_mid_run(tmp_path, monkeypatch):
    """ISSUE 5 satellite: the drop count must reach the registry gauge
    PERIODICALLY (every BLUEFOG_TIMELINE_FLUSH_EVERY drains / on drain
    to empty), not only at close() — a long-running saturated run is
    visible before shutdown.  Saturate the bounded queue behind a
    blocked file, release, and poll the gauge BEFORE closing."""
    import time as _time

    monkeypatch.setenv("BLUEFOG_TIMELINE_QUEUE_CAPACITY", "8")
    monkeypatch.setenv("BLUEFOG_TIMELINE_FLUSH_EVERY", "4")
    from bluefog_tpu.timeline import Timeline

    observe.get_registry().reset()
    tl = Timeline(str(tmp_path / "midrun"), rank=1, use_native=False)
    try:
        release = threading.Event()
        real_file = tl._writer._file

        class _BlockingFile:
            def write(self, s):
                release.wait(timeout=10.0)
                return real_file.write(s)

            def flush(self):
                real_file.flush()

            def close(self):
                real_file.close()

        tl._writer._file = _BlockingFile()
        for i in range(64):  # queue cap 8 -> must overflow
            tl.instant(f"burst{i}")
        assert tl.dropped_events() > 0
        release.set()
        gauge = observe.get_registry().gauge("bf_timeline_dropped_events",
                                             rank=1)
        deadline = _time.monotonic() + 10.0
        while gauge.value == 0.0 and _time.monotonic() < deadline:
            _time.sleep(0.01)
        # the run is still OPEN — the writer thread disclosed the drops
        assert gauge.value > 0
        assert gauge.value <= tl.dropped_events()
    finally:
        tl.close()
    assert gauge.value == tl.dropped_events()


def test_timeline_under_opt_out_stays_private(tmp_path, monkeypatch):
    """BLUEFOG_OBSERVE=0 + BLUEFOG_TIMELINE: the file still records
    (producers fall back to the timeline's PRIVATE tracer via
    effective_tracer) but the observe layer's global tracer buffers
    stay empty — the opt-out is honored."""
    from bluefog_tpu import timeline as timeline_mod
    from bluefog_tpu.observe.tracer import effective_tracer

    monkeypatch.setenv("BLUEFOG_OBSERVE", "0")
    monkeypatch.setenv("BLUEFOG_TIMELINE_NATIVE", "0")
    global_before = len(observe.get_tracer().events())
    tl = timeline_mod.start_timeline(str(tmp_path / "priv"))
    try:
        assert tl.tracer is not observe.get_tracer()
        tr = effective_tracer(timeline_mod.get_timeline())
        assert tr is tl.tracer  # the documented fallback
        with tr.span("track", "SPAN_UNDER_OPTOUT"):
            pass
    finally:
        timeline_mod.stop_timeline()
    assert "SPAN_UNDER_OPTOUT" in (tmp_path / "priv0.json").read_text()
    assert len(observe.get_tracer().events()) == global_before


# --------------------------------------------------------------------- #
# step profiler
# --------------------------------------------------------------------- #
def test_profile_step_matches_benchutil_primitives():
    """profile_step IS the promoted benchutil machinery: FLOPs equal
    compiled_step_flops, collective bytes equal hlo_collective_bytes of
    the same compiled module."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))

    def f(x):
        return jax.lax.psum(x @ x, "bf")

    sm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("bf"),
                               out_specs=P(), check_vma=False))
    x = jnp.ones((N, 16, 16), jnp.float32)
    prof = profile_step(sm, x, name="toy", publish=False)
    assert prof.flops == BU.compiled_step_flops(sm, x) > 0
    hlo = sm.lower(x).compile().as_text()
    assert prof.collective_bytes == BU.hlo_collective_bytes(hlo)
    assert "all-reduce" in prof.collective_bytes
    d = prof.to_dict()
    json.dumps(d)  # JSON-ready
    assert d["flops"] == prof.flops and "mfu" in d


def test_profile_step_caches_hlo_analysis_per_executable():
    """ISSUE 6 satellite: repeat profile_step calls on the SAME
    compiled step hit the per-module analysis cache (XLA cost analysis
    + per-op parse run once); a different program misses.  The cached
    artifacts are identical objects across calls."""
    from bluefog_tpu.observe import stepprof

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))

    def f(x):
        return jax.lax.psum(x @ x, "bf")

    def g(x):
        return jax.lax.psum(x + x, "bf")

    sm_f = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("bf"),
                                 out_specs=P(), check_vma=False))
    sm_g = jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P("bf"),
                                 out_specs=P(), check_vma=False))
    x = jnp.ones((N, 16, 16), jnp.float32)
    stepprof.profile_cache_clear()
    p1 = profile_step(sm_f, x, name="a", publish=False)
    info = stepprof.profile_cache_info()
    assert info["misses"] == 1 and info["hits"] == 0
    p2 = profile_step(sm_f, x, name="b", step_seconds=0.5,
                      publish=False)
    info = stepprof.profile_cache_info()
    assert info["hits"] == 1 and info["misses"] == 1
    # cached parse is shared, not re-derived
    assert p2.op_breakdown is p1.op_breakdown
    assert p2.collective_bytes is p1.collective_bytes
    assert p2.flops == p1.flops
    # a different executable is a miss
    profile_step(sm_g, x, name="c", publish=False)
    info = stepprof.profile_cache_info()
    assert info["misses"] == 2 and info["entries"] == 2
    stepprof.profile_cache_clear()
    assert stepprof.profile_cache_info() == {
        "hits": 0, "misses": 0, "entries": 0}


def _bucketed_step(mesh, K=4):
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    base = {f"w{i}": jnp.eye(16) * 0.5 for i in range(4)}
    base.update({f"b{i}": jnp.zeros((16,)) for i in range(4)})

    def loss_fn(params, batch):
        h = batch
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
        return jnp.mean((h - 1.0) ** 2)

    opt = optax.sgd(0.05)
    step = F.build_train_step(
        loss_fn, opt, mesh, comm_mode="atc",
        topology=one_peer_dynamic_schedule(N)[0], overlap="bucketed",
        overlap_buckets=K, donate=False)
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)
    batch = jax.device_put(
        np.zeros((N, 8, 16)), NamedSharding(mesh, P("bf")))
    return step, params, ostate, batch


def test_profile_step_reproduces_overlap_accounting():
    """Acceptance: on the bucketed overlap step, the profiler's
    per-collective transfer windows reproduce overlap_accounting's
    numbers — same windows, same per-kind byte totals, same
    byte-weighted fraction."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    step, params, ostate, batch = _bucketed_step(mesh, K=4)
    peak, link = 1e6, 1e12
    prof = profile_step(step, params, ostate, batch, jnp.int32(0),
                        name="bucketed", publish=False,
                        peak_flops=peak, link_bytes_per_s=link,
                        hbm_bytes_per_s=0.0)
    hlo = step.lower(params, ostate, batch, jnp.int32(0)) \
        .compile().as_text()
    acc = BU.overlap_accounting(hlo, peak_flops_per_s=peak,
                                link_bytes_per_s=link)
    assert prof.overlap["windows"] == acc["windows"]
    assert prof.overlap["per_kind"] == acc["per_kind"]
    assert prof.overlap["fraction"] == acc["fraction"] == 1.0
    # the profile's window list is the full module view the accounting
    # filtered from
    permutes = [w for w in prof.windows
                if w["kind"] == "collective-permute"]
    assert len(permutes) >= 4
    assert sum(w["bytes"] for w in permutes) == \
        prof.collective_bytes["collective-permute"]["bytes"] == \
        acc["bytes_total"]


def _scoped_step(mesh, kind):
    """One step of each builder ``build_train_step`` can return, all of
    which carry the ``bf.*`` named scopes: ``(step, args)``."""
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    if kind == "bucketed":
        step, params, ostate, batch = _bucketed_step(mesh)
        return step, (params, ostate, batch)
    base = {"w": jnp.eye(16) * 0.5, "v": jnp.ones((16, 4)) * 0.1}

    def loss_fn(params, batch):
        return jnp.mean((jnp.tanh(batch @ params["w"]) @ params["v"]) ** 2)

    opt = optax.adamw(1e-2)
    kw = {"none": dict(comm_mode="none"),
          "atc": dict(comm_mode="atc",
                      schedule=one_peer_dynamic_schedule(N)),
          "guarded": dict(comm_mode="atc",
                          schedule=one_peer_dynamic_schedule(N),
                          guard=F.GuardConfig())}[kind]
    step = F.build_train_step(loss_fn, opt, mesh, donate=False, **kw)
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)
    batch = jax.device_put(np.ones((N, 8, 16), np.float32) * 0.3,
                           NamedSharding(mesh, P("bf")))
    return step, (params, ostate, batch)


def _step_args(step, args, i):
    out = args + (jnp.int32(i),)
    if hasattr(step, "guard_config"):   # a guarded step takes weights
        out = out + (step.default_comm_weights,)
    return out


def _stripped_hlo(step, args, i=0):
    """The optimized HLO of the step's program for step ``i`` with what
    a named scope may change taken out: every ``metadata={...}`` and
    the tables of file and function names that the stack frames refer
    to."""
    import re

    text = step.lower(*_step_args(step, args, i)).compile().as_text()
    out, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip:
            skip = line != ""
        else:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return text, "\n".join(out)


@pytest.mark.parametrize("kind", ["bucketed", "none", "atc", "guarded"])
def test_observe_toggle_leaves_compiled_programs_untouched(monkeypatch,
                                                           kind):
    """Acceptance: identical jit cache sizes and bit-identical
    train-step outputs with BLUEFOG_OBSERVE on vs off, for every
    builder that carries the named scopes; and two builds of one step
    give the same optimized HLO once metadata is stripped (a scope is
    metadata and nothing else)."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    step, args = _scoped_step(mesh, kind)

    def run3():
        p, o = args[0], args[1]
        for i in range(3):
            out = step(*_step_args(step, (p, o) + args[2:], i))
            p, o, loss = out[0], out[1], out[2]
        return p, loss

    monkeypatch.setenv("BLUEFOG_OBSERVE", "1")
    p_on, loss_on = run3()
    size_on = step.jitted._cache_size()
    monkeypatch.setenv("BLUEFOG_OBSERVE", "0")
    p_off, loss_off = run3()
    assert step.jitted._cache_size() == size_on  # no recompiles either way
    np.testing.assert_array_equal(np.asarray(loss_on),
                                  np.asarray(loss_off))
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    again, args2 = _scoped_step(mesh, kind)
    assert _stripped_hlo(step, args)[1] == _stripped_hlo(again, args2)[1]


@pytest.mark.parametrize("kind,scopes", [
    ("none", ("bf.forward_backward", "bf.optimizer")),
    ("atc", ("bf.forward_backward", "bf.optimizer", "bf.exchange")),
    ("bucketed", ("bf.forward_backward", "bf.optimizer", "bf.exchange")),
    ("guarded", ("bf.forward_backward", "bf.optimizer", "bf.exchange")),
])
def test_named_scopes_show_in_compiled_op_names(kind, scopes):
    """The parts of the train step are named in the compiled program's
    ``op_name`` metadata — in every round's program of a scheduled
    step, the exchange's permutes under ``bf.exchange`` at the
    program's top level — and JAX's own ``transpose(jvp(...))`` below
    ``bf.forward_backward`` splits forward from backward."""
    import re

    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    step, args = _scoped_step(mesh, kind)
    rounds = 1 if kind in ("none", "bucketed") else \
        len(one_peer_dynamic_schedule(N))
    for r in range(rounds):
        text = _stripped_hlo(step, args, r)[0]
        names = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in scopes:
            assert any(f"/{scope}/" in n for n in names), (scope, kind, r)
        if "bf.exchange" not in scopes:
            assert not any("bf.exchange" in n for n in names)
        else:
            assert any(re.search(r"bf\.exchange/.*ppermute", n)
                       for n in names), (kind, r)
            assert "conditional" not in text and \
                not any("branch_" in n for n in names), (kind, r)
        assert any("bf.forward_backward/transpose(jvp(" in n
                   for n in names)
        assert any("bf.forward_backward/jvp(" in n for n in names)


def test_train_step_publishes_and_opt_out(monkeypatch):
    """The built step reports dispatches (counter + span) by default;
    BLUEFOG_OBSERVE=0 silences it."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    step, params, ostate, batch = _bucketed_step(mesh)
    ctr = observe.get_registry().counter(
        "bf_train_steps_total", comm_mode="atc", overlap="bucketed",
        guarded="false")
    before = ctr.value
    monkeypatch.setenv("BLUEFOG_OBSERVE", "1")
    step(params, ostate, batch, jnp.int32(0))
    assert ctr.value == before + 1
    monkeypatch.setenv("BLUEFOG_OBSERVE", "0")
    step(params, ostate, batch, jnp.int32(1))
    assert ctr.value == before + 1  # publication stopped


def test_serving_metrics_publish_and_opt_out(monkeypatch):
    """ServingMetrics rides the registry (isolated here via registry=)
    and the summary dict keeps a pinned key set (the original shape plus
    the fleet-serving prefix/speculative counters and the failover
    counter); with
    BLUEFOG_OBSERVE=0 and no explicit registry nothing is published."""
    from bluefog_tpu.serving.metrics import ServingMetrics

    reg = MetricsRegistry()
    m = ServingMetrics(registry=reg)
    m.on_submit(1, 0.0)
    m.on_admit(1, 0.5)
    m.on_first_token(1, 1.0)
    m.on_token(1, 1.25)
    m.on_tokens(2)          # the counter moves once a step (PR 34)
    m.on_retire(1, 1.5, "completed")
    m.on_step(0.5, 3)
    snap = reg.snapshot()
    assert snap["bf_serving_requests_total"][0]["value"] == 1.0
    assert snap["bf_serving_tokens_total"][0]["value"] == 2.0
    assert snap["bf_serving_ttft_seconds"][0]["count"] == 1
    assert snap["bf_serving_ttft_seconds"][0]["p50"] == 1.0
    assert snap["bf_serving_retired_total"][0]["labels"] == \
        {"outcome": "completed"}
    assert snap["bf_serving_queue_depth"][0]["value"] == 3.0
    s = m.summary()
    assert s["n_finished"] == 1 and s["tokens_generated"] == 2
    assert set(s) == {
        "n_requests", "n_finished", "n_rejected", "outcomes",
        "tokens_generated", "tokens_per_sec", "ttft_p50", "ttft_p99",
        "latency_p50", "latency_p99", "mean_slot_occupancy",
        "mean_queue_depth", "max_queue_depth", "prefill_chunks",
        "prefix_chunks_restored", "prefix_tokens_restored",
        "prefix_hit_rate", "spec_steps", "accepted_per_step",
        "n_failovers", "longest_step"}
    assert s["longest_step"] is None        # no step was timed

    monkeypatch.setenv("BLUEFOG_OBSERVE", "0")
    global_before = observe.get_registry().snapshot()
    m2 = ServingMetrics()
    m2.on_submit(2, 0.0)
    m2.on_reject(3, 0.0)
    assert observe.get_registry().snapshot() == global_before
    assert m2.summary()["n_rejected"] == 1  # the summary still works


def test_run_resilient_publishes_events(tmp_path):
    """The resilience runner's event stream lands in the registry as
    bf_resilience_events_total{kind=} and per-rank skip counters."""
    import bluefog_tpu.resilience as R
    from bluefog_tpu.checkpoint import Checkpointer
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    base = {"w": jnp.eye(4)}

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    opt = optax.sgd(0.05)
    step = F.build_train_step(loss_fn, opt, mesh, comm_mode="cta",
                              schedule=sched, donate=False,
                              guard=F.GuardConfig(max_consecutive_bad=3))
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)

    def batch_fn(step_i):
        return jax.device_put(np.ones((N, 2, 4), np.float32),
                              NamedSharding(mesh, P("bf")))

    plan = R.FaultPlan.nan_burst(N, rank=1, step=2, duration=2)
    reg = observe.get_registry()
    ck_before = reg.counter("bf_resilience_events_total",
                            kind="checkpoint").value
    sk_before = reg.counter("bf_resilience_skips_total", rank=1).value
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(step, params, ostate, batch_fn, steps=6,
                          checkpointer=ck, mesh=mesh, schedule=sched,
                          fault_plan=plan, checkpoint_every=5,
                          sleep=lambda s: None)
    ck.close()
    assert res.total_skips[1] == 2
    assert reg.counter("bf_resilience_events_total",
                       kind="checkpoint").value > ck_before
    assert reg.counter("bf_resilience_skips_total",
                       rank=1).value == sk_before + 2


# --------------------------------------------------------------------- #
# exporters
# --------------------------------------------------------------------- #
def test_prometheus_text_format(registry):
    registry.counter("bf_reqs_total", "requests", op="a").inc(2)
    registry.gauge("bf_depth", "queue depth").set(4)
    h = registry.histogram("bf_lat", "latency")
    h.observe(1.0)
    h.observe(3.0)
    text = observe.prometheus_text(registry)
    lines = text.strip().splitlines()
    assert "# TYPE bf_reqs_total counter" in lines
    assert 'bf_reqs_total{op="a"} 2.0' in lines
    assert "bf_depth 4.0" in lines
    assert "# TYPE bf_lat summary" in lines
    assert "bf_lat_count 2" in lines
    assert "bf_lat_sum 4.0" in lines
    assert 'bf_lat{quantile="0.5"} 2.0' in lines


_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_LABEL = r"[a-zA-Z_][a-zA-Z0-9_]*"


def _strict_parse_prometheus(text):
    """A STRICT exposition-format parser (the test's own, so the
    exporter can't grade its own homework): validates HELP/TYPE
    grammar, HELP-before-samples ordering, one TYPE per family, label
    escaping, and sample-line shape.  Returns {family: {"type", "help",
    "samples": [(name, labels, value)]}}."""
    import re

    families = {}
    current = None
    for ln in text.splitlines():
        assert ln == ln.rstrip(), f"trailing whitespace: {ln!r}"
        if ln.startswith("# HELP "):
            m = re.fullmatch(rf"# HELP ({_PROM_NAME}) (.*)", ln)
            assert m, f"bad HELP line: {ln!r}"
            name, help_text = m.group(1), m.group(2)
            # escaped help: no raw newline possible (we're line-split),
            # and any backslash must start \\ or \n
            assert re.fullmatch(r"([^\\]|\\\\|\\n)*", help_text), \
                f"unescaped backslash in HELP: {help_text!r}"
            assert name not in families, f"duplicate HELP for {name}"
            families[name] = {"type": None, "help": help_text,
                              "samples": []}
            current = name
        elif ln.startswith("# TYPE "):
            m = re.fullmatch(
                rf"# TYPE ({_PROM_NAME}) "
                r"(counter|gauge|summary|histogram|untyped)", ln)
            assert m, f"bad TYPE line: {ln!r}"
            name = m.group(1)
            fam = families.setdefault(
                name, {"type": None, "help": "", "samples": []})
            assert fam["type"] is None, f"duplicate TYPE for {name}"
            assert not fam["samples"], f"TYPE after samples for {name}"
            fam["type"] = m.group(2)
            current = name
        else:
            m = re.fullmatch(
                rf"({_PROM_NAME})(?:\{{(.*)\}})? "
                r"([0-9eE.+-]+|NaN|[+-]Inf)", ln)
            assert m, f"bad sample line: {ln!r}"
            name, labels_body, value = m.groups()
            labels = {}
            if labels_body:
                # tokenize k="v" pairs honoring \\ \" \n escapes
                pair = re.compile(
                    rf'({_PROM_LABEL})="((?:[^"\\]|\\.)*)"(,|$)')
                pos = 0
                while pos < len(labels_body):
                    pm = pair.match(labels_body, pos)
                    assert pm, f"bad labels at {labels_body[pos:]!r}"
                    for esc in re.finditer(r"\\(.)", pm.group(2)):
                        assert esc.group(1) in ('\\', '"', 'n'), \
                            f"bad escape \\{esc.group(1)}"
                    labels[pm.group(1)] = pm.group(2)
                    pos = pm.end()
            base = name
            for suffix in ("_count", "_sum", "_bucket"):
                if name.endswith(suffix) and name[:-len(suffix)] in families:
                    base = name[:-len(suffix)]
            assert base in families, f"sample {name} before its TYPE"
            float(value)
            families[base]["samples"].append((name, labels, value))
    for name, fam in families.items():
        assert fam["type"] is not None, f"{name} has HELP but no TYPE"
        assert fam["samples"], f"family {name} emitted no samples"
    return families


def test_prometheus_exposition_strict(registry):
    """ISSUE 5 satellite: strict-parser test over prometheus_text() —
    HELP/TYPE lines, label + HELP escaping, summary family naming —
    with fleet metrics included."""
    import numpy as np
    from bluefog_tpu.observe import fleet as FL
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    registry.counter("bf_ops_total", "eager op dispatches",
                     op="allreduce").inc(2)
    registry.counter("bf_ops_total", "eager op dispatches",
                     op="broadcast").inc()
    # hostile label value and HELP text: escaping must round-trip
    registry.gauge("bf_hostile", 'a "quoted"\nback\\slash help',
                   path='we"ird\nva\\lue').set(1)
    h = registry.histogram("bf_lat_seconds", "latency")
    h.observe(0.5)
    # fleet metrics land through the same registry
    agg = FL.FleetAggregator(one_peer_dynamic_schedule(8),
                             registry=registry)
    agg.publish(("step_time_p50",), np.arange(8, dtype=float))

    text = observe.prometheus_text(registry)
    fams = _strict_parse_prometheus(text)
    assert fams["bf_ops_total"]["type"] == "counter"
    assert len(fams["bf_ops_total"]["samples"]) == 2
    assert fams["bf_lat_seconds"]["type"] == "summary"
    names = [s[0] for s in fams["bf_lat_seconds"]["samples"]]
    assert names == ["bf_lat_seconds_count", "bf_lat_seconds_sum",
                     "bf_lat_seconds", "bf_lat_seconds"]
    quantiles = [s[1]["quantile"] for s in
                 fams["bf_lat_seconds"]["samples"][2:]]
    assert quantiles == ["0.5", "0.99"]
    hostile = fams["bf_hostile"]["samples"][0][1]["path"]
    assert hostile == r'we\"ird\nva\\lue'
    assert fams["bf_hostile"]["help"] == \
        'a "quoted"\\nback\\\\slash help'
    assert fams["bf_fleet_step_time_p50"]["type"] == "gauge"
    assert fams["bf_edge_bytes_total"]["type"] == "counter"
    assert all(set(s[1]) == {"src", "dst"}
               for s in fams["bf_edge_bytes_total"]["samples"])


def test_jsonl_and_snapshot(tmp_path):
    tr = Tracer()
    with tr.span("track", "phase"):
        tr.instant("tick", track="track")
    text = observe.jsonl_events(tr)
    objs = [json.loads(ln) for ln in text.splitlines()]
    assert [o["ph"] for o in objs] == ["B", "i", "E"]
    assert objs[0]["name"] == "phase" and objs[0]["track"] == "track"

    snap = observe.snapshot(str(tmp_path / "dump"))
    assert "metrics" in snap and "trace" in snap
    assert sorted(snap["files"]) == ["events.jsonl", "metrics.prom",
                                     "trace.json"]
    json.loads((tmp_path / "dump" / "trace.json").read_text())


def test_engine_profile_emits_step_profiles():
    """ServingEngine.profile(): HLO-attributed StepProfiles of every
    resident program (two for a plain engine), enumerated from the
    build-time registry, FLOPs from XLA's own cost analysis."""
    from bluefog_tpu import models
    from bluefog_tpu.serving import ServingEngine

    cfg = models.LlamaConfig.tiny(dtype=jnp.float32)
    variables = models.Llama(cfg).init(jax.random.PRNGKey(1),
                                       jnp.zeros((2, 4), jnp.int32))
    eng = ServingEngine(variables, cfg, capacity=2, max_len=16,
                        prefill_chunk=4)
    profs = eng.profile(publish=False)
    assert set(profs) == {"prefill_chunk", "decode_step"}
    assert profs["decode_step"].flops > 0
    assert profs["prefill_chunk"].flops > 0
    json.dumps({k: p.to_dict() for k, p in profs.items()})


# --------------------------------------------------------------------- #
# structured logging
# --------------------------------------------------------------------- #
def _reset_thread_spans():
    """Start the calling thread's span view clean: earlier suite
    activity (e.g. an op handle a test never synchronized) may have
    left a genuinely-open span on the global tracer."""
    observe.get_tracer()._tls.stack = []


def test_json_log_format(monkeypatch, capsys):
    """BLUEFOG_LOG_FORMAT=json: one JSON object per line with
    rank/timestamp/level."""
    import bluefog_tpu.logging_util as LU

    _reset_thread_spans()
    monkeypatch.setenv("BLUEFOG_LOG_FORMAT", "json")
    monkeypatch.setenv("BLUEFOG_TPU_PROCESS_ID", "3")
    monkeypatch.setattr(LU, "_logger", None)  # rebuild with the env
    logger = LU.get_logger()
    try:
        logger.warning("queue %s is full", "prefill")
        err = capsys.readouterr().err
    finally:
        for h in list(logger.handlers):
            logger.removeHandler(h)
        monkeypatch.setattr(LU, "_logger", None)
    line = [ln for ln in err.splitlines() if ln.strip()][-1]
    obj = json.loads(line)
    assert obj["level"] == "WARNING"
    assert obj["rank"] == 3
    assert obj["msg"] == "queue prefill is full"
    assert obj["logger"] == "bluefog_tpu"
    assert isinstance(obj["ts"], float)
    assert "span" not in obj and "track" not in obj  # no open span


def test_json_log_carries_span_correlation(monkeypatch, capsys):
    """ISSUE 5 satellite: a JSON log line emitted INSIDE an open tracer
    span carries span/track fields, so structured logs join against
    the Chrome trace; outside any span the fields are absent."""
    import bluefog_tpu.logging_util as LU

    _reset_thread_spans()
    monkeypatch.setenv("BLUEFOG_LOG_FORMAT", "json")
    monkeypatch.setattr(LU, "_logger", None)
    logger = LU.get_logger()
    tr = observe.get_tracer()
    try:
        with tr.span("train", "train_step"):
            with tr.span("train", "combine"):
                logger.warning("inside nested span")
            logger.warning("inside outer span")
        logger.warning("outside any span")
        err = capsys.readouterr().err
    finally:
        for h in list(logger.handlers):
            logger.removeHandler(h)
        monkeypatch.setattr(LU, "_logger", None)
    objs = [json.loads(ln) for ln in err.splitlines() if ln.strip()]
    nested, outer, outside = objs[-3:]
    assert (nested["track"], nested["span"]) == ("train", "combine")
    assert (outer["track"], outer["span"]) == ("train", "train_step")
    assert "span" not in outside and "track" not in outside


def test_json_log_span_from_another_thread(monkeypatch, capsys):
    """Per-THREAD correlation: a worker thread logging inside its own
    span gets its own track/span, not the main thread's."""
    import bluefog_tpu.logging_util as LU

    monkeypatch.setenv("BLUEFOG_LOG_FORMAT", "json")
    monkeypatch.setattr(LU, "_logger", None)
    logger = LU.get_logger()
    tr = observe.get_tracer()
    try:
        def worker():
            with tr.span("serving", "decode"):
                logger.warning("from worker")

        with tr.span("train", "train_step"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        err = capsys.readouterr().err
    finally:
        for h in list(logger.handlers):
            logger.removeHandler(h)
        monkeypatch.setattr(LU, "_logger", None)
    obj = json.loads([ln for ln in err.splitlines() if ln.strip()][-1])
    assert (obj["track"], obj["span"]) == ("serving", "decode")


# --------------------------------------------------------------------- #
# bench regression gate
# --------------------------------------------------------------------- #
def test_bench_headline_extraction():
    from bluefog_tpu.benchutil import bench_headline

    raw = {"metric": "resnet", "value": 2746.5, "unit": "img/s/chip",
           "vs_baseline": 10.2, "mfu": 0.335,
           "flops_per_step_per_device": 3e12}
    assert bench_headline(raw) == {"value": 2746.5, "mfu": 0.335,
                                   "vs_baseline": 10.2}
    # the driver's BENCH_*.json wrapper
    assert bench_headline({"n": 5, "parsed": raw}) == bench_headline(raw)
    # serving_bench's sectioned record
    serving = {"bench": "serving_poisson",
               "continuous": {"tokens_per_sec": 1056.0, "ttft_p99": 0.4,
                              "latency_p99": 1.2},
               "static": {"tokens_per_sec": 901.0},
               "speedup_tokens_per_sec": 1.17}
    h = bench_headline(serving)
    assert h["continuous.tokens_per_sec"] == 1056.0
    assert h["continuous.ttft_p99"] == 0.4
    assert h["speedup_tokens_per_sec"] == 1.17


def test_bench_compare_direction_and_tolerance(tmp_path, capsys):
    from bluefog_tpu.benchutil import bench_compare, bench_regression_gate

    prev = {"value": 1000.0, "mfu": 0.30,
            "continuous": {"ttft_p99": 0.10}}
    # within 5% tolerance both ways -> ok
    ok, rows = bench_compare(
        {"value": 960.0, "mfu": 0.29,
         "continuous": {"ttft_p99": 0.104}}, prev)
    assert ok and len(rows) == 3
    # throughput regression beyond tolerance -> fails
    ok, rows = bench_compare({"value": 900.0, "mfu": 0.30,
                              "continuous": {"ttft_p99": 0.10}}, prev)
    assert not ok
    assert [r["name"] for r in rows if r["regressed"]] == ["value"]
    # p99 is lower-better: a big INCREASE fails, a decrease never does
    ok, _ = bench_compare({"value": 1000.0, "mfu": 0.30,
                           "continuous": {"ttft_p99": 0.2}}, prev)
    assert not ok
    ok, _ = bench_compare({"value": 1500.0, "mfu": 0.9,
                           "continuous": {"ttft_p99": 0.01}}, prev)
    assert ok  # improvements never fail the gate
    # per-metric tolerance override
    ok, _ = bench_compare({"value": 900.0, "mfu": 0.30,
                           "continuous": {"ttft_p99": 0.10}}, prev,
                          tolerances={"value": 0.2})
    assert ok

    # the file-based gate prints the one-line delta table
    prev_path = tmp_path / "prev.json"
    prev_path.write_text(json.dumps(prev))
    assert not bench_regression_gate({"value": 900.0}, str(prev_path))
    out = capsys.readouterr().out
    assert "[bench-gate]" in out and "REGRESSED" in out
    assert out.count("\n") == 1  # ONE line

def test_bench_gate_names_baseline_file_and_round(tmp_path, capsys):
    """The gate line attributes the comparison: baseline path plus the
    record round (filename ``_r<N>`` convention, explicit ``round``
    field, else ``r?``)."""
    from bluefog_tpu.benchutil import bench_regression_gate

    prev_path = tmp_path / "fleet_sim_r20.json"
    prev_path.write_text(json.dumps({"value": 1000.0}))
    assert bench_regression_gate({"value": 1000.0}, str(prev_path))
    out = capsys.readouterr().out
    assert f"vs {prev_path} (r20):" in out
    p2 = tmp_path / "baseline.json"
    p2.write_text(json.dumps({"value": 1000.0, "round": 7}))
    bench_regression_gate({"value": 995.0}, str(p2))
    assert f"vs {p2} (r7):" in capsys.readouterr().out
    p3 = tmp_path / "plain.json"
    p3.write_text(json.dumps({"value": 1.0}))
    bench_regression_gate({"value": 1.0}, str(p3))
    assert "(r?):" in capsys.readouterr().out


def test_bench_gate_no_shared_metrics_lists_sections(tmp_path, capsys):
    """Comparing records with disjoint headline sections names BOTH
    sides' sections (the 'you gated serving against training' case)
    instead of silently passing with an empty table."""
    from bluefog_tpu.benchutil import bench_regression_gate

    prev_path = tmp_path / "serving_r3.json"
    prev_path.write_text(json.dumps(
        {"continuous": {"tokens_per_sec": 1.0},
         "static": {"tokens_per_sec": 2.0}}))
    current = {"sim_training": {"p50": 0.01},
               "replay": {"mismatches": 0.0}}
    assert bench_regression_gate(current, str(prev_path))
    out = capsys.readouterr().out
    assert "no shared headline metrics" in out
    assert f"{prev_path} (r3)" in out
    assert "current sections [replay,sim_training]" in out
    assert "baseline sections [continuous,static]" in out
    assert out.count("\n") == 1  # still ONE line


def test_bench_headline_replay_section():
    """The replay-verification section gates: decisions_replayed is
    higher-better, mismatches lower-better."""
    from bluefog_tpu.benchutil import bench_compare, bench_headline

    rec = {"replay": {"decisions_replayed": 6.0, "mismatches": 0.0}}
    assert bench_headline(rec) == {"replay.decisions_replayed": 6.0,
                                   "replay.mismatches": 0.0}
    ok, rows = bench_compare(
        {"replay": {"decisions_replayed": 6.0, "mismatches": 1.0}},
        rec, tolerances={"replay.mismatches": 0.0})
    assert not ok
    assert [r["name"] for r in rows if r["regressed"]] == \
        ["replay.mismatches"]


# --------------------------------------------------------------------- #
# tracer sink hardening
# --------------------------------------------------------------------- #
class _BoomSink:
    def __init__(self):
        self.calls = 0

    def record(self, name, tid, phase):
        self.calls += 1
        raise RuntimeError("disk full")


class _ListSink:
    def __init__(self):
        self.events = []

    def record(self, name, tid, phase):
        self.events.append((phase, name, tid))


def test_tracer_broken_sink_detached_after_limit(monkeypatch):
    """A persistently-failing sink is fault-isolated (other sinks and
    the buffer see every event), counted, and detached after
    SINK_ERROR_LIMIT consecutive failures."""
    from bluefog_tpu.observe.tracer import SINK_ERROR_LIMIT

    monkeypatch.setenv("BLUEFOG_OBSERVE", "1")
    tr = Tracer()
    boom, good = _BoomSink(), _ListSink()
    ctr = observe.get_registry().counter(
        "bf_tracer_sink_errors_total", sink="_BoomSink")
    before = ctr.value
    tr.add_sink(boom)
    tr.add_sink(good)
    n = SINK_ERROR_LIMIT + 3
    for i in range(n):
        tr.instant(f"e{i}")
    assert boom.calls == SINK_ERROR_LIMIT  # detached, never called again
    assert len(good.events) == n           # the good sink never starved
    assert len(tr.events()) == n           # the buffer saw everything
    assert ctr.value - before == SINK_ERROR_LIMIT


def test_tracer_sink_error_streak_resets_on_success():
    """Only CONSECUTIVE failures detach: a flaky sink that recovers
    before the limit stays attached."""
    from bluefog_tpu.observe.tracer import SINK_ERROR_LIMIT

    class _Flaky:
        def __init__(self):
            self.calls = 0
            self.failing = False

        def record(self, name, tid, phase):
            self.calls += 1
            if self.failing:
                raise RuntimeError("transient")

    tr = Tracer()
    flaky = _Flaky()
    tr.add_sink(flaky)
    for _ in range(3):  # each burst: LIMIT-1 failures, then a success
        flaky.failing = True
        for _ in range(SINK_ERROR_LIMIT - 1):
            tr.instant("x")
        flaky.failing = False
        tr.instant("x")
    total = 3 * SINK_ERROR_LIMIT
    assert flaky.calls == total  # still attached through every burst
    tr.instant("x")
    assert flaky.calls == total + 1


# --------------------------------------------------------------------- #
# decision flight recorder: exposition + zero-cost toggle
# --------------------------------------------------------------------- #
def test_prometheus_exposition_blackbox_metrics(registry):
    """Strict-parser pass over the recorder's metric families:
    bf_decisions_total{plane,kind,outcome} counters and the
    bf_blackbox_dropped_events gauge."""
    from bluefog_tpu.observe.blackbox import BlackBox

    bb = BlackBox(capacity=2, registry=registry)
    trig = bb.record("topology", "trigger", step=0)
    bb.record("topology", "commit", step=1, parent=trig)
    bb.record("mix", "swap", step=2)  # overflows the 2-slot ring
    text = observe.prometheus_text(registry)
    fams = _strict_parse_prometheus(text)
    assert fams["bf_decisions_total"]["type"] == "counter"
    samples = fams["bf_decisions_total"]["samples"]
    assert all(set(s[1]) == {"plane", "kind", "outcome"}
               for s in samples)
    by = {(s[1]["plane"], s[1]["kind"], s[1]["outcome"]): float(s[2])
          for s in samples}
    assert by[("topology", "trigger", "pending")] == 1.0
    assert by[("topology", "commit", "committed")] == 1.0
    assert by[("mix", "swap", "pending")] == 1.0
    assert fams["bf_blackbox_dropped_events"]["type"] == "gauge"
    (dropped,) = fams["bf_blackbox_dropped_events"]["samples"]
    assert float(dropped[2]) == 1.0


def test_blackbox_toggle_leaves_compiled_programs_untouched(monkeypatch):
    """The recorder is host-side only: a control plane making recorded
    decisions between jitted steps leaves jit cache sizes and step
    outputs bit-identical with the recorder on vs off."""
    from bluefog_tpu.observe.blackbox import BlackBox
    from bluefog_tpu.topology import PodSpec, TopologyControlPlane
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    step, params0, ostate0, batch = _bucketed_step(mesh)
    carrier = list(one_peer_dynamic_schedule(N))[:2]

    def run3(arm):
        plane = TopologyControlPlane(
            PodSpec(2, 4), carrier, synchronous=True, window=4,
            probation=1, blackbox=arm)
        plane.force_candidate(list(carrier), "forced")
        p, o = params0, ostate0
        for i in range(3):
            plane.on_step(i)  # swap at 0, probation commit after
            p, o, loss = step(p, o, batch, jnp.int32(i))
        return p, loss

    monkeypatch.setenv("BLUEFOG_BLACKBOX", "1")
    bb = BlackBox(capacity=64)
    p_on, loss_on = run3(bb)
    size_on = step.jitted._cache_size()
    assert bb.n_recorded >= 5  # trigger/synthesize/ready/swap/commit
    monkeypatch.setenv("BLUEFOG_BLACKBOX", "0")
    p_off, loss_off = run3(False)
    assert step.jitted._cache_size() == size_on  # no recompiles
    np.testing.assert_array_equal(np.asarray(loss_on),
                                  np.asarray(loss_off))
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
