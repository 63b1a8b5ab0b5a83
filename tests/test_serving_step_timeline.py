"""PR 34: the engine step's timeline from inside the program.  The two
spans inside ``bf.engine.token_fetch``, ``launch=`` on the spans that
dispatch a program, the phases' seconds handed to ``on_step``, the
record of the longest step with its one alarm, and the token counter
that moves once a step."""

import logging

import jax
import numpy as np
import pytest

import served_model
from bluefog_tpu.logging_util import get_logger
from bluefog_tpu.observe import tracer as obs_tracer
from bluefog_tpu.observe.registry import MetricsRegistry
from bluefog_tpu.observe.tracer import Tracer
from bluefog_tpu.serving import Request, ServingEngine, SpeculativeConfig
from bluefog_tpu.serving import engine as engine_mod
from bluefog_tpu.serving import metrics as metrics_mod
from bluefog_tpu.serving.metrics import ServingMetrics

MAX_LEN = 48
DISPATCHERS = ("decode_dispatch", "prefill_chunk")


@pytest.fixture(scope="module")
def model():
    return served_model.tiny_llama()


def engine_of(model, **kw):
    cfg, variables = model
    kw.setdefault("registry", MetricsRegistry())
    return ServingEngine(variables, cfg, capacity=2, max_len=MAX_LEN,
                         prefill_chunk=4, **kw)


def requests(lengths=(9, 6, 11), new=4, seed=3):
    rs = np.random.RandomState(seed)
    return [Request(rs.randint(0, 256, (n,)).astype(np.int32), new)
            for n in lengths]


def serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [list(r.tokens) for r in reqs]


@pytest.fixture
def ring(monkeypatch):
    """A tracer of this test's own as the process's."""
    tracer = Tracer()
    monkeypatch.setattr(obs_tracer, "_tracer", tracer)
    return tracer


def nested(events, track="engine"):
    """``[(depth, name, args)]`` of the spans of one track, by begin."""
    out, depth = [], 0
    for phase, name, tid, _, args in events:
        if tid != track:
            continue
        if phase == "B":
            out.append((depth, name, args or {}))
            depth += 1
        elif phase == "E":
            depth -= 1
    assert depth == 0
    return out


# --------------------------------------------------------------------- #
# the spans
# --------------------------------------------------------------------- #
def test_the_fetch_is_split_in_the_ring_where_a_tracer_takes_it(model, ring):
    serve(engine_of(model), requests())
    spans = nested(ring.events())
    fetches = [i for i, (_, name, _) in enumerate(spans)
               if name == "token_fetch"]
    assert len(fetches) >= 4
    for i in fetches:
        (d0, _, _), (d1, n1, _), (d2, n2, a2) = spans[i:i + 3]
        assert (d0, d1, d2) == (1, 2, 2)        # step > token_fetch > parts
        assert (n1, n2) == ("device_wait", "host_copy")
        assert a2 == {"leaves": 1, "bytes": 2 * 4}   # [horizon 1, capacity 2]
    # nothing else nests that deep, and the parts lie nowhere else
    assert {name for depth, name, _ in spans if depth == 2} == \
        {"device_wait", "host_copy"}


def test_the_fetch_is_split_in_a_profilers_trace(model, tmp_path):
    from jax.profiler import ProfileData
    from perfbench.harness import program_trace as pt, trace as tr

    eng = engine_of(model)
    serve(eng, requests((9,), 3))                # compiles
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        serve(eng, requests((6, 11)))
    finally:
        jax.profiler.stop_trace()
    spans = pt.program_spans_of(
        ProfileData.from_file(tr.find_xplane(str(tmp_path))))
    fetches = [(s, e) for name, s, e, _, _ in spans
               if name == "bf.engine.token_fetch"]
    parts = {name: [(s, e, args) for n, s, e, args, _ in spans if n == name]
             for name in ("bf.engine.device_wait", "bf.engine.host_copy")}
    assert len(fetches) >= 4
    assert all(len(v) == len(fetches) for v in parts.values())
    for (s, e), (ws, we, _), (cs, ce, args) in zip(
            fetches, *parts.values()):
        assert s <= ws <= we <= cs <= ce <= e
        assert args == {"leaves": 1, "bytes": 8}
    launches = [args["launch"] for name, _, _, args, _ in spans
                if name.split(".")[-1] in DISPATCHERS]
    assert launches == list(range(launches[0], launches[0] + len(launches)))
    starts = [args["start"] for name, _, _, args, _ in spans
              if name == "bf.engine.prefill_chunk"]
    assert starts == [0, 4, 0, 4, 8]            # prompts of 6 and 11: 5 and 10


def test_with_observe_off_the_fetch_is_the_one_call_it_was(
        model, monkeypatch, ring):
    """Same tokens, one ``device_get`` a step and no other call on the
    arrays, no new entry in a jit cache, nothing in the ring."""
    eng = engine_of(model, registry=None)
    want = serve(eng, requests())
    sizes = {k: fn._cache_size() for k, (fn, _, _) in eng._resident.items()}
    seen = len(ring.events())
    assert seen

    monkeypatch.setenv("BLUEFOG_OBSERVE", "0")
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda tree: calls.append(tree) or real(tree))

    def forbidden(*a, **k):
        raise AssertionError("the plain path makes no such call")

    monkeypatch.setattr(jax.Array, "copy_to_host_async", forbidden,
                        raising=False)
    off = engine_of(model, registry=None)
    got = serve(off, requests())
    assert got == want
    decode_steps = sum(len(t) for t in got) - 0   # one token a slot a step
    assert 4 <= len(calls) <= decode_steps
    assert all(isinstance(c, jax.Array) for c in calls)   # the tokens alone
    assert len(ring.events()) == seen
    assert {k: fn._cache_size()
            for k, (fn, _, _) in off._resident.items()} == sizes
    # and the step's record still fills, for summary()
    longest = off.metrics.summary()["longest_step"]
    assert longest["seconds"] > 0
    assert "device_wait" not in longest["phases"]
    # (the longest step may be a stretch's first, which reads nothing)
    assert sum(longest["phases"].values()) > 0


def launches_of(ring):
    return [(name, args["launch"]) for _, name, args in nested(ring.events())
            if name in DISPATCHERS]


def test_launch_counts_every_program_once(model, ring):
    eng = engine_of(model)
    serve(eng, requests())
    got = launches_of(ring)
    assert [n for _, n in got] == list(range(len(got)))
    assert eng._launches == len(got)
    # ceil(8 / 4) + ceil(5 / 4) + ceil(10 / 4) chunks
    assert sum(name == "prefill_chunk" for name, _ in got) == 2 + 2 + 3
    chunks = [a for _, name, a in nested(ring.events())
              if name == "prefill_chunk"]
    assert [a["start"] for a in chunks if a["rid"] == chunks[0]["rid"]] == \
        [0, 4]


def test_launch_counts_the_speculative_steps_and_the_drafts_chunks(
        model, ring):
    cfg, variables = model
    spec = SpeculativeConfig(variables=variables, cfg=cfg, lookahead=3)
    eng = engine_of(model, speculative=spec)
    serve(eng, requests((9, 6), 5))
    got = launches_of(ring)
    want, n = [], 0
    for name, _ in got:
        want.append(n)
        n += 2 if name == "prefill_chunk" else 1    # target's and draft's
    assert [k for _, k in got] == want
    assert eng._launches == n
    assert any(name == "decode_dispatch" for name, _ in got)
    parts = [name for depth, name, _ in nested(ring.events()) if depth == 2]
    assert set(parts) == {"device_wait", "host_copy"}
    copies = [a for _, name, a in nested(ring.events())
              if name == "host_copy"]
    assert {a["leaves"] for a in copies} == {2}     # tokens and counts


# --------------------------------------------------------------------- #
# the token counter
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("horizon", [1, 4])
def test_the_token_counter_holds_the_tokens_emitted(model, horizon):
    reg = MetricsRegistry()
    eng = engine_of(model, registry=reg, decode_horizon=horizon)
    got = serve(eng, requests((9, 6, 11), new=6))
    emitted = sum(len(t) for t in got)
    assert emitted == 18
    snap = reg.snapshot()
    assert snap["bf_serving_tokens_total"][0]["value"] == emitted
    assert eng.metrics.summary()["tokens_generated"] == emitted
    assert snap["bf_serving_ttft_seconds"][0]["count"] == 3


def test_the_counter_moves_once_a_step(model, monkeypatch):
    reg = MetricsRegistry()
    eng = engine_of(model, registry=reg)
    from bluefog_tpu.observe.registry import Counter

    incs = []
    counter = reg.counter("bf_serving_tokens_total", "tokens generated")
    real = Counter.inc

    def inc(self, amount=1.0):
        if self is counter:
            incs.append(amount)
        real(self, amount)

    monkeypatch.setattr(Counter, "inc", inc)
    serve(eng, requests((5, 5), new=4))
    assert sum(incs) == 8 and max(incs) == 2


# --------------------------------------------------------------------- #
# the stalled step
# --------------------------------------------------------------------- #
class Capture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


@pytest.fixture
def warnings():
    handler = Capture()
    get_logger().addHandler(handler)
    yield handler.messages
    get_logger().removeHandler(handler)


class SteppedClock:
    """Every reading is 1 ms after the one before; ``jump`` adds to the
    next."""

    def __init__(self):
        self.t, self.jump = 0.0, 0.0

    def __call__(self):
        self.t += 1e-3 + self.jump
        self.jump = 0.0
        return self.t


def test_a_stall_in_the_fetch_trips_one_alarm_with_its_phase(
        model, monkeypatch, warnings):
    """Steps of some 20 ms on an injected clock, one whose wait for the
    device takes 3 s more: one instant, one line, the gauge; the 200 ms
    step before it (the first decode step that drains queued chunks)
    trips nothing."""
    clock = SteppedClock()
    tracer = Tracer(clock=clock)
    monkeypatch.setattr(obs_tracer, "_tracer", tracer)
    reg = MetricsRegistry()
    eng = engine_of(model, registry=reg)
    serve(eng, requests((9,), 3))                # compiles; short steps
    real, waits = engine_mod.ServingEngine._fetch, []

    class Held:
        """The step's token array, whose wait takes ``seconds`` more."""

        def __init__(self, array, seconds):
            self.array, self.seconds = array, seconds

        def block_until_ready(self):
            clock.jump = self.seconds
            self.array.block_until_ready()

    def fetch(self, fetch_span, first, tree):
        waits.append(1)
        held = Held(first, {3: 0.2, 7: 3.0}.get(len(waits), 0.0))
        return real(self, fetch_span, held, tree)

    monkeypatch.setattr(engine_mod.ServingEngine, "_fetch", fetch)
    before = eng.metrics.n_steps
    serve(eng, requests((6, 11), new=8))
    assert len(waits) >= 8
    instants = [(name, args) for phase, name, _, _, args in tracer.events()
                if phase == "i" and name == "engine.slow_step"]
    assert len(instants) == 1
    args = instants[0][1]
    assert args["held"] == "device_wait"
    assert 3.0 < args["device_wait"] < 3.01 < args["seconds"] < 3.1
    assert args["compiles"] == 0
    lines = [m for m in warnings if m.startswith("engine.slow_step")]
    assert len(lines) == 1
    assert f"step={args['step']} " in lines[0]
    assert "held=device_wait" in lines[0] and "gc2=" in lines[0]
    assert "compiles=0" in lines[0] and "device_wait=3.0" in lines[0]
    longest = eng.metrics.summary()["longest_step"]
    assert longest["step"] == args["step"] >= before
    assert longest["seconds"] == args["seconds"]
    assert longest["decoding"] >= 1 and longest["chunk"] in (True, False)
    assert set(longest["phases"]) >= {"admit", "decode_inputs",
                                      "decode_dispatch", "token_fetch",
                                      "device_wait", "host_copy", "emit"}
    gauges = {g["labels"]["phase"]: g["value"]
              for g in reg.snapshot()["bf_serving_longest_step_seconds"]}
    assert gauges["step"] == longest["seconds"]
    assert gauges["device_wait"] == longest["phases"]["device_wait"]
    # the phases are the step but for what lies between them
    top = sum(v for k, v in longest["phases"].items()
              if k not in metrics_mod.FETCH_PARTS)
    assert 0 < longest["seconds"] - top < 0.05


def test_the_record_and_the_alarm_by_hand(monkeypatch, warnings):
    """``on_step`` alone: the record follows the maximum and writes the
    gauge only then; a slow first step, a step under the floor, a step
    under the multiple and a step that compiled trip nothing or are not
    the record."""
    reg = MetricsRegistry()
    tracer = Tracer()
    monkeypatch.setattr(obs_tracer, "_tracer", tracer)
    m = ServingMetrics(registry=reg)
    sets = []
    real = reg.gauge

    def gauge(name, *a, **k):
        if name == "bf_serving_longest_step_seconds":
            sets.append(k["phase"])
        return real(name, *a, **k)

    monkeypatch.setattr(reg, "gauge", gauge)

    def step(seconds, **phases):
        m.on_step(0.5, 0, seconds, now=0.0, phases=phases, decoding=2)

    step(0.9, token_fetch=0.8)                  # the first: nothing to hold it to
    assert m.longest_step["step"] == 0 and sets == ["step", "token_fetch"]
    for _ in range(6):
        step(0.010, token_fetch=0.008)
    step(0.7, token_fetch=0.6)                  # seven steps make no median
    for _ in range(4):
        step(0.010, token_fetch=0.008)
    assert len(sets) == 2 and not warnings      # no new maximum, no write
    step(0.2, token_fetch=0.19)                 # 20 x the median, under the floor
    step(0.6, prefill_chunk=0.55, token_fetch=0.01)  # over both
    assert [m for m in warnings if "slow_step" in m] and len(warnings) == 1
    assert "held=prefill_chunk" in warnings[0] and "chunk=1" in warnings[0]
    assert "median=0.0100" in warnings[0]
    assert m.longest_step["step"] == 0          # 0.9 is still the longest
    seen = m._compiles_seen
    compiles = iter([seen, seen + 1, seen + 1])
    monkeypatch.setattr(metrics_mod.obs_compiles, "backend_compiles",
                        lambda: next(compiles))
    step(0.011)
    step(7.0, decode_dispatch=6.9)              # it compiled: told, not kept
    assert "compiles=1" in warnings[-1] and "held=decode_dispatch" in \
        warnings[-1]
    assert m.longest_step["seconds"] == 0.9
    step(2.0, emit=0.1)                         # held by no phase
    assert "held=self" in warnings[-1]
    assert m.longest_step["seconds"] == 2.0
    assert m.longest_step["phases"] == {"emit": 0.1}
    assert m.longest_step["chunk"] is False
    # the phase the old record had and the new one lacks reads 0
    got = {g["labels"]["phase"]: g["value"]
           for g in reg.snapshot()["bf_serving_longest_step_seconds"]}
    assert got == {"step": 2.0, "emit": 0.1, "token_fetch": 0.0}
    assert len([1 for p, n, *_ in tracer.events()
                if p == "i" and n == "engine.slow_step"]) == 3
    assert metrics_mod.SLOW_STEP_MIN_HISTORY == 8
