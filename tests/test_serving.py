"""Continuous-batching serving engine (bluefog_tpu/serving/).

Contract under test: the engine is a pure SCHEDULING layer over the
one-shot decode substrate — for any arrival pattern, every request's
output is token-exact with its own one-shot
``llama_generate(prompt[None], n, max_len=pool_max_len)`` call.  Plus
the serving behaviors that make it an engine rather than a loop: slot
reuse, EOS retirement, deadline cancellation, pool-full backpressure,
metrics, and timeline spans.
"""

import functools
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bluefog_tpu.models import llama_generate
from bluefog_tpu.serving import (FifoScheduler, Request, RequestRejected,
                                 ServingEngine, SlotPool)
from served_model import (VirtualClock, one_shot as _one_shot,
                          tiny_engine as _engine, tiny_llama as _setup)

pytestmark = pytest.mark.serving

MAX_LEN = 48


def _prompts(sizes, seed=0, vocab=256):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (n,)).astype(np.int32) for n in sizes]


def test_staggered_arrivals_match_one_shot():
    """The acceptance property: requests arriving at different engine
    steps, with different prompt lengths and budgets, sharing 2 slots —
    each output equals its per-request one-shot generation exactly."""
    cfg, variables = _setup()
    prompts = _prompts((5, 9, 3, 1))
    budgets = [6, 4, 8, 5]
    eng = _engine(variables, cfg)
    reqs = [Request(p, b) for p, b in zip(prompts, budgets)]
    eng.submit(reqs[0])
    eng.step()
    eng.step()
    eng.submit(reqs[1])
    eng.step()
    eng.submit(reqs[2])
    eng.submit(reqs[3])
    eng.run()
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.state == "completed"
        np.testing.assert_array_equal(
            r.output(), _one_shot(variables, cfg, p, b))


def test_scan_layers_layout_served():
    """Both layer layouts decode through the engine (the scanned stack
    carries a [n_layers] cache axis — slots stack outside it)."""
    cfg, variables = _setup(scan_layers=True)
    prompts = _prompts((4, 6))
    eng = _engine(variables, cfg, prefill_chunk=3)
    reqs = [eng.submit(Request(p, 5)) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            r.output(), _one_shot(variables, cfg, p, 5))


def test_slot_reuse_is_invisible():
    """capacity=1: the second request reuses the first's slot and still
    matches one-shot exactly (freed slots are zeroed — reuse leaves no
    trace)."""
    cfg, variables = _setup()
    prompts = _prompts((7, 5), seed=3)
    eng = _engine(variables, cfg, capacity=1)
    r0 = eng.submit(Request(prompts[0], 6))
    eng.step()  # r0 admitted into slot 0, mid-flight
    r1 = eng.submit(Request(prompts[1], 6))
    eng.run()
    assert r0.slot is None and r1.slot is None
    assert eng.pool.n_free == 1
    for r, p in zip((r0, r1), prompts):
        np.testing.assert_array_equal(
            r.output(), _one_shot(variables, cfg, p, 6))


def test_eos_retires_slot_and_truncates():
    """A request whose stream hits its eos_id retires early: its output
    is the one-shot prefix through the first EOS, and the freed slot
    admits the next queued request."""
    cfg, variables = _setup()
    (prompt,) = _prompts((5,), seed=1)
    full = _one_shot(variables, cfg, prompt, 10)
    eos = int(full[prompt.size + 3])  # forces a stop after 4 tokens
    assert eos not in full[prompt.size:prompt.size + 3]
    eng = _engine(variables, cfg, capacity=1)
    r0 = eng.submit(Request(prompt, 10, eos_id=eos))
    r1 = eng.submit(Request(prompt, 2))  # waits for r0's slot
    eng.run()
    assert r0.state == "completed"
    assert len(r0.tokens) == 4 and r0.tokens[-1] == eos
    np.testing.assert_array_equal(r0.output(), full[:prompt.size + 4])
    assert r1.state == "completed" and len(r1.tokens) == 2


def test_decode_horizon_invariant():
    """decode_horizon is pure host-overhead amortization: the emitted
    streams (including EOS truncation mid-horizon) are identical for
    every horizon, and still one-shot-exact."""
    cfg, variables = _setup()
    prompts = _prompts((5, 9, 3), seed=11)
    budgets = [7, 4, 6]
    full = _one_shot(variables, cfg, prompts[0], 10)
    eos = int(full[prompts[0].size + 2])

    def serve(horizon):
        eng = _engine(variables, cfg, decode_horizon=horizon)
        reqs = [Request(prompts[0], 10, eos_id=eos)] + \
            [Request(p, b) for p, b in zip(prompts[1:], budgets[1:])]
        eng.submit(reqs[0])
        eng.step()
        for r in reqs[1:]:
            eng.submit(r)
        eng.run()
        return [r.output() for r in reqs]

    a, b = serve(1), serve(4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for y, p, n in zip(b[1:], prompts[1:], budgets[1:]):
        np.testing.assert_array_equal(y, _one_shot(variables, cfg, p, n))


# --------------------------------------------------------------------- #
# one decode program ahead of the host's knowledge of the tokens
# --------------------------------------------------------------------- #
AHEAD_LEN = 40      # a max_len no other test uses: these programs are
# traced here, so the executables can be counted


def _drive(eng, arrivals, sync=False, on_step=None):
    """Step ``eng`` until idle, submitting ``arrivals[k]`` before step
    ``k``.  ``sync``: read every program right after the step that
    dispatched it, so that the host knows every token before the next
    dispatch: the order the engine had before it ran ahead, and the
    reference its streams are held to."""
    k = 0
    while True:
        for r in arrivals.get(k, ()):
            eng.submit(r)
        busy = eng.step()
        if sync:
            eng.collect()
            busy = eng.busy
        if on_step is not None:
            on_step(k)
        k += 1
        assert k < 200
        if not busy and k > max(arrivals):
            return k


def _ahead_engine(variables, cfg, horizon, **kw):
    return ServingEngine(variables, cfg, capacity=2, max_len=AHEAD_LEN,
                         prefill_chunk=4, decode_horizon=horizon, **kw)


@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("horizon", [1, 2])
def test_an_eos_learnt_one_program_late_and_the_slot_reused(horizon,
                                                            temperature):
    """The streams of an engine that runs one program ahead are the
    streams of one that reads every program before the next dispatch
    (and, greedy, of ``generate``), for every horizon: with an EOS that
    lands while the next program is in flight and holds the slot, the
    slot then reused by a request that PREFILLS into it beside a third
    that decodes throughout; a request whose prefill ends while a
    program is in flight; sampled streams too (the rng's token count
    includes the tokens in flight).  One decode executable serves every
    mix of slots that go on and slots that join, and the two counters
    say how often the host ran ahead and what the late EOS cost."""
    from bluefog_tpu.observe import MetricsRegistry
    from bluefog_tpu.serving.engine import _decode_step_prog

    cfg, variables = _setup()
    prompts = _prompts((5, 9, 3, 11), seed=21)

    def requests(eos=None):
        return [Request(prompts[0], 12, eos_id=eos, temperature=temperature,
                        seed=3),
                Request(prompts[1], 14, temperature=temperature, seed=4),
                Request(prompts[2], 5, temperature=temperature, seed=5),
                Request(prompts[3], 4, temperature=temperature, seed=6)]

    def serve(eos, sync):
        reg = MetricsRegistry()
        eng = _ahead_engine(variables, cfg, horizon, registry=reg)
        reqs = requests(eos)
        # r2 and r3 wait for a slot: r0's, when its EOS lands
        _drive(eng, {0: reqs[:2], 2: reqs[2:]}, sync=sync)
        assert all(r.state == "completed" for r in reqs)
        assert eng.pool.n_free == 2 and not eng.busy
        return eng, reg, [list(r.tokens) for r in reqs]

    n_before = _decode_step_prog._cache_size()
    _, _, free_run = serve(None, sync=True)
    n_traced = _decode_step_prog._cache_size()
    eos = free_run[0][4]                      # r0 stops at its 5th token
    assume_first = free_run[0].index(eos)
    want = [free_run[0][:assume_first + 1]] + free_run[1:]
    _, sync_reg, sync_run = serve(eos, sync=True)
    eng, reg, ahead_run = serve(eos, sync=False)
    assert sync_run == want
    assert ahead_run == want
    if temperature == 0.0:
        for toks, p in zip(free_run, prompts):
            np.testing.assert_array_equal(
                np.concatenate([p, toks]),
                llama_generate(variables, cfg, jnp.asarray(p[None]),
                               len(toks), max_len=AHEAD_LEN)[0])
    # (g) one executable (traced by the first of these tests to run at
    # this horizon): the first program of a stretch, programs ahead,
    # slots that join, an overrun lane and the synchronous order alike
    assert n_traced - n_before <= 1
    assert _decode_step_prog._cache_size() == n_traced
    # (h) the counters
    value = lambda r, name: (r.snapshot().get(name) or [{"value": 0}])[0][
        "value"]
    steps = value(reg, "bf_serving_decode_steps_total")
    assert value(sync_reg, "bf_serving_decode_ahead_total") == 0
    assert value(sync_reg, "bf_serving_decode_overrun_slots_total") == 0
    assert 0 < steps - value(reg, "bf_serving_decode_ahead_total") <= 2
    assert eng.metrics.n_decode_ahead == value(
        reg, "bf_serving_decode_ahead_total")
    # the program in flight when r0's EOS was read held r0
    assert value(reg, "bf_serving_decode_overrun_slots_total") == 1
    assert eng.metrics.n_decode_overrun_slots == 1
    # an overrun lane is device work and no token
    emitted = sum(len(t) for t in want)
    assert value(reg, "bf_serving_tokens_total") == emitted
    lanes = value(reg, "bf_serving_decode_slots_total")
    sync_lanes = value(sync_reg, "bf_serving_decode_slots_total")
    assert lanes == sync_lanes + 1
    if horizon == 1:
        assert sync_lanes == emitted


def test_a_prefill_that_ends_beside_a_program_in_flight_joins_the_next():
    """The request's first token comes from the program dispatched in
    the step its last chunk ran in: it takes the host's token (the
    prompt's last) while the slot beside it takes the device's."""
    cfg, variables = _setup()
    prompts = _prompts((3, 10), seed=5)
    eng = _ahead_engine(variables, cfg, 1)
    r0, r1 = Request(prompts[0], 12), Request(prompts[1], 4)
    seen = []

    def watch(k):
        flight = eng._flight
        seen.append((r1.state, len(r1.tokens),
                     sorted(flight.decoding) if flight else None))

    _drive(eng, {0: [r0], 2: [r1]}, on_step=watch)
    joined = next(i for i, (state, _, _) in enumerate(seen)
                  if state == "decode")
    # the step before: r0 alone in flight, r1 still prefilling
    assert seen[joined - 1] == ("prefill", 0, [0])
    # the step its prefill ended: both slots dispatched, no token yet
    assert seen[joined] == ("decode", 0, [0, 1])
    assert seen[joined + 1][:2] == ("decode", 1)
    for r, p in zip((r0, r1), prompts):
        np.testing.assert_array_equal(
            r.output(), llama_generate(
                variables, cfg, jnp.asarray(p[None]), r.max_new_tokens,
                max_len=AHEAD_LEN)[0])


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_that_leaves_while_a_program_holds_it(how):
    """``cancel()`` and a deadline shed of a request the program in
    flight advances: its lane is dropped (an overrun), its stream is a
    prefix of what it would have been, the slot beside it and the
    request that takes the freed slot are exact."""
    cfg, variables = _setup()
    clock = VirtualClock()
    prompts = _prompts((5, 7, 6), seed=9)
    eng = _ahead_engine(variables, cfg, 1, clock=clock)
    full = [np.asarray(llama_generate(
        variables, cfg, jnp.asarray(p[None]), 10, max_len=AHEAD_LEN)[0])
        for p in prompts]
    r0 = Request(prompts[0], 10, deadline=3.5 if how == "deadline" else None)
    r1, r2 = Request(prompts[1], 10), Request(prompts[2], 10)

    def tick(k):
        clock.advance(1.0)
        if k == 3:
            assert eng._flight.decoding[r0.slot] is r0 and len(r0.tokens) >= 2
            if how == "cancel":
                eng.cancel(r0)

    _drive(eng, {0: [r0, r1], 1: [r2]}, on_step=tick)
    assert r0.state == "cancelled" and r0.slot is None
    n = len(r0.tokens)
    assert 2 <= n < 10
    np.testing.assert_array_equal(r0.output(), full[0][:prompts[0].size + n])
    assert eng.metrics.n_decode_overrun_slots == 1
    for r, want in ((r1, full[1]), (r2, full[2])):
        assert r.state == "completed"
        np.testing.assert_array_equal(r.output(), want)
    assert eng.pool.n_free == 2 and not eng.busy


def test_temperature_sampling_deterministic_and_in_range():
    """Per-request sampling is a function of (seed, token index) only —
    re-serving the same request reproduces the stream, independent of
    co-batching."""
    cfg, variables = _setup()
    prompts = _prompts((5, 6), seed=7)

    def serve(reqs, capacity):
        eng = _engine(variables, cfg, capacity=capacity)
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.output() for r in reqs]

    a = serve([Request(prompts[0], 6, temperature=0.8, seed=5),
               Request(prompts[1], 6, temperature=1.2, seed=9)], 2)
    b = serve([Request(prompts[0], 6, temperature=0.8, seed=5)], 1)
    np.testing.assert_array_equal(a[0], b[0])
    assert np.all((a[1] >= 0) & (a[1] < 256))


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 7])
def test_the_kept_sampling_key_is_the_one_prngkey_makes(seed):
    """``_rng_key`` keeps a seed's key on the host, made once a seed
    and not once a slot a decode step: the same words ``PRNGKey`` gives,
    and one array however often it is asked for."""
    from bluefog_tpu.serving.engine import _rng_key

    np.testing.assert_array_equal(
        _rng_key(seed), np.asarray(jax.random.PRNGKey(seed)))
    assert _rng_key(seed) is _rng_key(seed)


def test_deadline_cancels_running_and_queued():
    cfg, variables = _setup()
    clock = VirtualClock()
    prompts = _prompts((4, 4), seed=2)
    eng = _engine(variables, cfg, capacity=1, clock=clock)
    # r0 runs but can never finish 20 tokens by t=2.0 (1s per step;
    # its first decode program is dispatched at t=0 and read at t=1)
    r0 = eng.submit(Request(prompts[0], 20, deadline=2.0))
    # r1 is stuck behind r0 and expires in the queue
    r1 = eng.submit(Request(prompts[1], 2, deadline=0.5))
    steps = 0
    while eng.step():
        clock.advance(1.0)
        steps += 1
        assert steps < 50
    assert r0.state == "cancelled"
    assert 0 < len(r0.tokens) < 20  # partial stream delivered
    assert r1.state == "cancelled" and r1.tokens == []
    assert eng.pool.n_free == 1  # cancelled slots come back
    # the program in flight at the deadline held r0: its token is dropped
    assert eng.metrics.n_decode_overrun_slots == 1


def test_deadline_cancels_mid_prefill():
    """Deadline contract, prefill phase: a request whose deadline
    expires while its prompt is still being chunk-prefilled (state
    'prefill', not yet decoding) is cancelled at the next step
    boundary with ZERO tokens delivered, its slot comes back, and the
    request behind it serves to one-shot exactness through the
    reclaimed slot."""
    cfg, variables = _setup()
    clock = VirtualClock()
    long_prompt, short_prompt = _prompts((17, 4), seed=5)
    # chunk=2 -> prompt[:-1] needs 8 chunks at 1 chunk/step: the
    # deadline at t=2.5 lands mid-prefill (1 s per step)
    eng = _engine(variables, cfg, capacity=1, prefill_chunk=2, clock=clock)
    r0 = eng.submit(Request(long_prompt, 8, deadline=2.5))
    r1 = eng.submit(Request(short_prompt, 3))
    saw_prefill = False
    steps = 0
    while eng.step():
        saw_prefill = saw_prefill or r0.state == "prefill"
        clock.advance(1.0)
        steps += 1
        assert steps < 50
    assert saw_prefill                      # it WAS mid-prefill
    assert r0.state == "cancelled"
    assert r0.tokens == [] and r0.slot is None   # never reached decode
    assert r1.state == "completed"
    assert eng.pool.n_free == 1             # the slot came back
    np.testing.assert_array_equal(
        r1.output(), _one_shot(variables, cfg, short_prompt, 3))
    m = eng.metrics.summary()
    assert m["outcomes"].get("cancelled") == 1


def test_explicit_cancellation():
    cfg, variables = _setup()
    prompts = _prompts((4, 4), seed=4)
    eng = _engine(variables, cfg, capacity=1, prefill_chunk=8)
    r0 = eng.submit(Request(prompts[0], 20))
    r1 = eng.submit(Request(prompts[1], 3))
    eng.step()
    assert eng.cancel(r0)   # running: retired at the next step boundary
    eng.run()
    assert r0.state == "cancelled"
    assert r1.state == "completed"
    assert not eng.cancel(r0)  # already retired


def test_pool_full_rejects_with_queue_depth():
    """Backpressure, not stalls: pool full -> queue; queue full ->
    immediate RequestRejected carrying the queue depth."""
    cfg, variables = _setup()
    (prompt,) = _prompts((4,))
    eng = _engine(variables, cfg, capacity=1, prefill_chunk=8, max_queue=2)
    eng.submit(Request(prompt, 4))
    eng.step()  # occupy the slot
    eng.submit(Request(prompt, 4))
    eng.submit(Request(prompt, 4))  # queue now at max_queue=2
    with pytest.raises(RequestRejected) as ei:
        eng.submit(Request(prompt, 4))
    assert ei.value.queue_depth == 2
    assert ei.value.max_queue == 2
    assert "queue depth 2/2" in str(ei.value)
    assert eng.metrics.summary()["n_rejected"] == 1
    eng.run()


def test_submit_validates_slot_capacity():
    cfg, variables = _setup()
    (prompt,) = _prompts((40,))
    eng = _engine(variables, cfg, capacity=1, prefill_chunk=8)
    big = Request(prompt, MAX_LEN)
    with pytest.raises(ValueError, match="cache positions"):
        eng.submit(big)
    # refusal paths agree: a request the engine will never run is
    # terminal AND counted, same as the RequestRejected backpressure
    # path — a caller polling req.done must not wait on a phantom, and
    # a dashboard must see every refusal
    assert big.state == "rejected" and big.done
    assert eng.metrics.summary()["n_rejected"] == 1
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt, 0)
    # a chunk window that could cross the cache end is refused up front
    # (an overrunning dynamic_update_slice start would CLAMP, silently
    # corrupting near-max_len prompts)
    with pytest.raises(ValueError, match="divide max_len"):
        _engine(variables, cfg, capacity=1, prefill_chunk=32)


def test_prompt_filling_the_slot_is_exact():
    """Boundary regression: a prompt whose final prefill chunk ends
    exactly at the cache end (prompt + budget == max_len) stays
    token-exact — no chunk window crosses max_len."""
    cfg, variables = _setup()
    (prompt,) = _prompts((MAX_LEN - 6,), seed=12)  # 42 tokens, 6 budget
    eng = _engine(variables, cfg, capacity=1, prefill_chunk=8)
    r = eng.submit(Request(prompt, 6))
    eng.run()
    np.testing.assert_array_equal(
        r.output(), _one_shot(variables, cfg, prompt, 6))


def test_quantized_interop_matches_one_shot():
    """int8 weights + int8 K/V slots serve through the engine and match
    the equally-quantized one-shot path (models/quant.py interop)."""
    from bluefog_tpu.models.quant import quantize_llama_params

    cfg, variables = _setup()
    qvars = quantize_llama_params(variables)
    prompts = _prompts((5, 7), seed=6)
    eng = _engine(qvars, cfg, kv_quant="int8", weight_quant="int8")
    reqs = [eng.submit(Request(p, 5)) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        want = _one_shot(qvars, cfg, p, 5, kv_quant="int8",
                         weight_quant="int8")
        np.testing.assert_array_equal(r.output(), want)
    with pytest.raises(ValueError, match="quantize_llama_params"):
        ServingEngine(variables, cfg, capacity=1, max_len=MAX_LEN,
                      weight_quant="int8")


def test_kv_pool_alloc_free():
    cfg, _ = _setup()
    pool = SlotPool(cfg, capacity=3, max_len=16)
    slots = [pool.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert pool.alloc() is None and pool.n_free == 0
    assert pool.occupancy() == 1.0
    pool.free(slots[1])
    assert pool.n_free == 1
    assert pool.alloc() == slots[1]  # freed slot comes back
    pool.free(slots[0])
    with pytest.raises(ValueError, match="not allocated"):
        pool.free(slots[0])  # double free


def test_scheduler_fifo_and_expiry():
    class R:
        def __init__(self, deadline=None):
            self.deadline = deadline

    s = FifoScheduler(max_queue=3)
    a, b, c = R(), R(deadline=1.0), R()
    for r in (a, b, c):
        s.submit(r)
    with pytest.raises(RequestRejected):
        s.submit(R())
    assert s.admit(now=2.0) is a      # FIFO
    assert s.admit(now=2.0) is c      # b expired (deadline 1.0 < 2.0)
    assert s.admit(now=2.0) is None


def test_metrics_and_timeline_spans(tmp_path):
    """TTFT/latency/occupancy land in the summary, and request
    lifecycle spans (admission -> prefill -> decode -> retire) reach the
    chrome://tracing file through the existing timeline writer."""
    from bluefog_tpu import timeline

    cfg, variables = _setup()
    clock = VirtualClock()
    path = str(tmp_path / "serve_tl")
    timeline.start_timeline(path)
    try:
        eng = _engine(variables, cfg, clock=clock)
        reqs = [eng.submit(Request(p, 4))
                for p in _prompts((5, 6), seed=8)]
        while eng.step():
            clock.advance(0.25)
    finally:
        timeline.stop_timeline()
    m = eng.metrics.summary()
    assert m["n_finished"] == 2
    assert m["tokens_generated"] == 8
    assert m["tokens_per_sec"] > 0
    assert 0 < m["ttft_p50"] <= m["ttft_p99"]
    assert 0 < m["latency_p50"] <= m["latency_p99"]
    assert 0 < m["mean_slot_occupancy"] <= 1.0
    events = json.load(open(path + "0.json"))
    names = {e.get("name") for e in events}
    for phase in ("admission", "prefill", "decode", "retire"):
        assert phase in names, (phase, names)
    tracks = {e.get("tid") for e in events}
    for r in reqs:
        assert f"request.{r.rid}" in tracks


def _engine_spans(events):
    """``[(name, begin_us, end_us, args)]`` of the ``engine`` track's
    spans, from the tracer's B/E events, in the order they began."""
    out, stack = [], []
    for phase, name, track, ts, args in events:
        if track != "engine":
            continue
        if phase == "B":
            stack.append(len(out))
            out.append([name, ts, None, args])
        elif phase == "E":
            out[stack.pop()][2] = ts
    assert not stack
    return out


PHASE_ORDER = ["admit", "prefill_chunk", "decode_inputs",
               "decode_dispatch", "token_fetch", "emit"]
#: what a step holds of the decode path: the next program's dispatch,
#: then the read of the one the step before dispatched; the first step
#: of a busy stretch has nothing to read, the last nothing to dispatch
DECODE_PHASES = ([], PHASE_ORDER[2:4], PHASE_ORDER[2:], PHASE_ORDER[4:])


@pytest.mark.parametrize("budget", [1, 2])
def test_every_engine_step_holds_its_phases_in_order(budget):
    """One ``step`` span a call on the ``engine`` track, its phases
    inside it in the order of the work, each at most once a step with
    the default prefill budget (a larger budget may admit again after a
    prefill that ended inside the step), durations summing to no more
    than the step's; ``prefill_chunk`` names the request it worked
    for.  A step dispatches the next decode program and THEN reads the
    one before: ``token_fetch`` carries the ``launch=`` of the
    ``decode_dispatch`` it waits for, which lies in the step before."""
    from bluefog_tpu import observe

    cfg, variables = _setup()
    eng = _engine(variables, cfg, prefill_budget=budget)
    tracer = observe.get_tracer()
    tracer.clear()
    reqs = [Request(p, b) for p, b in
            zip(_prompts((11, 2, 7, 1), seed=12), (4, 6, 2, 3))]
    eng.submit(reqs[0])
    eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    n_steps = 1
    while eng.step():
        n_steps += 1
    n_steps += 1
    spans = _engine_spans(tracer.events())
    steps = [sp for sp in spans if sp[0] == "step"]
    assert len(steps) == n_steps
    chunk_rids, emitted, admitted = set(), 0, 0
    dispatched, in_flight = [], None
    for i, (_, s0, s1, _) in enumerate(steps):
        nxt = steps[i + 1][1] if i + 1 < len(steps) else float("inf")
        held = [sp for sp in spans
                if sp[0] != "step" and s0 <= sp[1] < nxt]
        # the two parts of token_fetch lie inside it (PR 34) and are
        # no phases of the step
        parts = [sp for sp in held if sp[0] in ("device_wait", "host_copy")]
        held = [sp for sp in held if sp not in parts]
        fetch = [sp for sp in held if sp[0] == "token_fetch"]
        assert [sp[0] for sp in parts] == ["device_wait",
                                           "host_copy"] * len(fetch)
        assert all(fetch[0][1] <= sp[1] <= sp[2] <= fetch[0][2]
                   for sp in parts)
        assert all(s0 <= sp[1] <= sp[2] <= s1 for sp in held)
        assert all(a[2] <= b[1] for a, b in zip(held, held[1:]))
        names = [sp[0] for sp in held]
        assert names[0] == "admit"
        decode = [n for n in names if n not in ("admit", "prefill_chunk")]
        assert decode in DECODE_PHASES
        # a read exactly where a program was in flight at the step's start
        assert ("token_fetch" in decode) == (in_flight is not None)
        if "token_fetch" in decode:
            assert fetch[0][3]["launch"] == in_flight
        in_flight = None
        before = names[:len(names) - len(decode)]
        assert set(before) <= {"admit", "prefill_chunk"}
        assert before.count("prefill_chunk") <= budget
        if budget == 1:
            assert before in (["admit"], ["admit", "prefill_chunk"])
        assert sum(sp[2] - sp[1] for sp in held) <= s1 - s0
        for name, _, _, args in held:
            if name == "prefill_chunk":
                chunk_rids.add(args["rid"])
                assert 1 <= args["tokens"] <= 4 and args["slot"] in (0, 1)
            elif name == "emit":
                emitted += args["tokens"]
            elif name == "admit":
                admitted += args["admitted"]
            elif name == "decode_inputs":
                assert 1 <= args["slots"] <= 2
            elif name == "decode_dispatch":
                in_flight = args["launch"]
                dispatched.append(in_flight)
    assert in_flight is None and len(dispatched) == len(
        [sp for sp in spans if sp[0] == "emit"])
    assert chunk_rids == {r.rid for r in reqs if r.prompt.size > 1}
    assert emitted == sum(len(r.tokens) for r in reqs) == 15
    assert admitted == len(reqs)


def test_engine_counters_equal_what_the_requests_imply():
    """The counters at the phase boundaries: decode slots summed over
    decode steps are the tokens generated (horizon 1), valid prefill
    positions are every prompt less its last token, chunks are their
    ceilings, and every admitted request gave one queue-wait sample
    (``admit_t - submit_t`` on the engine's clock)."""
    from bluefog_tpu.observe import MetricsRegistry

    cfg, variables = _setup()
    clock = VirtualClock()
    reg = MetricsRegistry()
    eng = _engine(variables, cfg, clock=clock, registry=reg)
    sizes, budgets = (11, 2, 7, 1, 6), (4, 6, 2, 3, 5)
    reqs = [eng.submit(Request(p, b))
            for p, b in zip(_prompts(sizes, seed=13), budgets)]
    n_steps = 1
    while eng.step():
        clock.advance(0.5)
        n_steps += 1
    snap = reg.snapshot()

    def value(name):
        return snap[name][0]["value"]

    assert all(r.state == "completed" for r in reqs)
    assert value("bf_serving_decode_slots_total") == sum(budgets)
    steps = value("bf_serving_decode_steps_total")
    assert 0 < steps <= n_steps
    # two slots, so between one and two tokens a decode step
    assert sum(budgets) / 2 <= steps <= sum(budgets)
    assert value("bf_serving_prefill_tokens_total") == \
        sum(n - 1 for n in sizes)
    assert value("bf_serving_prefill_chunks_total") == \
        sum(-(-(n - 1) // 4) for n in sizes)
    assert value("bf_serving_steps_total") == n_steps
    waits = snap["bf_serving_queue_wait_seconds"][0]
    assert waits["count"] == len(reqs)
    # the first finds a free slot at once; the rest wait for a slot or
    # for the one prefill that runs at a time
    recs = eng.metrics._req.values()
    assert sorted(reg.histogram(
        "bf_serving_queue_wait_seconds").window_values) == sorted(
            r.admit_t - r.submit_t for r in recs)
    assert min(r.admit_t - r.submit_t for r in recs) == 0.0
    assert waits["sum"] > 0
    # the step's wall time is the step span's two stamps: one sample a
    # step, each a real (positive) duration although the engine's own
    # clock is virtual
    wall = reg.histogram("bf_step_wall_seconds", loop="serving")
    assert wall.count == n_steps
    assert all(0 < v < 60 for v in wall.window_values)


def test_compile_listener_counts_one_backend_compile_a_function():
    """``observe.compiles``: a fresh function's first call is one
    backend compile, with seconds in every stage's counter and one
    ``compile.<fun_name>`` instant; a second call is none."""
    from bluefog_tpu import observe

    reg = observe.get_registry()

    def count(name, **labels):
        for got, _, _, got_labels, m in reg.collect():
            if got == name and got_labels == labels:
                return m.value
        return 0.0

    @jax.jit
    def a_function_no_test_has_compiled(x):
        return jnp.tanh(x) * 3.0 + jnp.sum(x)

    x = jnp.arange(7.0)     # (made before counting: arange compiles too)
    jax.block_until_ready(x)
    tracer = observe.get_tracer()
    tracer.clear()
    compiles = count("bf_compiles_total")
    stages = {s: count("bf_compile_seconds_total", stage=s)
              for s in ("trace", "lower", "backend")}
    jax.block_until_ready(a_function_no_test_has_compiled(x))
    assert count("bf_compiles_total") == compiles + 1
    after = {s: count("bf_compile_seconds_total", stage=s)
             for s in stages}
    assert all(after[s] > stages[s] for s in stages)
    instants = [e for e in tracer.events()
                if e[0] == "i" and e[2] == "compile"]
    assert len(instants) == 1
    assert "a_function_no_test_has_compiled" in instants[0][1]
    jax.block_until_ready(a_function_no_test_has_compiled(x))
    assert count("bf_compiles_total") == compiles + 1
    assert {s: count("bf_compile_seconds_total", stage=s)
            for s in stages} == after


def test_no_recompiles_across_arrival_patterns():
    """The continuous-batching invariant: serving different prompts,
    lengths, budgets, and arrival orders reuses the SAME compiled
    programs — shapes depend only on (capacity, max_len, chunk)."""
    from bluefog_tpu.serving.engine import (_decode_step_prog,
                                            _prefill_chunk_prog)

    cfg, variables = _setup()
    eng = _engine(variables, cfg)
    reqs = [eng.submit(Request(p, 3)) for p in _prompts((5, 9), seed=9)]
    eng.run()
    pre = _prefill_chunk_prog._cache_size()
    dec = _decode_step_prog._cache_size()
    reqs = [Request(p, b) for p, b in
            zip(_prompts((11, 2, 7), seed=10), (4, 6, 2))]
    eng.submit(reqs[0])
    eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    eng.run()
    assert _prefill_chunk_prog._cache_size() == pre
    assert _decode_step_prog._cache_size() == dec
    assert all(r.state == "completed" for r in reqs)


def test_poisson_arrival_trace_is_deterministic():
    from bluefog_tpu.benchutil import poisson_arrivals

    a = poisson_arrivals(2.0, 16, seed=3)
    b = poisson_arrivals(2.0, 16, seed=3)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (16,) and a[0] == 0.0
    assert np.all(np.diff(a) >= 0)
    assert not np.array_equal(a, poisson_arrivals(2.0, 16, seed=4))
    with pytest.raises(ValueError, match="rate"):
        poisson_arrivals(0.0, 4)


@pytest.mark.slow
def test_serving_bench_smoke(tmp_path):
    """The Poisson-load bench runs end to end and reports both engines
    (slow: out of tier-1 — the bench measures wall time)."""
    import subprocess
    import sys
    import os

    out = str(tmp_path / "bench.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "serving_bench.py"),
         "--num-requests", "6", "--rate", "4", "--capacity", "2",
         "--max-len", "48", "--prompt-len", "3", "8",
         "--new-tokens", "2", "6", "--dim", "64", "--layers", "2",
         "--prefill-chunk", "4", "--out", out, "--compare", ""],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.load(open(out))
    for side in ("continuous", "static"):
        assert rec[side]["tokens_per_sec"] > 0
        assert rec[side]["ttft_p99"] >= rec[side]["ttft_p50"] >= 0


# ------------------------------------------------------------------ #
# a model with a STATE leaf (serving/protocol.py): recurrent layers
# beside a latent one, through the same engine
# ------------------------------------------------------------------ #
STATE_LEN = MAX_LEN      # the file's one engine shape (``_engine``)


@functools.cache
def _state_model(seed=3):
    """A tiny decoder of two recurrent layers around a latent one
    (``models/mla_moe.py`` with ``layer_types``), float32; its weights
    drawn by one program, once."""
    from bluefog_tpu.models import mla_moe

    cfg = mla_moe.MlaMoeConfig(
        vocab_size=64, dim=32, n_layers=3, n_heads=2, q_lora_rank=None,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, kda_head_dim=8, head_gate=True,
        layer_types=("kda", "latent", "kda"), n_experts=8, top_k=2,
        n_group=2, topk_group=1, score_func="sigmoid",
        expert_hidden_dim=16, initializer_range=0.3, dtype=jnp.float32,
        key_block=8)
    variables = jax.jit(mla_moe.MlaMoe(cfg).init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 4), jnp.int32))
    return cfg, {"params": variables["params"]}


_state_prompts = functools.partial(_prompts, vocab=64)


def _alone(variables, cfg, prompt, budget, **kw):
    """The request's stream from an engine nothing else has touched."""
    eng = _engine(variables, cfg, capacity=1, **kw)
    req = eng.submit(Request(prompt, budget))
    eng.run()
    assert req.state == "completed"
    return list(req.tokens)


def _state_leaves(eng, slot):
    from bluefog_tpu.serving import protocol

    return [np.asarray(leaf[slot]) for path, leaf in
            jax.tree_util.tree_flatten_with_path(eng.pool.cache)[0]
            if protocol.leaf_kind(path) == protocol.STATE]


@pytest.mark.parametrize("zero_on_free", [False, True])
def test_a_state_leaf_of_a_slot_freed_and_taken_again(zero_on_free):
    """Three requests through ONE slot: a freed slot's index is reset
    and its state left as it was (unless the pool zeroes), and the next
    request, whose first call is at index 0, starts from nothing."""
    cfg, variables = _state_model()
    prompts = _state_prompts((9, 1, 14), seed=2)
    budgets = (5, 6, 4)
    eng = _engine(variables, cfg, capacity=1, zero_on_free=zero_on_free)
    assert len(eng.pool.state_leaves) == 4       # two leaves a kda layer
    reqs = [eng.submit(Request(p, b)) for p, b in zip(prompts, budgets)]
    eng.run()
    # a single-token prompt decodes at index 0: no prefill wiped the slot
    for r, p, b in zip(reqs, prompts, budgets):
        assert list(r.tokens) == _alone(variables, cfg, p, b)
    # the last request's state is still in the freed slot, unless the
    # pool zeroes: nothing but the index was reset
    dirty = any(np.abs(leaf).max() > 0 for leaf in _state_leaves(eng, 0))
    assert dirty is not zero_on_free


@pytest.mark.parametrize("chunk", [1, 6, 8])
def test_a_padded_chunk_tail_leaves_a_state_leaf_alone(chunk):
    """Prompts whose last chunk is part-full (and one that fills its
    chunks): the stream is the stream of chunks of 4, whatever the
    chunk; the padded tail decayed nothing and shifted no input of the
    convolution."""
    cfg, variables = _state_model()
    prompts = _state_prompts((7, 17, 12), seed=4)
    eng = _engine(variables, cfg, prefill_chunk=chunk)
    reqs = [eng.submit(Request(p, 6)) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        assert list(r.tokens) == _alone(variables, cfg, p, 6)


def test_a_slot_that_sits_a_decode_step_out_keeps_its_state():
    """A request prefills over several steps while the slot beside it
    decodes (its own slot computes in every one of those decode
    programs, not live), and a free slot sits beside a decoding one:
    both streams are those of an engine to themselves."""
    cfg, variables = _state_model()
    prompts = _state_prompts((3, 22), seed=6)
    eng = _engine(variables, cfg)
    r0, r1 = Request(prompts[0], 16), Request(prompts[1], 5)
    mid = []

    def watch(k):
        if r1.state == "prefill" and 0 < r1._prefill_pos < 21:
            mid.append(k)

    _drive(eng, {0: [r0], 3: [r1]}, on_step=watch)
    assert len(mid) >= 3        # decode programs ran over the half-filled slot
    assert list(r0.tokens) == _alone(variables, cfg, prompts[0], 16)
    assert list(r1.tokens) == _alone(variables, cfg, prompts[1], 5)


def test_an_overrun_step_on_a_state_leaf_is_never_observed():
    """An EOS learnt one program late: the slot ran once more and its
    state took a token nobody asked for; the request that takes the
    slot starts at index 0 and its stream is a fresh engine's."""
    cfg, variables = _state_model()
    prompts = _state_prompts((5, 8, 6), seed=8)
    free_run = _alone(variables, cfg, prompts[0], 12)
    eos = free_run[4]
    stop = free_run.index(eos)
    eng = _engine(variables, cfg)
    r0 = Request(prompts[0], 12, eos_id=eos)
    r1, r2 = Request(prompts[1], 14), Request(prompts[2], 7)
    _drive(eng, {0: [r0, r1], 2: [r2]})
    assert eng.metrics.n_decode_overrun_slots >= 1
    assert list(r0.tokens) == free_run[:stop + 1]
    assert list(r1.tokens) == _alone(variables, cfg, prompts[1], 14)
    assert list(r2.tokens) == _alone(variables, cfg, prompts[2], 7)


def test_a_prefix_cache_and_the_speculative_step_refuse_a_state_leaf():
    from bluefog_tpu.serving import SpeculativeConfig
    from bluefog_tpu.serving.prefix_cache import PrefixCache, seq_axes

    cfg, variables = _state_model()
    with pytest.raises(ValueError, match=r"state_conv.*recurrent state"):
        seq_axes(cfg, STATE_LEN)
    with pytest.raises(ValueError, match="state leaf"):
        _engine(variables, cfg, capacity=1, prefix_cache=True)
    with pytest.raises(ValueError, match="state leaf"):
        SlotPool(cfg, 1, STATE_LEN, prefix=PrefixCache(4, 1 << 20))
    with pytest.raises(ValueError,
                       match=r"state_conv.*does not roll back"):
        _engine(variables, cfg, capacity=1, speculative=SpeculativeConfig(
            variables=variables, cfg=cfg, lookahead=2))
    # a draft with a state leaf under a target without one is refused too
    dense, dense_vars = _setup(vocab_size=64)
    with pytest.raises(ValueError, match="does not roll back"):
        _engine(dense_vars, dense, capacity=1, speculative=SpeculativeConfig(
            variables=variables, cfg=cfg, lookahead=2))


def test_the_pool_and_the_counters_report_the_state():
    from bluefog_tpu.observe import MetricsRegistry

    cfg, variables = _state_model()
    reg = MetricsRegistry()
    eng = _engine(variables, cfg, capacity=3, registry=reg)
    # a kda layer a slot: 2 heads x 8 x 8 float32 and 3 x 48 inputs
    per_slot = 2 * (2 * 8 * 8 * 4 + 3 * 48 * 4)
    assert eng.pool.cache_bytes() == {
        "state": 3 * per_slot, "full": 3 * STATE_LEN * 12 * 4}
    assert reg.gauge("bf_serving_state_bytes_per_slot", "").value == per_slot
    assert reg.gauge("bf_serving_cache_bytes", "", kind="state").value \
        == 3 * per_slot
    assert (reg.gauge("bf_moe_groups", "").value,
            reg.gauge("bf_moe_groups_kept", "").value) == (2, 1)
    lengths, budgets = (9, 5, 1), (4, 6, 3)
    for p, b in zip(_state_prompts(lengths, seed=1), budgets):
        eng.submit(Request(p, b))
    eng.run()
    # every prompt token but the last is prefilled, every served token
    # comes from a decode step; two recurrent layers
    assert eng.cfg.state_layers == 2 and eng.cfg.latent_layers == 1
    assert reg.counter("bf_serving_state_chunk_tokens_total", "").value \
        == 2 * sum(n - 1 for n in lengths)
    assert reg.counter("bf_serving_state_steps_total", "").value \
        == 2 * sum(budgets)
    # the XLA step reads the state of every slot of the pool, whoever
    # decodes: three slots x two layers a decode program
    assert reg.counter("bf_serving_state_streamed_steps_total", "").value \
        == 2 * 3 * reg.counter("bf_serving_decode_steps_total", "").value
    assert eng.cfg.cache_kinds() == {"full": (1, None)}
    # a model without a state leaf sets and counts none of it
    plain = MetricsRegistry()
    dense, dense_vars = _setup()
    eng = _engine(dense_vars, dense, capacity=1, registry=plain)
    eng.submit(Request(_prompts((6,))[0], 2))
    eng.run()
    assert not any("state" in name or "groups" in name
                   for name, *_ in plain.collect())
