"""Serving-side fault tolerance (ISSUE 14): replica chaos, token-exact
failover, and graceful drain.

Contracts under test:

* **Deterministic serving fault plans** — ``ServingFaultPlan`` follows
  the training-side ``FaultPlan`` semantics over replicas and engine
  steps (death permanent, stall/reject windowed, merged plans sorted).
* **Token-exact failover** — killing a replica mid-run and resubmitting
  its stranded requests (mid-prefill, mid-decode, and queued) through
  :func:`failover_stranded` yields outputs BIT-EQUAL to a fault-free
  run, greedy and sampled alike: the survivor re-prefills
  ``prompt ‖ tokens`` (prompt chunks restore from the shared prefix
  cache) and its decode continues the per-request rng fold chain.
* **Failure-aware router** — the staleness guard excises a replica
  whose step heartbeat went stale and re-admits it the moment it steps
  again; explicit dead-masks behave the same; retries absorb transient
  rejection windows through seeded backoff; ``FleetSaturated`` carries
  per-replica ``causes``.
* **Graceful drain** — admission stops, queued requests get terminal
  outcomes, residents (mixed prefill/decode) either finish in place or
  hand off with their written K/V flushed to the prefix cache.
* **Zero recompiles** — every fault pattern is host-side control flow:
  the resident jit cache sizes never move.
"""

import numpy as np
import pytest

from bluefog_tpu.observe.registry import MetricsRegistry
from bluefog_tpu.resilience import ServingFault, ServingFaultPlan
from bluefog_tpu.resilience.faults import (REPLICA_DEATH, REPLICA_STALL,
                                           SUBMIT_REJECT)
from bluefog_tpu.serving import (FaultyReplica, FleetRouter,
                                 FleetSaturated, PrefixCache, Request,
                                 RequestRejected, ServingEngine,
                                 backoff_sleep, failover_stranded,
                                 seeded_backoff)
from bluefog_tpu.serving.engine import (_decode_step_prog,
                                        _prefill_chunk_prog)
from served_model import VirtualClock as _Clock, tiny_llama as _setup

pytestmark = pytest.mark.chaos_serving

MAX_LEN = 48


def _engine(variables, cfg, clock, prefix=None, **kw):
    kw.setdefault("capacity", 2)
    kw.setdefault("max_queue", 8)
    kw.setdefault("prefill_chunk", 4)
    return ServingEngine(variables, cfg, max_len=MAX_LEN, clock=clock,
                         registry=MetricsRegistry(),
                         prefix_cache=(prefix if prefix is not None
                                       else False), **kw)


def _requests(rs, n=3, prompt_len=(6, 14), max_new=6):
    """A deterministic request family with mixed temperatures — the
    sampled ones prove failover continues the rng fold chain, not just
    the greedy argmax."""
    reqs = []
    for i in range(n):
        plen = int(rs.randint(*prompt_len))
        prompt = rs.randint(0, 256, (plen,)).astype(np.int32)
        reqs.append(Request(prompt, max_new, temperature=(0.0, 0.9)[i % 2],
                            seed=100 + i))
    return reqs


def _clone(req):
    r = Request(req.prompt.copy(), req.max_new_tokens, eos_id=req.eos_id,
                temperature=req.temperature, seed=req.seed)
    return r


# --------------------------------------------------------------------- #
# ServingFaultPlan semantics
# --------------------------------------------------------------------- #
def test_serving_fault_plan_semantics():
    with pytest.raises(ValueError):
        ServingFault(0, 0, "nan")          # training kinds don't leak in
    with pytest.raises(ValueError):
        ServingFault(-1, 0, REPLICA_DEATH)
    with pytest.raises(ValueError):
        ServingFaultPlan(2, [ServingFault(0, 2, REPLICA_DEATH)])

    plan = ServingFaultPlan.replica_death(3, 1, step=5).merged(
        ServingFaultPlan.replica_stall(3, 2, step=2, duration=3,
                                       stall_seconds=0.5)).merged(
        ServingFaultPlan.submit_rejection(3, 0, step=4, duration=2))
    # death is permanent from onset
    assert not plan.is_dead(1, 4)
    assert plan.is_dead(1, 5) and plan.is_dead(1, 500)
    assert plan.dead_replicas(5) == [1] and plan.dead_replicas(0) == []
    # stall is windowed and per-replica
    assert plan.stall_seconds(2, 1) == 0.0
    assert plan.stall_seconds(2, 2) == 0.5
    assert plan.stall_seconds(2, 4) == 0.5
    assert plan.stall_seconds(2, 5) == 0.0
    assert plan.stall_seconds(0, 3) == 0.0
    # submit rejection is windowed
    assert not plan.rejects_submit(0, 3)
    assert plan.rejects_submit(0, 4) and plan.rejects_submit(0, 5)
    assert not plan.rejects_submit(0, 6)
    assert plan.last_onset() == 5
    # faults sorted by (step, replica), healthy plan empty
    assert [f.step for f in plan.faults] == [2, 4, 5]
    assert ServingFaultPlan.healthy(4).active(10) == []
    with pytest.raises(ValueError):
        plan.merged(ServingFaultPlan.healthy(2))


def test_seeded_backoff_deterministic_and_bounded():
    a = [seeded_backoff(k, base=0.05, cap=1.0, seed=7, salt=3)
         for k in range(8)]
    b = [seeded_backoff(k, base=0.05, cap=1.0, seed=7, salt=3)
         for k in range(8)]
    assert a == b                           # replayable
    assert a != [seeded_backoff(k, base=0.05, cap=1.0, seed=7, salt=4)
                 for k in range(8)]         # salt decorrelates requests
    assert all(0.0 < d <= 1.0 for d in a)   # capped
    assert a[3] > a[0]                      # grows before the cap bites
    slept = []
    d = backoff_sleep(2, base=0.05, seed=7, salt=3, sleep=slept.append)
    assert slept == [d] == [seeded_backoff(2, base=0.05, seed=7, salt=3)]


# --------------------------------------------------------------------- #
# FaultyReplica injection
# --------------------------------------------------------------------- #
def test_faulty_replica_death_stall_and_reject():
    cfg, variables = _setup()
    clock = _Clock()
    eng = _engine(variables, cfg, clock)
    plan = ServingFaultPlan.replica_death(2, 0, step=2).merged(
        ServingFaultPlan.replica_stall(2, 0, step=1, duration=1,
                                       stall_seconds=0.25)).merged(
        ServingFaultPlan.submit_rejection(2, 0, step=1, duration=1))
    slept = []
    rep = FaultyReplica(eng, plan, 0, sleep=slept.append)
    rep.submit(Request(np.arange(5, dtype=np.int32), 3))  # step 0: fine
    assert rep.step() is True and rep.steps == 1
    with pytest.raises(RequestRejected):                  # reject window
        rep.submit(Request(np.arange(5, dtype=np.int32), 3))
    assert rep.step() is True                             # stalled step
    assert slept == [0.25]
    # step counter is at the death onset: the replica never steps again
    assert rep.step() is False and rep.dead
    assert rep.step() is False                            # latched
    with pytest.raises(RequestRejected):
        rep.submit(Request(np.arange(5, dtype=np.int32), 3))
    # attribute passthrough: the wrapper quacks like its engine
    assert rep.metrics is eng.metrics and rep.pool is eng.pool
    with pytest.raises(ValueError):
        FaultyReplica(eng, plan, 2)


# --------------------------------------------------------------------- #
# token-exact failover on replica death
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("steps", [6, 7])
def test_failover_is_token_exact_and_zero_recompile(steps):
    """Kill a replica holding a mid-decode request (with emitted
    tokens), a mid-prefill request (no tokens yet), and a queued one;
    fail everything over to a survivor sharing the prefix cache.  Every
    output must be bit-equal to a fault-free run, and the resident jit
    caches must not grow across the whole exercise.  The replica dies
    with a decode program in flight: its requests leave with that
    program's tokens, once."""
    cfg, variables = _setup()
    rs = np.random.RandomState(11)
    reqs = _requests(rs, n=3, prompt_len=(9, 14))
    # fault-free reference on a plain engine
    ref_eng = _engine(variables, cfg, _Clock())
    ref = []
    for r in [_clone(r) for r in reqs]:
        ref_eng.submit(r)
        ref.append(r)
    ref_eng.run()
    ref_out = [r.output().copy() for r in ref]

    prefix = PrefixCache(4, 1 << 24)
    clock = _Clock()
    e0 = _engine(variables, cfg, clock, prefix=prefix)
    e1 = _engine(variables, cfg, clock, prefix=prefix)
    n_prefill0 = _prefill_chunk_prog._cache_size()
    n_decode0 = _decode_step_prog._cache_size()
    live = [e0.submit(_clone(r)) for r in reqs]
    # step until the first resident has emitted tokens but nobody is
    # done — capacity 2 keeps the third request queued
    for _ in range(steps):
        clock.advance(0.01)
        e0.step()
    assert any(r.tokens and not r.done for r in live)
    assert any(r.state == "queued" for r in live)
    in_flight = {r.rid for r in e0._flight.decoding.values()}
    assert in_flight
    pre_counts = {r.rid: len(r.tokens) + (r.rid in in_flight) for r in live}
    moved, expired = failover_stranded(e0, e1.submit)
    assert expired == [] and e0._flight is None
    assert sorted(r.rid for r in moved) == sorted(r.rid for r in live)
    assert e0.metrics.summary()["n_failovers"] == 3
    # tokens survived the move, the program's in flight with them;
    # nothing was re-emitted or lost
    for r in live:
        assert len(r.tokens) == pre_counts[r.rid]
        assert r.state == "queued" and r.slot is None
    while e1.step():
        clock.advance(0.01)
    for r, want in zip(live, ref_out):
        assert r.state == "completed"
        np.testing.assert_array_equal(r.output(), want)
    # the resumed decode REPLAYED nothing: prompt chunks restored from
    # the cache the original prefill stashed into
    assert e1.metrics.summary()["prefix_chunks_restored"] > 0
    # zero-recompile contract: death + failover are host-side only
    assert _prefill_chunk_prog._cache_size() == n_prefill0
    assert _decode_step_prog._cache_size() == n_decode0


def test_expired_on_dead_replica_retires_with_metrics():
    """A request whose deadline passed while its replica was dead gets
    a terminal ``expired`` record — not a silent strand (the satellite
    guarantee), and the failover resubmit never sees it."""
    cfg, variables = _setup()
    clock = _Clock()
    eng = _engine(variables, cfg, clock)
    ok = eng.submit(Request(np.arange(6, dtype=np.int32), 4))
    late = eng.submit(Request(np.arange(7, dtype=np.int32), 4,
                              deadline=1.0))
    for _ in range(2):
        eng.step()
    assert not ok.done and not late.done
    clock.advance(5.0)           # the replica is "dead" while time runs
    resubmitted = []
    moved, expired = failover_stranded(eng, resubmitted.append)
    assert [r.rid for r in moved] == [ok.rid]
    assert [r.rid for r in expired] == [late.rid]
    assert late.state == "expired" and late.done and late.slot is None
    assert [r.rid for r in resubmitted] == [ok.rid]
    m = eng.metrics.summary()
    assert m["outcomes"].get("expired") == 1
    assert m["outcomes"].get("failover") == 1
    assert m["n_failovers"] == 1


# --------------------------------------------------------------------- #
# failure-aware router: staleness, re-admission, retries, causes
# --------------------------------------------------------------------- #
def _fleet(variables, cfg, clock, n=2, prefix=None, **router_kw):
    engines = [_engine(variables, cfg, clock, prefix=prefix,
                       max_queue=2) for _ in range(n)]
    regs = [e.metrics._registry for e in engines]
    return engines, FleetRouter(engines, registries=regs, clock=clock,
                                **router_kw)


def test_staleness_guard_excises_and_readmits():
    cfg, variables = _setup()
    clock = _Clock()
    engines, router = _fleet(variables, cfg, clock, n=3, stale_after=1.0)
    # nobody has stepped: everyone cold, nobody suspect, all routable
    snap = router.poll()
    assert snap.suspect == (False, False, False)
    assert snap.ages == (-1.0, -1.0, -1.0)
    assert snap.as_dict()["ages"] == [-1.0, -1.0, -1.0]
    for e in engines:
        e.step()                 # heartbeat at t=0 everywhere
    clock.advance(0.5)
    engines[0].step()
    engines[1].step()            # replica 2 stops stepping (dead host)
    clock.advance(0.8)           # replica 2's heartbeat now 1.3s old
    snap = router.poll()
    assert snap.suspect == (False, False, True)
    assert snap.ages[2] == pytest.approx(1.3)
    assert not np.isfinite(snap.scores[2])
    assert 2 not in {router.submit(
        Request(np.arange(5, dtype=np.int32), 2), snapshot=snap)[0]}
    # the replica steps again -> re-admitted immediately
    engines[2].step()
    snap = router.poll()
    assert snap.suspect == (False, False, False)
    assert np.isfinite(snap.scores[2])
    # explicit dead-mask path: excised the same way, back when cleared
    snap = router.poll(dead_mask=[False, True, False])
    assert not np.isfinite(snap.scores[1])
    i, _ = router.submit(Request(np.arange(5, dtype=np.int32), 2),
                         snapshot=snap)
    assert i != 1
    snap = router.poll(dead_mask=[False, False, False])
    assert np.all(np.isfinite(snap.scores))
    assert 1 in snap.order


def test_fleet_saturated_carries_causes():
    cfg, variables = _setup()
    clock = _Clock()
    engines, router = _fleet(variables, cfg, clock, n=2)
    for _ in range(2):  # fill every replica's queue (max_queue=2)
        for e in engines:
            e.submit(Request(np.arange(5, dtype=np.int32), 2))
    with pytest.raises(FleetSaturated) as ei:
        router.submit(Request(np.arange(5, dtype=np.int32), 2))
    exc = ei.value
    assert exc.queue_depths == [2, 2]
    assert [i for i, _ in exc.causes] == [0, 1]  # walk order preserved
    assert all(isinstance(c, RequestRejected) for _, c in exc.causes)
    assert "queue full" in str(exc.causes[0][1])


def test_router_retries_absorb_transient_rejection():
    """A replica inside a submit_reject window refuses the first walk;
    with retries > 0 the router backs off (seeded, virtually slept),
    re-polls, and lands the request once the window passes — no
    FleetSaturated surfaces."""
    cfg, variables = _setup()
    clock = _Clock()
    slept = []
    reps = []

    def vsleep(dt):
        # virtual backoff sleep: time passes AND the replicas keep
        # stepping, which is what lets the per-step reject window lapse
        slept.append(dt)
        clock.advance(dt)
        for rep in reps:
            rep.step()

    engines, router = _fleet(variables, cfg, clock, n=2, retries=2,
                             retry_base_s=0.01, sleep=vsleep, seed=3)
    plan = ServingFaultPlan.submit_rejection(2, 0, step=0, duration=1) \
        .merged(ServingFaultPlan.submit_rejection(2, 1, step=0,
                                                  duration=1))
    reps[:] = [FaultyReplica(e, plan, i) for i, e in enumerate(engines)]
    router.engines = list(reps)  # route through the fault wrappers
    req = Request(np.arange(5, dtype=np.int32), 2)
    # both replicas reject at their step 0 — the first walk fails whole
    i, _ = router.submit(req)
    assert i in (0, 1) and slept  # succeeded only via a backoff retry
    assert slept[0] == seeded_backoff(0, base=0.01, seed=3, salt=req.rid)
    # with retries=0 (the default) the same double-rejection surfaces
    engines2, router2 = _fleet(variables, cfg, clock, n=2)
    plan2 = ServingFaultPlan.submit_rejection(2, 0, step=0, duration=9) \
        .merged(ServingFaultPlan.submit_rejection(2, 1, step=0,
                                                  duration=9))
    router2.engines = [FaultyReplica(e, plan2, i)
                       for i, e in enumerate(engines2)]
    with pytest.raises(FleetSaturated) as ei:
        router2.submit(Request(np.arange(5, dtype=np.int32), 2))
    assert len(ei.value.causes) == 2


def test_cooldown_demotes_but_never_saturates():
    cfg, variables = _setup()
    clock = _Clock()
    engines, router = _fleet(variables, cfg, clock, n=2,
                             cooldown_s=10.0, cooldown_after=1)
    # replica 0 permanently rejects submits; replica 1 healthy
    plan = ServingFaultPlan.submit_rejection(2, 0, step=0, duration=10 ** 6)
    router.engines = [FaultyReplica(engines[0], plan, 0), engines[1]]
    r1 = Request(np.arange(5, dtype=np.int32), 2)
    assert router.submit(r1)[0] == 1     # fell through to 1, 0 cooling
    assert router._cooldown_until[0] > clock()
    # while cooling, replica 0 is tried LAST but still tried
    snap = router.poll()
    assert router._walk(snap, clock())[-1] == 0
    assert router.submit(Request(np.arange(5, dtype=np.int32), 2))[0] == 1


# --------------------------------------------------------------------- #
# drain
# --------------------------------------------------------------------- #
def test_drain_completes_mixed_residents_in_place():
    """No handoff: a drain with one decoding resident (tokens emitted),
    one mid-prefill resident, and queued requests finishes the
    residents in place, rejects the queue, and refuses new submits."""
    cfg, variables = _setup()
    clock = _Clock()
    prefix = PrefixCache(4, 1 << 24)
    eng = _engine(variables, cfg, clock, prefix=prefix)
    rs = np.random.RandomState(4)
    a = eng.submit(Request(rs.randint(0, 256, (6,)).astype(np.int32), 4))
    b = eng.submit(Request(rs.randint(0, 256, (13,)).astype(np.int32), 4))
    c = eng.submit(Request(rs.randint(0, 256, (6,)).astype(np.int32), 4))
    for _ in range(3):
        eng.step()
    assert a.state == "decode" and a.tokens
    assert b.state == "prefill" and not b.done   # mid-prefill resident
    assert c.state == "queued"
    summary = eng.drain()
    assert a.state == "completed" and b.state == "completed"
    assert c.state == "rejected"
    assert summary["completed"] == 2
    assert summary["rejected_queue"] == 1
    assert summary["handed_off"] == 0
    assert summary["flushed_chunks"] > 0     # context K/V left behind
    assert len(prefix) >= summary["flushed_chunks"]
    with pytest.raises(RequestRejected, match="draining"):
        eng.submit(Request(np.arange(5, dtype=np.int32), 2))
    assert eng.metrics.summary()["outcomes"].get("rejected") == 1


@pytest.mark.parametrize("steps", [4, 5, 6, 7])
def test_drain_hands_off_token_exact(steps):
    """With a handoff target: mixed prefill/decode residents and the
    queue all migrate, and the drained replica's flushed K/V makes the
    target restore rather than recompute — outputs bit-equal to a
    fault-free run.  The drain finds a decode program in flight: it is
    read first, so no token is lost or doubled and the chunks flushed are
    the chunks written, the program's own position among them."""
    cfg, variables = _setup()
    rs = np.random.RandomState(21)
    reqs = _requests(rs, n=3, prompt_len=(9, 14))
    ref_eng = _engine(variables, cfg, _Clock())
    ref = [ref_eng.submit(_clone(r)) for r in reqs]
    ref_eng.run()
    ref_out = [r.output().copy() for r in ref]

    prefix = PrefixCache(4, 1 << 24)
    clock = _Clock()
    e0 = _engine(variables, cfg, clock, prefix=prefix)
    e1 = _engine(variables, cfg, clock, prefix=prefix)
    live = [e0.submit(_clone(r)) for r in reqs]
    for _ in range(steps):
        clock.advance(0.01)
        e0.step()
    assert any(r.tokens for r in live)
    in_flight = {r.rid for r in e0._flight.decoding.values()}
    assert in_flight
    pre_counts = {r.rid: len(r.tokens) + (r.rid in in_flight) for r in live}
    handed = []
    summary = e0.drain(handoff=lambda r: handed.append(r) or e1.submit(r))
    assert summary["handed_off"] == 3 and summary["completed"] == 0
    assert not e0._running and e0._admitting is None
    assert e0.scheduler.queue_depth == 0 and not e0.busy
    assert {r.rid: len(r.tokens) for r in live} == pre_counts
    for r in handed:
        if r.rid in in_flight:
            # every chunk the resident WROTE is flushed, the one the
            # program in flight completed among them (steps 5: the 13th
            # token's program wrote position 11, the third chunk's last)
            ctx = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
            assert all(k in prefix for k in prefix.chunk_keys(ctx))
    while e1.step():
        clock.advance(0.01)
    for r, want in zip(live, ref_out):
        assert r.state == "completed"
        np.testing.assert_array_equal(r.output(), want)
    # drain flushed beyond what plain prefill stashing already did:
    # decode-emitted context chunks land too
    assert e1.metrics.summary()["prefix_chunks_restored"] > 0
