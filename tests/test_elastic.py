"""Elastic membership (bluefog_tpu/elastic/): ranks that join, not
just die.

The acceptance properties of the elastic subsystem:

(a) growth is the EXACT inverse of healing: ``heal_weights`` ->
    ``grow_weights`` round-trips byte-equal to the pristine tables
    (and any partial growth equals a fresh heal of the remaining dead
    set, bitwise), row-stochastic at every intermediate step — a
    property test over random weighted schedules in rank and torus
    spaces (the PR-7 style);
(b) a joiner bootstraps by pulled neighbor averaging ONLY (self-weight
    annealed 0 -> pristine, live receivers keep zero weight on it), so
    a preempted rank re-enters the n=32 consensus floor (<= 1e-12)
    without a broadcast;
(c) the MembershipController's lifecycle (LIVE -> DEAD -> JOINING ->
    LIVE) renders as pure weight DATA in the unchanged comm-weight
    shapes, the FailureDetector readmits without latched suspicion,
    and the FleetAggregator heals AND re-grows from the controller;
(d) the full preempt -> heal -> rollback -> admit -> anneal -> promote
    cycle runs through ``run_resilient(elastic=...)`` with ZERO
    recompiles (asserted via the jitted cache size, the PR-3
    methodology).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from bluefog_tpu import resilience as R
from bluefog_tpu.checkpoint import Checkpointer
from bluefog_tpu.elastic import (
    DEAD,
    JOINING,
    LIVE,
    ElasticConfig,
    MembershipController,
    anneal_fraction,
    bootstrap_comm_weights,
    bootstrap_weights,
    disagreement,
    grow_spec,
    grow_weights,
    grown_comm_weights,
    sanitize_rank_rows,
    zero_rank_rows,
)
from bluefog_tpu.observe.fleet import FleetAggregator
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology import one_peer_dynamic_schedule
from bluefog_tpu.topology.compiler import (Candidate, CandidateRound,
                                           materialize)
from bluefog_tpu.topology.spec import self_weights_of

pytestmark = pytest.mark.elastic

N = 8


# ------------------------------------------------------------------ #
# acceptance (a): heal -> grow round-trips byte-equal (property test)
# ------------------------------------------------------------------ #
def test_heal_grow_round_trip_byte_equal_property():
    """Property: on random weighted circulant schedules (rank-space
    n=16 and (4, 4) torus-space, random shifts/self-weights), healing
    a random dead set and growing ANY subset back is byte-equal to a
    fresh heal of the remaining dead set — and growing everyone back
    is byte-equal to the pristine tables — with every intermediate
    mixing matrix row-stochastic.  Growth re-plans from the pristine
    spec instead of subtracting, which is the only way ``(a + w) - w``
    rounding residue never appears."""
    rng = np.random.default_rng(11)
    cases = []
    for _ in range(10):  # rank space, n = 16
        period = int(rng.integers(2, 5))
        rounds = tuple(
            CandidateRound(((None, int(rng.integers(1, 16))),),
                           float(rng.uniform(0.05, 0.9)))
            for _ in range(period))
        cases.append((Candidate("rnd", "rank", rounds), (2, 8)))
    for _ in range(6):  # torus space, (4, 4)
        period = int(rng.integers(2, 5))
        rounds = tuple(
            CandidateRound(((int(rng.integers(0, 2)),
                             int(rng.integers(1, 4))),),
                           float(rng.uniform(0.05, 0.9)))
            for _ in range(period))
        cases.append((Candidate("rnd", "torus", rounds), (4, 4)))
    checked = 0
    for cand, axes in cases:
        for spec in materialize(cand, axes):
            n = spec.size
            cw0, sw0 = R.heal_weights(spec, np.zeros(n, bool))
            # the no-dead heal IS the pristine plan
            np.testing.assert_array_equal(
                sw0, np.asarray(self_weights_of(spec), np.float64))
            n_dead = int(rng.integers(1, 4))
            dead_ranks = rng.choice(n, size=n_dead, replace=False)
            dead = np.zeros(n, bool)
            dead[dead_ranks] = True
            cwh, swh = R.heal_weights(spec, dead)
            M = R.mixing_matrix_from_weights(spec, cwh, swh)
            np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-12)
            # partial growth == fresh heal of the survivors' dead set
            k = int(rng.integers(1, n_dead + 1))
            back = [int(r) for r in
                    rng.choice(dead_ranks, size=k, replace=False)]
            gcw, gsw = grow_weights(spec, dead, back)
            rem = dead.copy()
            rem[back] = False
            fcw, fsw = R.heal_weights(spec, rem)
            assert gcw.tobytes() == fcw.tobytes()
            assert gsw.tobytes() == fsw.tobytes()
            Mg = R.mixing_matrix_from_weights(spec, gcw, gsw)
            np.testing.assert_allclose(Mg.sum(axis=1), 1.0, atol=1e-12)
            # full round trip: everyone back == pristine, bitwise
            acw, asw = grow_weights(spec, dead,
                                    [int(r) for r in dead_ranks])
            assert acw.tobytes() == cw0.tobytes()
            assert asw.tobytes() == sw0.tobytes()
            checked += 1
    assert checked >= 30  # the property was actually exercised


def test_grow_weights_validation():
    sched = one_peer_dynamic_schedule(N)
    dead = np.zeros(N, bool)
    dead[2] = True
    with pytest.raises(ValueError, match="dead mask"):
        grow_weights(sched[0], np.zeros(3, bool), [0])
    with pytest.raises(ValueError, match="outside topology"):
        grow_weights(sched[0], dead, [N])
    with pytest.raises(ValueError, match="not dead"):
        grow_weights(sched[0], dead, [3])
    with pytest.raises(ValueError, match="not dead"):
        grow_spec(sched[0], dead, 3)


def test_grown_comm_weights_keeps_traced_shapes():
    """Growth is deliverable to the compiled program: the re-grown
    weight DATA has exactly the unchanged ``comm_weight_inputs``
    structure (same shapes/dtypes), and growing everyone back equals
    the program's own default weights."""
    sched = one_peer_dynamic_schedule(N)
    dead = np.zeros(N, bool)
    dead[[1, 4]] = True
    base = F.comm_weight_inputs(sched)
    grown = grown_comm_weights(sched, dead, [1])
    assert len(grown) == len(base)
    for (cw0, sw0), (cw1, sw1) in zip(base, grown):
        assert cw0.shape == cw1.shape and sw0.shape == sw1.shape
        assert cw0.dtype == cw1.dtype and sw0.dtype == sw1.dtype
    full = grown_comm_weights(sched, dead, [1, 4])
    for (cw0, sw0), (cw1, sw1) in zip(base, full):
        np.testing.assert_array_equal(np.asarray(cw0), np.asarray(cw1))
        np.testing.assert_array_equal(np.asarray(sw0), np.asarray(sw1))
    g = grow_spec(sched[0], dead, [1, 4])
    assert R.is_row_stochastic(g)


# ------------------------------------------------------------------ #
# acceptance (b): the bootstrap pull
# ------------------------------------------------------------------ #
def test_anneal_fraction():
    assert anneal_fraction(0, 8) == 0.0
    assert anneal_fraction(4, 8) == 0.5
    assert anneal_fraction(8, 8) == 1.0
    assert anneal_fraction(100, 8) == 1.0  # clamped
    with pytest.raises(ValueError, match="rounds"):
        anneal_fraction(0, 0)
    with pytest.raises(ValueError, match="progress"):
        anneal_fraction(-1, 8)


def test_bootstrap_weights_anneal_semantics():
    """At fraction 0 the joiner's row is a pure pull (self-weight 0);
    at fraction 1 with live in-neighbors it is the pristine row
    EXACTLY; a round with no live in-neighbor freezes the joiner; live
    receivers keep zero weight on the joiner throughout; every row
    stays row-stochastic."""
    sched = one_peer_dynamic_schedule(N)
    j = 2
    live = np.ones(N, bool)
    live[j] = False
    for spec in sched:
        cw0, sw0 = R.heal_weights(spec, np.zeros(N, bool))
        src = [(j - cls.shift) % N for cls in spec.shift_classes
               if cls.recv_weights[j] != 0.0]
        # fraction 0: pure pull
        cw, sw = bootstrap_weights(spec, live, {j: 0.0})
        assert sw[j] == 0.0
        M = R.mixing_matrix_from_weights(spec, cw, sw)
        np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-12)
        assert abs(M[j, src].sum() - 1.0) < 1e-12
        # quarantine: no live receiver reads the joiner
        for i in range(N):
            if i != j:
                assert M[i, j] == 0.0
        # fraction 1, every in-neighbor live: the pristine row, exactly
        cw, sw = bootstrap_weights(spec, live, {j: 1.0})
        assert sw[j] == sw0[j]
        np.testing.assert_array_equal(cw[:, j], cw0[:, j])
        # no live in-neighbor this round: freeze (self-weight 1.0)
        live2 = live.copy()
        for s in src:
            live2[s] = False
        cw, sw = bootstrap_weights(spec, live2, {j: 0.5})
        assert sw[j] == 1.0 and (cw[:, j] == 0.0).all()
    # empty anneal IS the plain heal — the controller's single render
    dead = ~live
    for spec in sched:
        bcw, bsw = bootstrap_weights(spec, live, {})
        hcw, hsw = R.heal_weights(spec, dead)
        assert bcw.tobytes() == hcw.tobytes()
        assert bsw.tobytes() == hsw.tobytes()
    # jnp wrapper keeps the traced shapes
    base = F.comm_weight_inputs(sched)
    boot = bootstrap_comm_weights(sched, live, {j: 0.25})
    for (cw0_, sw0_), (cw1, sw1) in zip(base, boot):
        assert cw0_.shape == cw1.shape and sw0_.shape == sw1.shape


def test_bootstrap_weights_validation():
    spec = one_peer_dynamic_schedule(N)[0]
    live = np.ones(N, bool)
    live[2] = False
    with pytest.raises(ValueError, match="live mask"):
        bootstrap_weights(spec, np.ones(3, bool), {})
    with pytest.raises(ValueError, match="is live"):
        bootstrap_weights(spec, live, {0: 0.5})
    with pytest.raises(ValueError, match="outside topology"):
        bootstrap_weights(spec, live, {N: 0.5})
    with pytest.raises(ValueError, match="anneal fraction"):
        bootstrap_weights(spec, live, {2: 1.5})


def test_disagreement_metric():
    """The promotion gate is NORMALIZED: the joiner's L2 distance from
    the live mean in units of the live ranks' own max deviation —
    decentralized replicas intentionally differ by the consensus
    distance, so <= 1.0 means "inside the live consensus cloud"."""
    # live ranks at +1/-1 around mean 0 (max deviation exactly 1):
    # a joiner at 0.5 scores 0.5, a joiner at 3 scores ~3
    arr = np.array([[1.0], [-1.0], [0.5]])
    live = np.array([True, True, False])
    assert abs(disagreement({"w": arr}, 2, live) - 0.5) < 1e-6
    arr2 = arr.copy()
    arr2[2] = 3.0
    assert disagreement({"w": arr2}, 2, live) > 2.5
    # non-finite joiner state: infinite disagreement, never promoted
    arr3 = arr.copy()
    arr3[2] = np.nan
    assert disagreement({"w": arr3}, 2, live) == float("inf")
    with pytest.raises(ValueError, match="no live ranks"):
        disagreement({"w": arr}, 2, np.zeros(3, bool))
    with pytest.raises(ValueError, match="rank-major"):
        disagreement({"w": np.zeros((5, 2))}, 0, live)
    with pytest.raises(ValueError, match="inexact"):
        disagreement({"w": np.zeros((3, 2), np.int32)}, 0, live)


def test_sanitize_rank_rows():
    tree = {"a": np.arange(8.0).reshape(4, 2), "b": np.arange(4)}
    tree["a"][1, 0] = np.nan
    tree["a"][2, 1] = np.inf
    mask = np.array([False, True, False, False])
    out = sanitize_rank_rows(tree, mask)
    assert out["a"][1, 0] == 0.0 and out["a"][1, 1] == 3.0
    assert np.isinf(out["a"][2, 1])        # unmasked rows untouched
    assert out["b"] is tree["b"]           # int leaves pass through
    # finite masked rows: identity, no copy
    clean = {"a": np.ones((4, 2))}
    assert sanitize_rank_rows(clean, mask)["a"] is clean["a"]
    assert sanitize_rank_rows(tree, np.zeros(4, bool)) is tree
    with pytest.raises(ValueError, match="rank-major"):
        sanitize_rank_rows({"a": np.full((3, 2), np.nan)}, mask)


def test_zero_rank_rows():
    """Admission hygiene for optimizer state: the masked ranks' rows
    are zeroed (stale-but-finite moments must not ride through the
    params-only promotion gate), everything else is untouched, and
    already-zero rows / empty masks are identity."""
    tree = {"m": np.arange(1.0, 9.0).reshape(4, 2), "c": np.arange(4)}
    mask = np.array([False, True, False, False])
    out = zero_rank_rows(tree, mask)
    assert (out["m"][1] == 0.0).all()
    np.testing.assert_array_equal(out["m"][[0, 2, 3]],
                                  tree["m"][[0, 2, 3]])
    assert out["c"] is tree["c"]  # int passthrough
    assert zero_rank_rows(tree, np.zeros(4, bool)) is tree
    zeroed = {"m": np.zeros((4, 2))}
    assert zero_rank_rows(zeroed, mask)["m"] is zeroed["m"]
    with pytest.raises(ValueError, match="rank-major"):
        zero_rank_rows({"m": np.ones((3, 2))}, mask)


# ------------------------------------------------------------------ #
# acceptance (c): controller lifecycle + detector readmission
# ------------------------------------------------------------------ #
def test_membership_controller_lifecycle():
    det = R.FailureDetector(N)
    mc = MembershipController(one_peer_dynamic_schedule(N),
                              bootstrap_rounds=4, detector=det)
    assert mc.states() == [LIVE] * N
    assert not mc.effective_dead_mask().any()
    mc.mark_dead(3)
    assert mc.state(3) == DEAD and det.dead_mask()[3]
    assert mc.dead_ranks() == [3] and mc.live_ranks() == [
        r for r in range(N) if r != 3]
    # streak keeps counting while dead (observe has no special-case)
    for _ in range(5):
        det.observe(np.eye(N, dtype=bool)[3])
    mc.admit(3)
    assert mc.state(3) == JOINING and mc.joining_ranks() == [3]
    # still excised from receivers AND still dead to the detector:
    # bootstrap-window skips must not trigger fleet rollbacks
    assert mc.effective_dead_mask()[3] and det.dead_mask()[3]
    assert not mc.live_mask()[3]
    mc.tick()
    mc.tick()
    assert mc.progress(3) == 2 and mc.anneal() == {3: 0.5}
    assert mc.counts() == {LIVE: 7, DEAD: 0, JOINING: 1}
    mc.promote(3)
    assert mc.states() == [LIVE] * N
    # readmitted: dead flag AND latched streak cleared
    assert not det.dead_mask()[3]
    assert det.consecutive_bad()[3] == 0
    assert mc.progress(3) == 0
    assert "live=8" in repr(mc)


def test_membership_controller_transition_validation():
    mc = MembershipController(one_peer_dynamic_schedule(N),
                              bootstrap_rounds=4)
    with pytest.raises(ValueError, match="not dead"):
        mc.admit(0)
    with pytest.raises(ValueError, match="not joining"):
        mc.promote(0)
    with pytest.raises(ValueError, match="not joining"):
        mc.kick(0)
    with pytest.raises(ValueError, match="outside world"):
        mc.state(N)
    mc.mark_dead([2, 5])
    mc.admit(2)
    mc.kick(2)  # bootstrap failed: back to DEAD
    assert mc.state(2) == DEAD
    mc.seed_dead(np.eye(N, dtype=bool)[7])
    assert mc.state(7) == DEAD and mc.state(5) == DEAD
    with pytest.raises(ValueError, match="dead mask"):
        mc.seed_dead(np.zeros(3, bool))
    with pytest.raises(ValueError, match="non-empty"):
        MembershipController([])
    with pytest.raises(ValueError, match="bootstrap_rounds"):
        MembershipController(one_peer_dynamic_schedule(N),
                             bootstrap_rounds=0)


def test_detector_readmit():
    det = R.FailureDetector(4)
    for _ in range(3):
        det.observe([0, 1, 0, 0])
    det.suspect([1], source="straggler")
    det.declare_dead([1])
    with pytest.raises(ValueError, match="nothing to readmit"):
        det.readmit([0])
    det.readmit([1])
    assert not det.dead_mask()[1]
    assert det.consecutive_bad()[1] == 0     # streak cleared
    assert det.total_skips()[1] == 3          # history kept
    assert det.external_suspects() == []      # suspicion dropped
    assert det.suspects(1) == []              # nothing re-excises it


def test_controller_weights_cache_and_matrices():
    """Steady (no-joiner) weight tables are cached per membership
    pattern — bounded, so churn never grows host memory — and the
    per-round mixing matrices quarantine the joiner correctly."""
    sched = one_peer_dynamic_schedule(N)
    mc = MembershipController(sched, bootstrap_rounds=4)
    out1 = mc.comm_weight_arrays()
    out2 = mc.comm_weight_arrays()
    assert out1[0][0] is out2[0][0]  # cache hit: same arrays
    # cached tables are frozen: a caller mutating a returned array
    # must get a loud error, not silently corrupt later renders
    assert not out1[0][0].flags.writeable
    assert not out1[0][1].flags.writeable
    with pytest.raises(ValueError, match="read-only"):
        out1[0][0][0, 0] = 7.0
    mc.mark_dead(5)
    out3 = mc.comm_weight_arrays()
    assert out3[0][0] is not out1[0][0]
    mc.admit(5)
    mc.tick()
    mc.tick()  # anneal fraction 0.5
    for spec, M in zip(sched, mc.mixing_matrices()):
        np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-12)
        for i in range(N):
            if i != 5:
                assert M[i, 5] == 0.0  # quarantined: nobody reads it
        src = [(5 - cls.shift) % N for cls in spec.shift_classes
               if cls.recv_weights[5] != 0.0]
        if src:  # the joiner's own row pulls from its live neighbors
            assert M[5, src].sum() > 0.0
    # bounded steady cache: one entry per distinct pattern, LRU-capped
    mc2 = MembershipController(sched, bootstrap_rounds=4)
    for r in range(N):
        mc2.mark_dead(r)
        mc2.comm_weight_arrays()
        mc2.mark_dead((r + 1) % N)
        mc2.comm_weight_arrays()
        mc2._code[:] = 0  # reset pattern for the next pair
    assert len(mc2._steady) <= 16
    # the traced render matches comm_weight_inputs structurally
    base = F.comm_weight_inputs(sched)
    cur = mc.comm_weights()
    for (cw0, sw0), (cw1, sw1) in zip(base, cur):
        assert cw0.shape == cw1.shape and sw0.shape == sw1.shape


def test_bootstrap_consensus_recovery_n32():
    """Acceptance (b), simulation half: at n=32, kill ranks {3, 17},
    heal, converge the survivors, then admit both back through the
    annealed bootstrap — the joiners re-enter the consensus cloud (the
    normalized disagreement clears 1.0), growth restores the pristine
    tables byte-equal, and the FULL 32-rank fleet re-converges to a
    <= 1e-12 floor.  Pure numpy: the controller's mixing_matrices()
    drive the same seeded simulation the chaos bench uses."""
    n = 32
    sched = one_peer_dynamic_schedule(n)
    mc = MembershipController(sched, bootstrap_rounds=8)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((n, 16))
    d0 = float(np.linalg.norm(x - x.mean(axis=0)))
    t = 0

    def mix(rounds, tick=False):
        nonlocal x, t
        for _ in range(rounds):
            M = mc.mixing_matrices()[t % len(sched)]
            x = M @ x
            t += 1
            if tick:
                mc.tick()

    def floor(mask):
        sub = x[mask]
        return float(np.linalg.norm(sub - sub.mean(axis=0))) / d0

    live = np.ones(n, bool)
    mix(120)
    assert floor(live) < 1e-12
    # preempt: two ranks die, survivors re-converge among themselves
    mc.mark_dead([3, 17])
    x[[3, 17]] += rng.standard_normal((2, 16))  # stale + drifted state
    live[[3, 17]] = False
    mix(120)
    assert floor(live) < 1e-12
    # rejoin: quarantined annealed bootstrap, then the promotion gate
    mc.admit([3, 17])
    mix(60, tick=True)
    for r in (3, 17):
        assert disagreement({"w": x}, r, mc.live_mask()) <= 1.0
    mc.promote([3, 17])
    # grown == pristine, byte-equal (the round-trip, via the controller)
    for spec, (cw, sw) in zip(sched, mc.comm_weight_arrays()):
        pcw, psw = R.heal_weights(spec, np.zeros(n, bool))
        assert cw.tobytes() == pcw.tobytes()
        assert sw.tobytes() == psw.tobytes()
    live[[3, 17]] = True
    mix(120)
    assert floor(live) < 1e-12  # the WHOLE fleet, rejoined ranks in


def test_fault_plan_preempt_queries():
    plan = R.FaultPlan.preempt(N, rank=3, step=5, duration=4)
    assert R.PREEMPT == "preempt"
    np.testing.assert_array_equal(plan.corrupt_codes(4), np.zeros(N))
    np.testing.assert_array_equal(plan.corrupt_codes(5),
                                  np.eye(N, dtype=np.int8)[3])
    np.testing.assert_array_equal(plan.corrupt_codes(8),
                                  np.eye(N, dtype=np.int8)[3])
    np.testing.assert_array_equal(plan.corrupt_codes(9), np.zeros(N))
    assert plan.preempted_ranks(6) == [3] and plan.preempted_ranks(9) == []
    # rejoinable only once the window has ENDED
    assert plan.rejoinable_ranks(8) == []
    assert plan.rejoinable_ranks(9) == [3]
    # a later re-preempt holds the rank again until ITS window passes
    plan2 = plan.merged(R.FaultPlan.preempt(N, rank=3, step=12,
                                            duration=2))
    assert plan2.rejoinable_ranks(9) == [3]
    assert plan2.rejoinable_ranks(12) == []
    assert plan2.rejoinable_ranks(14) == [3]


def test_fleet_aggregator_grows_with_membership():
    """The gossip layer heals AND re-grows from the controller: the
    duck-typed ``effective_dead_mask()`` is read live, so the same
    aggregator excises a dead rank's row and folds it back in after
    promotion — both to the exact live mean.  The matrices cache stays
    bounded under membership churn."""
    sched = one_peer_dynamic_schedule(N)
    agg = FleetAggregator(sched, record_traffic=False)
    mc = MembershipController(sched, bootstrap_rounds=4)
    rng = np.random.default_rng(3)
    vals = rng.standard_normal((N, 2))
    mc.mark_dead(2)
    res = agg.aggregate(vals, dead_mask=mc)
    live = [r for r in range(N) if r != 2]
    assert np.isnan(res.per_rank[2]).all()
    np.testing.assert_allclose(
        res.per_rank[live],
        np.broadcast_to(vals[live].mean(axis=0), (len(live), 2)),
        atol=1e-12)
    # JOINING is still excised: quarantine means nobody reads it
    mc.admit(2)
    res = agg.aggregate(vals, dead_mask=mc)
    assert np.isnan(res.per_rank[2]).all()
    # promotion re-grows the gossip to the full-fleet mean
    mc.promote(2)
    res = agg.aggregate(vals, dead_mask=mc)
    np.testing.assert_allclose(
        res.per_rank, np.broadcast_to(vals.mean(axis=0), (N, 2)),
        atol=1e-12)
    # churn through > _MATS_CACHE_MAX membership patterns: bounded
    import itertools
    for combo in itertools.islice(
            itertools.combinations(range(N), 2), 36):
        mask = np.zeros(N, bool)
        mask[list(combo)] = True
        agg.aggregate(vals, dead_mask=mask)
    assert len(agg._mats) <= 32


# ------------------------------------------------------------------ #
# acceptance (d): the end-to-end cycle through run_resilient
# ------------------------------------------------------------------ #
def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


_OPT = optax.sgd(0.05, momentum=0.9)


def _state(mesh):
    params = F.rank_major({"w": jnp.zeros((6, 2))}, mesh)
    opt_state = F.rank_major(_OPT.init({"w": jnp.zeros((6, 2))}), mesh)
    return params, opt_state


_DATA = None


def _batch_fn(step):
    global _DATA
    if _DATA is None:
        rng = np.random.RandomState(7)
        _DATA = (rng.randn(32, N, 4, 6), rng.randn(32, N, 4, 2))
    return (_DATA[0][step % 32], _DATA[1][step % 32])


_GSTEP = {}


def _guarded_step():
    """One guarded atc + one-peer-schedule step shared by the elastic
    e2e tests — compile once, reuse everywhere (what lets the
    zero-recompile assertion span admission/anneal/promotion too)."""
    if "step" not in _GSTEP:
        mesh = _mesh()
        sched = one_peer_dynamic_schedule(N)
        _GSTEP["mesh"] = mesh
        _GSTEP["sched"] = sched
        _GSTEP["step"] = F.build_train_step(
            _loss_fn, _OPT, mesh, comm_mode="atc", schedule=sched,
            guard=F.GuardConfig())
    return _GSTEP["step"], _GSTEP["sched"], _GSTEP["mesh"]


def test_elastic_validation():
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    with pytest.raises(ValueError, match="schedule"):
        R.run_resilient(step_g, params, opt_state, _batch_fn, steps=1,
                        checkpointer=None, mesh=mesh,
                        elastic=ElasticConfig())
    with pytest.raises(ValueError, match="max_quarantine_steps"):
        R.run_resilient(step_g, params, opt_state, _batch_fn, steps=1,
                        checkpointer=None, mesh=mesh, schedule=sched,
                        elastic=ElasticConfig(bootstrap_rounds=8,
                                              max_quarantine_steps=4))


def test_preempt_rejoin_cycle_zero_recompiles(tmp_path):
    """Acceptance (d): preempt a rank past the death window — the
    fleet declares it dead, heals, rolls back; the window ends, the
    rank is admitted (rank_joining), bootstraps under quarantine, and
    is PROMOTED back to a fully-live fleet — all through the programs
    one cycle of the schedule compiled, one a round (join/leave/rejoin
    are pure weight data)."""
    step_g, sched, mesh = _guarded_step()
    for s in range(len(sched)):
        params, opt_state = _state(mesh)  # a call donates the buffers
        step_g(params, opt_state, _batch_fn(0), np.int32(s),
               step_g.default_comm_weights)
    baseline = step_g.jitted._cache_size()
    assert baseline == len(sched)
    params, opt_state = _state(mesh)
    plan = R.FaultPlan.preempt(N, rank=2, step=6, duration=6)
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=30,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None,
        elastic=ElasticConfig(bootstrap_rounds=4,
                              max_quarantine_steps=16))
    ck.close()
    # zero recompiles across the whole death + rejoin cycle
    assert step_g.jitted._cache_size() == baseline
    kinds = [e.kind for e in res.events if e.kind != "skip"]
    assert kinds.count("rank_dead") == 1
    assert kinds.count("rollback") == 1
    assert kinds.count("rank_joining") == 1
    assert kinds.count("rank_promoted") == 1
    by_kind = {e.kind: e for e in res.events}
    assert by_kind["rank_dead"].detail["rank"] == 2
    assert by_kind["rank_joining"].step > by_kind["rollback"].step
    promo = by_kind["rank_promoted"]
    assert promo.detail["rank"] == 2
    assert promo.detail["rounds"] >= 4
    assert promo.detail["disagreement"] <= 1.0
    # the fleet ends FULLY live: the death verdict was reversed
    assert res.membership == [LIVE] * N
    assert not res.dead_mask.any()
    assert res.n_rollbacks == 1 and res.step == 30
    assert R.update_health(res.params).all()
    # only the preempted rank ever skipped
    assert res.total_skips[2] > 0
    assert res.total_skips[[r for r in range(N) if r != 2]].sum() == 0


def test_rollback_kicks_inflight_joiners(tmp_path):
    """A rollback invalidates in-flight joiners (the restored
    checkpoint predates their bootstrap): the stranded joiner is
    kicked (rank_join_failed, reason=rollback), then re-admitted on a
    later step and promoted — while the newly dead rank stays out."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    plan = R.FaultPlan.preempt(N, rank=2, step=4, duration=4).merged(
        R.FaultPlan(N, [R.Fault(12, 5, "dead")]))
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=36,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None,
        elastic=ElasticConfig(bootstrap_rounds=10,
                              max_quarantine_steps=24))
    ck.close()
    joins = [e for e in res.events if e.kind == "rank_joining"]
    fails = [e for e in res.events if e.kind == "rank_join_failed"]
    assert [e.detail["rank"] for e in joins] == [2, 2]
    assert len(fails) == 1 and fails[0].detail["rank"] == 2
    assert fails[0].detail["reason"] == "rollback"
    promos = [e for e in res.events if e.kind == "rank_promoted"]
    assert [e.detail["rank"] for e in promos] == [2]
    assert res.n_rollbacks == 2
    assert res.membership[5] == DEAD
    assert [res.membership[r] for r in range(N) if r != 5] == [LIVE] * 7


def test_quarantine_expiry_kicks(tmp_path):
    """A joiner that can never clear the gate (threshold forced below
    any possible disagreement) is kicked back to DEAD after
    max_quarantine_steps — a half-synced rank never leaks in."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    plan = R.FaultPlan.preempt(N, rank=2, step=4, duration=4)
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=20,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None,
        elastic=ElasticConfig(bootstrap_rounds=4,
                              max_quarantine_steps=6,
                              quarantine_threshold=-1.0))
    ck.close()
    fails = [e for e in res.events if e.kind == "rank_join_failed"]
    assert fails and all(e.detail["rank"] == 2 for e in fails)
    assert all(e.detail["reason"] == "quarantine_expired" for e in fails)
    assert not any(e.kind == "rank_promoted" for e in res.events)
    assert res.dead_mask[2]  # the detector verdict was never reversed
    assert res.membership[2] in (DEAD, JOINING)


def test_quarantine_expiry_enforced_between_checks(tmp_path):
    """With ``check_every > 1`` the expiry deadline must not wait for
    the next scheduled measurement: the joiner is kicked the tick its
    quarantine budget runs out, without a disagreement reading."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    plan = R.FaultPlan.preempt(N, rank=2, step=4, duration=4)
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=20,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None,
        elastic=ElasticConfig(bootstrap_rounds=4,
                              max_quarantine_steps=6,
                              check_every=4,
                              quarantine_threshold=-1.0))
    ck.close()
    joins = [e for e in res.events if e.kind == "rank_joining"]
    fails = [e for e in res.events if e.kind == "rank_join_failed"]
    assert joins and fails
    # measurements land at progress 4, 8, ...; the deadline (6) falls
    # between them — the kick fires there anyway, measurement-free
    # (progress p is reached at the joining step + p - 1)
    assert fails[0].step - joins[0].step == 5
    assert "disagreement" not in fails[0].detail
    assert all(e.detail["reason"] == "quarantine_expired" for e in fails)


def test_rollback_demotes_promotion_past_restored_checkpoint(tmp_path):
    """A rank PROMOTED inside a bad window (where checkpoints are
    refused) must not stay LIVE through the rollback: the restore
    rewinds its rows to mid-bootstrap state the disagreement gate never
    certified, so the runner demotes it back to DEAD
    (``reason="promotion_rolled_back"``), the admission poll re-offers
    it, and it re-bootstraps cleanly.  On a clean step, promotion
    instead FORCES a checkpoint so the certified state is durable."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    # rank 2: preempt -> rejoin; rank 5 dies RIGHT as rank 2 rejoins,
    # so rank 2's promotion lands inside rank 5's bad window
    plan = R.FaultPlan.preempt(N, rank=2, step=4, duration=4).merged(
        R.FaultPlan(N, [R.Fault(8, 5, "dead")]))
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=24,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None,
        elastic=ElasticConfig(bootstrap_rounds=2,
                              max_quarantine_steps=16,
                              quarantine_threshold=1e9))
    ck.close()
    joins = [e for e in res.events if e.kind == "rank_joining"]
    promos = [e for e in res.events if e.kind == "rank_promoted"]
    fails = [e for e in res.events if e.kind == "rank_join_failed"]
    rollbacks = [e for e in res.events if e.kind == "rollback"]
    assert [e.detail["rank"] for e in joins] == [2, 2]
    assert [e.detail["rank"] for e in promos] == [2, 2]
    assert len(fails) == 1 and fails[0].detail["rank"] == 2
    assert fails[0].detail["reason"] == "promotion_rolled_back"
    # the demotion was justified: the restore predates the promotion
    assert rollbacks[1].detail["restored_step"] <= promos[0].step
    # the re-promotion happened on a clean step and was made durable
    # by a forced checkpoint right after it (step not on the cadence)
    ckpt_steps = [e.step for e in res.events if e.kind == "checkpoint"]
    assert promos[1].step + 1 in ckpt_steps
    assert (promos[1].step + 1) % 4 != 0
    assert res.n_rollbacks == 2
    assert res.membership[5] == DEAD
    assert [res.membership[r] for r in range(N) if r != 5] == [LIVE] * 7
    assert not res.dead_mask[2] and res.dead_mask[5]


@pytest.mark.slow
def test_chaos_rejoin_benchmark_smoke(tmp_path):
    """The chaos bench's rejoin part (part 4) runs end to end on tiny
    settings and its self-checks pass (slow: it measures wall time)."""
    import json
    import os
    import subprocess
    import sys

    out = str(tmp_path / "chaos.json")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "chaos_resilience.py"),
         "--steps", "24", "--dim", "6", "--sim-rounds", "80",
         "--out", out, "--compare", ""],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.load(open(out))
    assert all(rec["checks"].values()), rec["checks"]
    assert rec["rejoin"]["recompiles"] == 0
    assert rec["rejoin"]["final_membership_all_live"]
    assert rec["rejoin"]["sim"]["post_rejoin_floor"] <= 1e-12
