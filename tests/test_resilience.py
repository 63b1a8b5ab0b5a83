"""Resilience subsystem: fault injection, detection, healing, guarded
rollback (bluefog_tpu/resilience/ + build_train_step(guard=...)).

The acceptance properties of the fault-injection suite:

(a) with no faults injected, the guarded step's (params, opt_state,
    loss) are BIT-identical to the unguarded step's;
(b) a NaN-emitting rank is skipped without poisoning neighbors, and the
    skip counter advances;
(c) after a rank death the healed weight matrix is row-stochastic and a
    seeded consensus-distance simulation still converges;
(d) run_resilient's rollback restores the exact checkpointed state, with
    ZERO recompiles across fault patterns (asserted via the jitted
    cache size, the same way test_serving.py asserts compile counts).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import resilience as R
from bluefog_tpu.checkpoint import Checkpointer
from bluefog_tpu.context import BluefogError
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology import (ExponentialTwoGraph,
                                  one_peer_dynamic_schedule,
                                  uniform_topology_spec)
from bluefog_tpu.topology.spec import Topology

pytestmark = pytest.mark.resilience

N = 8


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
    """Deterministic rank-major batch stream (pure function of step —
    the replay-determinism contract run_resilient relies on)."""
    global _DATA
    if _DATA is None:
        rng = np.random.RandomState(7)
        _DATA = (rng.randn(32, N, 4, 6), rng.randn(32, N, 4, 2))
    return (_DATA[0][step % 32], _DATA[1][step % 32])


_GSTEP = {}


def _guarded_step():
    """One guarded atc + one-peer-schedule step shared by the run_
    resilient tests — compile once, reuse everywhere (also what lets
    the zero-recompile assertion span multiple fault patterns)."""
    if "step" not in _GSTEP:
        mesh = _mesh()
        sched = one_peer_dynamic_schedule(N)
        _GSTEP["mesh"] = mesh
        _GSTEP["sched"] = sched
        _GSTEP["step"] = F.build_train_step(
            _loss_fn, _OPT, mesh, comm_mode="atc", schedule=sched,
            guard=F.GuardConfig())
    return _GSTEP["step"], _GSTEP["sched"], _GSTEP["mesh"]


# ------------------------------------------------------------------ #
# faults.py
# ------------------------------------------------------------------ #
def test_fault_plan_queries_and_determinism():
    plan = R.FaultPlan(N, [
        R.Fault(3, 1, "nan", duration=2),
        R.Fault(5, 2, "inf"),
        R.Fault(6, 4, "dead"),
        R.Fault(2, 0, "stall", stall_seconds=0.25),
    ])
    assert plan.active(2) == [R.Fault(2, 0, "stall", stall_seconds=0.25)]
    assert plan.stall_seconds(2) == 0.25 and plan.stall_seconds(3) == 0.0
    np.testing.assert_array_equal(
        plan.corrupt_codes(3), [0, 1, 0, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(
        plan.corrupt_codes(4), [0, 1, 0, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(
        plan.corrupt_codes(5), [0, 0, 2, 0, 0, 0, 0, 0])
    # a dead rank emits NaN forever from its onset
    assert plan.dead_ranks(5) == [] and plan.dead_ranks(6) == [4]
    np.testing.assert_array_equal(
        plan.corrupt_codes(100), [0, 0, 0, 0, 1, 0, 0, 0])
    assert plan.last_onset() == 6
    with pytest.raises(ValueError, match="kind"):
        R.Fault(0, 0, "flaky")
    with pytest.raises(ValueError, match="outside world"):
        R.FaultPlan(4, [R.Fault(0, 7, "nan")])


def test_fault_plan_congestion_and_persistent_straggler():
    """The ISSUE-15 fault kinds: a congested directed link is a pure
    cost-model fault (nothing corrupted, nothing stalled), overlapping
    congestions multiply, and a persistent straggler stalls its rank
    from onset past any bench horizon."""
    plan = R.FaultPlan.congest_link(N, 0, 2, 4.0, start=8, duration=10)
    assert plan.congested_links(7) == {}
    assert plan.congested_links(8) == {(0, 2): 4.0}
    assert plan.congested_links(17) == {(0, 2): 4.0}
    assert plan.congested_links(18) == {}
    # nothing else is perturbed by a congest fault
    np.testing.assert_array_equal(plan.corrupt_codes(8), np.zeros(N))
    assert plan.stall_seconds(8) == 0.0
    assert plan.dead_ranks(8) == []
    # merged overlapping congestion on the SAME link multiplies
    both = plan.merged(
        R.FaultPlan.congest_link(N, 0, 2, 2.0, start=10, duration=4))
    assert both.congested_links(9) == {(0, 2): 4.0}
    assert both.congested_links(10) == {(0, 2): 8.0}
    assert both.congested_links(14) == {(0, 2): 4.0}
    # ... and distinct links report separately
    two = plan.merged(
        R.FaultPlan.congest_link(N, 1, 3, 6.0, start=8, duration=10))
    assert two.congested_links(8) == {(0, 2): 4.0, (1, 3): 6.0}
    # validation: dst must be a rank, factor must be a slowdown
    with pytest.raises(ValueError, match="dst"):
        R.FaultPlan.congest_link(4, 0, 7, 2.0, start=0, duration=1)
    with pytest.raises(ValueError, match="factor"):
        R.FaultPlan.congest_link(N, 0, 2, 0.5, start=0, duration=1)

    slow = R.FaultPlan.persistent_straggler(N, 5, 8, stall_seconds=0.25)
    assert slow.stall_seconds(7) == 0.0
    np.testing.assert_array_equal(slow.stall_seconds_by_rank(8),
                                  [0, 0, 0, 0, 0, 0.25, 0, 0])
    # open-ended: still stalling far past any bench horizon
    assert slow.stall_seconds_by_rank(500_000)[5] == 0.25
    # two stalls on one rank add up in the per-rank vector
    stacked = slow.merged(R.FaultPlan.straggler(
        N, 5, 10, duration=2, stall_seconds=0.1))
    assert stacked.stall_seconds_by_rank(10)[5] == pytest.approx(0.35)
    assert stacked.stall_seconds_by_rank(12)[5] == pytest.approx(0.25)


def test_fault_plan_corrupt_batch():
    plan = R.FaultPlan.nan_burst(N, rank=3, step=2)
    x = np.ones((N, 4, 6))
    y = np.arange(N, dtype=np.int32)  # int leaves pass through untouched
    bx, by = plan.corrupt_batch((x, y), 2)
    assert np.isnan(bx[3]).all() and np.isfinite(bx[[r for r in range(N)
                                                     if r != 3]]).all()
    np.testing.assert_array_equal(by, y)
    assert np.isfinite(x).all()  # input not mutated
    # healthy step: identity, no copy
    out = plan.corrupt_batch((x, y), 0)
    assert out[0] is x and out[1] is y
    with pytest.raises(ValueError, match="rank-major"):
        plan.corrupt_batch((np.ones((3, 2)),), 2)


# ------------------------------------------------------------------ #
# detector.py
# ------------------------------------------------------------------ #
def test_detector_streaks_suspects_and_death():
    det = R.FailureDetector(4)
    det.observe([0, 1, 0, 1])
    det.observe([0, 1, 0, 0])
    det.observe([0, 1, 0, 1])
    np.testing.assert_array_equal(det.consecutive_bad(), [0, 3, 0, 1])
    np.testing.assert_array_equal(det.total_skips(), [0, 3, 0, 2])
    assert det.suspects(3) == [1] and det.suspects(1) == [1, 3]
    det.declare_dead([1])
    assert det.suspects(3) == []  # dead ranks are no longer suspects
    np.testing.assert_array_equal(det.dead_mask(), [0, 1, 0, 0])
    # dead-rank skips are expected: only live skips count
    assert det.live_bad([0, 1, 0, 0]) is False
    assert det.live_bad([0, 1, 1, 0]) is True
    det.reset_streaks()
    np.testing.assert_array_equal(det.consecutive_bad(), [0, 0, 0, 0])
    np.testing.assert_array_equal(det.total_skips(), [0, 3, 0, 2])


def test_detector_heartbeats_indeterminate_single_process():
    # no KV store / single process: liveness cannot be determined,
    # the detector says so rather than guessing
    assert R.FailureDetector.heartbeat_dead_processes(0.01) == []
    assert R.FailureDetector.heartbeat_dead_ranks(0.01) == []


def test_update_health():
    tree = {"a": np.ones((4, 3)), "b": np.ones((4, 2))}
    tree["a"][2, 1] = np.nan
    tree["b"][1, 0] = np.inf
    np.testing.assert_array_equal(R.update_health(tree),
                                  [True, False, False, True])


# ------------------------------------------------------------------ #
# healing.py — acceptance (c)
# ------------------------------------------------------------------ #
def test_healed_static_matrix_row_stochastic():
    dead = np.zeros(N, bool)
    dead[2] = True
    for spec in (uniform_topology_spec(ExponentialTwoGraph(N)),
                 _weighted_ring()):
        assert R.is_row_stochastic(spec)
        healed = R.heal_spec(spec, dead)
        assert R.is_row_stochastic(healed)
        M = R.mixing_matrix(healed)
        # the dead rank is excised: frozen in place, weight 0 everywhere
        np.testing.assert_array_equal(M[2], np.eye(N)[2])
        assert M[:, 2].sum() == M[2, 2] == 1.0
        # live rows keep their sums EXACTLY (mass moved to self weight)
        np.testing.assert_allclose(R.row_sums(healed), 1.0, atol=1e-12)


def _weighted_ring():
    """A non-uniform row-stochastic ring (healing must preserve exact
    sums even when nothing is a neat 1/k)."""
    W = np.zeros((N, N))
    for r in range(N):
        W[(r - 1) % N, r] = 0.3
        W[(r + 1) % N, r] = 0.1
        W[r, r] = 0.6
    return Topology.from_weight_matrix(W)


def test_healed_schedule_consensus_converges():
    """Acceptance (c): kill a rank mid-schedule; the healed one-peer
    rounds keep the surviving ranks contracting to THEIR consensus —
    the seeded pure-numpy mixing simulation (wire_quant_consensus
    machinery pointed at healing)."""
    dead = np.zeros(N, bool)
    dead[5] = True
    sched = one_peer_dynamic_schedule(N)
    healed = [R.heal_spec(s, dead) for s in sched]
    for s in healed:
        assert R.is_row_stochastic(s)
    trace = R.consensus_simulation(healed, rounds=120, dim=16, seed=3,
                                   dead_mask=dead)
    assert trace[0] > 0.1           # starts genuinely dispersed
    assert trace[-1] < 1e-8         # and converges among survivors
    assert trace[40] < trace[0] * 1e-2
    # the healed weight DATA has the unhealed shapes — the
    # zero-recompile delivery contract
    base = F.comm_weight_inputs(sched)
    healed_w = R.healed_comm_weights(sched, dead)
    for (cw0, sw0), (cw1, sw1) in zip(base, healed_w):
        assert cw0.shape == cw1.shape and sw0.shape == sw1.shape
        assert cw0.dtype == cw1.dtype


def test_heal_weights_rejects_bad_mask():
    spec = uniform_topology_spec(ExponentialTwoGraph(N))
    with pytest.raises(ValueError, match="dead mask"):
        R.heal_weights(spec, np.zeros(3, bool))


# ------------------------------------------------------------------ #
# guarded train step — acceptance (a) and (b)
# ------------------------------------------------------------------ #
def test_guard_no_faults_bit_identical():
    """Acceptance (a): faults absent, the guarded step IS the unguarded
    step — bit-identical params/opt_state/loss across a multi-step
    trajectory, for a static topology (atc), the lax.switch dynamic
    schedule (cta), AND uniform-weight static CTA.  The last config was
    excluded by design before ISSUE 6 (the unguarded builder baked the
    uniform weight vector as a constant that XLA folded into (sum)*w,
    a 1-ulp rewrite traced weight operands cannot legally reproduce);
    the fused epilogue pipeline feeds BOTH builds the same traced-
    weight combine, so the association orders agree everywhere
    (tests/test_epilogue.py pins the same guarantee)."""
    mesh = _mesh()
    configs = [
        dict(comm_mode="atc",
             topology=uniform_topology_spec(ExponentialTwoGraph(N))),
        dict(comm_mode="cta", schedule=one_peer_dynamic_schedule(N)),
        dict(comm_mode="cta",
             topology=uniform_topology_spec(ExponentialTwoGraph(N))),
    ]
    for cfg in configs:
        step_u = F.build_train_step(_loss_fn, _OPT, mesh, donate=False,
                                    **cfg)
        step_g = F.build_train_step(_loss_fn, _OPT, mesh, donate=False,
                                    guard=F.GuardConfig(), **cfg)
        params, opt_state = _state(mesh)
        params2, opt_state2 = params, opt_state
        for s in range(5):
            batch = _batch_fn(s)
            params, opt_state, loss = step_u(params, opt_state, batch,
                                             jnp.int32(s))
            params2, opt_state2, loss2, skipped = step_g(
                params2, opt_state2, batch, jnp.int32(s),
                step_g.default_comm_weights)
            np.testing.assert_array_equal(np.asarray(skipped),
                                          np.zeros(N, np.int32))
        for a, b in zip(jax.tree.leaves((params, opt_state, loss)),
                        jax.tree.leaves((params2, opt_state2, loss2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(cfg.keys()))


def test_nan_rank_skipped_without_poisoning_neighbors():
    """Acceptance (b): one rank's NaN gradients cost exactly that
    rank's update — the skip flag fires for it alone, every parameter
    everywhere stays finite (its neighbors combined its last-good
    params), and the next healthy step clears the flag."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    w = step_g.default_comm_weights
    plan = R.FaultPlan(N, [R.Fault(2, 3, "nan"), R.Fault(4, 6, "inf")])
    total = np.zeros(N, np.int64)
    for s in range(6):
        batch = plan.corrupt_batch(_batch_fn(s), s)
        params, opt_state, loss, skipped = step_g(
            params, opt_state, batch, jnp.int32(s), w)
        sk = np.asarray(skipped)
        total += sk
        want = np.zeros(N, np.int32)
        if s == 2:
            want[3] = 1
        if s == 4:
            want[6] = 1
        np.testing.assert_array_equal(sk, want, err_msg=f"step {s}")
        for leaf in jax.tree.leaves((params, opt_state)):
            assert np.isfinite(np.asarray(leaf)).all(), f"step {s}"
    # the skip counter advanced by exactly the injected faults
    np.testing.assert_array_equal(total,
                                  [0, 0, 0, 1, 0, 0, 1, 0])
    # and per-rank health of the params agrees with the guard
    assert R.update_health(params).all()


def test_guard_validation():
    mesh = _mesh()
    spec = uniform_topology_spec(ExponentialTwoGraph(N))
    with pytest.raises(ValueError, match="push_sum"):
        F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="push_sum",
                           topology=spec, guard=F.GuardConfig())
    # guard + hierarchical composes now; what must still fail loudly is
    # a RANK-sized spec passed where the machine schedule belongs
    with pytest.raises(ValueError, match="machine"):
        F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="cta",
                           topology=spec, hierarchical_local_size=2,
                           guard=F.GuardConfig())
    step_u = F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="none")
    with pytest.raises(ValueError, match="GUARDED"):
        R.run_resilient(step_u, None, None, _batch_fn, steps=1,
                        checkpointer=None, mesh=mesh)

    def aux_loss(params, aux, batch):
        return _loss_fn(params, batch), aux

    step_aux = F.build_train_step(aux_loss, _OPT, mesh, comm_mode="none",
                                  has_aux=True, guard=F.GuardConfig())
    with pytest.raises(ValueError, match="no-aux"):
        R.run_resilient(step_aux, None, None, _batch_fn, steps=1,
                        checkpointer=None, mesh=mesh)


# ------------------------------------------------------------------ #
# run_resilient — acceptance (d)
# ------------------------------------------------------------------ #
def test_rollback_restores_exact_checkpoint(tmp_path):
    """Acceptance (d): a rank death at step 6 trips the K=3 window at
    step 8, the runner declares it dead, heals, and rolls back to the
    step-4 checkpoint — whose state must be BIT-identical to the same
    trajectory replayed by hand.  The completed run ends healthy with
    the dead rank excised."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)

    # hand-replay the healthy prefix to step 4 (faults start at 6)
    p_ref, o_ref = _state(mesh)
    w = step_g.default_comm_weights
    for s in range(4):
        p_ref, o_ref, _, _ = step_g(p_ref, o_ref, _batch_fn(s),
                                    jnp.int32(s), w)

    plan = R.FaultPlan.rank_death(N, rank=2, step=6)
    ck = Checkpointer(str(tmp_path / "ck"))
    slept = []
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=14,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.125),
        fault_plan=plan, checkpoint_every=4, sleep=slept.append)

    rollbacks = [e for e in res.events if e.kind == "rollback"]
    assert len(rollbacks) == 1
    assert rollbacks[0].detail["restored_step"] == 4
    assert rollbacks[0].detail["dead"] == [2]
    assert res.n_rollbacks == 1 and slept == [0.125]
    np.testing.assert_array_equal(res.dead_mask,
                                  np.eye(N, dtype=bool)[2])
    assert res.step == 14

    # the checkpoint the rollback restored == the hand-replayed state
    saved = ck.restore(4, mesh, like={"params": p_ref,
                                      "opt_state": o_ref, "step": 0})
    ck.close()
    assert int(saved["step"]) == 4
    for a, b in zip(jax.tree.leaves((saved["params"],
                                     saved["opt_state"])),
                    jax.tree.leaves((p_ref, o_ref))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # post-death training stayed finite and the dead rank kept skipping
    assert R.update_health(res.params).all()
    assert res.total_skips[2] > 3
    assert res.total_skips[[r for r in range(N) if r != 2]].sum() == 0


def test_zero_recompiles_across_fault_patterns(tmp_path):
    """Acceptance (d), compile half: the SAME compiled programs (one a
    round of the schedule, all there after one cycle) serve a healthy
    run, a transient NaN burst, and a rank death with healed weights —
    fault patterns are pure input data (asserted the way
    test_serving.py asserts compile counts)."""
    step_g, sched, mesh = _guarded_step()
    for s in range(len(sched)):
        params, opt_state = _state(mesh)  # a call donates the buffers
        step_g(params, opt_state, _batch_fn(0), np.int32(s),
               step_g.default_comm_weights)
    # (the shared step may hold entries of earlier tests too: pin the
    # count after the cycle and require that it never grows)
    baseline = step_g.jitted._cache_size()
    assert baseline >= len(sched)
    plans = [
        R.FaultPlan.healthy(N),
        R.FaultPlan.nan_burst(N, rank=1, step=2, duration=2),
        R.FaultPlan.rank_death(N, rank=6, step=3),
    ]
    for i, plan in enumerate(plans):
        params, opt_state = _state(mesh)
        ck = Checkpointer(str(tmp_path / f"ck{i}"))
        res = R.run_resilient(
            step_g, params, opt_state, _batch_fn, steps=10,
            checkpointer=ck, mesh=mesh, schedule=sched,
            guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
            fault_plan=plan, checkpoint_every=5,
            sleep=lambda s: None)
        ck.close()
        assert res.step == 10
        assert step_g.jitted._cache_size() == baseline, plan
    assert res.dead_mask[6] and res.n_rollbacks == 1


def test_overlapping_transients_survive_without_rollback(tmp_path):
    """Overlapping transient bursts from DIFFERENT ranks (each shorter
    than K) trip the global bad-window counter but are NOT attributable
    to any single rank — the skip guard already contained them, and a
    rollback would deterministically replay the identical transients.
    The runner must note the window and keep training, not enter a
    futile rollback loop."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    # rank 1 bad at steps 5-6, rank 3 at steps 7-8: four consecutive
    # live-bad steps, but every per-rank streak is only 2 < K=3
    plan = R.FaultPlan(N, [R.Fault(5, 1, "nan", duration=2),
                           R.Fault(7, 3, "nan", duration=2)])
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _batch_fn, steps=14,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None)
    ck.close()
    assert res.n_rollbacks == 0 and res.step == 14
    assert not res.dead_mask.any()
    assert any(e.kind == "bad_window_unattributed" for e in res.events)
    np.testing.assert_array_equal(res.total_skips,
                                  [0, 2, 0, 2, 0, 0, 0, 0])
    assert R.update_health(res.params).all()


def test_run_resilient_gives_up_after_max_rollbacks(tmp_path):
    """Two staggered rank deaths with max_rollbacks=1: the first death
    heals and rolls back; the second must raise instead of retrying —
    the rollback budget bounds the recovery storm."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    plan = R.FaultPlan(N, [R.Fault(2, 1, "dead"),
                           R.Fault(8, 4, "dead")])
    ck = Checkpointer(str(tmp_path / "ck"))
    with pytest.raises(BluefogError, match="rollbacks"):
        R.run_resilient(
            step_g, params, opt_state, _batch_fn, steps=30,
            checkpointer=ck, mesh=mesh, schedule=sched,
            guard=F.GuardConfig(max_consecutive_bad=2, backoff_base=0.0,
                                max_rollbacks=1),
            fault_plan=plan, checkpoint_every=4, sleep=lambda s: None)
    ck.close()


def test_guard_config_rides_the_step(tmp_path):
    """The GuardConfig the step was BUILT with is the runner's default
    policy — repeating it at run_resilient would be a drift trap.  K=2
    attached at build time must drive the rollback window."""
    mesh = _mesh()
    sched = one_peer_dynamic_schedule(N)
    cfg = F.GuardConfig(max_consecutive_bad=2, backoff_base=0.0)
    step_g = F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="atc",
                                schedule=sched, guard=cfg)
    assert step_g.guard_config is cfg
    params, opt_state = _state(mesh)
    plan = R.FaultPlan.rank_death(N, rank=6, step=4)
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(  # note: no guard= — policy comes off the step
        step_g, params, opt_state, _batch_fn, steps=10,
        checkpointer=ck, mesh=mesh, schedule=sched,
        fault_plan=plan, checkpoint_every=2, sleep=lambda s: None)
    ck.close()
    # K=2 (not the default 3): death at 4 -> bad at 4,5 -> rollback
    # fires at step 6, restoring the step-4 checkpoint
    rb = [e for e in res.events if e.kind == "rollback"]
    assert len(rb) == 1 and rb[0].step == 6
    assert rb[0].detail["restored_step"] == 4
    assert res.dead_mask[6] and res.step == 10


def test_run_resilient_all_dead_raises(tmp_path):
    """Every rank dead = nothing to heal around: an explicit give-up,
    not a silent run of frozen parameters."""
    step_g, sched, mesh = _guarded_step()
    params, opt_state = _state(mesh)
    plan = R.FaultPlan(N, [R.Fault(0, r, "dead") for r in range(N)])
    ck = Checkpointer(str(tmp_path / "ck"))
    with pytest.raises(BluefogError, match="every rank"):
        R.run_resilient(
            step_g, params, opt_state, _batch_fn, steps=10,
            checkpointer=ck, mesh=mesh, schedule=sched,
            guard=F.GuardConfig(max_consecutive_bad=2, backoff_base=0.0),
            fault_plan=plan, sleep=lambda s: None)
    ck.close()


@pytest.mark.slow
def test_chaos_benchmark_smoke(tmp_path):
    """The chaos bench runs end to end on tiny settings and its
    self-checks pass (slow: it measures wall time)."""
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
    assert rec["chaos"]["n_rollbacks"] >= 1
    assert rec["chaos"]["recompiles"] == 0
