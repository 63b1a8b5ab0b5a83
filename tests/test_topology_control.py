"""Closed-loop topology control plane (bluefog_tpu/topology/control.py).

The acceptance properties of the control plane:

(a) **projection** re-expresses a candidate over the carrier's declared
    edges (zero weight on the unused ones) without touching the edge
    tuples — and REJECTS (raises) a candidate whose nonzero edges the
    carrier round never declared, instead of silently dropping them;
(b) **scoring** compares the incumbent and every candidate through one
    function — cost-to-consensus of the HEALED schedule under the
    actual dead mask — so the margin gate is apples-to-apples;
(c) **detection** is debounced and relative: a uniformly busy fleet
    never trips the degrade test (units cancel against the median), a
    hot edge must persist ``patience`` windows, while a membership
    transition triggers immediately;
(d) **hot-swap** is pure weight data: the swapped tables keep the
    carrier's shapes, compose with the current dead mask, and the
    whole trigger -> swap -> probation -> commit/rollback cycle runs
    through ``run_resilient(control=...)`` with ZERO recompiles;
(e) a bad candidate put on probation is ROLLED BACK to the incumbent
    when the consensus-distance health worsens past tolerance.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from bluefog_tpu import resilience as R
from bluefog_tpu.observe import MetricsRegistry
from bluefog_tpu.observe.fleet import StragglerDetector, record_edge_timing
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology import TopologyControlPlane
from bluefog_tpu.topology.compiler import PodSpec
from bluefog_tpu.topology.control import swap_comm_weights
from bluefog_tpu.topology.spec import DynamicTopology

pytestmark = pytest.mark.topology

N = 8
MACHINES, LOCAL = 4, 2
SHIFTS = (1, 2, 4, 6, 7)


def _pod():
    return PodSpec(MACHINES, LOCAL, ici_cost=1.0, dcn_cost=4.0)


def _carrier(rounds=4):
    """A rich carrier: every round declares FULL shift permutations for
    shifts {1,2,4,6,7} — any candidate whose edges live on those shifts
    is expressible; anything else is not."""
    ew = {}
    for s in SHIFTS:
        for i in range(N):
            ew[(i, (i + s) % N)] = 1.0 / (len(SHIFTS) + 1)
    base = DynamicTopology.from_edges(N, ew, [1.0 / (len(SHIFTS) + 1)] * N)
    return [base] * rounds


def _shift_round(shift, weight=0.5):
    ew = {(i, (i + shift) % N): weight for i in range(N)}
    return DynamicTopology.from_edges(N, ew, [1.0 - weight] * N)


def _plane(**kw):
    kw.setdefault("window", 4)
    kw.setdefault("patience", 2)
    kw.setdefault("degrade_ratio", 1.5)
    kw.setdefault("margin", 0.05)
    kw.setdefault("cooldown", 4)
    kw.setdefault("probation", 3)
    kw.setdefault("synchronous", True)
    return TopologyControlPlane(_pod(), _carrier(), **kw)


def _live(n=N):
    return np.zeros(n, bool)


# ------------------------------------------------------------------ #
# (a) projection
# ------------------------------------------------------------------ #
def test_project_reexpresses_on_carrier_edges():
    plane = _plane()
    cand = [_shift_round(1), _shift_round(2)]
    proj = plane.project(cand)
    assert len(proj) == len(plane.carrier)
    for t, spec in enumerate(proj):
        base = plane.carrier[t]
        # declared edges untouched (the recompile-free invariant)
        assert spec.edges == base.edges
        want = cand[t % len(cand)]
        wmap = dict(zip(want.edges, want.edge_weight_values))
        for e, v in zip(spec.edges, spec.edge_weight_values):
            assert v == pytest.approx(wmap.get(e, 0.0))
        np.testing.assert_allclose(spec.self_weight_values,
                                   want.self_weight_values)


def test_project_rejects_undeclared_edges():
    plane = _plane()
    bad = DynamicTopology.from_edges(  # shift 3 is NOT in the carrier
        N, {(i, (i + 3) % N): 0.5 for i in range(N)}, [0.5] * N)
    with pytest.raises(ValueError, match="never\\s+declared"):
        plane.project([bad])
    with pytest.raises(ValueError, match="empty"):
        plane.project([])
    with pytest.raises(ValueError, match="ranks"):
        plane.project([DynamicTopology.from_edges(4, {(0, 1): 0.5},
                                                  [0.5] * 4)])


def test_project_zero_weight_on_undeclared_edge_is_fine():
    """A candidate may DECLARE an alien edge as long as it never pushes
    on it — only nonzero weights must be expressible."""
    plane = _plane()
    ew = {(i, (i + 1) % N): 0.5 for i in range(N)}
    ew[(0, 3)] = 0.0  # shift 3: declared by the candidate, weight 0
    cand = DynamicTopology.from_edges(N, ew, [0.5] * N)
    proj = plane.project([cand])
    assert proj[0].edges == plane.carrier[0].edges


# ------------------------------------------------------------------ #
# (b) scoring under the dead mask
# ------------------------------------------------------------------ #
def test_score_active_healed_under_dead_mask():
    plane = _plane()
    sched = plane.project([_shift_round(1)])
    full = plane.score_active(sched, _live())
    dead = _live()
    dead[[6, 7]] = True
    healed = plane.score_active(sched, dead)
    for sc in (full, healed):
        assert set(sc) == {"mean_round_cost", "max_round_cost", "sigma",
                           "rounds_to_consensus", "cost_to_consensus"}
        assert sc["cost_to_consensus"] > 0
    # fewer live ranks on the same ring -> different contraction
    assert healed["sigma"] != pytest.approx(full["sigma"])
    with pytest.raises(ValueError, match="no live"):
        plane.score_active(sched, np.ones(N, bool))


def test_score_active_calibrated_pod_reprices():
    plane = _plane()
    sched = plane.project([_shift_round(2)])  # shift 2 crosses machines
    base = plane.score_active(sched, _live())
    hot = plane.pod.calibrated(
        {(0, 2): 100.0}, contention=3.0)
    repriced = plane.score_active(sched, _live(), hot)
    assert (repriced["cost_to_consensus"] > base["cost_to_consensus"])
    # contraction is a property of the weights, not the prices
    assert repriced["sigma"] == pytest.approx(base["sigma"])


# ------------------------------------------------------------------ #
# (c) detection: debounce, relativity, membership
# ------------------------------------------------------------------ #
def test_uniform_load_never_triggers():
    """Every edge equally slow: pressure is relative to the median, so
    the fleet is busy, not degraded — no trigger, ever."""
    reg = MetricsRegistry()
    plane = _plane(registry=reg)
    for step in range(1, 25):
        for spec in plane.active_schedule():
            for e, v in zip(spec.edges, spec.edge_weight_values):
                if v != 0.0:
                    # every edge at 2x its NOMINAL cost: busy, but
                    # relatively uniform — the median normalizes it out
                    record_edge_timing(None,
                                       2.0 * plane.pod.round_cost([e]),
                                       registry=reg, pairs=[e])
        events = plane.on_step(step, dead_mask=_live())
        assert events == []
    assert plane.triggers == 0 and plane.state == "steady"


def test_hot_edge_debounced_then_triggers():
    """One edge 10x over nominal: the FIRST degraded window must not
    trigger (patience=2); the second consecutive one does."""
    reg = MetricsRegistry()
    plane = _plane(registry=reg)
    triggered_at = None
    for step in range(1, 13):
        for spec in plane.active_schedule():
            for e, v in zip(spec.edges, spec.edge_weight_values):
                if v != 0.0:
                    nominal = plane.pod.round_cost([e])
                    slow = 10.0 if e == (0, 2) else 1.0
                    record_edge_timing(None, nominal * slow,
                                       registry=reg, pairs=[e])
        events = plane.on_step(step, dead_mask=_live())
        kinds = [k for k, _ in events]
        if "topology_trigger" in kinds:
            triggered_at = step
            break
    # windows close at steps 4 and 8; patience=2 -> trigger at 8
    assert triggered_at == 8
    assert plane.triggers == 1


def test_membership_transition_triggers_immediately():
    plane = _plane(window=0)  # telemetry off: only membership can act
    assert plane.on_step(1, dead_mask=_live()) == []
    dead = _live()
    dead[5] = True
    events = plane.on_step(2, dead_mask=dead)
    kinds = [k for k, _ in events]
    assert "topology_trigger" in kinds
    assert dict(events)["topology_trigger"]["reason"] == "membership"


def test_margin_gate_rejects_noise_wins():
    """With margin ~1 no candidate can clear the bar: the synthesis
    round ends in a reject event and a cooldown, not a swap."""
    plane = _plane(window=0, margin=0.999)
    dead = _live()
    dead[7] = True
    events = plane.on_step(1, dead_mask=dead)
    assert [k for k, _ in events] == ["topology_trigger"]
    events = plane.on_step(2, dead_mask=dead)
    kinds = [k for k, _ in events]
    assert "topology_reject" in kinds and "topology_swap" not in kinds
    assert plane.swaps == 0 and plane.state == "steady"
    assert plane.last_scores["incumbent"] > 0


def test_margin_gate_accepts_clear_win():
    plane = _plane(window=0, margin=0.05)
    dead = _live()
    dead[[6, 7]] = True
    plane.on_step(1, dead_mask=dead)       # trigger + inline synthesis
    events = plane.on_step(2, dead_mask=dead)
    kinds = [k for k, _ in events]
    assert "topology_swap" in kinds
    swap = dict(events)["topology_swap"]
    assert swap["cost_to_consensus"] < swap["incumbent"]
    assert plane.active_name() == swap["schedule"] != "carrier"
    assert plane.state == "probation"


def test_cooldown_suppresses_retrigger():
    plane = _plane(window=0, margin=0.999, cooldown=50)
    dead = _live()
    dead[7] = True
    plane.on_step(1, dead_mask=dead)
    plane.on_step(2, dead_mask=dead)       # reject -> cooldown
    assert plane.triggers == 1
    dead2 = dead.copy()
    dead2[6] = True                        # fresh membership change...
    for step in range(3, 20):
        plane.on_step(step, dead_mask=dead2)
    assert plane.triggers == 1             # ...held until cooldown ends


# ------------------------------------------------------------------ #
# swap mechanics: carrier shapes, dead-mask composition, boundary fn
# ------------------------------------------------------------------ #
def test_swap_comm_weights_keeps_shapes_and_composes_mask():
    plane = _plane()
    before = swap_comm_weights(plane, _live())
    dead = _live()
    dead[3] = True
    plane.force_candidate([_shift_round(1), _shift_round(2)],
                          name="swapped")
    plane.on_step(1, dead_mask=dead)       # delivers the swap
    assert plane.active_name() == "swapped"
    after = swap_comm_weights(plane, dead)
    assert len(after) == len(before) == len(plane.carrier)
    for (cw0, sw0), (cw1, sw1) in zip(before, after):
        # traced shapes identical round-for-round: no recompile
        assert np.asarray(cw0).shape == np.asarray(cw1).shape
        assert np.asarray(sw0).shape == np.asarray(sw1).shape
    # ... and equal to healing the active schedule directly
    from bluefog_tpu.resilience.healing import healed_comm_weights

    want = healed_comm_weights(plane.active_schedule(), dead)
    for (wcw, wsw), (cw1, sw1) in zip(want, after):
        np.testing.assert_array_equal(np.asarray(wcw), np.asarray(cw1))
        np.testing.assert_array_equal(np.asarray(wsw), np.asarray(sw1))


def test_force_candidate_still_enforces_projection():
    plane = _plane()
    bad = DynamicTopology.from_edges(
        N, {(i, (i + 3) % N): 0.5 for i in range(N)}, [0.5] * N)
    with pytest.raises(ValueError, match="never\\s+declared"):
        plane.force_candidate([bad])


# ------------------------------------------------------------------ #
# (e) probation rollback
# ------------------------------------------------------------------ #
def _params_with_spread(spread):
    w = np.zeros((N, 3))
    w[:, 0] = np.linspace(0.0, spread, N)
    return {"w": w}


def test_probation_rolls_back_on_worse_health():
    plane = _plane(rollback_tolerance=1.2)
    plane.force_candidate([_shift_round(1)], name="bad")
    events = plane.on_step(1, dead_mask=_live(),
                           params=_params_with_spread(1.0))
    assert [k for k, _ in events] == ["topology_swap"]
    assert plane.active_name() == "bad"
    # consensus distance BLOWS UP past preswap * tolerance
    events = plane.on_step(2, dead_mask=_live(),
                           params=_params_with_spread(10.0))
    assert [k for k, _ in events] == ["topology_rollback"]
    assert plane.active_name() == "carrier"
    assert plane.rollbacks == 1 and plane.state == "steady"
    detail = dict(events)["topology_rollback"]
    assert detail["restored"] == "carrier"
    assert detail["health"] > detail["preswap_health"]


def test_probation_commits_on_clean_health():
    plane = _plane(probation=3, rollback_tolerance=1.2)
    plane.force_candidate([_shift_round(1)], name="good")
    plane.on_step(1, dead_mask=_live(), params=_params_with_spread(1.0))
    for step in (2, 3):
        assert plane.on_step(step, dead_mask=_live(),
                             params=_params_with_spread(0.5)) == []
    events = plane.on_step(4, dead_mask=_live(),
                           params=_params_with_spread(0.2))
    assert [k for k, _ in events] == ["topology_commit"]
    assert plane.active_name() == "good"
    assert plane.rollbacks == 0 and plane.state == "steady"


# ------------------------------------------------------------------ #
# background-thread synthesis path
# ------------------------------------------------------------------ #
def test_background_synthesis_delivers_swap():
    plane = _plane(window=0, synchronous=False)
    dead = _live()
    dead[[6, 7]] = True
    events = plane.on_step(1, dead_mask=dead)
    assert [k for k, _ in events] == ["topology_trigger"]
    plane.join(timeout=30.0)
    assert plane.state == "candidate_ready"
    events = plane.on_step(2, dead_mask=dead)
    assert "topology_swap" in [k for k, _ in events]
    assert plane.swaps == 1


# ------------------------------------------------------------------ #
# straggler z-scores degrade the window and reprice the pod
# ------------------------------------------------------------------ #
def test_straggler_z_hot_degrades_and_triggers():
    det = StragglerDetector(N, z_threshold=3.0, patience=2)
    plane = _plane(straggler=det, z_threshold=3.0, patience=1)
    rng = np.random.RandomState(0)
    for step in range(1, 9):
        t = 1.0 + 0.01 * rng.randn(N)
        t[5] += 5.0            # persistent straggler
        det.observe(t)
        events = plane.on_step(step, dead_mask=_live())
        if any(k == "topology_trigger" for k, _ in events):
            assert dict(events)["topology_trigger"]["reason"] == "degraded"
            break
    else:
        pytest.fail("straggler z never degraded a window")
    assert det.z_scores().get(5, 0.0) >= 3.0


# ------------------------------------------------------------------ #
# constructor validation + config defaults
# ------------------------------------------------------------------ #
def test_constructor_validation_and_env_defaults(monkeypatch):
    with pytest.raises(ValueError, match="non-empty carrier"):
        TopologyControlPlane(_pod(), [])
    with pytest.raises(ValueError, match="does not match"):
        TopologyControlPlane(PodSpec(2, 2), _carrier())
    monkeypatch.setenv("BLUEFOG_TOPOLOGY_REPLAN_WINDOW", "17")
    monkeypatch.setenv("BLUEFOG_TOPOLOGY_REPLAN_PATIENCE", "5")
    monkeypatch.setenv("BLUEFOG_TOPOLOGY_REPLAN_MARGIN", "0.25")
    plane = TopologyControlPlane(_pod(), _carrier())
    assert plane.window == 17
    assert plane.patience == 5
    assert plane.margin == 0.25
    # explicit kwargs beat the env
    plane = TopologyControlPlane(_pod(), _carrier(), window=3)
    assert plane.window == 3


# ------------------------------------------------------------------ #
# (d) end-to-end: run_resilient(control=...) with zero recompiles
# ------------------------------------------------------------------ #
def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2)


_OPT = optax.sgd(0.05, momentum=0.9)
_E2E = {}


def _e2e_setup():
    if "step" not in _E2E:
        mesh = _mesh()
        sched = _carrier()
        _E2E["mesh"] = mesh
        _E2E["sched"] = sched
        _E2E["step"] = F.build_train_step(
            _loss_fn, _OPT, mesh, comm_mode="atc", schedule=sched,
            guard=F.GuardConfig())
        rng = np.random.RandomState(11)
        _E2E["data"] = (rng.randn(16, N, 4, 6), rng.randn(16, N, 4, 2))
    return _E2E["step"], _E2E["sched"], _E2E["mesh"]


def _e2e_state(mesh):
    params = F.rank_major({"w": jnp.zeros((6, 2))}, mesh)
    opt_state = F.rank_major(_OPT.init({"w": jnp.zeros((6, 2))}), mesh)
    return params, opt_state


def _e2e_batch(step):
    return (_E2E["data"][0][step % 16], _E2E["data"][1][step % 16])


def test_control_requires_matching_carrier():
    step_g, sched, mesh = _e2e_setup()
    params, opt_state = _e2e_state(mesh)
    plane = _plane()
    with pytest.raises(ValueError, match="schedule"):
        R.run_resilient(step_g, params, opt_state, _e2e_batch, steps=1,
                        checkpointer=None, mesh=mesh, control=plane)


def test_shrink_swap_cycle_zero_recompiles_e2e(tmp_path):
    """Two ranks die -> membership trigger -> inline synthesis ->
    swap -> probation -> commit, all through the programs one cycle of
    the carrier compiled (one a round).  The delivered weights at every
    boundary stay carrier-shaped, so the jit cache never grows."""
    step_g, sched, mesh = _e2e_setup()
    for s in range(len(sched)):
        params, opt_state = _e2e_state(mesh)  # a call donates the buffers
        step_g(params, opt_state, _e2e_batch(0), np.int32(s),
               step_g.default_comm_weights)
    baseline = step_g.jitted._cache_size()
    assert baseline == len(sched)
    params, opt_state = _e2e_state(mesh)
    plane = TopologyControlPlane(
        _pod(), sched, window=0, margin=0.05, cooldown=4, probation=3,
        rollback_tolerance=4.0, synchronous=True)
    plan = R.FaultPlan(N, [R.Fault(4, 6, "dead"), R.Fault(4, 7, "dead")])
    det = R.FailureDetector(N)
    from bluefog_tpu.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step_g, params, opt_state, _e2e_batch, steps=20,
        checkpointer=ck, mesh=mesh, schedule=sched,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
        fault_plan=plan, detector=det, checkpoint_every=0,
        sleep=lambda s: None, control=plane)
    ck.close()
    assert step_g.jitted._cache_size() == baseline
    kinds = [e.kind for e in res.events]
    assert "topology_trigger" in kinds
    assert "topology_swap" in kinds
    assert "topology_commit" in kinds
    assert "topology_rollback" not in kinds
    assert plane.swaps == 1 and plane.rollbacks == 0
    assert plane.active_name() not in ("carrier", "initial")
    # the live ranks kept training through the swap
    assert res.step == 20
    assert R.update_health(res.params)[~res.dead_mask].all()


# ------------------------------------------------------------------ #
# (f) the mix-ratio ladder (ISSUE 17): the cheap lever before
#     re-synthesis — step DOWN on a degraded streak, probation with
#     health rollback, step back UP on clean windows, and only an
#     exhausted ladder falls through to a topology trigger
# ------------------------------------------------------------------ #
class _ForcedPlane(TopologyControlPlane):
    """Degradation verdict pinned by the test (the detector's inputs
    are exercised by the (c) tests; the ladder tests drive the state
    machine directly)."""

    degraded = True

    def _window_degraded(self, secs, z):
        return self.degraded, 9.9


def _ladder_plane(**kw):
    kw.setdefault("window", 1)
    kw.setdefault("patience", 2)
    kw.setdefault("cooldown", 0)
    kw.setdefault("probation", 2)
    kw.setdefault("synchronous", True)
    kw.setdefault("use_compiler", False)
    kw.setdefault("mix_ratios", (0.25, 0.1, 0.05))
    return _ForcedPlane(_pod(), _carrier(1), **kw)


def _ladder_params():
    return {"x": np.zeros((N, 3))}


def test_mix_ladder_steps_down_commits_and_recovers():
    """Degraded streak -> one rung down (reason 'degraded') -> commit
    after probation; degradation clears -> clean windows step back UP
    toward the build ratio (reason 'recover') -> commit.  Every live
    value comes from the sanctioned swap_mix_ratio producer."""
    from bluefog_tpu.topology.control import swap_mix_ratio

    health = {"v": 1.0}
    plane = _ladder_plane(mix_recover_windows=2,
                          health_fn=lambda p, live: health["v"])
    assert swap_mix_ratio(plane) == 0.25
    events = []
    for step in range(1, 30):
        for kind, data in plane.on_step(step, params=_ladder_params()):
            events.append((kind, data.get("ratio"), data.get("reason")))
        if (swap_mix_ratio(plane) == 0.1
                and ("mix_ratio_commit", 0.1, None) in events):
            plane.degraded = False
        if swap_mix_ratio(plane) == 0.25 and not plane.degraded:
            break
    kinds = [e[0] for e in events]
    assert ("mix_ratio_swap", 0.1, "degraded") in events
    assert ("mix_ratio_commit", 0.1, None) in events
    assert ("mix_ratio_swap", 0.25, "recover") in events
    assert swap_mix_ratio(plane) == 0.25
    assert kinds.count("mix_ratio_rollback") == 0
    assert plane.mix_swaps >= 2 and plane.mix_rollbacks == 0


def test_mix_ladder_rolls_back_on_worse_health():
    """Health blowing past rollback_tolerance x the pre-swap baseline
    during a rung's probation restores the previous rung."""
    from bluefog_tpu.topology.control import swap_mix_ratio

    health = {"v": 1.0}
    plane = _ladder_plane(patience=1, probation=5,
                          mix_ratios=(0.25, 0.1),
                          health_fn=lambda p, live: health["v"])
    evs = plane.on_step(1, params=_ladder_params())
    assert [k for k, _ in evs] == ["mix_ratio_swap"]
    assert swap_mix_ratio(plane) == 0.1
    health["v"] = 10.0  # consensus blew up under the coarser ratio
    evs = plane.on_step(2, params=_ladder_params())
    assert [k for k, _ in evs] == ["mix_ratio_rollback"]
    assert swap_mix_ratio(plane) == 0.25
    assert plane.mix_rollbacks == 1


def test_mix_ladder_exhausted_falls_through_to_topology():
    """With every rung spent and degradation persisting, the plane
    falls through to the topology path (a synthesis trigger) instead
    of spinning on the ladder."""
    from bluefog_tpu.topology.control import swap_mix_ratio

    plane = _ladder_plane(patience=1, probation=1,
                          mix_ratios=(0.25, 0.1),
                          health_fn=lambda p, live: 0.0)
    seen = []
    for step in range(1, 12):
        seen += [k for k, _ in plane.on_step(step,
                                             params=_ladder_params())]
        if "topology_trigger" in seen:
            break
    assert "topology_trigger" in seen
    assert swap_mix_ratio(plane) == 0.1  # parked on the last rung


def test_mix_ladder_validation():
    """The ladder must be >= 2 strictly descending positive rungs
    (rung 0 is the BUILD ratio that sized the static k), and
    mix_ratio() without a ladder raises instead of guessing."""
    for bad in [(0.25,), (0.25, 0.3), (0.25, 0.0), (0.25, 0.25)]:
        with pytest.raises(ValueError):
            _ladder_plane(mix_ratios=bad)
    with pytest.raises(ValueError):
        _plane().mix_ratio()
