"""Fleet telemetry (ISSUE 5): in-graph health signals + decentralized
cross-rank aggregation.

Contracts under test:

* **HealthVector** — ``build_train_step(health=HealthConfig(...))``
  emits shape-stable per-rank health scalars; with ``health=None`` the
  step is bit-identical to a pre-feature build and jit cache sizes are
  unchanged; with health enabled there are ZERO recompiles across fault
  patterns (the GuardConfig methodology); the consensus distance term
  matches a by-hand recomputation from the combine's own inputs/outputs.
* **FleetAggregator** — push-sum gossip over the training topology
  reproduces the centralized mean to <= 1e-12 relative error at n=32
  (the acceptance bar), including after a ``healing.py`` weight re-plan
  excises a dead rank; the host matrices are EXACTLY one round of
  ``collectives.push_sum_mix`` (device parity test); hierarchical
  intra-host/inter-host aggregation is an exact weighted mean with
  uneven live machines.
* **StragglerDetector** — a slow rank's robust step-time z-score flags
  it within ``patience`` observations, recovery clears the flag, and
  ``run_resilient`` wires flags into ``FailureDetector.suspect`` +
  ``straggler`` events.
* **Traffic accounting** — ``bf_edge_bytes_total{src,dst}`` families
  appear for every declared edge, from both the train-step wrapper and
  the gossip itself, and fleet gauges export through Prometheus text
  unchanged.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import observe
from bluefog_tpu.observe import fleet as FL
from bluefog_tpu.observe.registry import MetricsRegistry
from bluefog_tpu.optim import functional as F
from bluefog_tpu.parallel import collectives as C
from bluefog_tpu.resilience.healing import heal_spec
from bluefog_tpu.topology import (ExponentialTwoGraph,
                                  one_peer_dynamic_schedule,
                                  uniform_topology_spec)

pytestmark = pytest.mark.fleet

N = 8


# --------------------------------------------------------------------- #
# push-sum gossip core
# --------------------------------------------------------------------- #
def test_push_sum_matrix_column_stochastic():
    for spec in ([uniform_topology_spec(ExponentialTwoGraph(N))]
                 + one_peer_dynamic_schedule(N)):
        A = FL.push_sum_matrix(spec)
        np.testing.assert_allclose(A.sum(axis=0), 1.0, atol=1e-15)
    dead = np.zeros(N, bool)
    dead[2] = True
    A = FL.push_sum_matrix(one_peer_dynamic_schedule(N)[0], dead)
    np.testing.assert_allclose(A.sum(axis=0), 1.0, atol=1e-15)
    assert A[2, 2] == 1.0 and A[2].sum() == 1.0  # dead rank is inert


def test_push_sum_matrix_matches_device_push_sum_mix():
    """The host gossip matrix IS one round of the device push-sum mix:
    same column-stochastic structure, same numbers — the 'reuse the
    push-sum machinery' claim, measured."""
    spec = uniform_topology_spec(ExponentialTwoGraph(N))
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    x = np.arange(N, dtype=np.float64) + 1.0
    w = np.ones(N)

    def one_round(xs, ws):
        mixed, mps = C.push_sum_mix({"v": xs}, ws, spec, "bf")
        return mixed["v"], mps

    sm = jax.jit(jax.shard_map(one_round, mesh=mesh,
                               in_specs=(P("bf"), P("bf")),
                               out_specs=(P("bf"), P("bf")),
                               check_vma=False))
    dx, dw = sm(jnp.asarray(x), jnp.asarray(w))
    A = FL.push_sum_matrix(spec)
    np.testing.assert_allclose(np.asarray(dx), A @ x, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(dw), A @ w, rtol=1e-12)


def test_aggregator_matches_centralized_mean_32_ranks():
    """Acceptance: n=32 digraph, per-rank estimates vs the centralized
    mean to <= 1e-12 relative error."""
    n = 32
    sched = one_peer_dynamic_schedule(n)
    vals = np.random.default_rng(0).standard_normal((n, 3)) * 10
    agg = FL.FleetAggregator(sched, registry=MetricsRegistry())
    res = agg.aggregate(vals, names=("a", "b", "c"))
    true = vals.mean(axis=0)
    err = np.abs(res.per_rank - true).max() / np.abs(true).max()
    assert err <= 1e-12, (err, res.rounds)
    assert res.names == ("a", "b", "c")
    np.testing.assert_allclose(res.mean, true, rtol=1e-12)


def test_aggregator_healed_dead_rank_excision():
    """Acceptance: after a healing.py weight re-plan excises dead
    ranks, gossip over the HEALED schedule converges to the live mean
    to <= 1e-12 — and the internally-excised matrices are byte-equal to
    the healed-spec matrices (the two paths cannot drift)."""
    n = 32
    sched = one_peer_dynamic_schedule(n)
    dead = np.zeros(n, bool)
    dead[[3, 17]] = True
    vals = np.random.default_rng(1).standard_normal((n, 2))
    vals[dead] = 1e6  # a dead rank's garbage must not leak into means

    healed = [heal_spec(s, dead) for s in sched]
    for s, h in zip(sched, healed):
        np.testing.assert_array_equal(FL.push_sum_matrix(s, dead),
                                      FL.push_sum_matrix(h))

    agg = FL.FleetAggregator(healed, registry=MetricsRegistry())
    res = agg.aggregate(vals, dead_mask=dead)
    true_live = vals[~dead].mean(axis=0)
    err = np.nanmax(np.abs(res.per_rank - true_live)) / \
        max(np.abs(true_live).max(), 1e-12)
    assert err <= 1e-12, err
    assert np.isnan(res.per_rank[3]).all()  # dead ranks have no view


def test_aggregator_healed_schedule_without_dead_mask():
    """A healed schedule passed WITHOUT a dead mask must behave like
    one passed with it: the re-plan's fully-excised ranks (no edges in
    any round) are detected as isolated and folded into the effective
    dead mask, instead of blocking convergence forever with their stale
    values counted live."""
    n = 32
    sched = one_peer_dynamic_schedule(n)
    dead = np.zeros(n, bool)
    dead[[3, 17]] = True
    vals = np.random.default_rng(2).standard_normal((n, 2))
    vals[dead] = 1e6

    healed = [heal_spec(s, dead) for s in sched]
    agg = FL.FleetAggregator(healed, registry=MetricsRegistry())
    res = agg.aggregate(vals)  # no dead_mask: excision inferred
    true_live = vals[~dead].mean(axis=0)
    err = np.nanmax(np.abs(res.per_rank - true_live)) / \
        max(np.abs(true_live).max(), 1e-12)
    assert err <= 1e-12, (err, res.rounds, res.spread)
    assert res.rounds < agg.max_rounds
    assert np.isnan(res.per_rank[list(np.nonzero(dead)[0])]).all()
    np.testing.assert_allclose(res.mean, true_live, rtol=1e-12)


def test_gossip_traffic_skips_zero_weight_edges():
    """The gossip's wire account bills the weight-FILTERED push-sum
    structure: a healed spec's zeroed edges (declared but pushing
    nothing, exactly like a 0.0-weight DynamicTopology edge) must not
    accrue bf_edge_bytes_total."""
    sched = one_peer_dynamic_schedule(N)
    dead = np.zeros(N, bool)
    dead[2] = True
    healed = [heal_spec(s, dead) for s in sched]
    dropped = [e for s, h in zip(sched, healed)
               for e in set(FL.edge_list(s)) - set(FL.gossip_edge_list(h))]
    assert dropped  # healing actually zeroed some edges
    assert all(2 in e for e in dropped)

    reg = MetricsRegistry()
    agg = FL.FleetAggregator(healed, registry=reg)
    vals = np.random.default_rng(3).standard_normal(N)
    agg.aggregate(vals, dead_mask=dead)
    billed = {(lbl["src"], lbl["dst"])
              for name, kind, _h, lbl, m in reg.collect()
              if name == "bf_edge_bytes_total" and m.value > 0}
    assert billed  # live edges are billed
    assert not ({e for e in billed if 2 in e})


def test_aggregator_hierarchical_weighted_mean():
    """HiCCL-style two-level aggregation: exact intra-machine reduce,
    inter-machine push-sum with live-COUNT weights — the global live
    mean exactly, uneven machines included."""
    n, local = 32, 4
    dead = np.zeros(n, bool)
    dead[[0, 1, 2, 5]] = True  # machine 0 keeps ONE live rank
    vals = np.random.default_rng(2).standard_normal((n, 2))
    reg = MetricsRegistry()
    agg = FL.FleetAggregator(one_peer_dynamic_schedule(n), registry=reg)
    res = agg.aggregate_hierarchical(
        vals, local, one_peer_dynamic_schedule(n // local),
        dead_mask=dead)
    true_live = vals[~dead].mean(axis=0)
    err = np.nanmax(np.abs(res.per_rank - true_live)) / \
        max(np.abs(true_live).max(), 1e-12)
    assert err <= 1e-12, err
    # inter-host gossip wire cost is accounted on the machine LEADER
    # ranks' edges (multiples of local_size)
    snap = reg.snapshot()
    assert "bf_edge_bytes_total" in snap
    for r in snap["bf_edge_bytes_total"]:
        assert int(r["labels"]["src"]) % local == 0
        assert int(r["labels"]["dst"]) % local == 0
    # repeated publishes hit the matrix cache
    n_cached = len(agg._mats)
    agg.aggregate_hierarchical(vals, local,
                               one_peer_dynamic_schedule(n // local),
                               dead_mask=dead)
    assert len(agg._mats) == n_cached


def test_aggregator_publish_lands_bf_fleet_metrics():
    reg = MetricsRegistry()
    sched = one_peer_dynamic_schedule(N)
    agg = FL.FleetAggregator(sched, registry=reg, rank=0)
    vals = np.tile(np.arange(N, dtype=float)[:, None], (1, 2))
    agg.publish(("step_time_p50", "skips_total"), vals)
    snap = reg.snapshot()
    expect = float(np.arange(N).mean())
    assert abs(snap["bf_fleet_step_time_p50"][0]["value"] - expect) < 1e-9
    assert abs(snap["bf_fleet_skips_total"][0]["value"] - expect) < 1e-9
    assert snap["bf_fleet_gossip_rounds"][0]["value"] >= 1
    # the gossip's own wire cost is accounted per edge
    assert "bf_edge_bytes_total" in snap
    assert all(set(r["labels"]) == {"src", "dst"}
               for r in snap["bf_edge_bytes_total"])
    # and the exporters serve fleet metrics with no changes
    text = observe.prometheus_text(reg)
    assert "bf_fleet_step_time_p50" in text
    assert 'bf_edge_bytes_total{dst="' in text


def test_collect_local_reads_registry():
    reg = MetricsRegistry()
    reg.histogram("bf_step_wall_seconds", loop="train").observe(0.25)
    reg.counter("bf_resilience_skips_total", rank=1).inc(3)
    reg.counter("bf_resilience_skips_total", rank=2).inc(4)
    reg.gauge("bf_serving_queue_depth").set(5)
    local = FL.collect_local(reg)
    assert local == {"step_time_p50": 0.25, "skips_total": 7.0,
                     "queue_depth": 5.0}


# --------------------------------------------------------------------- #
# straggler detection
# --------------------------------------------------------------------- #
def test_straggler_detector_flags_within_patience_and_clears():
    det = FL.StragglerDetector(N, z_threshold=4.0, patience=3,
                               registry=MetricsRegistry())
    base = np.full(N, 0.01)
    rng = np.random.default_rng(0)
    for _ in range(5):  # healthy jitter never flags
        assert det.observe(base + rng.normal(0, 1e-4, N)) == []
    assert det.flagged() == []
    slow = base.copy()
    slow[5] += 0.2
    newly = []
    for i in range(3):
        newly += det.observe(slow + rng.normal(0, 1e-4, N))
        if i < 2:
            assert det.flagged() == []  # not yet: patience=3
    assert newly == [5] and det.flagged() == [5]
    z = det.z_scores()
    assert set(z) == set(range(N)) and z[5] > 4.0
    # sub-threshold drift is readable without any event having fired
    assert all(abs(z[r]) < 4.0 for r in range(N) if r != 5)
    # recovery clears the flag (and the streak) — and the z snapshot
    # tracks the LATEST observation, so the recovered rank reads sane
    assert det.observe(base + rng.normal(0, 1e-4, N)) == []
    assert det.flagged() == []
    assert det.z_scores()[5] < 4.0


def test_straggler_detector_robust_to_its_own_outlier():
    """A plain std would be inflated by the straggler itself; the
    median/MAD score must still separate one 25x outlier at n=8."""
    det = FL.StragglerDetector(N, z_threshold=4.0, patience=1)
    times = np.full(N, 0.02)
    times[3] = 0.5
    assert det.observe(times) == [3]


def test_run_resilient_wires_straggler_to_suspects(tmp_path):
    """The control loop names the slow rank: a straggler event is
    emitted, FailureDetector.suspect is fed (and suspects() includes
    it), and recovery withdraws the suspicion."""
    from bluefog_tpu import resilience as R
    from bluefog_tpu.checkpoint import Checkpointer

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    base = {"w": jnp.eye(4)}

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    opt = optax.sgd(0.05)
    step = F.build_train_step(loss_fn, opt, mesh, comm_mode="cta",
                              schedule=sched, donate=False,
                              guard=F.GuardConfig())
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)

    def batch_fn(step_i):
        return jax.device_put(np.ones((N, 2, 4), np.float32),
                              NamedSharding(mesh, P("bf")))

    # rank 6 is slow for steps 2..7 then recovers
    stalls = {s: 0.3 for s in range(2, 8)}

    def step_times_fn(step_i, wall):
        t = np.full(N, 0.01)
        t[6] += stalls.get(step_i, 0.0)
        return t

    det = FL.StragglerDetector(N, z_threshold=4.0, patience=2)
    fdet = R.FailureDetector(N)
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(step, params, ostate, batch_fn, steps=12,
                          checkpointer=ck, mesh=mesh, schedule=sched,
                          detector=fdet, checkpoint_every=0,
                          sleep=lambda s: None, straggler=det,
                          step_times_fn=step_times_fn)
    ck.close()
    strag_events = [e for e in res.events if e.kind == "straggler"]
    assert len(strag_events) == 1
    assert strag_events[0].detail["ranks"] == [6]
    assert strag_events[0].step == 3  # onset 2 + patience 2 - 1
    # recovered by the end -> suspicion withdrawn, nobody died
    assert fdet.external_suspects() == []
    assert not res.dead_mask.any() and res.n_rollbacks == 0


def test_failure_detector_external_suspects():
    from bluefog_tpu.resilience import FailureDetector

    det = FailureDetector(4)
    det.suspect([2])
    assert det.suspects(3) == [2]
    assert det.streak_suspects(3) == []  # numeric evidence only
    assert det.external_suspects() == [2]
    det.declare_dead([2])
    assert det.suspects(3) == []  # dead ranks are not suspects
    det.suspect([1, 3])
    det.clear_suspicion([1])
    assert det.external_suspects() == [3]
    det.clear_suspicion()
    assert det.suspects(3) == []
    with pytest.raises(ValueError):
        det.suspect([9])
    # per-SOURCE suspicion: one monitor clearing its claim must not
    # erase another's standing claim on the same rank
    det.suspect([1], source="operator")
    det.suspect([1], source="straggler")
    det.clear_suspicion([1], source="straggler")
    assert det.external_suspects() == [1]  # operator's claim stands
    det.clear_suspicion([1], source="operator")
    assert det.external_suspects() == []


def test_straggler_suspicion_never_attributes_a_nan_window(tmp_path):
    """A flagged straggler must NOT be declared dead by an
    unattributable NaN window: death attribution is numeric
    (streak_suspects), so rotating transients across OTHER ranks
    produce a bad_window_unattributed event and training continues —
    the healthy-but-slow rank survives."""
    from bluefog_tpu import resilience as R
    from bluefog_tpu.checkpoint import Checkpointer

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    base = {"w": jnp.eye(4)}

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    opt = optax.sgd(0.05)
    step = F.build_train_step(
        loss_fn, opt, mesh, comm_mode="cta", schedule=sched,
        donate=False, guard=F.GuardConfig(max_consecutive_bad=3))
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)

    def batch_fn(step_i):
        return jax.device_put(np.ones((N, 2, 4), np.float32),
                              NamedSharding(mesh, P("bf")))

    # transients ROTATE across ranks 0/1/2 (no rank holds a 3-streak)
    # while rank 6 is persistently slow and flagged
    plan = R.FaultPlan(N, [R.Fault(2, 0, "nan"), R.Fault(3, 1, "nan"),
                           R.Fault(4, 2, "nan")])
    det = FL.StragglerDetector(N, z_threshold=4.0, patience=2)
    fdet = R.FailureDetector(N)

    def step_times_fn(step_i, wall):
        t = np.full(N, 0.01)
        t[6] += 0.3
        return t

    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(step, params, ostate, batch_fn, steps=8,
                          checkpointer=ck, mesh=mesh, schedule=sched,
                          detector=fdet, fault_plan=plan,
                          checkpoint_every=0, sleep=lambda s: None,
                          straggler=det, step_times_fn=step_times_fn)
    ck.close()
    kinds = [e.kind for e in res.events]
    assert "bad_window_unattributed" in kinds
    assert "rank_dead" not in kinds  # nobody executed
    assert not res.dead_mask.any() and res.n_rollbacks == 0
    assert fdet.external_suspects() == [6]  # still NAMED, not shot


# --------------------------------------------------------------------- #
# in-graph health vector
# --------------------------------------------------------------------- #
def _toy(mesh, **kwargs):
    base = {"w": jnp.eye(4), "b": jnp.zeros((4,))}

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"] + params["b"]) ** 2)

    opt = optax.sgd(0.05, momentum=0.9)
    step = F.build_train_step(loss_fn, opt, mesh, donate=False, **kwargs)
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)
    batch = jax.device_put(
        np.random.RandomState(0).randn(N, 2, 4).astype(np.float32),
        NamedSharding(mesh, P("bf")))
    return step, params, ostate, batch


@pytest.mark.parametrize("kwargs", [
    dict(comm_mode="cta"),
    dict(comm_mode="atc"),
    dict(comm_mode="atc", overlap="bucketed", overlap_buckets=2),
], ids=["cta", "atc", "atc-bucketed"])
def test_health_disabled_is_bit_identical(kwargs):
    """Acceptance: with health=None the outputs are bit-identical to
    the health-enabled build's (params/opt_state/loss), and each build
    compiles exactly one executable a round of the schedule: three
    steps over its three rounds make three, three more make none."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    s0, params, ostate, batch = _toy(mesh, schedule=sched, **kwargs)
    s1, *_ = _toy(mesh, schedule=sched, health=F.HealthConfig(), **kwargs)
    p0, o0 = params, ostate
    p1, o1 = params, ostate
    for i in range(2 * len(sched)):
        p0, o0, l0 = s0(p0, o0, batch, i)
        p1, o1, l1, hv = s1(p1, o1, batch, i)
        if i + 1 >= len(sched):     # one program a round, then none
            assert s0.jitted._cache_size() == len(sched)
            assert s1.jitted._cache_size() == len(sched)
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    for a, b in zip(jax.tree.leaves((p0, o0)), jax.tree.leaves((p1, o1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s0.health_config is None
    assert isinstance(hv, F.HealthVector)


def test_health_vector_semantics():
    """Field-level checks: shapes [n]; loss mirrors the loss output;
    consensus is ~0 when every rank holds identical params (a
    row-stochastic combine is then the identity) and > 0 once ranks
    disagree; the consensus term equals a by-hand recomputation from
    the combine's inputs/outputs."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    spec = one_peer_dynamic_schedule(N)[0]
    step, params, ostate, batch = _toy(
        mesh, comm_mode="atc", topology=spec, health=F.HealthConfig())
    p, o, loss, hv = step(params, ostate, batch, jnp.int32(0))
    for field in hv:
        assert np.asarray(field).shape == (N,)
        assert np.asarray(field).dtype == np.float32
    np.testing.assert_array_equal(np.asarray(hv.loss),
                                  np.asarray(loss, np.float32))
    assert (np.asarray(hv.grad_norm) > 0).all()
    assert (np.asarray(hv.update_norm) > 0).all()
    assert np.asarray(hv.skipped).max() == 0.0

    # step 0 starts from identical ranks: the ATC combine mixes
    # already-applied (divergent) updates -> consensus > 0
    assert (np.asarray(hv.consensus) > 0).all()

    # by-hand: ATC consensus = || applied - combine(applied) || per rank
    applied = {k: np.asarray(v) for k, v in p.items()}  # post-combine
    # recompute the combine input: apply the same sgd update eagerly
    lr_params = jax.tree.map(lambda x: np.asarray(x), params)
    grads = jax.vmap(jax.grad(
        lambda pp, bb: jnp.mean((bb @ pp["w"] + pp["b"]) ** 2)))(
            lr_params, np.asarray(batch))
    pre = jax.tree.map(lambda x, g: np.asarray(x) - 0.05 * np.asarray(g),
                       lr_params, grads)
    M = np.zeros((N, N))
    from bluefog_tpu.resilience.healing import mixing_matrix

    M = mixing_matrix(spec)
    expect = np.zeros(N)
    for k in ("w", "b"):
        flat = pre[k].reshape(N, -1)
        expect += ((flat - M @ flat) ** 2).sum(axis=1)
    np.testing.assert_allclose(np.asarray(hv.consensus),
                               np.sqrt(expect), rtol=1e-4)


def test_health_zero_recompiles_across_fault_patterns():
    """Acceptance: health enabled (guard too) — zero recompiles across
    fault patterns, asserted via jit cache sizes (the GuardConfig
    methodology from tests/test_resilience.py): the step holds one
    program a round of its schedule once a cycle has run, and no fault
    pattern adds one."""
    from bluefog_tpu.resilience import FaultPlan

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    step, params, ostate, _ = _toy(
        mesh, comm_mode="atc", schedule=sched,
        guard=F.GuardConfig(), health=F.HealthConfig())
    plans = [FaultPlan.healthy(N),
             FaultPlan.nan_burst(N, rank=1, step=0, duration=1),
             FaultPlan.nan_burst(N, rank=5, step=1, duration=2),
             FaultPlan.rank_death(N, rank=2, step=0)]
    sharding = NamedSharding(mesh, P("bf"))
    for i in range(len(sched)):     # the cycle: one program a round
        step(params, ostate, jax.device_put(
            np.zeros((N, 2, 4), np.float32), sharding), jnp.int32(i),
            step.default_comm_weights)
    assert step.jitted._cache_size() == len(sched)
    for i, plan in enumerate(plans):
        raw = np.random.RandomState(i).randn(N, 2, 4).astype(np.float32)
        batch = jax.device_put(plan.corrupt_batch(raw, i), sharding)
        p, o, loss, sk, hv = step(params, ostate, batch, jnp.int32(i),
                                  step.default_comm_weights)
        assert step.jitted._cache_size() == len(sched), plan
        # the guard's actual skip flags ride the health vector
        np.testing.assert_array_equal(
            np.asarray(hv.skipped),
            np.asarray(sk).astype(np.float32))
        codes = plan.corrupt_codes(i)
        np.testing.assert_array_equal(np.asarray(sk) != 0, codes != 0)


def test_train_step_records_edge_traffic():
    """Each on-cycle dispatch adds the per-rank payload to every
    declared edge of the round's topology."""
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    step, params, ostate, batch = _toy(mesh, comm_mode="cta",
                                       schedule=sched)
    reg = observe.get_registry()
    edges0 = FL.edge_list(sched[0])
    before = reg.counter("bf_edge_bytes_total", src=edges0[0][0],
                         dst=edges0[0][1]).value
    step(params, ostate, batch, jnp.int32(0))
    payload = sum(l.nbytes for l in jax.tree.leaves(params)) // N
    for (src, dst) in edges0:
        assert reg.counter("bf_edge_bytes_total", src=src,
                           dst=dst).value >= payload
    after = reg.counter("bf_edge_bytes_total", src=edges0[0][0],
                        dst=edges0[0][1]).value
    assert after == before + payload

    # a topology passed alongside a NON-neighbor comm mode runs no
    # exchange — it must not count phantom edge bytes either
    step2, params2, ostate2, batch2 = _toy(
        mesh, comm_mode="gradient_allreduce", topology=sched[0])
    mid = reg.counter("bf_edge_bytes_total", src=edges0[0][0],
                      dst=edges0[0][1]).value
    step2(params2, ostate2, batch2, jnp.int32(0))
    assert reg.counter("bf_edge_bytes_total", src=edges0[0][0],
                       dst=edges0[0][1]).value == mid


# --------------------------------------------------------------------- #
# windowed traffic deltas + timing twin (ISSUE 15: the control plane's
# telemetry feed)
# --------------------------------------------------------------------- #
def test_record_edge_timing_bills_seconds_family():
    reg = MetricsRegistry()
    FL.record_edge_timing(None, 0.25, registry=reg, pairs=[(0, 1)])
    FL.record_edge_timing(None, 0.75, registry=reg, pairs=[(0, 1), (2, 3)])
    snap = FL.traffic_snapshot(reg, metric="bf_edge_seconds_total")
    assert snap[(0, 1)] == pytest.approx(1.0)
    assert snap[(2, 3)] == pytest.approx(0.75)
    # the per-leg label keeps hierarchical legs separable, same as bytes
    FL.record_edge_timing(None, 0.5, registry=reg, pairs=[(0, 2)],
                          link="dcn")
    assert FL.traffic_snapshot(
        reg, link="dcn", metric="bf_edge_seconds_total") == {(0, 2): 0.5}
    # and seconds never leak into the BYTES family the compiler reads
    assert FL.traffic_snapshot(reg) == {}


def test_traffic_deltas_window_semantics():
    """take() returns what moved SINCE the previous take — never
    lifetime totals — and construction snapshots the registry, so
    pre-history is excluded from the first window.  peek() reads the
    window without advancing it."""
    reg = MetricsRegistry()
    FL.record_edge_timing(None, 10.0, registry=reg, pairs=[(0, 1)])
    deltas = FL.TrafficDeltas(reg, metric="bf_edge_seconds_total")
    assert deltas.take() == {}  # the 10s of pre-history is not a delta
    FL.record_edge_timing(None, 2.0, registry=reg, pairs=[(0, 1)])
    FL.record_edge_timing(None, 3.0, registry=reg, pairs=[(4, 5)])
    assert deltas.peek() == {(0, 1): 2.0, (4, 5): 3.0}
    assert deltas.peek() == {(0, 1): 2.0, (4, 5): 3.0}  # no advance
    assert deltas.take() == {(0, 1): 2.0, (4, 5): 3.0}
    assert deltas.take() == {}  # quiet window: quiet edges omitted
    FL.record_edge_timing(None, 1.5, registry=reg, pairs=[(0, 1)])
    assert deltas.take() == {(0, 1): 1.5}


def test_traffic_snapshot_since_subtracts_marker():
    reg = MetricsRegistry()
    FL.record_edge_traffic(None, registry=reg, pairs=[(0, 1)],
                           payload_bytes=100)
    mark = FL.traffic_snapshot(reg)
    FL.record_edge_traffic(None, registry=reg, pairs=[(0, 1)],
                           payload_bytes=40)
    FL.record_edge_traffic(None, registry=reg, pairs=[(2, 3)],
                           payload_bytes=7)
    assert FL.traffic_snapshot(reg, since=mark) == {(0, 1): 40.0,
                                                    (2, 3): 7.0}
    # an edge with no NEW traffic is omitted, not reported as zero
    assert (0, 1) not in FL.traffic_snapshot(
        reg, since=FL.traffic_snapshot(reg))
