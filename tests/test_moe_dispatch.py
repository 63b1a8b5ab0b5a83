"""MoE expert parallelism (ISSUE 19): compiled all-to-all dispatch +
the decentralized expert-sharded train step.

Contracts under test:

* **dispatch exactness** — ``moe.all_to_all_dispatch`` over the
  compiled schedule is BIT-identical to ``lax.all_to_all`` (the naive
  baseline it outperforms on the wire), the transpose plan retraces the
  wire exactly (round trip = identity), and the host-side
  ``DispatchPlan`` issues exactly the permutes
  ``predicted_collectives`` charges for (the HLO byte-for-byte half
  lives in tests/test_hlo_guarantees.py).
* **capacity overflow is traced data** — the keep mask is a pure
  function of (batch, route_table, capacity_mask): same seed + same
  mask ⇒ bit-identical drop set across invocations, on the fp32 AND
  the int8 wire (the wire dtype may perturb values, never routing).
* **resilience is data, not structure** — ``heal_route_table``
  reroutes dead destinations round-robin over surviving replicas
  (raising when an expert has no survivor), and a full expert-machine
  kill→heal cycle through ``build_train_step(..., moe=...)`` completes
  with ZERO recompiles (jit cache pinned), experts staying rank-local
  while the router mixes.
* **composition** — guard + health and error-feedback compressed
  mixing build and run unchanged; the mix/EF state and wire layout
  cover ONLY the shared (non-expert) leaves.
* **control plane** — ``TopologyControlPlane.plan_all_to_all`` prices
  the dispatch schedule against the last telemetry-calibrated pod and
  re-plans lazily after each trigger (``a2a_replans``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import config
from bluefog_tpu.moe import (DispatchPlan, all_to_all_dispatch,
                             capacity_mask_of, default_capacity,
                             default_route_table, dispatch_plan,
                             expert_owner, heal_route_table,
                             init_moe_params, make_moe_loss, moe_apply,
                             naive_all_to_all)
from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology.compiler import (PodSpec, compile_all_to_all,
                                           naive_all_to_all_cost,
                                           one_shot_all_to_all_cost)
from bluefog_tpu.topology.torus import torus_one_peer_schedule

pytestmark = pytest.mark.moe

N = 8
POD = PodSpec(4, 2, dcn_cost=4.0)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


@pytest.fixture(scope="module")
def plan():
    return dispatch_plan(compile_all_to_all(POD).schedule)


def _shards(seed=0, c=3, d=4):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(N, N, c, d)).astype(np.float32)


# ------------------------------------------------------------------ #
# the compiled wire: exactness against lax.all_to_all
# ------------------------------------------------------------------ #
def test_compile_beats_naive_and_hits_lower_bound():
    compiled = compile_all_to_all(POD)
    cost = compiled.score["cost_to_dispatch"]
    assert cost < naive_all_to_all_cost(POD)
    # the one-shot congestion bound is unbeatable: the period must
    # move every pair once, and no partition can beat the single
    # round that congests least
    assert cost >= one_shot_all_to_all_cost(POD) - 1e-9
    assert compiled.score["compiled_advantage"] > 1.0
    # every (src, dst) pair covered exactly once per period
    seen = set()
    for r in compiled.schedule:
        for cls in r.shift_classes:
            for p in cls.perm:
                assert p not in seen
                seen.add(p)
    assert len(seen) == N * (N - 1)


def test_dispatch_bit_identical_to_lax_all_to_all(mesh, plan):
    x = _shards()

    def run(fn):
        sm = jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                           in_specs=P("bf"), out_specs=P("bf"),
                           check_vma=False)
        return np.asarray(jax.jit(sm)(x))

    ours = run(lambda v: all_to_all_dispatch(v, plan, "bf"))
    ref = run(lambda v: naive_all_to_all(v, "bf"))
    np.testing.assert_array_equal(ours, ref)


def test_transpose_round_trip_is_identity(mesh, plan):
    x = _shards(seed=3)
    back = plan.transpose()

    sm = jax.shard_map(
        lambda v: all_to_all_dispatch(
            all_to_all_dispatch(v[0], plan, "bf"), back, "bf")[None],
        mesh=mesh, in_specs=P("bf"), out_specs=P("bf"), check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(sm)(x)), x)


def test_int8_wire_close_and_deterministic(mesh, plan):
    x = _shards(seed=5)

    def run():
        sm = jax.shard_map(
            lambda v: all_to_all_dispatch(v[0], plan, "bf",
                                          wire_dtype="int8")[None],
            mesh=mesh, in_specs=P("bf"), out_specs=P("bf"),
            check_vma=False)
        return np.asarray(jax.jit(sm)(x))

    a, b = run(), run()
    np.testing.assert_array_equal(a, b)     # quantization is exact data
    ref_sm = jax.shard_map(
        lambda v: naive_all_to_all(v[0], "bf")[None], mesh=mesh,
        in_specs=P("bf"), out_specs=P("bf"), check_vma=False)
    ref = np.asarray(jax.jit(ref_sm)(x))
    err = np.abs(a - ref).max() / np.abs(ref).max()
    assert err < 0.02


def test_plan_matches_predicted_collectives(plan):
    compiled = compile_all_to_all(POD)
    pred = compiled.predicted_collectives(64.0)
    assert plan.permutes_per_period == pred["permutes_per_period"]
    assert plan.transpose().permutes_per_period == plan.permutes_per_period
    with pytest.raises(ValueError):
        dispatch_plan([])


# ------------------------------------------------------------------ #
# route tables + capacity: traced resilience data
# ------------------------------------------------------------------ #
def test_route_table_defaults_and_validation():
    route = default_route_table(N, 4)
    assert route.shape == (N, 4) and route.dtype == np.int32
    for src in range(N):
        for e in range(4):
            assert expert_owner(int(route[src, e]), 4) == e
    # sources fan out round-robin: both replicas of each expert serve
    for e in range(4):
        assert len(set(route[:, e].tolist())) == 2
    for bad in (0, N + 1):
        with pytest.raises(ValueError):
            default_route_table(N, bad)


def test_heal_reroutes_round_robin_over_survivors():
    route = default_route_table(N, 4)
    dead = np.zeros(N, bool)
    dead[5] = True                       # a replica of expert 1
    healed = heal_route_table(route, dead, 4)
    assert healed.shape == route.shape and healed.dtype == np.int32
    assert not (healed == 5).any()
    # only entries that pointed at the dead rank moved
    moved = healed != route
    assert (route[moved] == 5).all()
    # ...and they still point at replicas of the SAME expert
    assert all(expert_owner(int(r), 4) == 1 for r in healed[moved])
    # the untouched mask column semantics
    np.testing.assert_array_equal(capacity_mask_of(dead),
                                  (1.0 - dead).astype(np.float32))


def test_heal_raises_when_expert_has_no_survivor():
    route = default_route_table(N, 4)
    dead = np.zeros(N, bool)
    dead[[1, 5]] = True                  # BOTH replicas of expert 1
    with pytest.raises(ValueError, match="expert 1 has no surviving"):
        heal_route_table(route, dead, 4)


def test_default_capacity_env_knob(monkeypatch):
    assert default_capacity(8, N) == int(np.ceil(1.25 * 8 / N))
    assert default_capacity(1, N) == 1          # floor at 1
    monkeypatch.setenv("BLUEFOG_MOE_CAPACITY_FACTOR", "2.0")
    assert config.moe_capacity_factor() == 2.0
    assert default_capacity(8, N) == 2
    # bad env values fall back to the default (the env-knob idiom);
    # an EXPLICIT bad factor argument is a caller error and raises
    monkeypatch.setenv("BLUEFOG_MOE_CAPACITY_FACTOR", "-1")
    assert config.moe_capacity_factor() == 1.25
    monkeypatch.setenv("BLUEFOG_MOE_CAPACITY_FACTOR", "nope")
    assert config.moe_capacity_factor() == 1.25
    with pytest.raises(ValueError):
        default_capacity(8, N, factor=0.0)


def test_capacity_overflow_drop_set_deterministic(mesh, plan):
    """Same seed + same capacity mask ⇒ bit-identical keep mask across
    separate jit invocations, on the fp32 and the int8 wire — routing
    is data, and the wire encoding must never perturb it."""
    rng = np.random.default_rng(11)
    tokens = rng.normal(size=(N, 6, 4)).astype(np.float32)
    params = init_moe_params(jax.random.PRNGKey(2), 4, 4, 4)
    route = default_route_table(N, 4)
    dead = np.zeros(N, bool)
    dead[2] = True
    cmask = capacity_mask_of(dead)
    healed = heal_route_table(route, dead, 4)

    def keep_of(wire):
        def run(tok, rt, cm):
            _, keep = moe_apply(params, tok, rt, cm, plan=plan,
                                axis_name="bf", capacity=2,
                                wire_dtype=wire)
            return keep
        sm = jax.shard_map(
            lambda t, r, c: run(t[0], r[0], c[0])[None], mesh=mesh,
            in_specs=(P("bf"), P("bf"), P("bf")), out_specs=P("bf"),
            check_vma=False)
        tiled = np.broadcast_to(cmask[None], (N, N)).copy()
        return np.asarray(jax.jit(sm)(tokens, healed, tiled))

    fp_a, fp_b = keep_of(None), keep_of(None)
    q_a = keep_of("int8")
    np.testing.assert_array_equal(fp_a, fp_b)
    np.testing.assert_array_equal(fp_a, q_a)
    # with capacity 2 and 6 tokens/rank, overflow MUST have dropped
    # something, and every token routed at the dead rank dropped too
    assert not fp_a.all()


def test_dispatch_rejects_unknown_wire_dtype(plan):
    with pytest.raises(ValueError, match="wire_dtype"):
        all_to_all_dispatch(jnp.zeros((N, 2)), plan, "bf",
                            wire_dtype="fp8")


# ------------------------------------------------------------------ #
# the expert-sharded train step: kill→heal with zero recompiles
# ------------------------------------------------------------------ #
_OPT = optax.sgd(1e-2)


def _moe_state(mesh, d=4, h=4, e=4):
    sh = NamedSharding(mesh, P("bf"))
    keys = jax.random.split(jax.random.PRNGKey(0), N)
    per_rank = [init_moe_params(k, d, h, e) for k in keys]
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *per_rank)
    # shared leaves start at consensus, experts rank-diverse
    params["router"]["w"] = jnp.broadcast_to(
        per_rank[0]["router"]["w"][None], (N, d, e))
    ostate = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[_OPT.init(p) for p in per_rank])
    put = lambda t: jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x), sh), t)
    return put(params), put(ostate), put


def _moe_batch(put, route, cmask, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.normal(size=(N, 6, 4)).astype(np.float32)
    return (put(tokens), put(np.asarray(route)),
            put(np.broadcast_to(cmask[None], (N, N)).copy()))


def test_expert_kill_heal_cycle_zero_recompiles(mesh, plan):
    """ISSUE 19 acceptance: an expert-machine kill→heal cycle through
    the fused step is pure traced data — the round's program is the
    one its first visit compiled (steps 0, P, 2P of a P-round schedule
    run round 0), expert weights stay rank-local, the router keeps
    mixing."""
    loss_fn = make_moe_loss(plan, "bf", 3)
    sched = torus_one_peer_schedule((4, 2), "exp2")
    P_ = len(sched)
    step = F.build_train_step(loss_fn, _OPT, mesh, comm_mode="cta",
                              schedule=sched,
                              moe=F.MoEConfig(n_experts=4, capacity=3))
    assert step.moe_config.n_experts == 4
    p, o, put = _moe_state(mesh)
    route = default_route_table(N, 4)
    cmask0 = capacity_mask_of(np.zeros(N))
    p, o, loss = step(p, o, _moe_batch(put, route, cmask0),
                      jnp.int32(0))
    assert np.isfinite(np.asarray(loss)).all()
    baseline = step.jitted._cache_size()
    # kill rank 5 -> healed route + mask are the SAME traced operands
    dead = np.zeros(N, bool)
    dead[5] = True
    healed = heal_route_table(route, dead, 4)
    p, o, _ = step(p, o, _moe_batch(put, healed, capacity_mask_of(dead),
                                    seed=1), jnp.int32(P_))
    # heal back: the machine returns
    p, o, _ = step(p, o, _moe_batch(put, route, cmask0, seed=2),
                   jnp.int32(2 * P_))
    assert step.jitted._cache_size() == baseline == 1
    wi = np.asarray(p["expert"]["wi"])
    assert not np.allclose(wi[0], wi[1])     # experts stayed local
    rw = np.asarray(p["router"]["w"])
    r_spread = np.abs(rw - rw.mean(0)).max()
    assert r_spread < np.abs(wi - wi.mean(0)).max()  # router mixed


def test_moe_composes_with_guard_and_health(mesh, plan):
    loss_fn = make_moe_loss(plan, "bf", 3)
    sched = torus_one_peer_schedule((4, 2), "exp2")
    step = F.build_train_step(loss_fn, _OPT, mesh, comm_mode="atc",
                              schedule=sched,
                              guard=F.GuardConfig(),
                              health=F.HealthConfig(),
                              moe=F.MoEConfig(n_experts=4, capacity=3))
    p, o, put = _moe_state(mesh)
    route = default_route_table(N, 4)
    w = step.default_comm_weights
    out = step(p, o, _moe_batch(put, route, capacity_mask_of(np.zeros(N))),
               jnp.int32(0), w)
    baseline = step.jitted._cache_size()
    dead = np.zeros(N, bool)
    dead[5] = True
    out = step(out[0], out[1],
               _moe_batch(put, heal_route_table(route, dead, 4),
                          capacity_mask_of(dead), seed=1),
               jnp.int32(len(sched)), w)    # round 0 again, healed data
    assert step.jitted._cache_size() == baseline == 1
    assert isinstance(out[-1], F.HealthVector)


def test_moe_topk_mix_covers_only_shared_leaves(mesh, plan):
    """Compressed mixing under moe: the EF/mix state and the wire
    layout cover the router ONLY — expert leaves never touch the
    consensus wire, compressed or not."""
    loss_fn = make_moe_loss(plan, "bf", 3)
    sched = torus_one_peer_schedule((4, 2), "exp2")
    step = F.build_train_step(
        loss_fn, _OPT, mesh, comm_mode="cta", schedule=sched,
        compress=F.MixCompressConfig(ratio=0.5),
        moe=F.MoEConfig(n_experts=4, capacity=3))
    p, o, put = _moe_state(mesh)
    layout = step.mix_wire_layout(p)
    assert len(layout) == 1                  # one bucket: the router
    assert layout[0]["numel"] == 4 * 4
    ms = step.init_mix_state(p)
    route = default_route_table(N, 4)
    cmask = capacity_mask_of(np.zeros(N))
    state = (o, ms)
    p, state, loss = step(p, state, _moe_batch(put, route, cmask),
                          jnp.int32(0))
    baseline = step.jitted._cache_size()
    dead = np.zeros(N, bool)
    dead[5] = True
    p, state, _ = step(p, state,
                       _moe_batch(put, heal_route_table(route, dead, 4),
                                  capacity_mask_of(dead), seed=1),
                       jnp.int32(len(sched)))   # round 0, healed data
    assert step.jitted._cache_size() == baseline == 1
    wi = np.asarray(p["expert"]["wi"])
    assert not np.allclose(wi[0], wi[1])


def test_moe_config_validation(mesh, plan):
    with pytest.raises(ValueError):
        F.MoEConfig(n_experts=0, capacity=1)
    with pytest.raises(ValueError):
        F.MoEConfig(n_experts=4, capacity=0)
    loss_fn = make_moe_loss(plan, "bf", 3)
    sched = torus_one_peer_schedule((4, 2), "exp2")
    moe = F.MoEConfig(n_experts=4, capacity=3)
    with pytest.raises(ValueError, match="moe"):
        F.build_train_step(loss_fn, _OPT, mesh,
                           comm_mode="gradient_allreduce",
                           schedule=sched, moe=moe)
    with pytest.raises(ValueError, match="moe"):
        F.build_train_step(loss_fn, _OPT, mesh, comm_mode="push_sum",
                           schedule=sched, moe=moe)
    with pytest.raises(ValueError):
        F.MoEConfig(n_experts=4, capacity=3, expert_path_tokens=())


def test_moe_rejects_all_expert_params(mesh, plan):
    """A parameter tree with NO shared leaf is a config error the
    build surfaces at trace time, not a silent no-mix step."""

    def loss_fn(params, batch):
        tokens, route_row, cm = batch
        out, _ = moe_apply({"router": {"w": jnp.zeros((4, 4))},
                            "expert": params["expert"]}, tokens,
                           route_row, cm, plan=plan, axis_name="bf",
                           capacity=3)
        return jnp.mean(out ** 2)

    step = F.build_train_step(
        loss_fn, _OPT, mesh, comm_mode="cta",
        schedule=torus_one_peer_schedule((4, 2), "exp2"),
        moe=F.MoEConfig(n_experts=4, capacity=3))
    sh = NamedSharding(mesh, P("bf"))
    put = lambda t: jax.tree.map(
        lambda x: jax.device_put(jnp.asarray(x), sh), t)
    params = put({"expert": {"wi": jnp.zeros((N, 4, 4)),
                             "wo": jnp.zeros((N, 4, 4))}})
    ostate = put(jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[_OPT.init({"wi": jnp.zeros((4, 4)),
                                           "wo": jnp.zeros((4, 4))})
                                for _ in range(N)]))
    with pytest.raises(ValueError, match="EVERY param leaf"):
        step(params, ostate,
             _moe_batch(put, default_route_table(N, 4),
                        capacity_mask_of(np.zeros(N))), jnp.int32(0))


# ------------------------------------------------------------------ #
# control plane: a2a re-pricing from congestion telemetry
# ------------------------------------------------------------------ #
@pytest.mark.topology
def test_control_plane_replans_a2a_from_telemetry():
    """A congestion trigger re-prices the pod; the NEXT
    plan_all_to_all() call re-plans the dispatch schedule against the
    calibrated costs (lazily, counted in a2a_replans), and repeated
    calls reuse the cache."""
    from bluefog_tpu.observe import MetricsRegistry
    from bluefog_tpu.observe.fleet import record_edge_timing
    from bluefog_tpu.topology import TopologyControlPlane
    from bluefog_tpu.topology.spec import DynamicTopology

    pod = PodSpec(4, 2, ici_cost=1.0, dcn_cost=4.0)
    ew = {}
    for s in (1, 2, 4, 6, 7):
        for i in range(N):
            ew[(i, (i + s) % N)] = 1.0 / 6
    carrier = [DynamicTopology.from_edges(N, ew, [1.0 / 6] * N)] * 4
    reg = MetricsRegistry()
    plane = TopologyControlPlane(pod, carrier, registry=reg, window=4,
                                 patience=2, degrade_ratio=1.5,
                                 margin=0.05, cooldown=4, probation=3,
                                 synchronous=True)
    base_plan = plane.plan_all_to_all()
    assert plane.a2a_replans == 1
    assert plane.plan_all_to_all() is base_plan      # cached
    # one hot edge, persistently: windows at 4 and 8 -> trigger at 8
    live = np.zeros(N, bool)
    for step in range(1, 9):
        for spec in plane.active_schedule():
            for e, v in zip(spec.edges, spec.edge_weight_values):
                if v != 0.0:
                    nominal = plane.pod.round_cost([e])
                    slow = 10.0 if e == (0, 2) else 1.0
                    record_edge_timing(None, nominal * slow,
                                       registry=reg, pairs=[e])
        plane.on_step(step, dead_mask=live)
    assert plane.triggers == 1
    replanned = plane.plan_all_to_all()
    assert plane.a2a_replans == 2
    assert replanned is not base_plan
    # the calibrated pod priced the same wire higher
    assert (replanned.score["cost_to_dispatch"]
            > base_plan.score["cost_to_dispatch"])
    assert plane.plan_all_to_all() is replanned      # cached again
