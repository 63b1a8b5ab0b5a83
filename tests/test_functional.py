"""Fully-jitted decentralized train step (optim/functional.py).

Convergence checks mirror the reference's synthetic linear problem design
(reference test/torch_optimizer_test.py:100 LinearProblemBuilder).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology.graphs import ExponentialTwoGraph, RingGraph
from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

N = 8
DIM = 4


def _mesh(n=N):
    return Mesh(np.array(jax.devices()[:n]), ("bf",))


def _linear_problem(seed=0):
    """Per-rank (A_r, b_r) with a common true x; global least squares."""
    rng = np.random.RandomState(seed)
    x_true = rng.randn(DIM)
    As, bs = [], []
    for r in range(N):
        A = rng.randn(16, DIM)
        b = A @ x_true + 0.01 * rng.randn(16)
        As.append(A)
        bs.append(b)
    return np.stack(As), np.stack(bs), x_true


def _topology_spec():
    from bluefog_tpu.context import _uniform_topology_spec
    return _uniform_topology_spec(ExponentialTwoGraph(N))


def loss_fn(params, batch):
    A, b = batch
    pred = A @ params["x"]
    return jnp.mean((pred - b) ** 2)


@pytest.mark.parametrize("comm_mode", ["cta", "atc", "gradient_allreduce"])
def test_linear_convergence(comm_mode):
    mesh = _mesh()
    As, bs, x_true = _linear_problem()
    spec = _topology_spec() if comm_mode in ("cta", "atc") else None
    step_fn = F.build_train_step(
        loss_fn, optax.sgd(0.05), mesh, comm_mode=comm_mode,
        topology=spec)
    params = F.rank_major({"x": jnp.zeros(DIM)}, mesh)
    opt_state = F.rank_major(optax.sgd(0.05).init({"x": jnp.zeros(DIM)}), mesh)
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    for i in range(300):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          jnp.int32(i))
    xs = np.asarray(params["x"])
    # every rank near the truth, and ranks agree
    assert np.abs(xs - x_true).max() < 0.15, np.abs(xs - x_true).max()
    assert float(F.consensus_distance(params)) < 1e-2


def test_dynamic_schedule_consensus():
    """One-peer dynamic exp2 schedule via lax.switch: pure averaging (lr=0)
    must drive ranks to consensus."""
    mesh = _mesh()
    rounds = int(np.log2(N))
    schedule = one_peer_dynamic_schedule(N)
    assert len(schedule) == rounds

    step_fn = F.build_train_step(
        loss_fn, optax.sgd(0.0), mesh, comm_mode="cta", schedule=schedule)
    As, bs, _ = _linear_problem()
    params = {"x": jax.device_put(
        np.arange(N * DIM, dtype=np.float64).reshape(N, DIM),
        NamedSharding(mesh, P("bf")))}
    opt_state = F.rank_major(optax.sgd(0.0).init({"x": jnp.zeros(DIM)}), mesh)
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    for i in range(6 * rounds):
        params, opt_state, _ = step_fn(params, opt_state, batch, jnp.int32(i))
    assert float(F.consensus_distance(params)) < 1e-10


def test_periodic_communication():
    """num_steps_per_communication=2: combine fires only on even steps."""
    mesh = _mesh()
    spec = _topology_spec()
    step_fn = F.build_train_step(
        loss_fn, optax.sgd(0.0), mesh, comm_mode="cta", topology=spec,
        num_steps_per_communication=2)
    x0 = np.arange(N * DIM, dtype=np.float64).reshape(N, DIM)
    params = {"x": jax.device_put(x0, NamedSharding(mesh, P("bf")))}
    opt_state = F.rank_major(optax.sgd(0.0).init({"x": jnp.zeros(DIM)}), mesh)
    As, bs, _ = _linear_problem()
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    # step index 1: no communication -> params unchanged (lr=0)
    p1, opt_state, _ = step_fn(params, opt_state, batch, jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(p1["x"]), x0)
    # step index 2: communication -> consensus distance strictly drops
    p2, _, _ = step_fn(p1, opt_state, batch, jnp.int32(2))
    assert float(F.consensus_distance(p2)) < float(
        F.consensus_distance({"x": jnp.asarray(x0)}))


def test_dp_sp_composition():
    """2D mesh: 4-rank decentralized DP x 2-way sequence parallelism with
    ring attention inside the jitted step."""
    from bluefog_tpu import models
    from bluefog_tpu.context import _uniform_topology_spec

    n_dp, n_sp = 4, 2
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(n_dp, n_sp), ("bf", "sp"))
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32, attn_mode="ring",
                                  sp_axis="sp")
    model = models.Llama(cfg)
    t_total, t_local = 32, 16
    raw = np.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (n_dp, 2, t_total + 1), 0, cfg.vocab_size))
    inputs, targets = raw[:, :, :-1], raw[:, :, 1:]

    def llm_loss(params, batch):
        inp, tgt = batch
        offset = jax.lax.axis_index("sp") * t_local
        logits = model.apply(params, inp, pos_offset=offset)
        return jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, tgt))

    spec = _uniform_topology_spec(RingGraph(n_dp))
    step_fn = F.build_train_step(
        llm_loss, optax.adam(1e-3), mesh, comm_mode="atc", topology=spec,
        sp_axis="sp", batch_specs=P("bf", None, "sp"))

    base = models.Llama(models.LlamaConfig.tiny(dtype=jnp.float32)).init(
        jax.random.PRNGKey(1), jnp.asarray(inputs[0, :, :8]))
    params = F.rank_major(base, mesh)
    opt_state = F.rank_major(optax.adam(1e-3).init(base), mesh)
    sharding = NamedSharding(mesh, P("bf", None, "sp"))
    batch = (jax.device_put(inputs, sharding),
             jax.device_put(targets, sharding))

    losses = []
    for i in range(10):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          jnp.int32(i))
        losses.append(float(np.asarray(loss).mean()))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # training moves


def test_has_aux_state():
    """Mutable aux (batch-norm-style counter) threads through the step."""
    mesh = _mesh()

    def aux_loss(params, aux, batch):
        A, b = batch
        pred = A @ params["x"]
        return jnp.mean((pred - b) ** 2), {"count": aux["count"] + 1}

    step_fn = F.build_train_step(
        aux_loss, optax.sgd(0.01), mesh, comm_mode="cta",
        topology=_topology_spec(), has_aux=True)
    As, bs, _ = _linear_problem()
    params = F.rank_major({"x": jnp.zeros(DIM)}, mesh)
    aux = F.rank_major({"count": jnp.zeros((), jnp.int32)}, mesh)
    opt_state = F.rank_major(optax.sgd(0.01).init({"x": jnp.zeros(DIM)}), mesh)
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    for i in range(3):
        params, aux, opt_state, loss = step_fn(params, aux, opt_state, batch,
                                               jnp.int32(i))
    assert (np.asarray(aux["count"]) == 3).all()


def test_push_sum_invariant_and_convergence():
    """comm_mode='push_sum' on a DIRECTED ring (non-doubly-stochastic —
    plain neighbor averaging would bias toward some ranks): sum of
    ps weights stays == N every step (the reference's associated-P
    invariant, torch_win_ops_test.py:780-863), ranks reach consensus near
    the global least-squares solution."""
    from bluefog_tpu.topology.spec import Topology

    mesh = _mesh()
    # directed ring r -> r+1 (out-degree 1 everywhere)
    w = np.zeros((N, N))
    for r in range(N):
        w[r, (r + 1) % N] = 1.0
        w[r, r] = 1.0
    spec = Topology.from_weight_matrix(w)
    opt = optax.sgd(0.05)
    step_fn = F.build_train_step(
        loss_fn, opt, mesh, comm_mode="push_sum", topology=spec)
    As, bs, x_true = _linear_problem()
    params = F.rank_major({"x": jnp.zeros(DIM)}, mesh)
    base_state = F.rank_major(opt.init({"x": jnp.zeros(DIM)}), mesh)
    opt_state = (base_state, F.push_sum_weights(mesh))
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    for i in range(400):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          jnp.int32(i))
        if i % 97 == 0:
            ps_sum = float(np.sum(np.asarray(opt_state[1])))
            np.testing.assert_allclose(ps_sum, N, rtol=1e-5)
    ps_sum = float(np.sum(np.asarray(opt_state[1])))
    np.testing.assert_allclose(ps_sum, N, rtol=1e-5)
    xs = np.asarray(params["x"])
    assert np.abs(xs - x_true).max() < 0.15, np.abs(xs - x_true).max()
    assert float(F.consensus_distance(params)) < 1e-2


def test_push_sum_pure_mix_reaches_uniform_average():
    """lr=0 push-sum mixing on a directed exp2 graph converges every rank's
    de-biased value to the uniform initial average (the bias-correction
    property plain averaging lacks on directed graphs)."""
    mesh = _mesh()
    spec = _topology_spec()
    opt = optax.sgd(0.0)
    step_fn = F.build_train_step(
        loss_fn, opt, mesh, comm_mode="push_sum", topology=spec)
    init = np.arange(N, dtype=np.float64)[:, None] * np.ones((N, DIM))
    params = {"x": jax.device_put(init, NamedSharding(mesh, P("bf")))}
    base_state = F.rank_major(opt.init({"x": jnp.zeros(DIM)}), mesh)
    opt_state = (base_state, F.push_sum_weights(mesh))
    As, bs, _ = _linear_problem()
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    for i in range(60):
        params, opt_state, _ = step_fn(params, opt_state, batch, jnp.int32(i))
    xs = np.asarray(params["x"], np.float64)
    np.testing.assert_allclose(xs, np.mean(np.arange(N)), rtol=1e-5,
                               atol=1e-5)


def _multi_leaf_problem(seed=0):
    """Several leaves of mixed sizes so bucketing has real work."""
    rng = np.random.RandomState(seed)
    base = {"w1": jnp.asarray(rng.randn(DIM, 8) * 0.3),
            "b1": jnp.zeros((8,)),
            "w2": jnp.asarray(rng.randn(8, 1) * 0.3),
            "b2": jnp.zeros((1,))}

    def loss_fn(params, batch):
        A, b = batch
        h = jnp.tanh(A @ params["w1"] + params["b1"])
        pred = (h @ params["w2"] + params["b2"])[..., 0]
        return jnp.mean((pred - b) ** 2)

    return base, loss_fn


@pytest.mark.parametrize("comm_mode", ["cta", "atc"])
def test_bucketed_overlap_numerical_parity(comm_mode):
    """overlap='bucketed' computes the SAME training trajectory as the
    non-overlapped step (acceptance: same params/loss to f32
    tolerance) — the weighted combine distributes over concatenation,
    so bucketing is a schedule change, not a math change."""
    mesh = _mesh()
    base, loss_fn = _multi_leaf_problem()
    opt = optax.sgd(0.05)
    spec = _topology_spec()
    plain = F.build_train_step(
        loss_fn, opt, mesh, comm_mode=comm_mode, topology=spec,
        donate=False)
    bucketed = F.build_train_step(
        loss_fn, opt, mesh, comm_mode=comm_mode, topology=spec,
        donate=False, overlap="bucketed", overlap_buckets=3)
    As, bs, _ = _linear_problem()
    bs = bs[..., 0] * 0 + bs.mean(-1)
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    pA = pB = F.rank_major(base, mesh)
    oA = oB = F.rank_major(opt.init(base), mesh)
    for i in range(8):
        pA, oA, lA = plain(pA, oA, batch, jnp.int32(i))
        pB, oB, lB = bucketed(pB, oB, batch, jnp.int32(i))
    np.testing.assert_allclose(np.asarray(lA, np.float32),
                               np.asarray(lB, np.float32), rtol=1e-6)
    for k in base:
        np.testing.assert_allclose(
            np.asarray(pA[k], np.float32), np.asarray(pB[k], np.float32),
            rtol=1e-6, atol=1e-7, err_msg=f"leaf {k}")


def test_bucketed_dynamic_schedule_consensus():
    """Bucketed combine through the lax.switch dynamic schedule: lr=0
    one-peer averaging still reaches exact consensus (the plumbing the
    overlap engine must not disturb)."""
    mesh = _mesh()
    rounds = int(np.log2(N))
    schedule = one_peer_dynamic_schedule(N)
    step_fn = F.build_train_step(
        loss_fn, optax.sgd(0.0), mesh, comm_mode="cta",
        schedule=schedule, overlap="bucketed", overlap_buckets=2)
    As, bs, _ = _linear_problem()
    params = {"x": jax.device_put(
        np.arange(N * DIM, dtype=np.float64).reshape(N, DIM),
        NamedSharding(mesh, P("bf")))}
    opt_state = F.rank_major(optax.sgd(0.0).init({"x": jnp.zeros(DIM)}),
                             mesh)
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    for i in range(6 * rounds):
        params, opt_state, _ = step_fn(params, opt_state, batch,
                                       jnp.int32(i))
    assert float(F.consensus_distance(params)) < 1e-10


def test_bucketed_periodic_communication_still_applies_updates():
    """ATC bucketed + num_steps_per_communication=2: off-cycle steps
    skip the collectives but MUST still apply the optax update."""
    mesh = _mesh()
    base, loss_fn_ml = _multi_leaf_problem()
    opt = optax.sgd(0.05)
    step_fn = F.build_train_step(
        loss_fn_ml, opt, mesh, comm_mode="atc",
        topology=_topology_spec(), num_steps_per_communication=2,
        overlap="bucketed", overlap_buckets=2)
    As, bs, _ = _linear_problem()
    bs = bs.mean(-1)
    params = F.rank_major(base, mesh)
    opt_state = F.rank_major(opt.init(base), mesh)
    batch = (jax.device_put(As, NamedSharding(mesh, P("bf"))),
             jax.device_put(bs, NamedSharding(mesh, P("bf"))))
    before = np.asarray(params["w1"])
    # odd step: no communication, but the update must land
    params, opt_state, _ = step_fn(params, opt_state, batch, jnp.int32(1))
    assert np.abs(np.asarray(params["w1"]) - before).max() > 0


def test_bucketed_overlap_mode_validation():
    """Unsupported overlap combos are rejected up front."""
    mesh = _mesh()
    spec = _topology_spec()
    with pytest.raises(ValueError, match="overlap"):
        F.build_train_step(loss_fn, optax.sgd(0.1), mesh,
                           comm_mode="cta", topology=spec,
                           overlap="bogus")
    with pytest.raises(ValueError, match="bucketed"):
        F.build_train_step(loss_fn, optax.sgd(0.1), mesh,
                           comm_mode="gradient_allreduce",
                           overlap="bucketed")
    # push_sum + bucketed is supported: the pair's buckets mix as a unit
    step = F.build_train_step(loss_fn, optax.sgd(0.1), mesh,
                              comm_mode="push_sum", topology=spec,
                              overlap="bucketed")
    assert "exchange" in step.epilogue_stages
    with pytest.raises(ValueError, match="overlap_buckets"):
        F.build_train_step(loss_fn, optax.sgd(0.1), mesh,
                           comm_mode="cta", topology=spec,
                           overlap="bucketed", overlap_buckets=0)


def test_push_sum_non_doubly_stochastic_graph():
    """Regression: a directed ring PLUS one extra edge (out-degrees 2,1,...)
    is strongly connected but NOT doubly stochastic — mixing the de-biased
    params directly diverges here; only proper (x, w) biased-pair mixing
    converges to the shared optimum."""
    from bluefog_tpu.topology.spec import Topology

    mesh = _mesh()
    w = np.zeros((N, N))
    for r in range(N):
        w[r, (r + 1) % N] = 1.0
        w[r, r] = 1.0
    w[0, 4] = 1.0  # rank 0 out-degree 2; breaks double stochasticity
    spec = Topology.from_weight_matrix(w)
    opt = optax.sgd(0.1)

    def fit_loss(params, batch):
        return jnp.mean((params["x"] - batch) ** 2)

    step_fn = F.build_train_step(
        fit_loss, opt, mesh, comm_mode="push_sum", topology=spec)
    params = F.rank_major({"x": jnp.zeros(3)}, mesh)
    opt_state = (F.rank_major(opt.init({"x": jnp.zeros(3)}), mesh),
                 F.push_sum_weights(mesh))
    target = np.tile(np.array([1.0, 2.0, 3.0]), (N, 1))
    batch = jax.device_put(target, NamedSharding(mesh, P("bf")))
    for i in range(200):
        params, opt_state, loss = step_fn(params, opt_state, batch,
                                          jnp.int32(i))
    np.testing.assert_allclose(np.sum(np.asarray(opt_state[1])), N,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(params["x"]), target, atol=1e-3)
