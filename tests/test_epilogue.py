"""The train step against a plain reference, and the guarantees of its
per-bucket epilogue pipeline.

* **Parity matrix** — every configuration of
  ``bluefog_tpu.analysis.jaxpr_check.sweep_cases()`` that a plain
  reference can follow (all but ``topk`` and ``moe``: guard x health x
  wire x comm_mode x overlap on a weighted static ring, push_sum, the
  dynamic one-peer schedule, the two-level exchange, ``gradient_allreduce``
  and ``none``, an exchange every second step) is held two ways:

  - to ``tests/reference_step.py`` (per rank ``value_and_grad`` and the
    optax update, mixing as one dense float64 product with the round's
    matrix), step by step from the program's OWN state before each
    step, by a tolerance stated beside its derivation (``_hold``);
  - bit for bit to one anchor build of the same (comm_mode, graph,
    wire), wherever a feature is documented as a no-op on finite data:
    guard on against off, health on against off, and for a
    full-precision wire ``overlap="bucketed"`` against ``"none"``.

* **Uniform-weight static CTA bit-identity**: the combine carries its
  weights as traced operands in BOTH the guarded and unguarded builds,
  so the two share one association order on every topology.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import reference_step as R
from bluefog_tpu.analysis import jaxpr_check as J
from bluefog_tpu.optim import functional as F
from bluefog_tpu.optim import fusion
from bluefog_tpu.resilience import healing
from bluefog_tpu.topology import (ExponentialTwoGraph,
                                  uniform_topology_spec)
from bluefog_tpu.topology.spec import Topology

N = J.N_RANKS
_OPT = optax.sgd(0.05, momentum=0.9)
_U32, _U64 = 2.0 ** -24, 2.0 ** -53    # unit roundoffs
_weighted_ring, _machine_ring = J._weighted_ring, J._machine_ring
_problem = J._problem


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _build(**kwargs):
    _, loss_fn = _problem()
    return F.build_train_step(loss_fn, _OPT, _mesh(), donate=False,
                              **kwargs)


def _state(mesh, push_sum=False):
    base, _ = _problem()
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(_OPT.init(base), mesh)
    if push_sum:
        ostate = (ostate, F.push_sum_weights(mesh))
    return params, ostate


def _batch(mesh, s):
    raw = np.random.RandomState(100 + s).randn(N, 3, 4).astype(np.float32)
    return jax.device_put(raw, NamedSharding(mesh, P("bf")))


def _run(step, mesh, *, guarded, push_sum=False, steps=2, each=None):
    """Drive ``step`` from the common start; ``each(s, before, after)``
    sees the state around every step (``before`` = ``(params, ostate,
    batch)``, ``after`` = ``(params, ostate, loss, skips, hv)``)."""
    params, ostate = _state(mesh, push_sum=push_sum)
    skips, hv = None, None
    for s in range(steps):
        before = (params, ostate, _batch(mesh, s))
        args = before + (np.int32(s),)
        if guarded:
            args = args + (step.default_comm_weights,)
        out = step(*args)
        params, ostate, loss = out[0], out[1], out[2]
        rest = out[3:]
        if guarded:
            skips, rest = rest[0], rest[1:]
        if rest:
            hv = rest[0]
        if each is not None:
            each(s, before, (params, ostate, loss, skips, hv))
    return params, ostate, loss, skips, hv


# ------------------------------------------------------------------ #
# the parity matrix
# ------------------------------------------------------------------ #
def _cases():
    """The product's own list, less what a plain reference cannot
    follow: error-feedback state (``topk``) and an expert layer
    (``moe``) have their own tests."""
    return [c for c in J.sweep_cases()
            if c["compress"] != "topk" and not c.get("moe")]


def _specs(kwargs):
    return list(kwargs.get("schedule")
                or ([kwargs["topology"]] if "topology" in kwargs else []))


def _groups(kwargs, n_leaves):
    """The leaves that share one wire scale: the bucket plan's under
    ``overlap="bucketed"``, each leaf alone on the plain path."""
    if "overlap" not in kwargs:
        return [[i] for i in range(n_leaves)]
    base, _ = _problem()
    return fusion.EpiloguePlan.for_leaves(
        jax.tree.leaves(base), kwargs["overlap_buckets"]).groups


def _hold(kwargs, ref, s, before, after):
    """One step of the program against the reference's step from the
    same state.  The bounds, elementwise, ``amax`` the largest magnitude
    of the leaf over all ranks:

    * full-precision wire (and the modes with no wire): both sides
      compute in float64 and differ in the order of their sums alone.
      The longest chain behind an element is the gradient (3 rows x 4
      features x 2 layers, forward and backward: under 100 operations),
      the momentum update (3) and the mix (3 products, 2 sums; 8 terms
      for the all-reduce): under 200 roundings of ``2**-53``, ``3e-14``
      of ``amax``; the bound is ``1e-12 * amax`` (two steps of this
      problem stay 30 times inside it);
    * ``push_sum``: the step re-biases, mixes and de-biases in float32
      by design (``optim/functional.py``): a cast, the product with
      ``w``, the scale, at most two sums and the division, and as many
      on the weight's side: under 16 roundings of ``2**-24`` on values
      no larger than ``amax`` (the mixed weight divides out);
    * a quantized wire: ``reference_step.wire_error_bound`` of what the
      step put on the wire.  The update is momentum SGD's, which reads
      the gradient alone, so the wire's error reaches the parameters
      once, unamplified, in ``cta`` and ``atc`` alike; the optimizer's
      state holds to the float64 bound.

    ``HealthVector`` is float32: a norm over the tree's 30 elements
    carries at most 32 roundings (``2e-6`` relative); the consensus
    distance subtracts two float32 casts, ``2 * 2**-24 * amax`` an
    element, so ``2**-23 * sqrt(30) * amax`` on the norm, and under a
    quantized wire the norm of the wire's bounds besides."""
    mode = kwargs["comm_mode"]
    push_sum = mode == "push_sum"
    params0, ostate0, batch = before
    params1, ostate1, loss1, skips, hv = after
    ps0 = np.asarray(ostate0[1], np.float64) if push_sum else None
    base0 = ostate0[0] if push_sum else ostate0
    rp, ro, rl, rps, rh, premix = ref(
        R.unstack(params0, N), R.unstack(base0, N), np.asarray(batch),
        s, ps0)
    rp, ro = R.stack(rp), R.stack(ro)

    def close(got, want, atol, what):
        for (path, g), w, a in zip(
                jax.tree_util.tree_flatten_with_path(got)[0],
                jax.tree.leaves(want), atol):
            np.testing.assert_allclose(
                np.asarray(g), w, rtol=0, atol=a,
                err_msg=f"step {s} {what}{jax.tree_util.keystr(path)}")

    def f64_bound(tree):
        return [1e-12 * max(float(np.max(np.abs(l))), 1e-3)
                for l in jax.tree.leaves(tree)]

    amax = [float(np.max(np.abs(l))) for l in jax.tree.leaves(rp)]
    f64 = f64_bound(rp)
    wire = kwargs.get("compress")
    cons_tol = 0.0
    if push_sum:
        p_tol = [16 * _U32 * a for a in amax]
        np.testing.assert_allclose(np.asarray(ostate1[1]), rps,
                                   rtol=8 * _U32)
    elif wire and premix is not None:
        L = kwargs.get("hierarchical")
        bounds = R.wire_error_bound(
            ref.matrix(s), premix, _groups(kwargs, len(amax)), wire, L)
        # the same bound on every rank's row of a leaf: take the widest
        p_tol = [max(b[i] for b in bounds) + f64[i]
                 for i in range(len(amax))]
        sizes = [np.size(l[0]) for l in jax.tree.leaves(rp)]
        cons_tol = float(np.sqrt(sum(
            n * t * t for n, t in zip(sizes, p_tol))))
    else:
        p_tol = f64
    close(params1, rp, p_tol, "params")
    close(ostate1[0] if push_sum else ostate1, ro, f64_bound(ro),
          "opt_state")
    np.testing.assert_allclose(np.asarray(loss1), rl, rtol=1e-12)
    if skips is not None:
        np.testing.assert_array_equal(np.asarray(skips),
                                      np.zeros(N, np.int32))
    if kwargs["health"] is not None:
        assert isinstance(hv, F.HealthVector)
        np.testing.assert_allclose(np.asarray(hv.loss), rh.loss,
                                   rtol=2 * _U32)
        np.testing.assert_allclose(np.asarray(hv.grad_norm),
                                   rh.grad_norm, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(hv.update_norm),
                                   rh.update_norm, rtol=2e-6)
        np.testing.assert_array_equal(np.asarray(hv.skipped), rh.skipped)
        np.testing.assert_allclose(
            np.asarray(hv.consensus), rh.consensus, rtol=2e-6,
            atol=2 * _U32 * np.sqrt(30) * max(amax) + cons_tol)


def _follow(kwargs):
    """Run the program for the case's steps, every step held to the
    reference; returns the state after each step, as numpy."""
    _, loss_fn = _problem()
    mode = kwargs["comm_mode"]
    k = kwargs.get("num_steps_per_communication", 1)
    specs = _specs(kwargs)
    ref = R.ReferenceStep(loss_fn, _OPT, N, mode, specs=specs,
                          local_size=kwargs.get("hierarchical"), every=k)
    trace = []

    def each(s, before, after):
        _hold(kwargs, ref, s, before, after)
        trace.append(jax.tree.map(np.asarray, after[:3]))

    _run(_build(**kwargs), _mesh(), guarded=kwargs["guard"] is not None,
         push_sum=mode == "push_sum", steps=k * max(2, len(specs)),
         each=each)
    return trace


def _anchor_kwargs(kwargs):
    """The build a case must equal bit for bit: the same comm_mode,
    graph and wire with guard and health off and, for a full-precision
    wire, the plain path (a wire's scale is per bucket, so a quantized
    case keeps its overlap)."""
    anchor = dict(kwargs, guard=None, health=None)
    if "compress" not in anchor:
        anchor.pop("overlap", None)
        anchor.pop("overlap_buckets", None)
    return anchor


@pytest.fixture(scope="module")
def anchor_traces():
    """Each anchor is built and followed once for the cases that share
    it (itself held to the reference by ``_follow``)."""
    return {}


@pytest.mark.perf
@pytest.mark.parametrize("case", _cases(), ids=J.case_id)
def test_step_matches_plain_reference(case, anchor_traces):
    """Every step of the built program within the stated bound of the
    plain reference's step from the same state, and bit-identical
    (loss, parameters, optimizer state) to the anchor build wherever
    guard, health and bucketing are no-ops (``_hold`` has seen the skip
    flags all zero)."""
    kwargs = J.build_kwargs(case)
    trace = _follow(kwargs)
    anchor = _anchor_kwargs(kwargs)
    if anchor == kwargs:
        return
    anchor_id = J.case_id(dict(
        case, guard=False, health=False,
        overlap="bucketed" if "overlap" in anchor else "none"))
    if anchor_id not in anchor_traces:
        anchor_traces[anchor_id] = _follow(anchor)
    # One pair is not bitwise on this jaxlib's CPU: the guard over a
    # quantized wire.  With the guard's select between the update and the
    # wire, XLA re-derives ``p + u`` inside the quantizer's fusion and
    # inside the combine's instead of reading one buffer, and LLVM
    # contracts that multiply-add into an FMA in one and not the other:
    # the self term moves by one rounding (step 0: 6 to 10 elements a
    # leaf, 1.3e-16 relative).  Held to 16 roundings of ``amax`` over the
    # steps followed; every other pair is exact.
    exact = not ("compress" in kwargs and kwargs["guard"] is not None)
    for s, (got, want) in enumerate(zip(trace, anchor_traces[anchor_id])):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(
                a, b, rtol=0, err_msg=f"step {s}",
                atol=0 if exact else 16 * _U64 * float(np.max(np.abs(b))))


# ------------------------------------------------------------------ #
# the reference itself, against closed forms
# ------------------------------------------------------------------ #
def _digraph():
    """A directed ring plus one edge (out-degrees 2, 1, ...): strongly
    connected and not doubly stochastic."""
    w = np.eye(N)
    for r in range(N):
        w[r, (r + 1) % N] = 1.0
    w[0, 4] = 1.0
    return Topology.from_weight_matrix(w)


def test_reference_matrices():
    """The reference reads its matrices off the declared edges; the
    product's ``healing.mixing_matrix`` walks the shift classes.  They
    agree, rows of a mixing round sum to 1 and columns of a push round
    do."""
    for spec in [_weighted_ring(), _machine_ring(), _digraph(),
                 *J._weighted_schedule()]:
        np.testing.assert_array_equal(R.mixing_matrix(spec),
                                      healing.mixing_matrix(spec))
    H = R.hierarchical_matrix(_machine_ring(), 2)
    np.testing.assert_allclose(H.sum(axis=1), np.ones(N), rtol=1e-15)
    # ranks 0 and 1 are one machine: the same row, and a machine's
    # weight spread evenly over its members
    np.testing.assert_array_equal(H[0], H[1])
    np.testing.assert_allclose(H[0, :2], [0.3, 0.3], rtol=1e-15)
    A = R.push_sum_matrix(_digraph())
    np.testing.assert_allclose(A.sum(axis=0), np.ones(N), rtol=1e-15)
    assert A[0, 0] == pytest.approx(1 / 3) and A[4, 0] == A[1, 0] == A[0, 0]


@pytest.mark.parametrize("mode",
                         ["cta", "atc", "gradient_allreduce", "none"])
def test_reference_quadratic_fixed_point(mode):
    """On ``f_i(x) = |x - c_i|^2 / 2`` with plain SGD of rate ``a`` the
    iteration is linear and its fixed point known: ``cta``
    ``x = W x - a (x - c)``, so ``x* = a ((1 + a) I - W)^-1 c``; ``atc``
    ``x = W (x - a (x - c))``, so ``x* = a (I - (1 - a) W)^-1 W c``;
    the all-reduce reaches the mean of the ``c_i`` and ``none`` each
    rank's own.  The four differ, so a swapped order or a wrong matrix
    shows."""
    a = 0.2
    W = R.mixing_matrix(_weighted_ring())
    c = np.random.RandomState(3).randn(N, 5)
    want = {
        "cta": a * np.linalg.solve((1 + a) * np.eye(N) - W, c),
        "atc": a * np.linalg.solve(np.eye(N) - (1 - a) * W, W @ c),
        "gradient_allreduce": np.tile(c.mean(0), (N, 1)),
        "none": c,
    }[mode]
    ref = R.ReferenceStep(
        lambda p, b: 0.5 * jnp.sum((p["x"] - b) ** 2), optax.sgd(a), N,
        mode, specs=[_weighted_ring()])
    params, ostate, _ = ref.init({"x": np.zeros(5)})
    for s in range(300):
        params, ostate, *_ = ref(params, ostate, c, s)
    np.testing.assert_allclose(R.stack(params)["x"], want, atol=1e-12)


def test_reference_push_sum_averages_on_a_digraph():
    """Push-sum's defining property: on a strongly connected digraph
    that is NOT doubly stochastic, with no gradient, every rank's
    de-biased value reaches the mean of the initial values and the
    weights keep their sum; mixing the de-biased values directly with
    the same matrix does not reach the mean."""
    ref = R.ReferenceStep(lambda p, b: 0.0 * jnp.sum(p["x"]),
                          optax.sgd(0.0), N, "push_sum",
                          specs=[_digraph()])
    x0 = np.random.RandomState(5).randn(N, 3)
    params = [{"x": x0[r]} for r in range(N)]
    ostate = [optax.sgd(0.0).init(params[0])] * N
    ps = np.ones(N)
    for s in range(200):
        params, ostate, _, ps, health, _ = ref(params, ostate,
                                               np.zeros((N, 1)), s, ps)
    np.testing.assert_allclose(R.stack(params)["x"],
                               np.tile(x0.mean(0), (N, 1)), atol=1e-12)
    assert ps.sum() == pytest.approx(N, rel=1e-13)
    assert health.consensus.max() < 1e-12
    A = R.push_sum_matrix(_digraph())
    naive = np.linalg.matrix_power(A, 200) @ x0
    assert np.abs(naive - x0.mean(0)).max() > 1e-2


def test_uniform_static_cta_guarded_bit_identical():
    """Uniform-weight static CTA: with the weights baked in as
    constants XLA may fold the combine into ``(sum) * w``, which traced
    weight operands cannot legally reproduce, so a builder that baked
    them only without a guard gave guarded != unguarded by an ulp.  The
    step feeds BOTH builds the same traced-weight combine, so the
    association orders agree."""
    mesh = _mesh()
    spec = uniform_topology_spec(ExponentialTwoGraph(N))
    kwargs = dict(comm_mode="cta", topology=spec)
    step_u = _build(**kwargs)
    step_g = _build(guard=F.GuardConfig(), **kwargs)
    params, ostate = _state(mesh)
    p2, o2 = params, ostate
    for s in range(5):
        batch = _batch(mesh, s)
        params, ostate, loss = step_u(params, ostate, batch, jnp.int32(s))
        p2, o2, loss2, skipped = step_g(p2, o2, batch, jnp.int32(s),
                                        step_g.default_comm_weights)
        np.testing.assert_array_equal(np.asarray(skipped),
                                      np.zeros(N, np.int32))
    for a, b in zip(jax.tree.leaves((params, ostate, loss)),
                    jax.tree.leaves((p2, o2, loss2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.hier
def test_hierarchical_single_rank_machines_bitwise_flat():
    """The L == 1 degeneracy contract: with every machine holding ONE
    rank the two-level decomposition IS the flat exchange — singleton
    psum is the identity, counterpart expansion reproduces the rank
    permutes, the int8 wire path folds the same per-rank key — so the
    trajectories are bit-identical, full precision and int8 alike."""
    mesh = _mesh()
    ring = _weighted_ring()
    for compress in (None, "int8"):
        kw = dict(comm_mode="cta", topology=ring)
        if compress:
            kw["compress"] = compress
        flat = _build(**kw)
        hier = _build(hierarchical=1, **kw)
        pf, of, lf, _, _ = _run(flat, mesh, guarded=False, steps=4)
        ph, oh, lh, _, _ = _run(hier, mesh, guarded=False, steps=4)
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lh))
        for a, b in zip(jax.tree.leaves((pf, of)),
                        jax.tree.leaves((ph, oh))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.hier
def test_hierarchical_guarded_matches_unguarded_bitwise():
    """Guard + hierarchical composes (the rejection this PR lifts):
    the guarded build carries the MACHINE-level weight tables as traced
    operands exactly like the unguarded fused build, so on a clean run
    the two-level trajectories are bit-identical and no step skips."""
    mesh = _mesh()
    kwargs = dict(comm_mode="cta", topology=_machine_ring(),
                  hierarchical=2)
    step_u = _build(**kwargs)
    step_g = _build(guard=F.GuardConfig(), **kwargs)
    assert step_g.hierarchical_local_size == 2
    params, ostate = _state(mesh)
    p2, o2 = params, ostate
    for s in range(5):
        batch = _batch(mesh, s)
        params, ostate, loss = step_u(params, ostate, batch, jnp.int32(s))
        p2, o2, loss2, skipped = step_g(p2, o2, batch, jnp.int32(s),
                                        step_g.default_comm_weights)
        np.testing.assert_array_equal(np.asarray(skipped),
                                      np.zeros(N, np.int32))
    for a, b in zip(jax.tree.leaves((params, ostate, loss)),
                    jax.tree.leaves((p2, o2, loss2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_push_sum_bucketed_converges_and_keeps_invariant():
    """overlap='bucketed' now rides the push-sum exchange: the mixed
    ps-weights keep sum == n and the trajectory matches the plain
    push-sum step bitwise (bucketing distributes over the
    column-stochastic mix)."""
    mesh = _mesh()
    base, loss_fn = _problem()
    spec = _weighted_ring()
    plain = F.build_train_step(loss_fn, _OPT, mesh, donate=False,
                               comm_mode="push_sum", topology=spec)
    bucketed = F.build_train_step(loss_fn, _OPT, mesh, donate=False,
                                  comm_mode="push_sum", topology=spec,
                                  overlap="bucketed", overlap_buckets=2)
    pA, oA = _state(mesh, push_sum=True)
    pB, oB = pA, oA
    for s in range(6):
        batch = _batch(mesh, s)
        pA, oA, lA = plain(pA, oA, batch, jnp.int32(s))
        pB, oB, lB = bucketed(pB, oB, batch, jnp.int32(s))
    np.testing.assert_allclose(np.sum(np.asarray(oA[1])), N, rtol=1e-6)
    np.testing.assert_allclose(np.sum(np.asarray(oB[1])), N, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lA), np.asarray(lB),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)


def test_epilogue_plan_carries_stage_lists():
    """EpiloguePlan buckets carry their stage lists in canonical
    order, and build_train_step exposes the composed stages."""
    leaves = [jnp.zeros((16, 16)), jnp.zeros((16,)),
              jnp.zeros((16, 4)), jnp.zeros((4,))]
    plan = fusion.EpiloguePlan.for_leaves(
        leaves, 2, compress="int8", guard=True, health=True,
        consensus=True)
    assert plan.stages == ("pack", "quantize", "exchange", "dequantize",
                           "guard_select", "health_norm", "consensus",
                           "unpack")
    assert all(b.stages == plan.stages for b in plan.buckets)
    # buckets partition the leaves in tree order
    flat = [i for b in plan.buckets for i in b.leaves]
    assert flat == list(range(len(leaves)))
    # plain path: one bucket per leaf
    plain = fusion.EpiloguePlan.for_leaves(leaves, None)
    assert [list(b.leaves) for b in plain.buckets] == [[0], [1], [2], [3]]
    assert plain.stages == ("pack", "exchange", "unpack")
    # the eager FusionPlan's buckets carry stage lists too
    fp = fusion.FusionPlan.for_leaves(
        [jnp.zeros((N, 8)), jnp.zeros((N, 8))], threshold=1 << 20)
    assert all(b.stages == ("pack", "exchange", "unpack")
               for b in fp.buckets)

    mesh = _mesh()
    base, loss_fn = _problem()
    step = F.build_train_step(
        loss_fn, _OPT, mesh, comm_mode="atc", donate=False,
        topology=_weighted_ring(), compress="int8",
        health=F.HealthConfig(), overlap="bucketed", overlap_buckets=2)
    assert step.epilogue_stages == (
        "pack", "quantize", "exchange", "dequantize", "health_norm",
        "consensus", "unpack")


# ------------------------------------------------------------------ #
# compressed mixing with error feedback (ISSUE 17)
# ------------------------------------------------------------------ #
def _mix_problem_state(mesh, step):
    """(params, (base_opt_state, MixState)) for a mix-enabled step."""
    base, _ = _problem()
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(_OPT.init(base), mesh)
    return params, (ostate, step.init_mix_state(params))


def test_mix_ratio_one_short_circuits_to_dense():
    """``MixCompressConfig(ratio>=1.0)`` drops the whole mixing
    apparatus at BUILD time (``step.mix_config is None``, plain
    signature, no MixState) and the trajectory is bit-identical to an
    uncompressed build — identity by construction, not by tolerance."""
    mesh = _mesh()
    kwargs = dict(comm_mode="cta", topology=_weighted_ring(),
                  overlap="bucketed", overlap_buckets=2)
    dense = _build(**kwargs)
    one = _build(compress=F.MixCompressConfig(ratio=1.0), **kwargs)
    assert one.mix_config is None
    assert not hasattr(one, "init_mix_state")
    pA, _, lA, _, _ = _run(dense, mesh, guarded=False, steps=3)
    pB, _, lB, _, _ = _run(one, mesh, guarded=False, steps=3)
    np.testing.assert_array_equal(np.asarray(lA), np.asarray(lB))
    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mix_state_checkpoint_roundtrip(tmp_path):
    """The EF state survives a checkpoint: save mid-run, restore with
    ``like=`` (preserving the MixState/optax NamedTuple containers),
    and the restored trajectory continues bit-identically to the live
    one — ref/mirror consistency is state, so it must round-trip."""
    from bluefog_tpu.checkpoint import Checkpointer

    mesh = _mesh()
    step = _build(comm_mode="cta",
                  topology=_weighted_ring(),
                  compress=F.MixCompressConfig(ratio=0.5, values="int8"),
                  overlap="bucketed", overlap_buckets=2)
    assert step.epilogue_stages == (
        "pack", "ef_encode", "quantize", "exchange", "dequantize",
        "ef_decode", "unpack")
    params, state = _mix_problem_state(mesh, step)
    for s in range(2):
        params, state, _ = step(params, state, _batch(mesh, s),
                                jnp.int32(s))
    ck = Checkpointer(str(tmp_path))
    ck.save(2, {"params": params, "state": state})

    base, _ = _problem()
    p_t = F.rank_major(base, mesh)
    template = {"params": p_t,
                "state": (F.rank_major(_OPT.init(base), mesh),
                          step.init_mix_state(p_t))}
    got = ck.restore(2, mesh=mesh, like=template)
    rp, rs = got["params"], got["state"]
    assert isinstance(rs[1], F.MixState)
    for s in range(2, 4):
        b = _batch(mesh, s)
        params, state, live_loss = step(params, state, b, jnp.int32(s))
        rp, rs, rest_loss = step(rp, rs, b, jnp.int32(s))
    np.testing.assert_array_equal(np.asarray(live_loss),
                                  np.asarray(rest_loss))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mix_heal_grow_ratio_swap_zero_recompile():
    """The full elastic cycle on a guarded compressed step — heal a
    dead rank (weight DATA swap), grow it back, then drop the live
    compression ratio — all through ONE compiled program: the jit
    cache holds exactly one entry throughout, and every loss stays
    finite (the EF state keeps advancing through the swaps)."""
    from bluefog_tpu.resilience.healing import healed_comm_weights

    mesh = _mesh()
    ring = _weighted_ring()
    step = _build(comm_mode="atc", topology=ring,
                  compress=F.MixCompressConfig(ratio=0.25),
                  overlap="bucketed", overlap_buckets=2,
                  guard=F.GuardConfig(), health=F.HealthConfig())
    params, state = _mix_problem_state(mesh, step)
    dead = np.zeros(N, bool)
    dead[2] = True
    healed = healed_comm_weights([ring], dead)
    plans = [step.default_comm_weights,   # healthy
             healed,                      # rank 2 dead: healed DATA
             step.default_comm_weights,   # grown back
             step.default_comm_weights]   # post ratio swap
    losses = []
    for s, w in enumerate(plans):
        if s == 3:
            # the control plane's sanctioned boundary: pure data
            state = step.set_mix_ratio(state, 0.1)
        params, state, loss, _, hv = step(
            params, state, _batch(mesh, s), jnp.int32(s), w)
        losses.append(float(loss[0]))
        assert step.jitted._cache_size() == 1, s
    assert all(np.isfinite(l) for l in losses)
    assert np.isfinite(np.asarray(jax.tree.leaves(hv))).all()
    # the live ratio really moved (pure data, same compiled program)
    assert float(np.asarray(state[1].ratio)[0]) == pytest.approx(0.1)
