"""Eager-path tensor fusion (reference operations.cc:943-1020,
tensor_queue.h:75-124): the optimizer wrappers pack parameter leaves into
few flat buffers per combine, so an eager step issues O(1) collective
programs instead of one per leaf — with identical numerics.
"""

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import bluefog_tpu as bf
from bluefog_tpu.context import BluefogContext
from bluefog_tpu.optim import (
    DistributedAdaptThenCombineOptimizer,
    DistributedGradientAllreduceOptimizer,
)
from bluefog_tpu.optim.wrappers import _FusionPlan
from bluefog_tpu.topology import ExponentialTwoGraph

SIZE = 8


def many_leaf_params(n_leaves=40, seed=0):
    rng = np.random.default_rng(seed)
    return {
        f"p{i}": jnp.asarray(
            rng.normal(size=(SIZE,) + ((3, 5) if i % 3 else (7,))),
            jnp.float32)
        for i in range(n_leaves)
    }


def count_run_ops(monkeypatch):
    counter = {"n": 0}
    orig = BluefogContext.run_op

    def counting(self, key, kernel, x):
        counter["n"] += 1
        return orig(self, key, kernel, x)

    monkeypatch.setattr(BluefogContext, "run_op", counting)
    return counter


def test_fused_combine_issues_few_programs(bf_ctx, monkeypatch):
    """40 leaves, default 8 MB threshold -> ONE collective program."""
    bf.set_topology(ExponentialTwoGraph(SIZE))
    params = many_leaf_params()
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    opt = DistributedAdaptThenCombineOptimizer(optax.sgd(0.01))
    state = opt.init(params)
    counter = count_run_ops(monkeypatch)
    opt.step(params, grads, state)
    assert counter["n"] == 1, f"expected 1 fused program, got {counter['n']}"


def test_fusion_respects_threshold(bf_ctx, monkeypatch):
    """A tiny threshold splits the pack into multiple buffers; fusion off
    (threshold 0) issues one program per leaf."""
    bf.set_topology(ExponentialTwoGraph(SIZE))
    params = many_leaf_params(n_leaves=10)
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}

    monkeypatch.setenv("BLUEFOG_FUSION_THRESHOLD", "64")  # 16 floats/rank
    opt = DistributedAdaptThenCombineOptimizer(optax.sgd(0.01))
    state = opt.init(params)
    counter = count_run_ops(monkeypatch)
    opt.step(params, grads, state)
    assert 1 < counter["n"] <= 10

    monkeypatch.setenv("BLUEFOG_FUSION_THRESHOLD", "0")
    counter["n"] = 0
    opt.step(params, grads, state)
    assert counter["n"] == 10


def test_fused_numerics_match_unfused(bf_ctx, monkeypatch):
    """Fusion is invisible to the math: fused and unfused combines give
    bitwise-comparable results (the weighted combine distributes over
    concatenation)."""
    bf.set_topology(ExponentialTwoGraph(SIZE))
    params = many_leaf_params(seed=3)
    grads = {k: 0.1 * jnp.ones_like(v) for k, v in params.items()}

    opt = DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
    fused, _ = opt.step(params, grads, opt.init(params))

    monkeypatch.setenv("BLUEFOG_FUSION_THRESHOLD", "0")
    opt2 = DistributedAdaptThenCombineOptimizer(optax.sgd(0.05))
    unfused, _ = opt2.step(params, grads, opt2.init(params))

    for k in params:
        np.testing.assert_allclose(np.asarray(fused[k]),
                                   np.asarray(unfused[k]), atol=1e-6)


def test_fused_gradient_allreduce(bf_ctx, monkeypatch):
    """Gradient allreduce also fuses, and averages correctly."""
    params = {"a": jnp.zeros((SIZE, 4)), "b": jnp.zeros((SIZE, 2, 3))}
    grads = {
        "a": jnp.broadcast_to(
            jnp.arange(SIZE, dtype=jnp.float32)[:, None], (SIZE, 4)),
        "b": jnp.broadcast_to(
            jnp.arange(SIZE, dtype=jnp.float32)[:, None, None],
            (SIZE, 2, 3)),
    }
    opt = DistributedGradientAllreduceOptimizer(optax.sgd(1.0))
    state = opt.init(params)
    counter = count_run_ops(monkeypatch)
    new_params, _ = opt.step(params, grads, state)
    assert counter["n"] == 1
    mean_grad = (SIZE - 1) / 2
    np.testing.assert_allclose(np.asarray(new_params["a"]), -mean_grad,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_params["b"]), -mean_grad,
                               rtol=1e-6)


def test_fusion_plan_groups_by_dtype():
    """Mixed dtypes never share a buffer (no silent casting)."""
    sig = (((8, 4), "float32"), ((8, 4), "float32"), ((8, 4), "int32"),
           ((8, 4), "float32"))
    plan = _FusionPlan(sig, threshold=1 << 20)
    dtypes_per_group = [
        {sig[i][1] for i in g} for g in plan.groups
    ]
    assert all(len(ds) == 1 for ds in dtypes_per_group)


def test_fusion_plan_cache_bounded(bf_ctx):
    """Same signature -> same plan object (no per-step recompiles)."""
    params = many_leaf_params(n_leaves=5)
    leaves = list(params.values())
    p1 = _FusionPlan.for_leaves(leaves, 8 << 20)
    p2 = _FusionPlan.for_leaves(leaves, 8 << 20)
    assert p1 is p2


# --- shared planner: eager fusion and the jitted overlap engine must ---
# --- produce IDENTICAL bucket assignments (optim/fusion.py)          ---

def test_shared_planner_identity_with_eager_plan():
    """The eager _FusionPlan's groups == plan_groups over the same
    per-rank leaf signature and threshold — one grouping policy for
    both the eager fusion buffers and the jitted bucketed combine."""
    from bluefog_tpu.optim import fusion

    params = many_leaf_params(n_leaves=23, seed=5)
    leaves = list(params.values())
    for threshold in (64, 640, 8 << 20):
        plan = _FusionPlan.for_leaves(leaves, threshold)
        rows = fusion.bucket_signature(leaves, skip_leading_axis=True)
        assert fusion.plan_groups(rows, threshold) == plan.groups


def test_shared_planner_matches_jitted_bucket_groups():
    """The bucketed train step's trace-time bucket assignment
    (``EpiloguePlan.for_leaves(leaves, K)``, the plan the builder asks
    for) is the shared walk at the size-balanced threshold."""
    from bluefog_tpu.optim import fusion

    leaves = [jnp.zeros((32, 16), jnp.float32) for _ in range(10)]
    rows = fusion.bucket_signature(leaves)
    k = 4
    expect = fusion.plan_groups(
        rows, fusion.size_balanced_threshold(rows, k))
    plan = fusion.EpiloguePlan.for_leaves(leaves, k)
    assert plan.groups == expect
    assert [list(b.leaves) for b in plan.buckets] == expect
    assert len(expect) >= k  # size-balanced floor
    # every leaf appears exactly once, in order
    flat = [i for g in expect for i in g]
    assert flat == list(range(len(leaves)))


def test_planner_dtype_boundary_closes_bucket():
    """A dtype change ALWAYS closes the open bucket (no silent casting),
    in both consumers of the shared walk."""
    from bluefog_tpu.optim import fusion

    rows = [(100, "float32"), (100, "float32"), (100, "int32"),
            (100, "int32"), (100, "float32")]
    groups = fusion.plan_groups(rows, 1 << 20)
    assert groups == [[0, 1], [2, 3], [4]]


def test_planner_oversize_leaf_stands_alone():
    """A leaf larger than the threshold gets its own bucket; neighbors
    never ride along with it."""
    from bluefog_tpu.optim import fusion

    rows = [(100, "float32"), (100, "float32"), (1000, "float32"),
            (50, "float32"), (50, "float32")]
    groups = fusion.plan_groups(rows, 250)
    assert groups == [[0, 1], [2], [3, 4]]
    # and the size-balanced threshold keeps >= K buckets despite it
    k = 3
    t = fusion.size_balanced_threshold(rows, k)
    assert len(fusion.plan_groups(rows, t)) >= k
