"""The round of a dynamic schedule is chosen on the host (ISSUE 25).

``build_train_step(schedule=[...])`` with ``P`` rounds is ``P`` compiled
programs behind one jitted object, the round ``step % P`` its static
argument: no ``conditional`` holds a round's permutes, so each is a
top-level asynchronous operation.  These tests hold the contract on four
virtual CPU devices: what each round's program contains, that a
scheduled step is round by round the one-round build of that round's
spec, how many programs there are and that data never adds one, what a
tracer for ``step`` raises, and that a step with one program is the
program it was before.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.optim import functional as F
from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule

N = 4
ROUNDS = 2      # log2(4)


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _loss_fn(params, batch):
    return jnp.mean((jnp.tanh(batch @ params["w"]) @ params["v"]
                     + params["b"]) ** 2)


def _problem(mesh, opt=None, **kw):
    """A step over ``mesh`` and a state whose ranks differ:
    ``(step, args)`` with ``args`` the public arguments before
    ``step``."""
    opt = opt or optax.adamw(1e-2)
    base = {"w": jnp.eye(16) * 0.5, "v": jnp.ones((16, 4)) * 0.1,
            "b": jnp.zeros((4,))}
    step = F.build_train_step(_loss_fn, opt, mesh, donate=False, **kw)
    spread = lambda x: x + 0.01 * jnp.arange(N).reshape(
        (N,) + (1,) * (x.ndim - 1))
    params = jax.tree.map(spread, F.rank_major(base, mesh))
    ostate = F.rank_major(opt.init(base), mesh)
    if kw.get("comm_mode") == "push_sum":
        ostate = (ostate, F.push_sum_weights(mesh))
    batch = jax.device_put(
        np.random.RandomState(0).randn(N, 8, 16).astype(np.float32),
        NamedSharding(mesh, P("bf")))
    return step, (params, ostate, batch)


def _call(step, state, batch, i):
    """One public call; returns (params, opt_state, everything else)."""
    args = (*state, batch, np.int32(i))
    if hasattr(step, "guard_config"):
        args += (step.default_comm_weights,)
    out = step(*args)
    return (out[0], out[1]), out[2:]


def _assert_same(a, b, what):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=str(what))


# ------------------------------------------------------------------ #
# (a) what a round's program holds
# ------------------------------------------------------------------ #
def _permute_pairs(hlo: str):
    """The ``source_target_pairs`` of every collective-permute."""
    out = []
    for m in re.finditer(
            r"collective-permute(?:-start)?\(.*?source_target_pairs="
            r"\{((?:\{\d+,\d+\},?)*)\}", hlo):
        out.append(sorted(tuple(int(x) for x in p.split(","))
                          for p in re.findall(r"\{(\d+,\d+)\}",
                                              m.group(1))))
    return out


def test_each_round_is_a_program_of_its_own_edges_and_no_conditional(mesh):
    sched = one_peer_dynamic_schedule(N)
    assert len(sched) == ROUNDS
    step, (params, ostate, batch) = _problem(mesh, comm_mode="atc",
                                             schedule=sched)
    edges = [sorted(tuple(e) for e in s.edges) for s in sched]
    assert edges[0] != edges[1]
    for r in range(ROUNDS):
        hlo = step.lower(params, ostate, batch, r).compile().as_text()
        assert not re.search(r"\bconditional\(", hlo), r
        pairs = _permute_pairs(hlo)
        # one permute a leaf, every one over this round's edges
        assert len(pairs) == len(jax.tree.leaves(params)), r
        assert all(p == edges[r] for p in pairs), (r, pairs, edges)


# ------------------------------------------------------------------ #
# (b) a scheduled step is, round by round, the one-round build
# ------------------------------------------------------------------ #
KINDS = {
    "cta": dict(comm_mode="cta"),
    "atc": dict(comm_mode="atc"),
    "guarded-atc": dict(comm_mode="atc", guard=F.GuardConfig(),
                        health=F.HealthConfig()),
    "push_sum": dict(comm_mode="push_sum"),
    "k_comm=2": dict(comm_mode="atc", num_steps_per_communication=2),
    "bucketed": dict(comm_mode="atc", overlap="bucketed",
                     overlap_buckets=2),
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_scheduled_step_equals_one_round_build_round_by_round(mesh, kind):
    """Over ``2P`` steps every output of the scheduled step (parameters,
    optimizer state, loss, and the guard's and health's vectors where
    they exist) is bit for bit what the one-round build of that round's
    spec gives from the same input: the host's choice of a program and
    the chain that orders a round's permutes change no value."""
    sched = one_peer_dynamic_schedule(N)
    kw = KINDS[kind]
    step, (params, ostate, batch) = _problem(mesh, schedule=sched, **kw)
    single = [_problem(mesh, topology=s, **kw)[0] for s in sched]
    state = (params, ostate)
    for i in range(2 * ROUNDS):
        new, rest = _call(step, state, batch, i)
        want, want_rest = _call(single[i % ROUNDS], state, batch, i)
        _assert_same(new, want, (kind, i, "state"))
        _assert_same(rest, want_rest, (kind, i, "loss and vectors"))
        state = new
    if kind == "k_comm=2":
        # rounds 0 of steps 0 and 2, and the off-cycle program
        assert step.jitted._cache_size() == 2
    else:
        assert step.jitted._cache_size() == ROUNDS


# ------------------------------------------------------------------ #
# (c) P programs, then none; data never adds one
# ------------------------------------------------------------------ #
def test_cache_is_one_program_a_round_and_data_adds_none(mesh):
    from bluefog_tpu.resilience.healing import healed_comm_weights

    sched = one_peer_dynamic_schedule(N)
    traces = 0

    def loss_fn(params, batch):
        nonlocal traces
        traces += 1
        return _loss_fn(params, batch)

    opt = optax.sgd(0.05)
    base = {"w": jnp.eye(16) * 0.5, "v": jnp.ones((16, 4)) * 0.1,
            "b": jnp.zeros((4,))}
    step = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                              schedule=sched, guard=F.GuardConfig(),
                              donate=False)
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)
    batch = jax.device_put(np.ones((N, 8, 16), np.float32),
                           NamedSharding(mesh, P("bf")))
    w = step.default_comm_weights
    for i in range(ROUNDS):
        params, ostate, _, _ = step(params, ostate, batch, np.int32(i), w)
        assert step.jitted._cache_size() == i + 1
    assert traces == 1      # the model is traced once for every round
    dead = np.zeros(N, bool)
    dead[2] = True
    healed = healed_comm_weights(sched, dead)
    for i in range(ROUNDS, 4 * ROUNDS):   # three more cycles
        ws = healed if i >= 2 * ROUNDS else w     # a swap of the weights
        params, ostate, loss, _ = step(params, ostate, batch,
                                       np.int32(i), ws)
        assert step.jitted._cache_size() == ROUNDS, i
    assert traces == 1
    assert np.isfinite(np.asarray(loss)).all()


# ------------------------------------------------------------------ #
# (d) a tracer cannot name a program
# ------------------------------------------------------------------ #
def test_tracer_step_raises_and_one_round_steps_do_not(mesh):
    sched = one_peer_dynamic_schedule(N)
    step, (params, ostate, batch) = _problem(mesh, comm_mode="atc",
                                             schedule=sched)
    with pytest.raises(TypeError, match="concrete integer"):
        jax.jit(lambda s: step(params, ostate, batch, s))(0)
    with pytest.raises(TypeError, match="one-round step"):
        step.lower(params, ostate, batch,
                   jax.ShapeDtypeStruct((), jnp.int32))
    k2, _ = _problem(mesh, comm_mode="atc", topology=sched[0],
                     num_steps_per_communication=2)
    with pytest.raises(TypeError, match="num_steps_per_communication=2"):
        jax.jit(lambda s: k2(params, ostate, batch, s))(0)
    # one program: nothing of ``step`` is read on the host
    for kw in (dict(comm_mode="atc", topology=sched[0]),
               dict(comm_mode="none"),
               dict(comm_mode="gradient_allreduce")):
        one, _ = _problem(mesh, **kw)
        out = jax.jit(lambda s: one(params, ostate, batch, s))(3)
        for x, y in zip(jax.tree.leaves(out),
                        jax.tree.leaves(one(params, ostate, batch, 3))):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-7)


# ------------------------------------------------------------------ #
# (e) a step of one program is the program it was
# ------------------------------------------------------------------ #
def _stripped_hlo(step, args):
    """Optimized HLO less ``metadata={...}`` and the stack-frame tables
    (the comparison of tests/test_observe.py)."""
    text = step.lower(*args, jnp.int32(0)).compile().as_text()
    out, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip:
            skip = line != ""
        else:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


# sha256 of the stripped HLO that the commit before ISSUE 25 (a11e6a3)
# compiles for these two builds, taken with this file's own code on the
# suite's CPU backend (8 virtual devices, x64 on, jax 0.9.0)
PARENT_HLO = {
    "none": "93777408cb2f94139021562e877841a2"
            "9f1d3e597ce96f94cbd341831fe9b613",
    "atc-one-round": "12358c98518ae3e073b13f7bf80a0882"
                     "331fddc1c751cf7de5b94579ff1124c4",
}


@pytest.mark.parametrize("kind", list(PARENT_HLO))
def test_one_program_builds_are_byte_identical_to_the_parents(mesh, kind):
    """``comm_mode="none"`` and a one-round ``atc`` build carry no
    round: their optimized HLO, metadata stripped, is the parent's byte
    for byte (so the one-chip cells of the benchmark cannot move)."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the parent's HLO was taken with jax 0.9.0")
    kw = {"none": dict(comm_mode="none"),
          "atc-one-round": dict(
              comm_mode="atc",
              topology=one_peer_dynamic_schedule(N)[0])}[kind]
    step, args = _problem(mesh, **kw)
    got = hashlib.sha256(_stripped_hlo(step, args).encode()).hexdigest()
    assert got == PARENT_HLO[kind], got
