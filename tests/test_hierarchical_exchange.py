"""Hierarchical two-level exchange (ISSUE 11): exact ICI allreduce
inside the machine, decentralized mixing only across DCN.

Contracts under test (the compiled-step half; the eager ``bf.*`` API
half is tests/test_hierarchical.py and the HLO wire-pattern guarantees
are tests/test_hlo_guarantees.py):

* **Kron decomposition** — the two-level round IS the flat round over
  ``W_dcn (x) J_L/L``: a consensus simulation of the expanded matrix
  reaches the machine schedule's <= 1e-12 floor, because the exact
  local mean kills every intra-machine mode in round one.
* **Machine failure domain** — ``machine_dead_mask`` collapses a
  rank-level dead mask (ANY dead member kills the machine) and
  ``healed_hierarchical_comm_weights`` equals rank-level healing of
  the machine schedule under the collapsed mask, row-stochastic.
* **Zero recompiles** — one guarded hierarchical executable serves
  pristine -> healed -> elastically re-grown machine tables as pure
  data (``jitted._cache_size()`` never moves), and ``run_resilient``
  drives the whole death -> heal -> rollback loop through it.
* **Per-leg billing** — the step wrapper bills the ICI ring and the
  expanded DCN counterpart edges under disjoint ``link=`` labels, and
  ``PodSpec.from_telemetry(link="dcn")`` calibrates from ONLY the
  inter-machine leg.
* **Compiler** — hierarchical synthesis beats the flat schedule on
  ``cost_to_consensus`` at the 8x16 pod with 4x DCN links (the ISSUE
  acceptance pod), and builder validation fails loudly on every
  mis-decomposition.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import elastic as E
from bluefog_tpu import resilience as R
from bluefog_tpu.checkpoint import Checkpointer
from bluefog_tpu.observe import fleet as FL
from bluefog_tpu.observe.registry import MetricsRegistry
from bluefog_tpu.optim import functional as F
from bluefog_tpu.resilience.healing import (consensus_simulation,
                                            healed_comm_weights,
                                            healed_hierarchical_comm_weights,
                                            machine_dead_mask,
                                            mixing_matrix)
from bluefog_tpu.topology import (ExponentialTwoGraph,
                                  one_peer_dynamic_schedule,
                                  uniform_topology_spec)
from bluefog_tpu.topology.compiler import PodSpec, compile_topology
from bluefog_tpu.topology.spec import Topology

pytestmark = pytest.mark.hier

N = 8       # ranks on the CPU mesh
L = 2       # chips per machine
M = N // L  # machines


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _machine_sched():
    return one_peer_dynamic_schedule(M)


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
        rng = np.random.RandomState(11)
        _DATA = (rng.randn(32, N, 4, 6), rng.randn(32, N, 4, 2))
    return (_DATA[0][step % 32], _DATA[1][step % 32])


# ------------------------------------------------------------------ #
# kron decomposition: the two-level round as a flat matrix
# ------------------------------------------------------------------ #
def test_expanded_kron_schedule_reaches_consensus_floor():
    """Acceptance: a consensus simulation of the EXPANDED two-level
    rounds — flat n-rank specs built from ``W_dcn (x) J_L/L`` — hits
    the <= 1e-12 floor of the machine schedule itself.  The kron
    spectrum is the machine spectrum plus zeros (the exact local mean
    annihilates every intra-machine disagreement mode in one round),
    so the two-level exchange inherits the machine-level contraction."""
    sched = _machine_sched()
    J = np.full((L, L), 1.0 / L)
    expanded = [Topology.from_weight_matrix(
        np.kron(mixing_matrix(s), J).T) for s in sched]
    trace = consensus_simulation(expanded, rounds=80, dim=16, seed=2)
    assert trace[-1] <= 1e-12, trace[-1]
    machine_trace = consensus_simulation(sched, rounds=80, dim=16, seed=2)
    assert machine_trace[-1] <= 1e-12


# ------------------------------------------------------------------ #
# machine failure domain
# ------------------------------------------------------------------ #
def test_machine_dead_mask_collapses_any_dead_member():
    dead = np.zeros(N, bool)
    dead[3] = True  # rank 3 lives on machine 1 (L=2)
    np.testing.assert_array_equal(machine_dead_mask(dead, L),
                                  [False, True, False, False])
    dead[2] = True  # second member of the same machine: no change
    np.testing.assert_array_equal(machine_dead_mask(dead, L),
                                  [False, True, False, False])
    with pytest.raises(ValueError, match="local_size"):
        machine_dead_mask(np.zeros(7, bool), L)


def test_healed_hierarchical_weights_equal_machine_level_healing():
    """The hierarchical heal IS rank-level healing of the MACHINE
    schedule under the collapsed mask — same tables, row-stochastic."""
    sched = _machine_sched()
    dead = np.zeros(N, bool)
    dead[5] = True  # kills machine 2
    hier = healed_hierarchical_comm_weights(sched, dead, L)
    flat = healed_comm_weights(sched, machine_dead_mask(dead, L))
    assert len(hier) == len(flat) == len(sched)
    for (hc, hs), (fc, fs) in zip(hier, flat):
        np.testing.assert_array_equal(np.asarray(hc), np.asarray(fc))
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(fs))
        assert np.asarray(hc).shape[1] == M  # MACHINE-level tables
    # survivors still contract under the healed machine tables
    trace = consensus_simulation(sched, rounds=80, dim=16, seed=4,
                                 dead_mask=machine_dead_mask(dead, L),
                                 weights=hier)
    assert trace[-1] <= 1e-12


# ------------------------------------------------------------------ #
# zero recompiles across the membership lifecycle
# ------------------------------------------------------------------ #
def test_zero_recompiles_across_machine_membership_cycle():
    """One guarded hierarchical step (one executable a round of its
    machine schedule) serves pristine -> healed (rank death collapsed
    to its machine) -> elastically re-grown -> pristine machine
    tables: the inter-machine matrix is traced DATA, so a round's
    program is compiled once and ``jitted._cache_size()`` never passes
    the number of rounds."""
    mesh = _mesh()
    sched = _machine_sched()
    step = F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="atc",
                              schedule=sched, hierarchical=L,
                              guard=F.GuardConfig(), donate=False)
    assert step.hierarchical_local_size == L
    params, ostate = _state(mesh)
    dead = np.zeros(N, bool)
    dead[2] = True  # kills machine 1
    tables = [
        step.default_comm_weights,
        healed_hierarchical_comm_weights(sched, dead, L),
        E.grown_comm_weights(sched, machine_dead_mask(dead, L), [1]),
        step.default_comm_weights,
    ]
    for i in range(2 * len(sched) * len(tables)):
        w = tables[i // (2 * len(sched))]   # every table in every round
        params, ostate, loss, sk = step(params, ostate, _batch_fn(i),
                                        i, w)
        assert step.jitted._cache_size() == min(i + 1, len(sched)), i
        assert np.isfinite(np.asarray(loss)).all()
    # heal -> grow with the machine rejoining reproduces the pristine
    # machine tables exactly (the elastic round-trip, machine-level)
    for (gc, gs), (dc, ds) in zip(tables[2], tables[3]):
        np.testing.assert_array_equal(np.asarray(gc), np.asarray(dc))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ds))


def test_run_resilient_drives_hierarchical_heal(tmp_path):
    """A rank death under ``run_resilient`` + a hierarchical step:
    the detector watches RANKS, the heal delivery collapses to the
    machine failure domain, the rollback restores and the run ends
    with the victim's whole machine excised — zero recompiles."""
    mesh = _mesh()
    sched = _machine_sched()
    step = F.build_train_step(
        _loss_fn, _OPT, mesh, comm_mode="atc", schedule=sched,
        hierarchical_local_size=L,
        guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0))
    for s in range(len(sched)):     # one program a round
        params, ostate = _state(mesh)   # a call donates the buffers
        step(params, ostate, _batch_fn(0), np.int32(s),
             step.default_comm_weights)
    baseline = step.jitted._cache_size()
    assert baseline == len(sched)
    params, ostate = _state(mesh)
    plan = R.FaultPlan.rank_death(N, rank=5, step=3)
    ck = Checkpointer(str(tmp_path / "ck"))
    res = R.run_resilient(
        step, params, ostate, _batch_fn, steps=12,
        checkpointer=ck, mesh=mesh, schedule=sched,
        fault_plan=plan, checkpoint_every=4, sleep=lambda s: None)
    ck.close()
    assert res.step == 12 and res.n_rollbacks == 1
    assert res.dead_mask[5] and res.dead_mask.sum() == 1
    assert step.jitted._cache_size() == baseline
    assert R.update_health(res.params).all()


def test_run_resilient_elastic_rejects_hierarchical_step(tmp_path):
    """``elastic=`` anneals RANK-level weights; a hierarchical step
    mixes MACHINE-level tables — the runner must refuse the pair
    loudly instead of feeding mis-shaped weights."""
    mesh = _mesh()
    sched = _machine_sched()
    step = F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="atc",
                              schedule=sched, hierarchical=L,
                              guard=F.GuardConfig())
    params, ostate = _state(mesh)
    ck = Checkpointer(str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="machine"):
        R.run_resilient(step, params, ostate, _batch_fn, steps=2,
                        checkpointer=ck, mesh=mesh, schedule=sched,
                        elastic=E.ElasticConfig(), sleep=lambda s: None)
    ck.close()


# ------------------------------------------------------------------ #
# per-leg traffic billing
# ------------------------------------------------------------------ #
def test_step_bills_ici_and_dcn_legs_separately():
    """Each on-cycle hierarchical dispatch bills the intra-machine
    ring under ``link="ici"`` and the expanded counterpart machine
    edges under ``link="dcn"`` — disjoint pair sets, so
    ``traffic_snapshot(link="dcn")`` is exactly the inter-machine
    load; a flat step's rows stay in the unlabeled family."""
    mesh = _mesh()
    spec = uniform_topology_spec(ExponentialTwoGraph(M))

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    def build(**kw):
        step = F.build_train_step(loss_fn, _OPT, mesh, donate=False, **kw)
        params = F.rank_major({"w": jnp.eye(4)}, mesh)
        ostate = F.rank_major(_OPT.init({"w": jnp.eye(4)}), mesh)
        batch = jax.device_put(
            np.random.RandomState(0).randn(N, 2, 4).astype(np.float32),
            NamedSharding(mesh, P("bf")))
        return step, params, ostate, batch

    def delta(before, link):
        after = FL.traffic_snapshot(link=link)
        return {k: v - before.get(k, 0.0)
                for k, v in after.items() if v > before.get(k, 0.0)}

    b_ici = FL.traffic_snapshot(link="ici")
    b_dcn = FL.traffic_snapshot(link="dcn")
    step, params, ostate, batch = build(comm_mode="cta", topology=spec,
                                        hierarchical=L)
    step(params, ostate, batch, jnp.int32(0))
    d_ici, d_dcn = delta(b_ici, "ici"), delta(b_dcn, "dcn")
    assert d_ici and d_dcn and not (set(d_ici) & set(d_dcn))
    for (src, dst) in d_ici:
        assert src // L == dst // L  # intra-machine ring edge
    for (src, dst) in d_dcn:
        assert src // L != dst // L and src % L == dst % L  # counterpart
    payload = sum(l.nbytes for l in jax.tree.leaves(params)) // N
    assert set(d_dcn.values()) == {float(payload)}
    # the whole-fleet view sums both legs
    assert set(d_ici) | set(d_dcn) <= set(FL.traffic_snapshot())

    # a FLAT step must not touch the labeled families
    b_ici = FL.traffic_snapshot(link="ici")
    b_dcn = FL.traffic_snapshot(link="dcn")
    step_f, params, ostate, batch = build(
        comm_mode="cta", topology=uniform_topology_spec(
            ExponentialTwoGraph(N)))
    step_f(params, ostate, batch, jnp.int32(0))
    assert not delta(b_ici, "ici") and not delta(b_dcn, "dcn")


def test_from_telemetry_link_filter_feeds_only_dcn_bytes():
    """``PodSpec.from_telemetry(link="dcn")`` calibrates from ONLY the
    inter-machine counters: a huge ICI-labeled flow must not perturb
    the DCN-calibrated pod, and the resulting overrides land on torus
    axis 0 (the machine axis) where the hierarchical compiler's
    machine-pod aggregation reads them."""
    reg = MetricsRegistry()
    spec = uniform_topology_spec(ExponentialTwoGraph(M))
    # machine 0 -> 1 counterpart pair, both chip lanes, across DCN
    FL.record_edge_traffic(spec, 1e6, registry=reg,
                           pairs=[(0, 2), (1, 3)], link="dcn")
    # a 100x bigger intra-machine flow on machine 0's ICI ring
    FL.record_edge_traffic(spec, 1e8, registry=reg,
                           pairs=[(0, 1), (1, 0)], link="ici")
    pod = PodSpec.from_telemetry(M, L, registry=reg, link="dcn")
    assert pod.link_cost_overrides  # calibration took hold
    assert all(key[1] == 0 for key, _ in pod.link_cost_overrides)
    # ignoring the link filter WOULD see the ICI flow — prove the
    # filter is what kept it out
    pod_ici = PodSpec.from_telemetry(M, L, registry=reg, link="ici")
    assert all(key[1] == 1 for key, _ in pod_ici.link_cost_overrides)
    # the calibrated pod compiles hierarchically
    compiled = compile_topology(pod, hierarchical=True)
    assert compiled.local_size == L
    assert "hierarchical" in compiled.report


# ------------------------------------------------------------------ #
# compiler: hierarchical beats flat at the acceptance pod
# ------------------------------------------------------------------ #
@pytest.mark.topology
def test_hierarchical_synthesis_beats_flat_at_8x16():
    """ISSUE acceptance: at the 8-machine x 16-chip pod with 4x DCN
    links (the PodSpec default ratio), hierarchical synthesis wins
    ``cost_to_consensus`` over the flat compile — DCN rounds move one
    machine-mean instead of deg(rank) full-width payloads."""
    pod = PodSpec(8, 16)
    flat = compile_topology(pod)
    hier = compile_topology(pod, hierarchical=True)
    assert hier.local_size == 16
    assert hier.machine_schedule[0].size == 8
    assert (hier.score["cost_to_consensus"]
            < flat.score["cost_to_consensus"])
    assert hier.name.startswith("hier:")
    js = hier.as_json()
    assert js["local_size"] == 16


def test_compile_hierarchical_needs_multiple_machines():
    with pytest.raises(ValueError, match="machines"):
        compile_topology(PodSpec(1, 8), hierarchical=True)


# ------------------------------------------------------------------ #
# builder validation
# ------------------------------------------------------------------ #
def test_build_train_step_hierarchical_validation(monkeypatch):
    mesh = _mesh()
    mspec = uniform_topology_spec(ExponentialTwoGraph(M))
    # PodSpec local size conflicts with an explicit local size
    with pytest.raises(ValueError, match="conflicts"):
        F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="cta",
                           topology=mspec, hierarchical=PodSpec(M, L),
                           hierarchical_local_size=L + 1)
    # the pod must cover the mesh: 2 machines x 2 chips != 8 ranks
    # (the spec size is consistent with L=2, so this is the POD check)
    with pytest.raises(ValueError, match="cover"):
        F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="cta",
                           topology=mspec, hierarchical=PodSpec(2, 2))
    # push_sum mixes (x, w) as a unit — no hierarchical variant
    with pytest.raises(ValueError, match="push_sum"):
        F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="push_sum",
                           topology=uniform_topology_spec(
                               ExponentialTwoGraph(N)),
                           hierarchical_local_size=L)
    # a RANK-sized spec where the machine schedule belongs
    with pytest.raises(ValueError, match="does not match"):
        F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="cta",
                           topology=uniform_topology_spec(
                               ExponentialTwoGraph(N)),
                           hierarchical_local_size=L)
    # the env default drives builds that did not pass hierarchical=
    monkeypatch.setenv("BLUEFOG_HIER_LOCAL_SIZE", str(L))
    step = F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="cta",
                              topology=mspec)
    assert step.hierarchical_local_size == L
    # ... and explicit arguments win over it
    monkeypatch.setenv("BLUEFOG_HIER_LOCAL_SIZE", "3")
    step = F.build_train_step(_loss_fn, _OPT, mesh, comm_mode="cta",
                              topology=mspec, hierarchical=PodSpec(M, L))
    assert step.hierarchical_local_size == L
