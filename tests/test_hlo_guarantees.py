"""Machine-checks of the architecture's communication-cost guarantees.

The whole TPU-first bet is the shift-class decomposition: a digraph's edge
set partitions by ``(dst - src) mod n`` and each class lowers to exactly one
``lax.ppermute`` (bluefog_tpu/topology/spec.py).  That gives BlueFog's
headline O(1)-communication-per-step property for the dynamic one-peer
schedule (reference README.rst:51-60) and log2(n) permutes for the static
exponential-2 graph.  These tests compile the real programs and count
``collective-permute`` ops in the optimized HLO, turning the docstring claim
into a regression-guarded fact — and verify the dynamic schedule compiles
one program a ROUND, each holding that round's permutes at its top level
(no ``conditional``), with the model traced once for all of them.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.optim import functional as F
from bluefog_tpu.parallel import collectives as C
from bluefog_tpu.topology import graphs
from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule
from bluefog_tpu.topology.spec import uniform_topology_spec

# every test here is a machine-checked performance guarantee on the
# compiled HLO (the 8B audit below additionally carries `slow`)
pytestmark = pytest.mark.perf

N = 8


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _compiled_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _count_permutes(hlo_text: str) -> int:
    # Optimized CPU/TPU HLO spells the op "collective-permute(" (the async
    # TPU lowering uses "collective-permute-start(" — count both spellings,
    # start-only for the async pair so one op is not counted twice).
    return len(re.findall(r"collective-permute(?:-start)?\(", hlo_text))


def _sharded_combine(mesh, spec):
    def combine(x):
        return C.neighbor_allreduce(x, spec, "bf")

    return jax.shard_map(combine, mesh=mesh, in_specs=P("bf"),
                         out_specs=P("bf"), check_vma=False)


def test_static_exp2_combine_is_log_n_permutes(mesh):
    """Static exponential-2 combine: exactly log2(n) collective-permutes
    (one per shift class), nothing more."""
    spec = uniform_topology_spec(graphs.ExponentialTwoGraph(N))
    assert len(spec.shift_classes) == int(np.log2(N))
    x = jnp.zeros((N, 64), jnp.float32)
    hlo = _compiled_hlo(_sharded_combine(mesh, spec), x)
    assert _count_permutes(hlo) == int(np.log2(N))


def test_one_peer_round_is_one_permute(mesh):
    """Each one-peer dynamic round costs exactly ONE collective-permute —
    the O(1)-communication-per-iteration claim (reference README.rst:51-60),
    checked in compiled HLO."""
    schedule = one_peer_dynamic_schedule(N)
    assert len(schedule) == int(np.log2(N))
    x = jnp.zeros((N, 64), jnp.float32)
    for spec in schedule:
        assert len(spec.shift_classes) == 1
        hlo = _compiled_hlo(_sharded_combine(mesh, spec), x)
        assert _count_permutes(hlo) == 1


def test_ring_combine_is_one_permute_per_direction(mesh):
    """Unidirectional ring = 1 permute; bidirectional ring = 2."""
    x = jnp.zeros((N, 16), jnp.float32)
    uni = uniform_topology_spec(graphs.RingGraph(N, connect_style=1))
    bi = uniform_topology_spec(graphs.RingGraph(N, connect_style=0))
    assert _count_permutes(_compiled_hlo(_sharded_combine(mesh, uni), x)) == 1
    assert _count_permutes(_compiled_hlo(_sharded_combine(mesh, bi), x)) == 2


def test_dynamic_schedule_compiles_one_program_a_round(mesh):
    """The full dynamic train step traces the MODEL once and compiles
    one program a round: the host picks the round's executable from the
    step it is called with, so stepping through the schedule a second
    time neither retraces nor recompiles (SURVEY.md §7 hard part #2)."""
    schedule = one_peer_dynamic_schedule(N)
    trace_count = 0

    def loss_fn(params, batch):
        nonlocal trace_count
        trace_count += 1
        return jnp.mean((batch @ params["w"]) ** 2)

    step_fn = F.build_train_step(
        loss_fn, optax.sgd(0.1), mesh, comm_mode="cta", schedule=schedule,
        donate=False)

    sharding = NamedSharding(mesh, P("bf"))
    params = {"w": jax.device_put(jnp.ones((N, 4, 2)), sharding)}
    opt_state = F.rank_major(optax.sgd(0.1).init({"w": jnp.ones((4, 2))}),
                             mesh)
    batch = jax.device_put(jnp.ones((N, 3, 4)), sharding)

    for step in range(2 * len(schedule)):
        params, opt_state, _ = step_fn(params, opt_state, batch,
                                       jnp.asarray(step))
        assert step_fn.jitted._cache_size() == min(step + 1, len(schedule))
    assert trace_count == 1, (
        f"dynamic schedule retraced: loss_fn traced {trace_count} times "
        f"over {2 * len(schedule)} steps")


def test_dynamic_step_program_permute_total_is_schedule_size(mesh):
    """Each round of the dynamic step is its own program holding ONE
    permute (= log2(n) across the schedule's programs), at the
    program's top level and under no conditional: the per-step wire
    cost is a single permute that waits for its own operand alone."""
    schedule = one_peer_dynamic_schedule(N)
    step_fn = F.build_train_step(
        lambda params, batch: jnp.mean((batch @ params["w"]) ** 2),
        optax.sgd(0.1), mesh, comm_mode="cta", schedule=schedule,
        donate=False)
    sharding = NamedSharding(mesh, P("bf"))
    params = {"w": jax.device_put(jnp.ones((N, 4, 2)), sharding)}
    opt_state = F.rank_major(optax.sgd(0.1).init({"w": jnp.ones((4, 2))}),
                             mesh)
    batch = jax.device_put(jnp.ones((N, 3, 4)), sharding)
    total = 0
    for r in range(len(schedule)):
        hlo = step_fn.lower(params, opt_state, batch,
                            r).compile().as_text()
        assert _count_permutes(hlo) == 1, r
        assert "conditional" not in hlo, r
        total += _count_permutes(hlo)
    assert total == len(schedule)


@pytest.mark.topology
def test_compiled_schedule_lowers_to_predicted_permutes_and_bytes(mesh):
    """ISSUE 7 acceptance: the topology compiler's cost model and the
    real lowering must agree.  Compile the (1, 8)-pod schedule (its
    winner carries bidirectional multi-shift rounds), lower it one
    program a round (exactly how build_train_step consumes it), and
    hold the compiled HLO to the prediction: the predicted permute
    count per round — shift classes after the lowering's in-degree-1
    fusion rule — the rounds' programs together holding the period's,
    each permute carrying exactly the per-rank payload bytes, measured
    through benchutil.scheduled_collective_windows."""
    from bluefog_tpu import benchutil as BU
    from bluefog_tpu.topology.compiler import PodSpec, compile_topology

    compiled = compile_topology(PodSpec(1, 8))
    schedule = compiled.schedule
    payload = 64 * 4  # f32[64] per rank
    pred = compiled.predicted_collectives(payload)
    assert pred["permutes_per_period"] > len(schedule)  # multi-shift

    x = jnp.zeros((N, 64), jnp.float32)
    # thin wrapper over the supported contract check (count, per-permute
    # payload, total bytes — the assertions this test used to hand-roll):
    # each round's program reproduces the per-round permute counts the
    # cost model charged, and together they are the period
    rounds = [_compiled_hlo(_sharded_combine(mesh, rnd), x)
              for rnd in schedule]
    for i, hlo_r in enumerate(rounds):
        assert BU.verify_collective_contract(
            hlo_r, pred, payload, round_index=i) == []
        assert "conditional" not in hlo_r
    assert sum(_count_permutes(h) for h in rounds) == \
        pred["permutes_per_period"]


@pytest.mark.topology
@pytest.mark.moe
def test_compiled_all_to_all_lowers_to_predicted_permutes_and_bytes(mesh):
    """ISSUE 19 acceptance: the a2a schedule synthesizer's cost model
    and the real expert-dispatch lowering must agree.  Compile the
    (4, 2)-pod all-to-all, lower the full multi-round dispatch (and
    each round alone) through moe.all_to_all_dispatch, and hold the
    HLO to predicted_collectives permute-for-permute and
    byte-for-byte — the same verify_collective_contract the mixing
    schedules answer to."""
    from bluefog_tpu import benchutil as BU
    from bluefog_tpu.moe import all_to_all_dispatch, dispatch_plan
    from bluefog_tpu.topology.compiler import (
        PodSpec, compile_all_to_all, naive_all_to_all_cost)

    pod = PodSpec(4, 2, dcn_cost=4.0)
    compiled = compile_all_to_all(pod)
    payload = 16 * 4  # each pair moves one f32[16] shard
    pred = compiled.predicted_collectives(payload)
    plan = dispatch_plan(compiled.schedule)
    # host-side consistency: the lowering plan issues exactly the
    # permutes the prediction charges for
    assert plan.permutes_per_period == pred["permutes_per_period"]
    # and the synthesized schedule beats the topology-blind linear
    # baseline under the pod's own cost model
    assert compiled.score["cost_to_dispatch"] < naive_all_to_all_cost(pod)

    def _prog(p):
        def run(v):
            return all_to_all_dispatch(v[0], p, "bf")[None]
        return jax.shard_map(run, mesh=mesh, in_specs=P("bf"),
                             out_specs=P("bf"), check_vma=False)

    x = jnp.zeros((N, N, 16), jnp.float32)
    hlo = _compiled_hlo(_prog(plan), x)
    assert BU.verify_collective_contract(hlo, pred, payload) == []
    for i, rnd in enumerate(compiled.schedule):
        hlo_r = _compiled_hlo(_prog(dispatch_plan([rnd])), x)
        assert BU.verify_collective_contract(
            hlo_r, pred, payload, round_index=i) == []


# --- hierarchical two-level exchange: the wire-pattern guarantees ---

def _count_reduces(hlo_text: str) -> int:
    return len(re.findall(r"all-reduce(?:-start)?\(", hlo_text))


def _sharded_hier(mesh, spec, local_size, **kw):
    def combine(x):
        return C.hierarchical_neighbor_allreduce(x, spec, local_size,
                                                 "bf", **kw)

    return jax.shard_map(combine, mesh=mesh, in_specs=P("bf"),
                         out_specs=P("bf"), check_vma=False)


@pytest.mark.hier
def test_hierarchical_combine_is_one_grouped_reduce_plus_machine_permutes(mesh):
    """The two-level decomposition's wire pattern, machine-checked:
    exactly ONE all-reduce, grouped over the intra-machine rank blocks
    (the ICI leg — ``replica_groups`` must spell out the machine
    decomposition), plus one collective-permute per MACHINE shift class
    (the DCN leg) — and nothing else.  Per-machine DCN cost per round
    is the machine mean's width, not deg(rank) full-width sends."""
    spec = uniform_topology_spec(graphs.ExponentialTwoGraph(4))
    x = jnp.zeros((N, 64), jnp.float32)
    hlo = _compiled_hlo(_sharded_hier(mesh, spec, 2), x)
    assert _count_reduces(hlo) == 1
    assert "replica_groups={{0,1},{2,3},{4,5},{6,7}}" in hlo
    assert _count_permutes(hlo) == len(spec.shift_classes)
    # full-precision ICI leg with int8 on the wire: compression applies
    # to the DCN permutes only, so the reduce count cannot change
    hlo8 = _compiled_hlo(_sharded_hier(
        mesh, spec, 2, compress="int8",
        wire_key=jax.random.PRNGKey(0)), x, )
    assert _count_reduces(hlo8) == 1
    assert "replica_groups={{0,1},{2,3},{4,5},{6,7}}" in hlo8


@pytest.mark.hier
def test_hierarchical_one_rank_machines_lower_like_flat(mesh):
    """L == 1: the singleton-group reduce is free to fold away, and the
    permute structure must equal the flat exchange's — the bitwise
    parity the epilogue matrix asserts, visible at the HLO level."""
    spec = uniform_topology_spec(graphs.ExponentialTwoGraph(N))
    x = jnp.zeros((N, 64), jnp.float32)
    hlo = _compiled_hlo(_sharded_hier(mesh, spec, 1), x)
    assert _count_permutes(hlo) == len(spec.shift_classes)


@pytest.mark.hier
@pytest.mark.topology
def test_compiled_hierarchical_lowers_to_predictions(mesh):
    """The hierarchical compiler artifact and the real lowering agree:
    per machine round, exactly the predicted ONE grouped all-reduce and
    the predicted permute count, each permute carrying exactly the
    per-rank payload bytes of ``predicted_collectives``."""
    from bluefog_tpu import benchutil as BU
    from bluefog_tpu.topology.compiler import PodSpec, compile_topology

    compiled = compile_topology(PodSpec(4, 2), hierarchical=True)
    assert compiled.local_size == 2
    payload = 64 * 4  # f32[64] per rank
    pred = compiled.predicted_collectives(payload)
    assert pred["all_reduces_per_period"] == len(compiled.machine_schedule)
    assert pred["all_reduce_group_size"] == 2
    x = jnp.zeros((N, 64), jnp.float32)
    # thin wrapper: each round held to per_round[i] (one grouped reduce
    # with the machine replica_groups + predicted permutes/bytes); the
    # per-period total follows because the contract check also verifies
    # the prediction's per-round/per-period internal consistency
    for i, rnd in enumerate(compiled.machine_schedule):
        hlo = _compiled_hlo(_sharded_hier(mesh, rnd, 2), x)
        assert BU.verify_collective_contract(
            hlo, pred, payload, round_index=i) == []


def test_pipeline_is_one_permute_per_tick(mesh):
    """The GPipe pipeline's wire cost: activations move stage-to-stage
    with a single nearest-neighbor collective-permute per tick, inside
    ONE scan loop (not unrolled) — so the compiled forward contains
    exactly one permute instruction, and forward+backward exactly two
    (the reversed permute the autodiff transpose inserts)."""
    from bluefog_tpu.parallel.pipeline import gpipe

    n_micro = 4

    def fwd(w, x_micro):
        def stage_fn(w, x):
            return jnp.tanh(x @ w[0])  # [1,16,16] per-shard slice

        outs = gpipe(stage_fn, w, x_micro, "bf", N)
        return outs

    def loss(w, x_micro):
        return jnp.sum(fwd(w, x_micro) ** 2)

    sm_fwd = jax.shard_map(fwd, mesh=mesh, in_specs=(P("bf"), P()),
                           out_specs=P(), check_vma=False)
    sm_grad = jax.shard_map(jax.grad(loss), mesh=mesh,
                            in_specs=(P("bf"), P()), out_specs=P("bf"),
                            check_vma=False)
    w = jnp.zeros((N, 16, 16), jnp.float32)
    x = jnp.zeros((n_micro, 2, 16), jnp.float32)
    hlo_fwd = _compiled_hlo(sm_fwd, w, x)
    assert _count_permutes(hlo_fwd) == 1, hlo_fwd.count("collective-permute")
    # the scan stayed a loop: one while op, not M+S-1 unrolled bodies
    assert "while" in hlo_fwd
    hlo_grad = _compiled_hlo(sm_grad, w, x)
    assert _count_permutes(hlo_grad) == 2


def test_allreduce_baseline_uses_no_permute_but_psum(mesh):
    """Sanity contrast: the centralized baseline lowers to all-reduce, the
    decentralized combine to collective-permute — they are genuinely
    different wire patterns, which is what the scaling claim rides on."""
    def ar(x):
        return C.allreduce(x, "bf")

    sm = jax.shard_map(ar, mesh=mesh, in_specs=P("bf"), out_specs=P("bf"),
                       check_vma=False)
    hlo = _compiled_hlo(sm, jnp.zeros((N, 16), jnp.float32))
    assert _count_permutes(hlo) == 0
    assert "all-reduce" in hlo


# --- overlap engine: bucketed exchange, verified at the schedule level ---

def _overlap_problem():
    """Multi-leaf model (4 matmul kernels + 4 biases) so bucketing has
    something to balance; leaf dtypes are uniform f32."""
    base = {f"w{i}": jnp.eye(16) * 0.5 for i in range(4)}
    base.update({f"b{i}": jnp.zeros((16,)) for i in range(4)})

    def loss_fn(params, batch):
        h = batch
        for i in range(4):
            h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
        return jnp.mean((h - 1.0) ** 2)

    return base, loss_fn


def _lower_step(mesh, base, loss_fn, step=0, **kw):
    import optax as ox

    opt = ox.sgd(0.05)
    step_fn = F.build_train_step(loss_fn, opt, mesh, donate=False, **kw)
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)
    batch = jax.device_put(
        np.zeros((N, 8, 16)), NamedSharding(mesh, P("bf")))
    return step_fn.lower(params, ostate, batch,
                         np.int32(step)).compile().as_text()


@pytest.mark.parametrize("comm_mode", ["cta", "atc"])
def test_bucketed_step_k_exchanges_with_compute_between(mesh, comm_mode):
    """What build_train_step(overlap='bucketed', K) promises on any
    backend: >= K collective-permutes — one per size-balanced bucket,
    NOT one monolithic tail exchange and NOT one per leaf — every one
    within the planner's threshold, and every one with nonzero
    dataflow-INDEPENDENT compute, the admissible set a latency-hiding
    scheduler draws from.  Where the lowering is asynchronous (TPU)
    the scheduled program also carries compute inside each
    start->done window; where it is synchronous (this CPU) the order
    in which the backend's scheduler issues the permutes is its own
    and no promise of the builder."""
    from bluefog_tpu import benchutil as BU
    from bluefog_tpu.optim import fusion

    K = 4
    base, loss_fn = _overlap_problem()
    spec = one_peer_dynamic_schedule(N)[0]  # single shift class
    hlo = _lower_step(mesh, base, loss_fn, comm_mode=comm_mode,
                      topology=spec, overlap="bucketed",
                      overlap_buckets=K)
    assert _count_permutes(hlo) >= K

    wins = [w for w in BU.scheduled_collective_windows(hlo)
            if w["kind"] == "collective-permute"]
    assert len(wins) >= K
    # size-balanced: no bucket exceeds the planner's ceil(total/K)
    # threshold (uniform dtype, no oversize leaf in this tree)
    rows = fusion.bucket_signature(list(
        jax.tree_util.tree_flatten(base)[0]))
    threshold = fusion.size_balanced_threshold(rows, K)
    assert all(w["bytes"] <= threshold for w in wins)
    # the latency-hiding scheduler's admissible set is non-empty for
    # EVERY bucket: compute independent of that bucket's exchange
    assert all(w["independent_flops"] > 0 for w in wins)
    # async lowering (TPU): compute scheduled INSIDE each window
    assert all(w["window_flops"] > 0 for w in wins if w["async"])


def test_unbucketed_step_is_per_leaf_tail_exchange(mesh):
    """Contrast pin: overlap='none' issues one permute PER LEAF with
    unbalanced payloads — the structure the bucketed mode replaces."""
    from bluefog_tpu import benchutil as BU

    base, loss_fn = _overlap_problem()
    spec = one_peer_dynamic_schedule(N)[0]
    hlo = _lower_step(mesh, base, loss_fn, comm_mode="atc",
                      topology=spec)
    wins = [w for w in BU.scheduled_collective_windows(hlo)
            if w["kind"] == "collective-permute"]
    assert len(wins) == len(jax.tree_util.tree_leaves(base))
    sizes = sorted(w["bytes"] for w in wins)
    assert sizes[-1] > 4 * sizes[0]  # biases vs kernels: unbalanced


def test_bucketed_dynamic_schedule_total_permutes(mesh):
    """The bucketed combine plumbs through the dynamic schedule: each
    round's program holds >= K permutes (one program runs per step),
    under no conditional."""
    K = 3
    base, loss_fn = _overlap_problem()
    schedule = one_peer_dynamic_schedule(N)
    for r in range(len(schedule)):
        hlo = _lower_step(mesh, base, loss_fn, step=r, comm_mode="atc",
                          schedule=schedule, overlap="bucketed",
                          overlap_buckets=K)
        assert _count_permutes(hlo) >= K, r
        assert "conditional" not in hlo, r


_ASYNC_FIXTURE = """\
HloModule overlap_fixture, is_scheduled=true

ENTRY %main (p0: f32[1024,256], p1: f32[256,256]) -> f32[1024,256] {
  %p0 = f32[1024,256]{1,0} parameter(0)
  %p1 = f32[256,256]{1,0} parameter(1)
  %cps = (f32[1024,256]{1,0}, f32[1024,256]{1,0}) collective-permute-start(f32[1024,256]{1,0} %p0), source_target_pairs={{0,1},{1,0}}
  %hide = f32[1024,256]{1,0} dot(f32[1024,256]{1,0} %p0, f32[256,256]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %cpd = f32[1024,256]{1,0} collective-permute-done((f32[1024,256]{1,0}, f32[1024,256]{1,0}) %cps)
  %cps.2 = (f32[1024,256]{1,0}, f32[1024,256]{1,0}) collective-permute-start(f32[1024,256]{1,0} %hide), source_target_pairs={{0,1},{1,0}}
  %cpd.2 = f32[1024,256]{1,0} collective-permute-done((f32[1024,256]{1,0}, f32[1024,256]{1,0}) %cps.2)
  ROOT %out = f32[1024,256]{1,0} add(f32[1024,256]{1,0} %cpd, f32[1024,256]{1,0} %cpd.2)
}
"""


def test_overlap_accounting_async_windows():
    """The scheduled-window accounting on a TPU-style async module: the
    first permute's start->done window holds a dot (overlappable at a
    threshold its flops clear), the second's window is empty (never
    overlappable) -> byte-weighted fraction 0.5, basis 'scheduled'."""
    from bluefog_tpu.benchutil import (overlap_accounting,
                                       scheduled_collective_windows)

    wins = scheduled_collective_windows(_ASYNC_FIXTURE)
    assert [w["async"] for w in wins] == [True, True]
    payload = 1024 * 256 * 4
    assert [w["bytes"] for w in wins] == [payload, payload]
    dot_flops = 2 * 1024 * 256 * 256
    assert wins[0]["window_flops"] == dot_flops
    assert wins[1]["window_flops"] == 0.0
    # threshold the dot clears: transfer = payload/link; flops/peak must
    # exceed it.  peak=1e12, link=1e9 -> hide 1.34e-4 s >= 1.05e-3 s?
    # no — pick link so transfer is smaller: link=1e10 -> 1.05e-4 s.
    acc = overlap_accounting(_ASYNC_FIXTURE, peak_flops_per_s=1e12,
                             link_bytes_per_s=1e10)
    assert acc["basis"] == "scheduled"
    assert acc["bytes_total"] == 2 * payload
    assert acc["bytes_overlappable"] == payload
    assert acc["fraction"] == 0.5
    # an impossible link speed makes nothing overlappable
    slow = overlap_accounting(_ASYNC_FIXTURE, peak_flops_per_s=1e12,
                              link_bytes_per_s=1e6)
    assert slow["fraction"] == 0.0


def test_overlap_accounting_dataflow_basis_on_real_step(mesh):
    """On this CPU lowering (sync permutes) the accounting falls back to
    the dataflow basis and, with generous hardware figures, finds every
    bucket hideable; with an absurdly slow link, none."""
    from bluefog_tpu.benchutil import overlap_accounting

    base, loss_fn = _overlap_problem()
    spec = one_peer_dynamic_schedule(N)[0]
    hlo = _lower_step(mesh, base, loss_fn, comm_mode="atc",
                      topology=spec, overlap="bucketed",
                      overlap_buckets=4)
    acc = overlap_accounting(hlo, peak_flops_per_s=1e6,
                             link_bytes_per_s=1e12)
    assert acc["basis"] == "dataflow"
    assert sum(r["count"] for r in acc["per_kind"].values()) >= 4
    assert acc["fraction"] == 1.0
    none = overlap_accounting(hlo, peak_flops_per_s=1e15,
                              link_bytes_per_s=1.0)
    assert none["fraction"] == 0.0


@pytest.mark.slow
@pytest.mark.hier
def test_8b_overlap_audit_end_to_end(tmp_path):
    """The full 8B overlap audit (benchmarks/llama_8b_overlap.py): AOT
    compile of the bucketed tp8_seqshard step + accounting + defended
    projection.  Minutes of compile — excluded from tier-1 by the slow
    marker; the fast schedule checks above cover the engine."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "r06.json"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run(
        [sys.executable,
         os.path.join(repo, "benchmarks", "llama_8b_overlap.py"),
         "--out", str(out)], check=True, env=env, cwd=repo)
    import json

    got = json.loads(out.read_text())
    assert 0.0 <= got["overlap"]["dp_neighbor_exchange"]["fraction"] <= 1.0
    assert got["overlap"]["buckets"] >= 1
    # ISSUE 11: the hierarchical audit rides it too — the two-level
    # exchange halves measured DCN bytes/step at bounded cost-model
    # overhead, with the tp overlap fraction still defended
    hier = got["hierarchical"]["claims"]
    assert hier["dcn_bytes_cut"] is True
    assert hier["dcn_bytes_ratio"] <= 0.75
    assert hier["tp_overlap_defended"] is True
    assert hier["cost_model_overhead_bounded"] is True


def test_hlo_collective_bytes_extraction(mesh):
    """The scaling-projection harness's byte extractor
    (benchutil.hlo_collective_bytes) reads per-device payloads out of
    compiled HLO: one permute per shift class carrying the f32 shard, and
    the tuple-fused all-reduce counted with every element (the printed
    /*index=N*/ comments must not truncate the tuple)."""
    from bluefog_tpu.benchutil import hlo_collective_bytes

    spec = uniform_topology_spec(graphs.ExponentialTwoGraph(N))

    def combine(x, y):
        out = C.neighbor_allreduce(x, spec, "bf")
        # two leaves psum'd together -> one fused tuple all-reduce
        return out, C.allreduce(x, "bf") + 0.0 * out, C.allreduce(y, "bf")

    sm = jax.shard_map(combine, mesh=mesh,
                       in_specs=(P("bf"), P("bf")),
                       out_specs=(P("bf"), P("bf"), P("bf")),
                       check_vma=False)
    hlo = _compiled_hlo(sm, jnp.zeros((N, 64), jnp.float32),
                        jnp.zeros((N, 96), jnp.float32))
    got = hlo_collective_bytes(hlo)
    shard_bytes = 64 * 4
    assert got["collective-permute"]["count"] == int(np.log2(N))
    assert got["collective-permute"]["bytes"] == \
        int(np.log2(N)) * shard_bytes
    # both psums present with full payload regardless of fusion layout
    assert got["all-reduce"]["bytes"] == 64 * 4 + 96 * 4
