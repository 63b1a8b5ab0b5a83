"""Adaptive-topology chaos benchmark: the closed control loop, measured.

Round-16 evidence for the topology control plane (ISSUE 15): a running
``run_resilient`` fleet whose mixing schedule is re-planned ONLINE from
its own telemetry — congestion detected from ``bf_edge_seconds_total``
window deltas, a candidate synthesized and re-scored against the
incumbent, hot-swapped as pure ``(class_weights, self_weights)`` data at
a step boundary (zero recompiles, asserted), health-watched on
probation, and rolled back when a forced bad plan worsens consensus.

The wire is VIRTUAL: every step the harness bills each active
(nonzero-weight) edge of the live schedule ``pod.round_cost([edge]) *
congestion_factor`` seconds into the metrics registry — exactly the
``record_edge_timing`` feed a real fleet would emit — and the per-step
"wall time" is the bottleneck link's ``load * cost * factor`` after
routing the active edges onto the pod torus (the contention model
``round_cost`` prices), so the p50 step-time claims are deterministic
on CPU while measuring the same quantity a TPU fleet's clock would.  Congestion factors come from
``FaultPlan.congested_links`` (the ``congest_link`` fault this round
adds); zero-weight declared edges push nothing and are billed nothing.

Four scenarios, one JSON artifact (chaos_resilience.py style):

1. **Congested DCN link** (8 CPU 'ranks', 4 machines x 2 chips): the
   static incumbent is a DCN-heavy machine-ring plan (three DCN rounds
   and one intra-machine round per period — connected, but it leans on
   the wide-area links); from step 8 the two rank links of
   machine link 0->1 carry bytes 4x slower.  The plane must see the pressure in its windowed deltas,
   debounce it for ``patience`` windows, synthesize over the
   telemetry-calibrated pod, and swap a plan that avoids the slow link.
   Headline: post-swap p50 virtual step time / pre-swap (congested)
   p50, and incumbent/candidate cost-to-consensus — both from the run.
2. **25% fleet shrink**: machine 3 (ranks 6, 7) dies.  The membership
   transition triggers re-planning immediately (no patience); the
   adapted schedule is compared against a SECOND, control-free run of
   the same faults where the incumbent is merely healed — p50 virtual
   step time and cost-to-consensus, adapted vs static-healed.
3. **Forced bad candidate -> rollback**: ``force_candidate`` injects a
   frozen (no-mixing) schedule mid-run; per-rank target heterogeneity
   makes the consensus distance blow past the pre-swap health within
   probation, the plane rolls back to the incumbent, and the
   consensus floor at the end of the run is back at its pre-injection
   level — the rollback did not move it.
4. **Persistent straggler**: rank 5 runs 0.25 s/step slow forever
   (``FaultPlan.persistent_straggler``); the ``StragglerDetector``
   names it, its z-score degrades the plane's windows, and the
   trigger->synthesis cycle runs with synthetic load priced onto the
   straggler's links.  The decision (swap or reject) is recorded; the
   machine-checked claims are the z-driven trigger and zero recompiles.

Every scenario asserts ``step.jitted._cache_size() - len(carrier) == 0``
(one program a round of the carrier, compiled in the first cycle) across
its ENTIRE trigger -> swap -> (commit | rollback) cycle: the whole loop
is weight data through the same compiled programs.

The JSON doubles as the bench-gate baseline: ``--compare`` defaults to
the committed ``chaos_adaptive_topology_r16.json`` (pass ``''`` to
disable) and gates the ``adaptation.step_time_ratio`` (lower-better)
and ``adaptation.cost_to_consensus_advantage`` (higher-better)
headlines before overwriting ``--out``.

Run (CPU, no TPU): JAX_PLATFORMS=cpu python benchmarks/chaos_adaptive_topology.py
"""

import argparse
import json
import math
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

N = 8
MACHINES, LOCAL = 4, 2
SHIFTS = (1, 2, 4, 6, 7)   # declared by every carrier round
ROUNDS = 4                 # carrier period
WIRE_UNIT = 1e-3           # virtual seconds per unit of pod cost


def make_pod():
    from bluefog_tpu.topology import PodSpec

    return PodSpec(MACHINES, LOCAL, ici_cost=1.0, dcn_cost=4.0)


def rich_carrier():
    """The schedule the step COMPILES over: 4 identical rounds, each
    declaring the FULL permutation of every shift in ``SHIFTS`` —
    5 shift classes, so the ring/exp2/menu alternatives (and the
    incumbent) are all expressible as pure weight data."""
    from bluefog_tpu.topology import DynamicTopology

    w = 1.0 / (len(SHIFTS) + 1)
    ew = {(i, (i + s) % N): w for s in SHIFTS for i in range(N)}
    r = DynamicTopology.from_edges(N, ew, [w] * N)
    return [r] * ROUNDS


def ici_round():
    """Intra-machine chip exchange (pure ICI, shifts {1, 7})."""
    from bluefog_tpu.topology import DynamicTopology

    ew = {}
    for m in range(MACHINES):
        a, b = LOCAL * m, LOCAL * m + 1
        ew[(a, b)] = 0.5
        ew[(b, a)] = 0.5
    return DynamicTopology.from_edges(N, ew, [0.5] * N)


def dcn_round(direction):
    """Machine-ring DCN exchange expanded to counterpart rank pairs
    (shift +2 for direction +1, shift 6 for -1)."""
    from bluefog_tpu.topology import DynamicTopology, expand_machine_pairs

    order = list(range(MACHINES))
    if direction < 0:
        order = list(reversed(order))
    mpairs = [(order[i], order[(i + 1) % MACHINES])
              for i in range(MACHINES)]
    ew = {p: 0.5 for p in expand_machine_pairs(mpairs, LOCAL)}
    return DynamicTopology.from_edges(N, ew, [0.5] * N)


import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bluefog_tpu.sim.wire import LinkWire  # noqa: E402


class VirtualWire(LinkWire):
    """Per-step virtual transport — now a thin wrapper over the sim
    package's :class:`~bluefog_tpu.sim.wire.LinkWire` (the billing
    math moved there verbatim, so the committed r16 baselines stay
    valid): each step the ACTIVE (nonzero-weight, healed) edges of the
    live round are routed onto the pod's torus links; the step's
    charge is the bottleneck link's ``load * link_cost *
    congestion_factor``; each edge is billed its own
    ``pod.round_cost([edge]) * factor * WIRE_UNIT`` seconds into the
    registry — the ``record_edge_timing`` feed the control plane's
    windowed deltas read.  The p50 claims are over complete
    ``ROUNDS``-step schedule periods."""

    def __init__(self, pod, registry, schedule_fn, dead_fn, plan=None):
        super().__init__(
            pod, registry, schedule_fn, dead_fn,
            congestion_fn=(plan.congested_links
                           if plan is not None else None),
            wire_unit=WIRE_UNIT, period=ROUNDS)
        self.plan = plan


def _training_setup(seed, hetero=0.0):
    """Shared linear-regression fleet: rank-major data; ``hetero``
    offsets each rank's target so consensus distance is a live signal
    (without mixing the ranks diverge toward per-rank optima)."""
    import jax.numpy as jnp
    import optax

    dim, width = 16, 4
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dim, width)
    w_rank = w_true[None] + hetero * rng.randn(N, dim, width)
    xs = rng.randn(64, N, 8, dim)
    ys = np.einsum("bnsd,ndw->bnsw", xs, w_rank) \
        + 0.01 * rng.randn(64, N, 8, width)

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    opt = optax.sgd(0.05, momentum=0.9)
    return dim, width, xs, ys, loss_fn, opt


def _fresh(mesh, dim, width, opt):
    import jax.numpy as jnp

    from bluefog_tpu.optim import functional as F

    params = F.rank_major({"w": jnp.zeros((dim, width))}, mesh)
    opt_state = F.rank_major(opt.init({"w": jnp.zeros((dim, width))}),
                             mesh)
    return params, opt_state


def _consensus(params):
    """Max live-row deviation from the row mean over rank-major
    leaves (all ranks live — the rollback scenario kills nobody)."""
    import jax

    worst = 0.0
    for leaf in jax.tree.leaves(params):
        a = np.asarray(leaf, np.float64)
        if a.ndim < 1 or a.shape[0] != N:
            continue
        worst = max(worst, float(np.max(np.abs(a - a.mean(axis=0)))))
    return worst


def _events(res, kind):
    return [e for e in res.events if e.kind == kind]


def congestion_scenario(steps, seed):
    """Scenario 1: 4x congested DCN link -> windowed detection ->
    calibrated synthesis -> hot-swap, measured within one run."""
    import jax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.observe import MetricsRegistry
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import TopologyControlPlane

    pod = make_pod()
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    carrier = rich_carrier()
    static = [dcn_round(+1), ici_round(),
              dcn_round(+1), dcn_round(-1)]
    reg = MetricsRegistry()
    # rollback_tolerance 2.0: the first step under a new mixing
    # geometry transiently bumps consensus distance ~1.25x before it
    # contracts; probation should catch catastrophes, not that blip
    control = TopologyControlPlane(
        pod, carrier, registry=reg, window=8, patience=2,
        degrade_ratio=1.3, margin=0.05, cooldown=8, probation=6,
        rollback_tolerance=2.0, contention=3.0, synchronous=True,
        initial=static)

    congest_at = 8
    plan = R.FaultPlan.congest_link(N, 0, 2, 4.0, start=congest_at,
                                    duration=steps)
    plan = plan.merged(R.FaultPlan.congest_link(
        N, 1, 3, 4.0, start=congest_at, duration=steps))

    dim, width, xs, ys, loss_fn, opt = _training_setup(seed)
    det = R.FailureDetector(N)
    wire = VirtualWire(
        pod, reg,
        schedule_fn=lambda s: control.active_schedule()[s % ROUNDS],
        dead_fn=det.dead_mask, plan=plan)

    def batch_fn(step):
        wire.bill(step)
        return (xs[step % 64], ys[step % 64])

    step_g = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=carrier, guard=F.GuardConfig())
    params, opt_state = _fresh(mesh, dim, width, opt)
    import tempfile

    from bluefog_tpu.checkpoint import Checkpointer

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        res = R.run_resilient(
            step_g, params, opt_state, batch_fn, steps=steps,
            checkpointer=ck, mesh=mesh, schedule=carrier,
            fault_plan=plan, detector=det, checkpoint_every=0,
            sleep=lambda s: None, control=control)
        ck.close()
    wall_s = time.monotonic() - t0

    trig = _events(res, "topology_trigger")
    swaps = _events(res, "topology_swap")
    commits = _events(res, "topology_commit")
    swap_step = swaps[0].step if swaps else None
    p50_static = wire.p50(congest_at, swap_step if swap_step is not None
                          else steps)
    p50_adapted = (wire.p50(swap_step + 1, steps)
                   if swap_step is not None else float("nan"))
    inc = swaps[0].detail.get("incumbent") if swaps else None
    cand = swaps[0].detail.get("cost_to_consensus") if swaps else None
    return {
        "steps": steps,
        "congested_links": {"(0,2)": 4.0, "(1,3)": 4.0},
        "congest_at": congest_at,
        "events": [(e.kind, e.step) for e in res.events
                   if e.kind.startswith("topology")],
        "trigger_reasons": [e.detail.get("reason") for e in trig],
        "swap_step": swap_step,
        "adapted_schedule": control.active_name(),
        "committed": bool(commits),
        "recompiles": step_g.jitted._cache_size() - len(carrier),
        "p50_step_cost_static_congested": p50_static,
        "p50_step_cost_adapted": p50_adapted,
        "step_time_ratio": (p50_adapted / p50_static
                            if p50_static and swap_step is not None
                            else float("nan")),
        "incumbent_cost_to_consensus": inc,
        "adapted_cost_to_consensus": cand,
        "cost_to_consensus_advantage": (
            inc / cand if inc and cand else float("nan")),
        "wall_s": wall_s,
    }


def shrink_scenario(steps, seed):
    """Scenario 2: machine 3 dies (25% shrink); adapted run vs a
    control-free run of the SAME faults where the incumbent is only
    healed.  The +1/-1 incumbent stays path-connected after the
    shrink, so both runs converge — the adapted one just pays less."""
    import jax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.observe import MetricsRegistry
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import TopologyControlPlane

    pod = make_pod()
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    carrier = rich_carrier()
    static = [dcn_round(+1), ici_round(),
              dcn_round(+1), dcn_round(-1)]
    die_at = 8
    dim, width, xs, ys, loss_fn, opt = _training_setup(seed)

    import tempfile

    from bluefog_tpu.checkpoint import Checkpointer

    def one_run(with_control):
        reg = MetricsRegistry()
        control = (TopologyControlPlane(
            pod, carrier, registry=reg, window=8, patience=2,
            margin=0.05, cooldown=8, probation=6,
            rollback_tolerance=2.0, synchronous=True,
            initial=static) if with_control else None)
        plan = R.FaultPlan(N, [R.Fault(die_at, 6, "dead"),
                               R.Fault(die_at, 7, "dead")])
        det = R.FailureDetector(N)
        proj_static = None
        if control is None:
            # bill what the healed incumbent plays (the control run
            # bills whatever the plane made active)
            plane = TopologyControlPlane(pod, carrier, window=0,
                                         synchronous=True,
                                         initial=static)
            proj_static = plane.active_schedule()
        wire = VirtualWire(
            pod, reg,
            schedule_fn=(
                (lambda s: control.active_schedule()[s % ROUNDS])
                if control is not None
                else (lambda s: proj_static[s % ROUNDS])),
            dead_fn=det.dead_mask)

        def batch_fn(step):
            wire.bill(step)
            return (xs[step % 64], ys[step % 64])

        step_g = F.build_train_step(
            loss_fn, opt, mesh, comm_mode="atc", schedule=carrier,
            guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0))
        params, opt_state = _fresh(mesh, dim, width, opt)
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            res = R.run_resilient(
                step_g, params, opt_state, batch_fn, steps=steps,
                checkpointer=ck, mesh=mesh, schedule=carrier,
                fault_plan=plan, detector=det,
                checkpoint_every=max(2, steps // 6),
                sleep=lambda s: None, control=control)
            ck.close()
        return res, wire, control, step_g

    res_a, wire_a, control, step_a = one_run(True)
    res_s, wire_s, _, step_s = one_run(False)

    trig = _events(res_a, "topology_trigger")
    swaps = _events(res_a, "topology_swap")
    swap_step = swaps[0].step if swaps else None
    dead_declared = max((e.step for e in res_s.events
                         if e.kind == "rank_dead"), default=die_at)
    p50_static = wire_s.p50(dead_declared + 1, steps)
    p50_adapted = (wire_a.p50(swap_step + 1, steps)
                   if swap_step is not None else float("nan"))
    inc = swaps[0].detail.get("incumbent") if swaps else None
    cand = swaps[0].detail.get("cost_to_consensus") if swaps else None
    live = ~res_a.dead_mask
    return {
        "steps": steps,
        "dead_ranks": [6, 7],
        "die_at": die_at,
        "dead_declared_step": int(dead_declared),
        "trigger_reasons": [e.detail.get("reason") for e in trig],
        "swap_step": swap_step,
        "adapted_schedule": control.active_name(),
        "events": [(e.kind, e.step) for e in res_a.events
                   if e.kind.startswith("topology")],
        "recompiles_adapted": step_a.jitted._cache_size() - len(carrier),
        "recompiles_static": step_s.jitted._cache_size() - len(carrier),
        "p50_step_cost_static_healed": p50_static,
        "p50_step_cost_adapted": p50_adapted,
        "step_time_ratio": (p50_adapted / p50_static
                            if p50_static and swap_step is not None
                            else float("nan")),
        "incumbent_cost_to_consensus": inc,
        "adapted_cost_to_consensus": cand,
        "cost_to_consensus_advantage": (
            inc / cand if inc and cand else float("nan")),
        "final_loss_live_mean_adapted": float(
            np.asarray(res_a.last_loss)[live].mean()),
        "final_loss_live_mean_static": float(
            np.asarray(res_s.last_loss)[live].mean()),
    }


def rollback_scenario(steps, seed):
    """Scenario 3: a forced frozen (no-mixing) candidate must be
    rolled back by the probation health watch, and the consensus
    floor must end where it started."""
    import jax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import (DynamicTopology,
                                      TopologyControlPlane)

    pod = make_pod()
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    carrier = rich_carrier()
    static = [dcn_round(+1), ici_round(),
              dcn_round(+1), dcn_round(-1)]
    control = TopologyControlPlane(
        pod, carrier, window=0, probation=16, rollback_tolerance=1.2,
        cooldown=8, synchronous=True, initial=static)
    frozen = [DynamicTopology.from_edges(N, {}, [1.0] * N)]

    # heterogeneous targets: without mixing the ranks run to their own
    # optima, so the frozen plan visibly worsens consensus
    dim, width, xs, ys, loss_fn, opt = _training_setup(seed, hetero=0.5)
    force_at = max(8, steps // 3)
    health_trace = {}

    def batch_fn(step):
        if step == force_at:
            control.force_candidate(frozen, name="frozen")
        return (xs[step % 64], ys[step % 64])

    step_g = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=carrier, guard=F.GuardConfig())
    params, opt_state = _fresh(mesh, dim, width, opt)
    import tempfile

    from bluefog_tpu.checkpoint import Checkpointer

    def on_event(e):
        if e.kind.startswith("topology"):
            health_trace[e.kind] = dict(e.detail, step=e.step)

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        res = R.run_resilient(
            step_g, params, opt_state, batch_fn, steps=steps,
            checkpointer=ck, mesh=mesh, schedule=carrier,
            checkpoint_every=0, sleep=lambda s: None, control=control,
            on_event=on_event)
        ck.close()

    rb = _events(res, "topology_rollback")
    rb_detail = rb[0].detail if rb else {}
    pre = rb_detail.get("preswap_health")
    end = _consensus(res.params)
    return {
        "steps": steps,
        "force_at": force_at,
        "events": [(e.kind, e.step) for e in res.events
                   if e.kind.startswith("topology")],
        "rolled_back": bool(rb),
        "restored": rb_detail.get("restored"),
        "rollback_health": rb_detail.get("health"),
        "preswap_health": pre,
        "final_consensus": end,
        "floor_ratio_end_vs_preswap": (end / pre if pre else
                                       float("nan")),
        "active_schedule_at_end": control.active_name(),
        "recompiles": step_g.jitted._cache_size() - len(carrier),
        "rollbacks": control.rollbacks,
    }


def straggler_scenario(steps, seed):
    """Scenario 4: a persistent straggler's z-score degrades the
    windows; synthesis runs with synthetic load priced onto the slow
    rank's links.  The z-driven trigger and the zero-recompile cycle
    are the machine-checked claims; whether the re-plan pays (swap)
    or not (reject) is recorded either way."""
    import jax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.observe import MetricsRegistry
    from bluefog_tpu.observe.fleet import StragglerDetector
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import TopologyControlPlane

    pod = make_pod()
    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    carrier = rich_carrier()
    static = [dcn_round(+1), ici_round(),
              dcn_round(+1), dcn_round(-1)]
    reg = MetricsRegistry()
    sdet = StragglerDetector(N, z_threshold=4.0, patience=3)
    control = TopologyControlPlane(
        pod, carrier, registry=reg, straggler=sdet, z_threshold=4.0,
        window=8, patience=2, margin=0.05, cooldown=8, probation=6,
        rollback_tolerance=2.0, synchronous=True, initial=static)

    slow_rank, onset = 5, 8
    plan = R.FaultPlan.persistent_straggler(N, slow_rank, onset,
                                            stall_seconds=0.25)
    dim, width, xs, ys, loss_fn, opt = _training_setup(seed)
    det = R.FailureDetector(N)
    wire = VirtualWire(
        pod, reg,
        schedule_fn=lambda s: control.active_schedule()[s % ROUNDS],
        dead_fn=det.dead_mask)

    def batch_fn(step):
        wire.bill(step)
        return (xs[step % 64], ys[step % 64])

    def step_times_fn(step, wall):
        return wall + plan.stall_seconds_by_rank(step)

    step_g = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=carrier, guard=F.GuardConfig())
    params, opt_state = _fresh(mesh, dim, width, opt)
    import tempfile

    from bluefog_tpu.checkpoint import Checkpointer

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        res = R.run_resilient(
            step_g, params, opt_state, batch_fn, steps=steps,
            checkpointer=ck, mesh=mesh, schedule=carrier,
            fault_plan=plan, detector=det, checkpoint_every=0,
            sleep=lambda s: None, straggler=sdet,
            step_times_fn=step_times_fn, control=control)
        ck.close()

    trig = _events(res, "topology_trigger")
    flags = [e for e in res.events if e.kind == "straggler"]
    return {
        "steps": steps,
        "slow_rank": slow_rank,
        "onset": onset,
        "stall_seconds": 0.25,
        "flagged_ranks": sorted({r for e in flags
                                 for r in e.detail["ranks"]}),
        "z_scores_at_end": {str(k): float(v)
                            for k, v in sdet.z_scores().items()},
        "trigger_reasons": [e.detail.get("reason") for e in trig],
        "decision": ("swap" if _events(res, "topology_swap")
                     else "reject" if _events(res, "topology_reject")
                     else "none"),
        "active_schedule_at_end": control.active_name(),
        "events": [(e.kind, e.step) for e in res.events
                   if e.kind.startswith("topology")],
        "recompiles": step_g.jitted._cache_size() - len(carrier),
    }


DEFAULT_BASELINE = "benchmarks/chaos_adaptive_topology_r16.json"


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_BASELINE)
    ap.add_argument("--compare", metavar="PREV.json",
                    default=(DEFAULT_BASELINE
                             if os.path.exists(DEFAULT_BASELINE)
                             else None),
                    help="regression gate (default: the committed "
                         "chaos_adaptive_topology_r16.json when "
                         "present; pass '' to disable)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="gate tolerance (the virtual-wire p50s and "
                         "seeded scores are deterministic; slack "
                         "covers candidate-ranking ties)")
    args = ap.parse_args(argv)
    if args.compare == "":
        args.compare = None
    return args


def _finitize(obj):
    """Replace non-finite floats with ``None`` so the artifact stays
    strict JSON (``inf``/``nan`` are not valid JSON literals)."""
    if isinstance(obj, dict):
        return {k: _finitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finitize(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def main():
    args = parse_args()

    cong = congestion_scenario(args.steps, args.seed)
    shrink = shrink_scenario(args.steps, args.seed)
    rollback = rollback_scenario(args.steps, args.seed)
    strag = straggler_scenario(args.steps, args.seed)

    checks = {
        # the congested link is detected, debounced, and routed around
        "congested_triggered": "degraded" in cong["trigger_reasons"],
        "congested_swapped": cong["swap_step"] is not None,
        "congested_committed": cong["committed"],
        "congested_step_time_improves": cong["step_time_ratio"] < 0.9,
        "congested_c2c_improves": (
            cong["cost_to_consensus_advantage"] > 1.05),
        "congested_zero_recompiles": cong["recompiles"] == 0,
        # the shrink re-plan beats the merely-healed incumbent
        "shrink_triggered_by_membership": (
            "membership" in shrink["trigger_reasons"]),
        "shrink_swapped": shrink["swap_step"] is not None,
        "shrink_step_time_improves": shrink["step_time_ratio"] < 0.9,
        "shrink_c2c_improves": (
            shrink["cost_to_consensus_advantage"] > 1.05),
        "shrink_zero_recompiles": (
            shrink["recompiles_adapted"] == 0
            and shrink["recompiles_static"] == 0),
        # the forced bad candidate is rolled back, floor unmoved
        "rollback_happened": rollback["rolled_back"],
        "rollback_restored_incumbent": (
            rollback["restored"] == "initial"
            and rollback["active_schedule_at_end"] == "initial"),
        "rollback_floor_unmoved": (
            rollback["floor_ratio_end_vs_preswap"] < 1.5),
        "rollback_zero_recompiles": rollback["recompiles"] == 0,
        # the persistent straggler is named and drives the loop
        "straggler_named": (
            strag["flagged_ranks"] == [strag["slow_rank"]]),
        "straggler_triggered": (
            "degraded" in strag["trigger_reasons"]),
        "straggler_decided": strag["decision"] in ("swap", "reject"),
        "straggler_zero_recompiles": strag["recompiles"] == 0,
        # headline ratios must be real, finite measurements (a
        # disconnected incumbent would make cost-to-consensus infinite)
        "headlines_finite": all(
            isinstance(v, float) and math.isfinite(v)
            for v in (cong["step_time_ratio"],
                      cong["cost_to_consensus_advantage"],
                      shrink["step_time_ratio"],
                      shrink["cost_to_consensus_advantage"])),
    }
    for k, ok in checks.items():
        print(f"[check] {k}: {'OK' if ok else 'FAILED'}")

    out = {
        "congested": cong,
        "shrink": shrink,
        "rollback": rollback,
        "straggler": strag,
        # the headline section the bench gate reads
        "adaptation": {
            "step_time_ratio": cong["step_time_ratio"],
            "cost_to_consensus_advantage": (
                cong["cost_to_consensus_advantage"]),
        },
        "checks": {k: bool(v) for k, v in checks.items()},
    }
    print(json.dumps({"checks": out["checks"],
                      "adaptation": out["adaptation"]}))
    if not all(checks.values()):
        return 1
    if args.compare:
        from bluefog_tpu.benchutil import bench_regression_gate

        if not bench_regression_gate(out, args.compare,
                                     tolerance=args.tolerance):
            print(f"[bench-gate] regression: NOT writing {args.out}")
            return 1
    with open(args.out, "w") as fh:
        json.dump(_finitize(out), fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
