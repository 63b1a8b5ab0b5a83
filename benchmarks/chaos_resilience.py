"""Chaos benchmark: decentralized training under injected faults.

Round-8 evidence for the resilience subsystem (ISSUE 3): the same
guarded one-compiled-program train step survives a NaN burst, a rank
death, and the subsequent heal + rollback, and the surviving ranks keep
converging — measured, not asserted.  Round 10 (ISSUE 5) adds the
injected-STRAGGLER scenario: one rank runs slow, the fleet telemetry
layer's ``StragglerDetector`` must NAME it from the per-rank step-time
vector within a bounded number of steps (patience + 1), with no false
flags — the detection latency is a machine-checked claim in the JSON.

Three parts, one JSON artifact (wire_quant_consensus_r05.json style):

1. **Healed-mixing simulation** (pure numpy, no devices): kill ranks in
   the one-peer exponential-2 schedule at n=32, heal, and trace the
   survivors' consensus distance — the claim is the healed rounds stay
   row-stochastic and contract at a rate comparable to the unbroken
   schedule, while the UNHEALED schedule (a dead rank frozen but still
   weighted) stalls above it.
2. **End-to-end chaos run** (8 CPU 'ranks'): guarded atc training over
   the one-peer schedule with a scripted FaultPlan — a 2-step NaN burst
   on one rank, then a rank death — through ``run_resilient`` with
   checkpointing, vs the same data with no faults and no guard.
   Reported: final mean loss both sides, skip counts, rollbacks,
   recompiles (must be 0 across the whole chaotic run), wall time.
3. **Injected straggler** (8 CPU 'ranks'): the same guarded training
   with a ``FaultPlan.straggler`` stalling one rank per step; the
   per-rank step-time vector (measured wall + the plan's per-rank
   stall — what each process would gossip in a real fleet) feeds the
   ``StragglerDetector`` through ``run_resilient``.  Reported: the
   flag step, detection latency vs the bound, z-scores, false flags.
4. **Preempt -> rejoin cycle** (round 13 / ISSUE 10): elastic
   membership in both layers.  Simulation (n=32, pure numpy): preempt
   two ranks, converge the survivors on the healed schedule, admit
   both back through the annealed quarantined bootstrap
   (``MembershipController.mixing_matrices``), promote, and verify
   the re-GROWN tables are byte-equal to the pristine plan and the
   FULL 32-rank consensus floor recovers to <= 1e-12.  End to end
   (8 CPU 'ranks'): ``run_resilient(elastic=...)`` drives a
   ``FaultPlan.preempt`` through death, heal, rollback, admission,
   anneal, and promotion on the ONE compiled program — recompiles
   must be 0 and the fleet must end fully live, with the p50 step
   throughput after the promotion recovering to the pre-fault rate.

The JSON artifact doubles as the bench-gate baseline: ``--compare``
defaults to the committed ``chaos_resilience_r13.json`` (pass ``''``
to disable) and gates the rejoin headline metrics before overwriting
``--out`` — the rolling-baseline discipline of serving_bench.py.

Run (CPU, no TPU): JAX_PLATFORMS=cpu python benchmarks/chaos_resilience.py
"""

import argparse
import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

N = 8          # end-to-end world (the forced CPU device count)
SIM_N = 32     # simulation-only world (pure numpy)


def simulate(sim_rounds: int, dim: int, seed: int) -> dict:
    """Part 1: healed vs unhealed consensus traces at n=32."""
    from bluefog_tpu.resilience import (consensus_simulation, heal_spec,
                                        is_row_stochastic)
    from bluefog_tpu.topology import one_peer_dynamic_schedule

    sched = one_peer_dynamic_schedule(SIM_N)
    dead = np.zeros(SIM_N, bool)
    dead[[3, 17]] = True
    healed = [heal_spec(s, dead) for s in sched]
    out = {
        "n": SIM_N, "dead_ranks": [3, 17], "rounds": sim_rounds,
        "dim": dim,
        "healed_row_stochastic": all(is_row_stochastic(s)
                                     for s in healed),
    }
    traces = {
        "healthy": consensus_simulation(sched, sim_rounds, dim, seed),
        "healed": consensus_simulation(healed, sim_rounds, dim, seed,
                                       dead_mask=dead),
        # unhealed: the dead ranks' stale values keep their weight —
        # the failure mode healing exists to fix (live-rank consensus
        # still measured against the live mean)
        "unhealed": consensus_simulation(sched, sim_rounds, dim, seed,
                                         dead_mask=dead),
    }
    for name, tr in traces.items():
        out[name] = {
            "consensus_at": {str(t): float(tr[t])
                             for t in (0, sim_rounds // 4,
                                       sim_rounds // 2, sim_rounds - 1)},
            "floor_median_tail": float(np.median(tr[int(0.8 * len(tr)):])),
        }
    return out


def chaos_run(steps: int, seed: int) -> dict:
    """Part 2: guarded chaos training vs fault-free unguarded baseline."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.checkpoint import Checkpointer
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import one_peer_dynamic_schedule

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    dim, width = 16, 4
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dim, width)
    xs = rng.randn(64, N, 8, dim)
    ys = xs @ w_true + 0.01 * rng.randn(64, N, 8, width)

    def batch_fn(step):
        return (xs[step % 64], ys[step % 64])

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    opt = optax.sgd(0.05, momentum=0.9)

    def fresh_state():
        params = F.rank_major({"w": jnp.zeros((dim, width))}, mesh)
        opt_state = F.rank_major(opt.init({"w": jnp.zeros((dim, width))}),
                                 mesh)
        return params, opt_state

    # fault script: transient NaN burst early, rank death mid-run
    burst_at, death_at = max(2, steps // 8), max(4, steps // 3)
    plan = R.FaultPlan(N, [
        R.Fault(burst_at, 1, "nan", duration=2),
        R.Fault(death_at, 2, "dead"),
    ])

    step_g = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=sched, guard=F.GuardConfig())
    import tempfile

    params, opt_state = fresh_state()
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        res = R.run_resilient(
            step_g, params, opt_state, batch_fn, steps=steps,
            checkpointer=ck, mesh=mesh, schedule=sched,
            guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
            fault_plan=plan, checkpoint_every=max(2, steps // 6),
            sleep=lambda s: None)
        ck.close()
    chaos_s = time.monotonic() - t0
    live = ~res.dead_mask

    # fault-free unguarded baseline on the same data
    step_u = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=sched)
    params, opt_state = fresh_state()
    t0 = time.monotonic()
    loss = None
    for s in range(steps):
        params, opt_state, loss = step_u(params, opt_state, batch_fn(s),
                                         jnp.int32(s))
    base_s = time.monotonic() - t0
    base_loss = np.asarray(loss)

    chaos_live_loss = float(np.asarray(res.last_loss)[live].mean())
    base_live_loss = float(base_loss[live].mean())
    return {
        "steps": steps,
        "fault_plan": {"nan_burst": {"rank": 1, "step": burst_at,
                                     "duration": 2},
                       "rank_death": {"rank": 2, "step": death_at}},
        "n_rollbacks": res.n_rollbacks,
        "dead_ranks": [int(r) for r in np.nonzero(res.dead_mask)[0]],
        "skips_per_rank": [int(v) for v in res.total_skips],
        # (one program a round of the schedule is the contract)
        "recompiles": step_g.jitted._cache_size() - len(sched),
        "events": [(e.kind, e.step) for e in res.events
                   if e.kind != "skip"],
        "final_loss_live_mean_chaos": chaos_live_loss,
        "final_loss_live_mean_faultfree": base_live_loss,
        "params_all_finite": bool(R.update_health(res.params).all()),
        "wall_s_chaos": chaos_s,
        "wall_s_faultfree": base_s,
    }


def straggler_scenario(steps: int, seed: int) -> dict:
    """Part 3: one slow rank must be NAMED by the gossip-fed detector.

    The straggler's extra per-step latency rides the fault plan's STALL
    schedule; ``step_times_fn`` synthesizes the per-rank vector each
    process would gossip (measured wall + its injected stall) while the
    injected ``sleep`` is a no-op so the bench itself stays fast."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.checkpoint import Checkpointer
    from bluefog_tpu.observe.fleet import StragglerDetector
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import one_peer_dynamic_schedule

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    dim, width = 16, 4
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dim, width)
    xs = rng.randn(64, N, 8, dim)
    ys = xs @ w_true + 0.01 * rng.randn(64, N, 8, width)

    def batch_fn(step):
        return (xs[step % 64], ys[step % 64])

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    opt = optax.sgd(0.05, momentum=0.9)
    step_g = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=sched, guard=F.GuardConfig())
    params = F.rank_major({"w": jnp.zeros((dim, width))}, mesh)
    opt_state = F.rank_major(opt.init({"w": jnp.zeros((dim, width))}),
                             mesh)

    slow_rank, onset = 3, max(4, steps // 4)
    stall_s = 0.25  # far above CPU step noise -> a clean z outlier
    plan = R.FaultPlan.straggler(N, slow_rank, onset,
                                 duration=steps - onset,
                                 stall_seconds=stall_s)
    patience = 3
    det = StragglerDetector(N, z_threshold=4.0, patience=patience)
    fdet = R.FailureDetector(N)
    events = []

    def step_times_fn(step, wall):
        return wall + plan.stall_seconds_by_rank(step)

    import tempfile

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        res = R.run_resilient(
            step_g, params, opt_state, batch_fn, steps=steps,
            checkpointer=ck, mesh=mesh, schedule=sched,
            fault_plan=plan, detector=fdet, checkpoint_every=0,
            sleep=lambda s: None, straggler=det,
            step_times_fn=step_times_fn,
            on_event=events.append)
        ck.close()
    wall_s = time.monotonic() - t0

    flags = [e for e in events if e.kind == "straggler"]
    flag_step = flags[0].step if flags else None
    flagged_ranks = sorted({r for e in flags for r in e.detail["ranks"]})
    latency = (flag_step - onset + 1) if flag_step is not None else None
    bound = patience + 1
    return {
        "steps": steps,
        "slow_rank": slow_rank,
        "onset_step": onset,
        "stall_seconds": stall_s,
        "patience": patience,
        "flag_step": flag_step,
        "flagged_ranks": flagged_ranks,
        "detection_latency_steps": latency,
        "detection_bound_steps": bound,
        "failure_detector_suspects": fdet.external_suspects(),
        "skips_per_rank": [int(v) for v in res.total_skips],
        "n_rollbacks": res.n_rollbacks,
        "wall_s": wall_s,
    }


def rejoin_sim(sim_rounds: int, dim: int, seed: int) -> dict:
    """Part 4a: the preempt -> rejoin cycle in the n=32 mixing
    simulation — healed floor, quarantined bootstrap, byte-equal
    growth, recovered FULL-fleet floor."""
    from bluefog_tpu.elastic import MembershipController, disagreement
    from bluefog_tpu.resilience import heal_weights
    from bluefog_tpu.topology import one_peer_dynamic_schedule

    preempted = [3, 17]
    sched = one_peer_dynamic_schedule(SIM_N)
    mc = MembershipController(sched, bootstrap_rounds=8)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((SIM_N, dim))
    d0 = float(np.linalg.norm(x - x.mean(axis=0)))
    t = 0

    def mix(rounds, tick=False):
        nonlocal x, t
        for _ in range(rounds):
            M = mc.mixing_matrices()[t % len(sched)]
            x = M @ x
            t += 1
            if tick:
                mc.tick()

    def floor(mask):
        sub = x[mask]
        return float(np.linalg.norm(sub - sub.mean(axis=0))) / d0

    live = np.ones(SIM_N, bool)
    mix(sim_rounds)
    healthy_floor = floor(live)
    # preempt: the two ranks die with drifted state; survivors heal
    mc.mark_dead(preempted)
    x[preempted] += rng.standard_normal((len(preempted), dim))
    live[preempted] = False
    mix(sim_rounds)
    healed_floor = floor(live)
    # rejoin: annealed quarantine pull, then the promotion gate
    mc.admit(preempted)
    mix(sim_rounds, tick=True)
    dis = {str(r): float(disagreement({"x": x}, r, mc.live_mask()))
           for r in preempted}
    mc.promote(preempted)
    grow_byte_equal = all(
        cw.tobytes() == pcw.tobytes() and sw.tobytes() == psw.tobytes()
        for (cw, sw), (pcw, psw) in zip(
            mc.comm_weight_arrays(),
            (heal_weights(s, np.zeros(SIM_N, bool)) for s in sched)))
    live[preempted] = True
    mix(sim_rounds)
    return {
        "n": SIM_N, "preempted_ranks": preempted,
        "rounds_per_phase": sim_rounds,
        "healthy_floor": healthy_floor,
        "healed_floor": healed_floor,
        "promote_disagreement": dis,
        "grow_byte_equal": bool(grow_byte_equal),
        "post_rejoin_floor": floor(live),
    }


def rejoin_cycle(steps: int, sim_rounds: int, dim: int, seed: int) -> dict:
    """Part 4: preempt -> heal -> bootstrap -> rejoin, both layers."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from bluefog_tpu import resilience as R
    from bluefog_tpu.checkpoint import Checkpointer
    from bluefog_tpu.elastic import ElasticConfig
    from bluefog_tpu.optim import functional as F
    from bluefog_tpu.topology import one_peer_dynamic_schedule

    sim = rejoin_sim(sim_rounds, dim, seed)

    mesh = Mesh(np.array(jax.devices()[:N]), ("bf",))
    sched = one_peer_dynamic_schedule(N)
    pdim, width = 16, 4
    rng = np.random.RandomState(seed)
    w_true = rng.randn(pdim, width)
    xs = rng.randn(64, N, 8, pdim)
    ys = xs @ w_true + 0.01 * rng.randn(64, N, 8, width)

    # batch_fn timestamps are the per-step clock: successive calls
    # bracket exactly one executed step (replays included), so the
    # pre-fault vs post-promotion p50 comes out of the run itself
    calls = []

    def batch_fn(step):
        calls.append((step, time.monotonic()))
        return (xs[step % 64], ys[step % 64])

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    opt = optax.sgd(0.05, momentum=0.9)
    step_g = F.build_train_step(loss_fn, opt, mesh, comm_mode="atc",
                                schedule=sched, guard=F.GuardConfig())
    params = F.rank_major({"w": jnp.zeros((pdim, width))}, mesh)
    opt_state = F.rank_major(opt.init({"w": jnp.zeros((pdim, width))}),
                             mesh)

    preempt_at = max(4, steps // 5)
    duration = max(4, steps // 5)
    plan = R.FaultPlan.preempt(N, rank=2, step=preempt_at,
                               duration=duration)
    import tempfile

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        res = R.run_resilient(
            step_g, params, opt_state, batch_fn, steps=steps,
            checkpointer=ck, mesh=mesh, schedule=sched,
            guard=F.GuardConfig(max_consecutive_bad=3, backoff_base=0.0),
            fault_plan=plan, checkpoint_every=max(2, steps // 6),
            sleep=lambda s: None,
            elastic=ElasticConfig(bootstrap_rounds=6,
                                  max_quarantine_steps=24))
        ck.close()
    wall_s = time.monotonic() - t0

    promos = [e for e in res.events if e.kind == "rank_promoted"]
    promote_step = promos[0].step if promos else None
    # p50 step seconds before the fault vs after the promotion (step 0
    # carries the compile and is excluded)
    durs = [(calls[i][0], calls[i + 1][1] - calls[i][1])
            for i in range(len(calls) - 1)]
    pre = [d for s, d in durs if 1 <= s < preempt_at]
    post = ([d for s, d in durs if s > promote_step]
            if promote_step is not None else [])
    p50_pre = float(np.median(pre)) if pre else float("nan")
    p50_post = float(np.median(post)) if post else float("nan")
    recovery = (p50_pre / p50_post
                if post and p50_post > 0 else 0.0)
    return {
        "steps": steps,
        "preempt": {"rank": 2, "step": preempt_at,
                    "duration": duration},
        "events": [(e.kind, e.step) for e in res.events
                   if e.kind != "skip"],
        "n_rollbacks": res.n_rollbacks,
        # (one program a round of the schedule is the contract)
        "recompiles": step_g.jitted._cache_size() - len(sched),
        "promote_step": promote_step,
        "promote_disagreement": (
            float(promos[0].detail["disagreement"]) if promos else None),
        "final_membership_all_live": (
            res.membership == ["live"] * N and not res.dead_mask.any()),
        "p50_step_s_prefault": p50_pre,
        "p50_step_s_postpromote": p50_post,
        "throughput_recovery": recovery,
        "wall_s": wall_s,
        "sim": sim,
        # hoisted for the bench-gate headline grab (section scan is
        # one level deep)
        "post_rejoin_floor": sim["post_rejoin_floor"],
    }


DEFAULT_BASELINE = "benchmarks/chaos_resilience_r13.json"


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--dim", type=int, default=256,
                    help="payload width of the mixing simulation")
    ap.add_argument("--sim-rounds", type=int, default=400)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_BASELINE)
    ap.add_argument("--compare", metavar="PREV.json",
                    default=(DEFAULT_BASELINE
                             if os.path.exists(DEFAULT_BASELINE)
                             else None),
                    help="regression gate (default: the committed "
                         "chaos_resilience_r13.json when present; "
                         "pass '' to disable)")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="gate tolerance (loose: the throughput-"
                         "recovery ratio rides this host's wall "
                         "clock; the consensus floors are seeded "
                         "and deterministic)")
    args = ap.parse_args(argv)
    if args.compare == "":
        args.compare = None
    return args


def main():
    args = parse_args()

    sim = simulate(args.sim_rounds, args.dim, args.seed)
    chaos = chaos_run(args.steps, args.seed)
    strag = straggler_scenario(args.steps, args.seed)
    rejoin = rejoin_cycle(args.steps, min(args.sim_rounds, 120),
                          args.dim, args.seed)

    checks = {
        # healing keeps the surviving ranks contracting...
        "healed_row_stochastic": bool(sim["healed_row_stochastic"]),
        "healed_converges": sim["healed"]["floor_median_tail"] < 1e-6,
        # ...where the unhealed schedule visibly stalls above it
        "unhealed_stalls_above_healed": (
            sim["unhealed"]["floor_median_tail"]
            > 10 * max(sim["healed"]["floor_median_tail"], 1e-12)),
        # the chaos run survived: recovered, healed, finished finite
        "chaos_rolled_back": chaos["n_rollbacks"] >= 1,
        "chaos_declared_death": chaos["dead_ranks"] == [2],
        "chaos_zero_recompiles": chaos["recompiles"] == 0,
        "chaos_params_finite": chaos["params_all_finite"],
        # and the survivors' loss is in the same regime as fault-free
        "chaos_loss_comparable": (
            chaos["final_loss_live_mean_chaos"]
            < 10 * max(chaos["final_loss_live_mean_faultfree"], 1e-9)),
        # the injected straggler is NAMED within the bounded latency,
        # with no false flags and the suspicion wired to the detector
        "straggler_flagged": strag["flagged_ranks"] == [strag["slow_rank"]],
        "straggler_latency_bounded": (
            strag["detection_latency_steps"] is not None
            and strag["detection_latency_steps"]
            <= strag["detection_bound_steps"]),
        "straggler_feeds_suspects": (
            strag["failure_detector_suspects"] == [strag["slow_rank"]]),
        # the preempted rank came BACK: grown tables byte-equal to the
        # pristine plan, full-fleet consensus floor recovered, the
        # whole cycle on one compiled program, and the post-promotion
        # step rate back in the pre-fault regime
        "rejoin_grow_byte_equal": rejoin["sim"]["grow_byte_equal"],
        "rejoin_consensus_floor": (
            rejoin["sim"]["post_rejoin_floor"] <= 1e-12),
        "rejoin_zero_recompiles": rejoin["recompiles"] == 0,
        "rejoin_all_live": rejoin["final_membership_all_live"],
        "rejoin_promoted_inside_cloud": (
            rejoin["promote_disagreement"] is not None
            and rejoin["promote_disagreement"] <= 1.0),
        "rejoin_throughput_recovers": (
            rejoin["throughput_recovery"] >= 0.5),
    }
    for k, ok in checks.items():
        print(f"[check] {k}: {'OK' if ok else 'FAILED'}")

    out = {
        "simulation": sim,
        "chaos": chaos,
        "straggler": strag,
        "rejoin": rejoin,
        "checks": {k: bool(v) for k, v in checks.items()},
    }
    print(json.dumps({"checks": out["checks"]}))
    if not all(checks.values()):
        return 1
    # gate BEFORE writing --out (rolling-baseline discipline, same as
    # serving_bench.py / fleet_serving.py)
    if args.compare:
        from bluefog_tpu.benchutil import bench_regression_gate

        if not bench_regression_gate(out, args.compare,
                                     tolerance=args.tolerance):
            print(f"[bench-gate] regression: NOT writing {args.out}")
            return 1
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
