"""Jaxpr/HLO contract checker: the semantic half of the analyzer.

This pass builds the REAL programs — ``build_train_step`` across the
same comm_mode x guard x health x hierarchical x overlap matrix the
epilogue parity tests pin, the topology compiler's scheduled programs,
and the serving engine's resident executables — then walks their traced
jaxprs (and, for scheduled exchanges, their compiled HLO) to verify the
three framework contracts mechanically:

**weights-as-data** (:func:`check_step`)
    The comm-weight tables (``F.comm_weight_inputs`` pytree — class
    weights + self weights per round; the same shapes healing /
    elastic membership substitute at runtime) must enter the program as
    live traced invars with the declared avals.  Violations:

    * ``missing-weight-operand`` — the program doesn't end with the
      declared weight leaves (or their avals disagree);
    * ``dead-weight-operand`` — a weight invar exists but nothing
      reachable from the outputs consumes it (the combine ignored the
      traced table, i.e. it used something else — typically a baked
      constant);
    * ``baked-weight-const`` — a closed-over constant with a weight
      table's exact shape/dtype profile appears anywhere in the jaxpr
      (including sub-jaxprs).  This is the recompile bug: healing would
      swap the operand while XLA keeps folding the constant.

**no cond over per-rank-divergent predicates** (PR-3 guard rule)
    A forward replicated/per-rank taint walk: params / opt_state /
    batch shards and ``axis_index`` results are per-rank; the step
    counter, weight operands, and constants are replicated; ``psum``
    (and friends) launder per-rank values back to replicated;
    ``ppermute`` does not.  Any ``lax.cond``/``switch`` whose predicate
    carries per-rank taint is flagged ``divergent-cond``: under SPMD
    the branches would disagree across ranks inside one collective
    program — the silent-deadlock/garbage class of bug the guard
    refactor banned.

**collective contract** (:func:`check_collective_contracts`)
    The scheduled exchange programs (the ``compile_topology``
    schedule's rounds, one program a round as ``build_train_step``
    runs them; hierarchical per-machine-round) are
    lowered and held to ``predicted_collectives`` through the supported
    :func:`bluefog_tpu.benchutil.verify_collective_contract` — permute
    count after in-degree-1 fusion, per-permute payload bytes,
    grouped-all-reduce count and replica groups.

:func:`run_sweep` runs everything; the CLI and the tier-1 test both
call it.  Mutation tests in tests/test_analysis.py prove the teeth: a
step with baked weight constants, a program that drops its weight
operand, a divergent cond, and a tampered prediction must each be
flagged.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from bluefog_tpu.analysis import Finding

__all__ = ["check_step", "check_traced", "check_collective_contracts",
           "check_serving_residents", "sweep_cases", "run_sweep",
           "N_RANKS"]

N_RANKS = 8          # the sweep's mesh width (tier-1 CPU device count)
_LARGE_CONST = 4096  # float elements: a closed-over tensor this big in
                     # a resident program is model state baked at trace
                     # time, not a legitimate epsilon/table

# collectives whose OUTPUT is identical on every rank of the axis —
# they launder per-rank taint back to replicated
_REPLICATING_PRIMS = {"psum", "psum2", "pmax", "pmin", "all_gather",
                      "all_gather_invariant", "reduce_scatter"}
# primitives that INTRODUCE per-rank divergence
_DIVERGING_PRIMS = {"axis_index"}


# --------------------------------------------------------------------- #
# jaxpr plumbing
# --------------------------------------------------------------------- #

def _as_open(j):
    """(core.Jaxpr, consts) from a jax.stages.Traced, a ClosedJaxpr,
    or a raw Jaxpr."""
    if hasattr(j, "jaxpr") and not hasattr(j, "consts"):
        j = j.jaxpr                  # Traced -> ClosedJaxpr
    if hasattr(j, "consts"):         # ClosedJaxpr
        return j.jaxpr, list(j.consts)
    return j, []


def _sub_jaxprs(eqn) -> List[Any]:
    """Every jaxpr-valued entry in an equation's params (pjit 'jaxpr',
    shard_map 'jaxpr', cond 'branches', scan 'jaxpr', while
    'cond_jaxpr'/'body_jaxpr', custom_* 'call_jaxpr'/'fun_jaxpr'...),
    discovered structurally so new primitives are covered for free."""
    subs: List[Any] = []
    for v in eqn.params.values():
        for cand in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(cand, "eqns") or (hasattr(cand, "jaxpr")
                                         and hasattr(cand.jaxpr, "eqns")):
                subs.append(cand)
    return subs


def _walk_consts(closed) -> List[Any]:
    """All closed-over constants of a program, recursively (a baked
    weight table can hide inside a pjit/cond/scan sub-jaxpr)."""
    out: List[Any] = []
    seen: set = set()
    stack = [closed]
    while stack:
        jaxpr, consts = _as_open(stack.pop())
        if id(jaxpr) in seen:
            continue
        seen.add(id(jaxpr))
        out.extend(consts)
        for eqn in jaxpr.eqns:
            stack.extend(_sub_jaxprs(eqn))
    return out


def _direct_sub(eqn):
    """The single sub-jaxpr whose invars align 1:1 with the equation's
    operands (pjit / closed_call / shard_map and lookalikes), else
    None."""
    subs = _sub_jaxprs(eqn)
    if len(subs) != 1:
        return None
    jaxpr, _ = _as_open(subs[0])
    if len(jaxpr.invars) == len(eqn.invars):
        return jaxpr
    return None


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")  # Var, not Literal


def _live_invars(jaxpr) -> set:
    """Invars reachable (backwards) from the outputs.  Refined through
    1:1 call-like equations (pjit / shard_map): an operand is live only
    if the callee actually uses it — that's precisely how a dropped
    weight table hides behind a jit boundary."""
    live = {v for v in jaxpr.outvars if _is_var(v)}
    for eqn in reversed(jaxpr.eqns):
        if not any(ov in live for ov in eqn.outvars):
            continue
        sub = _direct_sub(eqn)
        if sub is not None:
            sub_live = _live_invars(sub)
            for v, sv in zip(eqn.invars, sub.invars):
                if _is_var(v) and sv in sub_live:
                    live.add(v)
        else:
            live.update(v for v in eqn.invars if _is_var(v))
    return {v for v in jaxpr.invars if v in live}


def _taint_walk(jaxpr, invar_taint: Dict[Any, bool], consts: Sequence,
                findings: List[Finding], name: str) -> List[bool]:
    """Forward replicated/per-rank walk; returns outvar taints.  True =
    per-rank (divergent), False = replicated."""
    taint: Dict[Any, bool] = dict(invar_taint)
    for cv in getattr(jaxpr, "constvars", ()):
        taint[cv] = False

    def t(v) -> bool:
        return taint.get(v, False) if _is_var(v) else False

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        in_taints = [t(v) for v in eqn.invars]
        if prim in ("cond", "switch"):
            if in_taints[0]:
                findings.append(Finding(
                    "divergent-cond", name, 0, "cond-predicate",
                    f"lax.{prim} predicate is per-rank-divergent: "
                    "branches would disagree across ranks inside one "
                    "SPMD program (PR-3 guard rule — reduce the "
                    "predicate with psum/consensus first)"))
            out_t = [False] * len(eqn.outvars)
            for br in eqn.params["branches"]:
                sub, _ = _as_open(br)
                sub_taint = {sv: ti for sv, ti in
                             zip(sub.invars, in_taints[1:])}
                br_out = _taint_walk(sub, sub_taint, [], findings, name)
                out_t = [a or b for a, b in zip(out_t, br_out)]
        elif prim in _DIVERGING_PRIMS:
            out_t = [True] * len(eqn.outvars)
        elif prim in _REPLICATING_PRIMS:
            out_t = [False] * len(eqn.outvars)
        else:
            sub = _direct_sub(eqn)
            if sub is not None:
                sub_taint = {sv: ti for sv, ti in
                             zip(sub.invars, in_taints)}
                out_t = _taint_walk(sub, sub_taint, [], findings, name)
            else:
                # conservative default: any per-rank operand taints
                # every output (covers scan/while/ppermute/elementwise)
                any_t = any(in_taints)
                for s in _sub_jaxprs(eqn):
                    subj, _ = _as_open(s)
                    # still recurse for nested conds, seeding
                    # conservatively from the operand taints
                    sub_taint = {sv: any_t for sv in subj.invars}
                    _taint_walk(subj, sub_taint, [], findings, name)
                out_t = [any_t] * len(eqn.outvars)
        for ov, ot in zip(eqn.outvars, out_t):
            taint[ov] = ot
    return [t(v) for v in jaxpr.outvars]


# --------------------------------------------------------------------- #
# the checks
# --------------------------------------------------------------------- #

def _weight_shape_profile(leaves) -> set:
    """(shape, dtype-kind) profiles of the declared weight tables."""
    import numpy as np

    return {(tuple(np.shape(leaf)), "f") for leaf in leaves}


def check_traced(closed, *, name: str,
                 weight_leaves: Sequence = (),
                 taint_seed: Optional[List[bool]] = None,
                 large_const_floor: Optional[int] = None,
                 live_weights: Optional[Sequence[int]] = None,
                 ) -> List[Finding]:
    """Contract-check one traced program (a ClosedJaxpr).

    ``weight_leaves``: the declared comm-weight arrays; when non-empty
    the trailing ``len(weight_leaves)`` invars must carry their avals
    and be live, and no closed-over constant may match their shape
    profile.  ``live_weights``: the indices among them that THIS
    program must consume (one round's program of a scheduled step
    consumes that round's tables and no other's); all of them where it
    is None.  ``taint_seed``: per-invar per-rank flags enabling the
    divergent-cond walk.  ``large_const_floor``: additionally flag any
    float constant with at least that many elements (serving residents:
    model state must arrive as arguments, not baked weights).
    """
    import numpy as np

    findings: List[Finding] = []
    jaxpr, consts = _as_open(closed)
    n_w = len(weight_leaves)

    if n_w:
        invars = jaxpr.invars
        if len(invars) < n_w:
            findings.append(Finding(
                "missing-weight-operand", name, 0, "comm_weights",
                f"program has {len(invars)} operands, fewer than the "
                f"{n_w} declared weight leaves"))
        else:
            for i, leaf in enumerate(weight_leaves):
                v = invars[len(invars) - n_w + i]
                want = tuple(np.shape(leaf))
                got = tuple(getattr(v.aval, "shape", ()))
                if got != want:
                    findings.append(Finding(
                        "missing-weight-operand", name, 0,
                        "comm_weights",
                        f"weight operand {i}: aval shape {got} != "
                        f"declared {want} (weights not traced as "
                        "comm_weight_inputs data)"))
                    break
            else:
                live = _live_invars(jaxpr)
                dead = [i for i in (range(n_w) if live_weights is None
                                    else live_weights)
                        if invars[len(invars) - n_w + i] not in live]
                if dead:
                    findings.append(Finding(
                        "dead-weight-operand", name, 0, "comm_weights",
                        f"weight leaves {dead} are traced operands but "
                        "unreachable from the outputs — the combine is "
                        "not consuming the traced tables"))
        profiles = _weight_shape_profile(weight_leaves)
        for c in _walk_consts(closed):
            arr = np.asarray(c)
            if arr.dtype.kind == "f" \
                    and (tuple(arr.shape), "f") in profiles \
                    and arr.size > 1 \
                    and np.all(np.isfinite(arr)) \
                    and float(arr.min()) >= 0.0 \
                    and float(arr.max()) <= 1.0:
                findings.append(Finding(
                    "baked-weight-const", name, 0, "consts",
                    f"closed-over float constant of weight-table shape "
                    f"{arr.shape} — a baked table recompiles on every "
                    "heal/membership change instead of swapping an "
                    "operand"))

    if large_const_floor:
        for c in _walk_consts(closed):
            arr = np.asarray(c)
            if arr.dtype.kind == "f" and arr.size >= large_const_floor:
                findings.append(Finding(
                    "baked-weight-const", name, 0, "consts",
                    f"closed-over float constant of {arr.size} elements "
                    f"(shape {arr.shape}) — model/table state must be a "
                    "traced argument"))

    if taint_seed is not None:
        if len(taint_seed) == len(jaxpr.invars):
            seed = {v: ti for v, ti in zip(jaxpr.invars, taint_seed)}
            _taint_walk(jaxpr, seed, consts, findings, name)
        else:
            findings.append(Finding(
                "divergent-cond", name, 0, "cond-predicate",
                f"taint seed length {len(taint_seed)} does not match "
                f"{len(jaxpr.invars)} invars — cannot run the "
                "divergence walk"))
    return findings


def check_step(step, args: Tuple, *, name: str,
               round_index: Optional[int] = None,
               off_cycle: bool = False) -> List[Finding]:
    """Contract-check one built train step against its public call
    ``step(*args)``: the program that call would run.  A scheduled step
    is one program a round, picked on the host from the ``step`` among
    ``args``: give its ``round_index`` and the program is held to
    consuming that round's weight tables (the others are operands it
    has no use for).  ``off_cycle``: the program of a step between two
    exchanges (``num_steps_per_communication``), which consumes none.

    The step's ``.trace`` (shared with ``.lower`` — same program) maps
    the public signature onto the jitted program, whose flattened
    operand list ends with the ``default_comm_weights`` leaves in both
    the guarded (explicit argument) and unguarded (default operand)
    builds.  The taint walk seeds params/opt_state/batch as per-rank
    and the step counter + weight tables as replicated.
    """
    import jax

    closed = step.trace(*args)
    weight_leaves = jax.tree.leaves(
        getattr(step, "default_comm_weights", ()))
    jaxpr, _ = _as_open(closed)
    n = len(jaxpr.invars)
    n_w = len(weight_leaves)
    # per-rank everywhere except the trailing [step_counter, *weights]
    seed = [True] * n
    for i in range(max(0, n - n_w - 1), n):
        seed[i] = False
    live_w = () if off_cycle else None
    if round_index is not None and n_w:
        # leaves of ``default_comm_weights[round_index]``
        sizes = [len(jax.tree.leaves(w))
                 for w in step.default_comm_weights]
        lo = sum(sizes[:round_index])
        live_w = range(lo, lo + sizes[round_index])
    return check_traced(closed, name=name, weight_leaves=weight_leaves,
                        taint_seed=seed, live_weights=live_w)


# --------------------------------------------------------------------- #
# the sweep: every program the repo ships
# --------------------------------------------------------------------- #

def _mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < N_RANKS:
        raise RuntimeError(
            f"analysis sweep needs {N_RANKS} devices (run under "
            "config.configure_host_platform(); bfcheck does this "
            "automatically)")
    return Mesh(np.array(devs[:N_RANKS]), ("bf",))


def _problem():
    import jax.numpy as jnp
    import numpy as np

    base = {"w1": jnp.asarray(np.random.RandomState(7).randn(4, 4) * .3),
            "b1": jnp.zeros((4,)),
            "w2": jnp.asarray(np.random.RandomState(8).randn(4, 2) * .3),
            "b2": jnp.zeros((2,))}

    def loss_fn(params, batch):
        h = jnp.tanh(batch @ params["w1"] + params["b1"])
        return jnp.mean((h @ params["w2"] + params["b2"]) ** 2)

    return base, loss_fn


def _weighted_ring():
    import numpy as np
    from bluefog_tpu.topology.spec import Topology

    W = np.zeros((N_RANKS, N_RANKS))
    for r in range(N_RANKS):
        W[(r - 1) % N_RANKS, r] = 0.3
        W[(r + 1) % N_RANKS, r] = 0.1
        W[r, r] = 0.6
    return Topology.from_weight_matrix(W)


def _machine_ring():
    import numpy as np
    from bluefog_tpu.topology.spec import Topology

    m = N_RANKS // 2
    W = np.zeros((m, m))
    for r in range(m):
        W[(r - 1) % m, r] = 0.3
        W[(r + 1) % m, r] = 0.1
        W[r, r] = 0.6
    return Topology.from_weight_matrix(W)


def _weighted_schedule():
    from bluefog_tpu.topology.dynamic import one_peer_dynamic_schedule
    from bluefog_tpu.topology.spec import DynamicTopology

    out = []
    for s in one_peer_dynamic_schedule(N_RANKS):
        out.append(DynamicTopology.from_edges(
            s.size, {e: 0.3 for e in s.edges}, [0.7] * s.size))
    return out


def sweep_cases() -> List[dict]:
    """The build_train_step configurations the sweep traces, and the
    ONE list of them: tests/test_epilogue.py draws its parity matrix
    from here (every case a plain reference step can follow: all but
    ``topk`` and ``moe``), so the analyzer covers the program space the
    parity tests pin.  Guard x health x compress x comm_mode x overlap
    on the weighted static ring, the quantized wires, push_sum (the
    in-graph gossip mix), dynamic schedules (``P`` programs, one a
    round, then none: every round's program is checked), hierarchical
    two-level, the two modes with no neighbor exchange, and
    ``num_steps_per_communication=2`` (the off-cycle program too)."""
    ring = _weighted_ring()
    cases: List[dict] = []
    for comm_mode in ("cta", "atc"):
        for overlap in ("none", "bucketed"):
            for guard in (False, True):
                for health in (False, True):
                    cases.append(dict(
                        comm_mode=comm_mode, overlap=overlap,
                        guard=guard, health=health, compress=None,
                        topology=ring))
        for guard in (False, True):
            cases.append(dict(comm_mode=comm_mode, overlap="bucketed",
                              guard=guard, health=True, compress="int8",
                              topology=ring))
    cases.append(dict(comm_mode="atc", overlap="none", guard=True,
                      health=True, compress="int8", topology=ring))
    # the other two wire formats: stochastic rounding, bfloat16
    cases.append(dict(comm_mode="atc", overlap="none", guard=False,
                      health=True, compress="int8_sr", topology=ring))
    cases.append(dict(comm_mode="cta", overlap="none", guard=False,
                      health=True, compress="bf16", topology=ring))
    # error-feedback compressed mixing: the "topk" epilogue threads
    # MixState through the round's branch — lint it like any other
    cases.append(dict(comm_mode="cta", overlap="none", guard=False,
                      health=False, compress="topk", topology=ring))
    cases.append(dict(comm_mode="atc", overlap="bucketed", guard=True,
                      health=True, compress="topk", topology=ring))
    for overlap in ("none", "bucketed"):
        for health in (False, True):
            cases.append(dict(comm_mode="push_sum", overlap=overlap,
                              guard=False, health=health, compress=None,
                              topology=ring))
    cases.append(dict(comm_mode="atc", overlap="none", guard=False,
                      health=False, compress=None, schedule="one_peer"))
    cases.append(dict(comm_mode="atc", overlap="bucketed", guard=True,
                      health=True, compress=None, schedule="one_peer"))
    # expert-parallel MoE: route tables / capacity masks are traced
    # communication-authority DATA (dispatch.py is _WEIGHT_AUTHORITY),
    # and the expert subtree must stay out of the consensus epilogue
    cases.append(dict(comm_mode="cta", overlap="none", guard=False,
                      health=False, compress=None, topology=ring,
                      moe=True))
    cases.append(dict(comm_mode="atc", overlap="none", guard=True,
                      health=True, compress=None, topology=ring,
                      moe=True))
    mring = _machine_ring()
    for comm_mode, overlap, guard, health, compress in (
            ("cta", "none", False, False, None),
            ("cta", "bucketed", True, True, None),
            ("atc", "none", True, False, None),
            ("atc", "bucketed", False, True, None),
            ("cta", "bucketed", True, True, "int8"),
            ("atc", "none", True, True, "int8"),
            ("cta", "none", False, False, "topk")):
        cases.append(dict(comm_mode=comm_mode, overlap=overlap,
                          guard=guard, health=health, compress=compress,
                          topology=mring, hierarchical=2))
    # no neighbor exchange: the all-reduce baseline and local SGD (what
    # the one-chip cells train under), plain and with guard and health
    for comm_mode in ("gradient_allreduce", "none"):
        for on in (False, True):
            cases.append(dict(comm_mode=comm_mode, overlap="none",
                              guard=on, health=on, compress=None))
    # an exchange every second step: an on-cycle and an off-cycle program
    for comm_mode in ("cta", "atc"):
        cases.append(dict(comm_mode=comm_mode, overlap="none", guard=False,
                          health=False, compress=None, topology=ring,
                          num_steps_per_communication=2))
    return cases


def case_id(c: dict) -> str:
    return "-".join([
        c["comm_mode"], c["overlap"],
        "guard" if c["guard"] else "noguard",
        "health" if c["health"] else "nohealth",
        c["compress"] or "fp",
        "hier" if "hierarchical" in c
        else "sched" if "schedule" in c
        else "static" if "topology" in c else "nograph"]
        + (["moe"] if c.get("moe") else [])
        + ([f"k{c['num_steps_per_communication']}"]
           if "num_steps_per_communication" in c else []))


def build_kwargs(case: dict) -> dict:
    """``build_train_step``'s keywords for one sweep case (``moe`` is
    the caller's to resolve: it brings its own loss)."""
    from bluefog_tpu.optim import functional as F

    kwargs = dict(case)
    if kwargs.pop("overlap") != "none":
        kwargs.update(overlap="bucketed", overlap_buckets=3)
    if kwargs.get("compress") is None:
        kwargs.pop("compress")
    if kwargs.get("schedule") == "one_peer":
        kwargs["schedule"] = _weighted_schedule()
    kwargs["guard"] = F.GuardConfig() if kwargs["guard"] else None
    kwargs["health"] = F.HealthConfig() if kwargs["health"] else None
    return kwargs


def _build_and_check(case: dict, mesh) -> List[Finding]:
    import jax.numpy as jnp
    import numpy as np
    import optax
    from bluefog_tpu.optim import functional as F

    opt = optax.sgd(0.05, momentum=0.9)
    base, loss_fn = _problem()
    c = dict(case)
    guarded = c["guard"]
    push_sum = c["comm_mode"] == "push_sum"
    moe = c.pop("moe", False)
    if moe:
        import jax
        from bluefog_tpu.moe import (dispatch_plan, init_moe_params,
                                     make_moe_loss)
        from bluefog_tpu.topology.compiler import PodSpec, compile_all_to_all

        plan = dispatch_plan(
            compile_all_to_all(PodSpec(4, N_RANKS // 4)).schedule)
        base = init_moe_params(jax.random.PRNGKey(0), 4, 4, 4)
        loss_fn = make_moe_loss(plan, "bf", 2)
        c["moe"] = F.MoEConfig(n_experts=4, capacity=2)
    kwargs = build_kwargs(c)

    step = F.build_train_step(loss_fn, opt, mesh, donate=False, **kwargs)
    params = F.rank_major(base, mesh)
    ostate = F.rank_major(opt.init(base), mesh)
    if push_sum:
        ostate = (ostate, F.push_sum_weights(mesh))
    if getattr(step, "mix_config", None) is not None:
        ostate = (ostate, step.init_mix_state(params))
    if moe:
        from bluefog_tpu.moe import default_route_table, capacity_mask_of
        # rank-major route data: tokens, this-rank route rows, and the
        # tiled liveness mask all shard over the leading rank axis
        batch = (np.zeros((N_RANKS, 3, 4), np.float32),
                 np.asarray(default_route_table(N_RANKS, 4)),
                 np.broadcast_to(capacity_mask_of(np.zeros(N_RANKS))[None],
                                 (N_RANKS, N_RANKS)).copy())
    else:
        batch = np.zeros((N_RANKS, 3, 4), np.float32)
    # a schedule of P rounds is P programs (the host picks the round's
    # from ``step``), an exchange every k-th step two (on and off
    # cycle): each is held to the contracts
    findings: List[Finding] = []
    scheduled = "schedule" in kwargs
    programs = (len(kwargs["schedule"]) if scheduled
                else kwargs.get("num_steps_per_communication", 1))
    for r in range(programs):
        args = (params, ostate, batch, np.int32(r))
        if guarded:
            args = args + (step.default_comm_weights,)
        findings += check_step(
            step, args, round_index=r if scheduled else None,
            off_cycle=not scheduled and r > 0,
            name=f"step[{case_id(case)}]"
            + (f"[step {r}]" if programs > 1 else ""))
    return findings


def check_collective_contracts() -> List[Finding]:
    """Lower the topology compiler's scheduled programs and hold the
    HLO to ``predicted_collectives`` via the supported
    ``verify_collective_contract`` — the flat (1, 8)-pod schedule, one
    program a round (exactly how build_train_step consumes a
    schedule), and the hierarchical (4, 2)-pod rounds."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bluefog_tpu import benchutil
    from bluefog_tpu.parallel import collectives as C
    from bluefog_tpu.topology.compiler import PodSpec, compile_topology

    mesh = _mesh()
    payload = 64 * 4
    x = jnp.zeros((N_RANKS, 64), jnp.float32)
    findings: List[Finding] = []

    compiled = compile_topology(PodSpec(1, 8))
    pred = compiled.predicted_collectives(payload)
    schedule = compiled.schedule

    # (each call also holds the period's totals to the rounds' sum)
    for i, rnd in enumerate(schedule):
        def one(v, r=rnd):
            return C.neighbor_allreduce(v, r, "bf")
        smr = jax.shard_map(one, mesh=mesh, in_specs=P("bf"),
                            out_specs=P("bf"), check_vma=False)
        hlo_r = jax.jit(smr).lower(x).compile().as_text()
        for msg in benchutil.verify_collective_contract(
                hlo_r, pred, payload, round_index=i):
            findings.append(Finding(
                "collective-contract", "schedule[pod_1x8]", 0,
                f"round_{i}", msg))

    # the MoE dispatch wire: lower the compiled all-to-all and hold it
    # to ITS predicted_collectives, full period and round-by-round —
    # the same contract the mixing schedules above answer to
    from bluefog_tpu.moe import all_to_all_dispatch, dispatch_plan
    from bluefog_tpu.topology.compiler import compile_all_to_all

    a2a = compile_all_to_all(PodSpec(4, 2))
    shard = jnp.zeros((N_RANKS, N_RANKS, 16), jnp.float32)
    a2a_payload = 16 * 4
    apred = a2a.predicted_collectives(a2a_payload)

    def _a2a_prog(plan):
        def run(v):
            return all_to_all_dispatch(v[0], plan, "bf")[None]
        sma = jax.shard_map(run, mesh=mesh, in_specs=P("bf"),
                            out_specs=P("bf"), check_vma=False)
        return jax.jit(sma).lower(shard).compile().as_text()

    hlo_a = _a2a_prog(dispatch_plan(a2a.schedule))
    for msg in benchutil.verify_collective_contract(hlo_a, apred,
                                                    a2a_payload):
        findings.append(Finding("collective-contract", "a2a[pod_4x2]",
                                0, "period", msg))
    for i, rnd in enumerate(a2a.schedule):
        hlo_ar = _a2a_prog(dispatch_plan([rnd]))
        for msg in benchutil.verify_collective_contract(
                hlo_ar, apred, a2a_payload, round_index=i):
            findings.append(Finding("collective-contract",
                                    "a2a[pod_4x2]", 0, f"round_{i}",
                                    msg))

    hier = compile_topology(PodSpec(4, 2), hierarchical=True)
    hpred = hier.predicted_collectives(payload)
    for i, rnd in enumerate(hier.machine_schedule):
        def two(v, r=rnd):
            return C.hierarchical_neighbor_allreduce(
                v, r, hier.local_size, "bf")
        smh = jax.shard_map(two, mesh=mesh, in_specs=P("bf"),
                            out_specs=P("bf"), check_vma=False)
        hlo_h = jax.jit(smh).lower(x).compile().as_text()
        for msg in benchutil.verify_collective_contract(
                hlo_h, hpred, payload, round_index=i):
            findings.append(Finding(
                "collective-contract", "hier[pod_4x2]", 0,
                f"round_{i}", msg))
    return findings


def check_serving_residents() -> List[Finding]:
    """Trace every resident serving executable (the engine's
    build-time registry: prefill chunk + decode step, and the
    speculative draft/verify pair) and require model/table state to
    arrive as traced arguments — any large closed-over float constant
    is baked state that would recompile on every weight swap."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu import models
    from bluefog_tpu.serving.engine import ServingEngine, SpeculativeConfig

    findings: List[Finding] = []
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32)
    variables = models.Llama(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((2, 4), jnp.int32))
    engines = {
        "serving": ServingEngine(variables, cfg, capacity=2, max_len=48,
                                 prefill_chunk=4),
        "spec_serving": ServingEngine(
            variables, cfg, capacity=2, max_len=48, prefill_chunk=4,
            speculative=SpeculativeConfig(variables=variables, cfg=cfg,
                                          lookahead=2)),
    }
    for eng_name, eng in engines.items():
        for prog, (fn, thunk, static) in eng._resident.items():
            closed = fn.trace(*thunk(), **static)
            findings += check_traced(
                closed, name=f"{eng_name}[{prog}]",
                large_const_floor=_LARGE_CONST)
    return findings


def run_sweep(*, include_serving: bool = True,
              include_collectives: bool = True,
              cases: Optional[Iterable[dict]] = None) -> List[Finding]:
    """The full semantic sweep: every train-step matrix point, the
    scheduled-exchange collective contracts, and the serving
    residents.  Returns all findings (empty = every contract holds)."""
    mesh = _mesh()
    findings: List[Finding] = []
    for case in (sweep_cases() if cases is None else cases):
        findings += _build_and_check(case, mesh)
    if include_collectives:
        findings += check_collective_contracts()
    if include_serving:
        findings += check_serving_residents()
    return findings
