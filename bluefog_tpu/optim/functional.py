"""Fully-jitted decentralized train steps.

The eager optimizer wrappers (``bluefog_tpu.optim.wrappers``) mirror the
reference's host-driven hook model (reference bluefog/torch/optimizers.py) —
good for parity, but each op is a separate dispatch.  This module is the
TPU-first fast path: ONE compiled SPMD program per train step containing
forward, backward, the base optax update, and the decentralized combine —
each leaf's ppermute a top-level asynchronous operation that XLA's
scheduler can fly under compute, which is what the reference gets
from its background thread + tensor fusion (reference
common/operations.cc:453-1020), but compiler-scheduled instead of
hand-scheduled.

Key design points (SURVEY.md §7 "hard parts"):

* **Dynamic topologies without retrace storms** — pass ``schedule`` (a list
  of ``P`` topology specs, e.g. the log2(n) one-peer exponential-2
  rounds): the step is ``P`` compiled programs, one a round, and the host
  picks the round's from the ``step`` it is called with
  (``_round_selector``; the round is a static argument of the step's one
  ``jax.jit``).  ``P`` compiles in a cold process (from the persistent
  cache afterwards), none after the first cycle, and the model is traced
  once for all of them: forward and backward are a jitted function of
  their own that every round's program calls (``fwd_bwd_all`` in
  ``_build_step``, traced at the top level before the first
  program is).  The round is NOT a
  ``lax.switch`` inside one program: a ``conditional`` takes the whole
  parameter tree as its operand, so no permute inside it could start
  before the backward pass's last gradient existed, and none overlapped
  anything (PERF.md §6, PR 25).  Within a round's program the buckets'
  exchanges are chained largest first (``_exchange_buckets``), so that
  each permute flies under the next bucket's weight-gradient fusion
  instead of after them all.  ``step`` must therefore be concrete (a
  Python or NumPy integer; a device scalar costs one read a dispatch): a
  tracer raises ``TypeError``.  Weights, faults and ratios remain data.
* **Rank-major state** — every rank owns its own parameters (decentralized
  DP: nothing is replicated).  Params/opt-state/batch leaves all carry a
  leading ``[n_ranks]`` axis sharded over ``axis_name``; use
  :func:`rank_major` / :func:`rank_spec_tree` to build them.
* **Sequence parallelism composes** — give the mesh an extra axis and pass
  ``sp_axis``; gradients are psum-reduced over it (params are replicated
  across sp), so a ring-attention model trains with dp x sp on one mesh.

Combine math is f32-accumulated via the shard-level kernels in
``bluefog_tpu.parallel.collectives``.

**One builder, a per-bucket epilogue pipeline** — ``build_train_step``
validates its arguments and hands them to ``_build_step``, which
builds every comm mode and every feature.  It plans the param tree into
fusion buckets (``optim.fusion.EpiloguePlan`` — one bucket per leaf on
the plain path, size-balanced buckets under ``overlap="bucketed"``) and
emits ONE composed closure per bucket running quantize → exchange →
dequantize → guard-select → health-norm over that bucket's leaves; the
guard/health reductions are accumulated as per-bucket partials combined
at the end, and the consensus distance is computed from the exchange's
already-materialized pre/post buffers (no re-mix, no second tree walk).
The cta/atc combine weights ride as TRACED OPERANDS in both the guarded
and unguarded builds, so the two share one association order (guarded ==
unguarded bit for bit on finite data, uniform-weight static CTA
included) and healing swaps weight data without recompiling either.
The step is held to a plain reference written from the definitions
(tests/reference_step.py, tests/test_epilogue.py): per rank
``value_and_grad`` and the optax update, mixing as one dense product
with the round's matrix.
"""

from __future__ import annotations

# This module legitimately constructs weight tables from scratch — the
# analysis lint's weight-matrix-bypass rule treats it as an authority
# (everywhere else, tables must come from the shared helpers here).
_WEIGHT_AUTHORITY = True

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu import config as _config
from bluefog_tpu.compressor import _resolve_k
from bluefog_tpu.optim import fusion as _fusion
from bluefog_tpu.parallel import collectives as C
from bluefog_tpu.topology.spec import DynamicTopology, Topology

CommSpec = Union[Topology, DynamicTopology]

__all__ = [
    "GuardConfig",
    "HealthConfig",
    "HealthVector",
    "MixCompressConfig",
    "MixState",
    "MoEConfig",
    "build_train_step",
    "comm_weight_inputs",
    "push_sum_weights",
    "rank_major",
    "rank_major_init",
    "rank_spec_tree",
    "optax_state_specs",
    "consensus_distance",
]


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Fault-tolerance policy for :func:`build_train_step`.

    Only the PRESENCE of a GuardConfig changes the compiled program (the
    non-finite skip guard + skip-flag output + traced combine weights);
    the fields below are host-side policy consumed by
    :func:`bluefog_tpu.resilience.run_resilient`:

    * ``max_consecutive_bad`` — K: after this many consecutive steps
      with a live-rank skip, the runner escalates — IF some rank was
      bad for the whole window it is declared dead, the topology heals,
      and the state rolls back to the last good checkpoint (an
      unattributable window is noted and training continues: the skip
      guard already contained it).
    * ``backoff_base`` / ``backoff_factor`` / ``max_backoff`` — the
      exponential backoff (seconds) slept before resuming after each
      rollback: ``min(base * factor**i, max_backoff)``.
    * ``max_rollbacks`` — give up (raise) after this many rollbacks.
    """

    max_consecutive_bad: int = 3
    backoff_base: float = 0.5
    backoff_factor: float = 2.0
    max_backoff: float = 30.0
    max_rollbacks: int = 8


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """In-graph training-health instrumentation policy for
    :func:`build_train_step`.

    Only the PRESENCE of a HealthConfig changes the compiled program:
    the step additionally emits a :class:`HealthVector` — a small,
    FIXED-SHAPE bundle of per-rank health scalars computed from tensors
    the step already materializes.  It is shape-stable across every
    fault pattern (faults are traced inputs, same discipline as
    :class:`GuardConfig` — zero recompiles, asserted via jit cache
    sizes in tests/test_fleet.py), and with ``health=None`` (the
    default) the built step is bit-identical to one built without the
    feature.

    * ``consensus`` — include the consensus distance
      ``‖x_i − Σ_j w_ij x_j‖`` (the rank's pre-combine state vs the
      neighbor combine's output, which the exchange materializes
      anyway).  ``False`` reports 0.0 there and skips the reduction.
    """

    consensus: bool = True


class HealthVector(NamedTuple):
    """Per-rank in-graph health scalars a train step emits under
    ``health=HealthConfig(...)`` — rank-major ``[n]`` float32 vectors
    (inside ``shard_map`` each field is the rank's scalar):

    * ``loss`` — the rank's step loss (duplicated from the step output
      so the vector is self-contained for gossip);
    * ``grad_norm`` — global L2 norm of the rank's LOCAL gradients
      (before any cross-rank reduction; model-parallel leaves
      contribute their shard);
    * ``update_norm`` — global L2 norm of the optax update;
    * ``skipped`` — the guard's skip flag under ``guard=``; without a
      guard, the same in-graph isfinite reduce as a *would-skip* bit
      (reported, not acted on);
    * ``consensus`` — ``‖x_i − Σ_j w_ij x_j‖`` over the rank's local
      parameter shard (0.0 when no neighbor combine ran this step:
      off-cycle steps under ``num_steps_per_communication``, or comm
      modes without a neighbor exchange).

    Being a NamedTuple it is a pytree: feed it straight to host-side
    consumers (``bluefog_tpu.observe.fleet``) or stack fields for
    gossip.
    """

    loss: Any
    grad_norm: Any
    update_norm: Any
    skipped: Any
    consensus: Any


@dataclasses.dataclass(frozen=True)
class MixCompressConfig:
    """Error-feedback compressed parameter mixing policy for
    :func:`build_train_step` (``compress="topk"`` is shorthand for the
    defaults here, with ``BLUEFOG_MIX_COMPRESS_RATIO`` consulted).

    The cta/atc combine's wire payload becomes
    ``compress(x − ref + e)``: a per-bucket top-k-by-magnitude delta
    against the reference copy of the last-exchanged state, with the
    residual accumulating into the per-rank error-feedback state ``e``
    and receivers reconstructing ``ref + delta``
    (:func:`bluefog_tpu.parallel.collectives.mix_compress_exchange`).

    * ``ratio`` — kept fraction of each bucket's elements, in (0, 1).
      This is the BUILD-TIME ratio: it fixes the static per-bucket k
      (``compressor._resolve_k``) and therefore the wire shapes.  The
      LIVE ratio is ``MixState.ratio`` — traced data the control plane
      tightens online (``k_live <= k``) with zero recompiles.  A value
      >= 1.0 means "keep everything" and builds the ordinary
      uncompressed exchange (bit-identical by construction).
    * ``values`` — wire encoding of the kept values: ``"int8"``
      (absmax per bucket, round-to-nearest — composes the existing
      int8 stage on top of the sparsity), ``"int8_sr"`` (stochastic
      rounding, per-step/per-rank/per-bucket PRNG folding), or
      ``"none"`` (f32 values).
    * ``error_feedback`` — accumulate the compression residual into
      ``e`` (the construction that keeps the mixing recursion
      contractive).  ``False`` drops the residual — the ablation arm of
      benchmarks/wire_quant_consensus.py's ratio sweep, not a mode to
      train with.
    """

    ratio: float = 0.25
    values: str = "int8"
    error_feedback: bool = True


class MixState(NamedTuple):
    """Per-rank error-feedback mixing state (rank-major pytree data,
    carried as the second element of the step's ``opt_state`` —
    ``(base_opt_state, MixState)``, the same convention as push_sum's
    weight).  Ordinary traced data: checkpoints, healing rollbacks, and
    elastic swaps move it with the rest of the state, nothing
    recompiles.  Build with ``train_step.init_mix_state(params)``.

    * ``ratio`` — ``[n]`` f32, each rank's LIVE compression ratio (the
      control plane's online knob; starts at the build ratio);
    * ``err`` — per compressible bucket, ``[n, numel]`` f32
      error-feedback accumulators;
    * ``ref`` — per compressible bucket, ``[n, R, numel]`` f32: the
      sender-side reference copies, one row per schedule round (a
      rotating schedule pairs different partners per round, so each
      round integrates its own delta stream);
    * ``mirror`` — per compressible bucket, ``[n, G, numel]`` f32: the
      receiver-side mirrors of each in-edge's sender state
      (``G = sum of mix_mirror_slots(spec) over rounds``)."""

    ratio: Any
    err: Any
    ref: Any
    mirror: Any


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Expert-sharded MoE policy for :func:`build_train_step`: which
    parameter leaves are EXPERT-LOCAL and therefore excluded from the
    neighbor mixing epilogue.  Everything else — router, embeddings,
    dense trunk — keeps flowing through the ordinary cta/atc combine
    unchanged, so guard/health/compression compose without new code
    paths; the expert all-to-all itself lives inside ``loss_fn``
    (:mod:`bluefog_tpu.moe`), not in the builder.

    * ``n_experts`` — expert count (each rank hosts replica
      ``rank % n_experts``; see ``moe.dispatch.expert_owner``);
    * ``capacity`` — per-destination shard depth of the dispatch wire
      (``moe.layer.default_capacity`` derives one from the
      ``BLUEFOG_MOE_CAPACITY_FACTOR`` knob);
    * ``expert_path_tokens`` — a param leaf whose tree path contains
      any of these substrings is expert-local (matched against
      ``jax.tree_util.keystr``; the default matches the ``"expert"``
      subtree of ``moe.layer.init_moe_params``).
    """

    n_experts: int
    capacity: int
    expert_path_tokens: Tuple[str, ...] = ("expert",)

    def __post_init__(self):
        if self.n_experts < 1 or self.capacity < 1:
            raise ValueError(
                f"MoEConfig needs n_experts >= 1 and capacity >= 1, "
                f"got {self.n_experts} / {self.capacity}")
        if not self.expert_path_tokens:
            raise ValueError("expert_path_tokens must be non-empty — "
                             "an MoE step with no local leaves is just "
                             "a dense step")


def _moe_shared_mask(tree, moe: "MoEConfig"):
    """Per-leaf booleans in ``jax.tree.leaves`` order: True = shared
    (mixed by the epilogue), False = expert-local (never on the mixing
    wire).  Path-based so it works on any pytree shape at trace time."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [not any(tok in jax.tree_util.keystr(path)
                    for tok in moe.expert_path_tokens)
            for path, _ in flat]


def comm_weight_inputs(specs: Sequence[CommSpec]) -> tuple:
    """The combine weights of a topology/schedule as TRACED-OPERAND data:
    one ``(class_weights [n_classes, n], self_weights [n])`` pair per
    round, the pytree a guarded train step takes as its ``comm_weights``
    argument.  Healing a topology (``resilience.healing``) produces a
    pytree of the SAME shapes over the same edge structure, so swapping
    weights never recompiles — the shape-stability contract of the
    resilience layer."""
    return tuple(
        (C.class_recv_weights(s), C.self_weight_vector(s)) for s in specs)


def _grouped_sq_sum(leaves, groups) -> jax.Array:
    """f32 sum of squares over inexact leaves, accumulated as
    per-bucket partials in plan order.  Groups partition the leaves in
    tree order, so the accumulation association is that of a flat walk
    over the tree."""
    acc = jnp.zeros((), jnp.float32)
    for g in groups:
        for i in g:
            leaf = jnp.asarray(leaves[i])
            if jnp.issubdtype(leaf.dtype, jnp.inexact):
                acc = acc + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return acc


def _grouped_all_finite(loss: jax.Array, upd_leaves, groups) -> jax.Array:
    """The guard's isfinite reduce as per-bucket partials combined at
    the end (boolean AND is associative: the flag of a flat walk over the
    tree), so the reduce fuses into the same per-bucket pass as the
    norms instead of a separate full-tree walk."""
    ok = jnp.all(jnp.isfinite(loss))
    for g in groups:
        part = jnp.bool_(True)
        for i in g:
            leaf = jnp.asarray(upd_leaves[i])
            if jnp.issubdtype(leaf.dtype, jnp.inexact):
                part = part & jnp.all(jnp.isfinite(leaf))
        ok = ok & part
    return ok


def _bucket_cons_sq(pre_buf: jax.Array, out_buf: jax.Array) -> jax.Array:
    """Squared consensus-distance partial of one bucket, from the
    exchange's own pre/post buffers — the tensors the combine already
    materializes, so no second tree walk and no re-mix survives in the
    HLO."""
    d = pre_buf.astype(jnp.float32) - out_buf.astype(jnp.float32)
    return jnp.sum(jnp.square(d))


# Named scopes of the compiled step: metadata only (the optimized HLO
# with ``metadata={...}`` stripped is what it was), so that a device
# trace can bill each operation to a part of the step by its ``tf_op``
# path instead of by an HLO number.  JAX itself writes ``jvp(...)`` and
# ``transpose(jvp(...))`` below ``bf.forward_backward``: the
# forward/backward split.  Where scopes nest, the innermost names the
# work.
SCOPE_FORWARD_BACKWARD = "bf.forward_backward"
SCOPE_OPTIMIZER = "bf.optimizer"
SCOPE_EXCHANGE = "bf.exchange"


def _opt_update(optimizer, grads, opt_state, params):
    with jax.named_scope(SCOPE_OPTIMIZER):
        return optimizer.update(grads, opt_state, params)


def _apply_updates(params, updates):
    with jax.named_scope(SCOPE_OPTIMIZER):
        return optax.apply_updates(params, updates)


def _allreduce_grads(grads, axis_name):
    with jax.named_scope(SCOPE_EXCHANGE):
        return jax.tree.map(
            lambda g: C.allreduce(g, axis_name, average=True), grads)


def _loss_and_grads(loss_fn, has_aux, sp_axis, pp_axis, param_specs,
                    params, aux, batch):
    """Forward+backward with the cross-axis reductions every builder
    shares: sp shards pmean grads/loss (params replicated over sp, each
    shard saw a different sequence slice); pp psums the last-stage-
    masked loss and restores pp-replicated leaves' gradients (the
    layer stacks sharded over pp got exact stage-local gradients
    through the reversed ppermutes — no reduction for those)."""
    with jax.named_scope(SCOPE_FORWARD_BACKWARD):
        if has_aux:
            (loss, new_aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, aux, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_aux = aux
    if sp_axis is not None:
        grads = lax.pmean(grads, sp_axis)
        loss = lax.pmean(loss, sp_axis)
    if pp_axis is not None:
        loss = lax.psum(loss, pp_axis)

        def _pp_reduce(g, spec):
            names = set()
            for el in spec:
                if isinstance(el, tuple):
                    names.update(el)
                elif el is not None:
                    names.add(el)
            return g if pp_axis in names else lax.psum(g, pp_axis)

        grads = jax.tree.map(_pp_reduce, grads, param_specs)
    return loss, grads, new_aux


def rank_major(tree, mesh: Mesh, axis_name: str = "bf", specs=None):
    """Stack ``n`` copies of every leaf along a new leading rank axis and
    shard it over ``axis_name`` — the initial state of decentralized
    training where every rank starts from the same point (the reference
    gets this from broadcast_parameters, torch/utility.py:26).
    ``specs``: optional PartitionSpec tree (leading rank axis included)
    for model-parallel leaves; default rank-sharded / replicated."""
    n = mesh.shape[axis_name]
    if specs is None:
        specs = jax.tree.map(lambda _: P(axis_name), tree)

    def stack(leaf, spec):
        leaf = jnp.asarray(leaf)
        return jax.device_put(
            jnp.broadcast_to(leaf[None], (n,) + leaf.shape),
            NamedSharding(mesh, spec))

    return jax.tree.map(stack, tree, specs)


def rank_major_init(init_fn: Callable[[], Any], mesh: Mesh,
                    axis_name: str = "bf", specs=None):
    """Build rank-major state directly sharded over the mesh: ``init_fn()``
    is traced once and compiled with rank-sharded outputs, so no device
    ever materializes the full unsharded ``[n, ...]`` stack — required at
    LLM scale where a single-device staging copy would not fit HBM.
    ``specs``: optional PartitionSpec tree for model-parallel leaves."""
    n = mesh.shape[axis_name]

    def build():
        tree = init_fn()
        return jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (n,) + leaf.shape),
            tree)

    shapes = jax.eval_shape(build)
    if specs is None:
        specs = jax.tree.map(lambda _: P(axis_name), shapes)
    out_shardings = jax.tree.map(
        lambda _, s: NamedSharding(mesh, s), shapes, specs)
    return jax.jit(build, out_shardings=out_shardings)()


def optax_state_specs(optimizer: optax.GradientTransformation,
                      params_shapes, param_specs,
                      axis_name: str = "bf"):
    """PartitionSpec tree for an optax state: any sub-tree structurally
    identical to the param tree (momentum, Adam moments, ...) inherits
    ``param_specs``; everything else (step counters, hyperparams) is
    rank-replicated scalars sharded only over the rank axis."""
    state_shapes = jax.eval_shape(optimizer.init, params_shapes)
    params_treedef = jax.tree.structure(params_shapes)
    default = P(axis_name)

    def match_specs(node):
        """param_specs, but leaves whose SHAPE differs from the matching
        param fall back to the default — factored optimizers (adafactor)
        keep param-structured subtrees with rank-reduced leaves, and a
        model-parallel spec longer than the leaf's rank would fail at
        device_put.  The fallback is only sound when the param itself is
        rank-sharded: a factored moment of a MODEL-PARALLEL param (e.g. a
        tp-sharded kernel's row statistics) would be replicated while the
        per-shard gradient is sliced, mismatching inside
        ``optimizer.update`` at trace time — reject that combination up
        front with a fix-it message instead."""

        def pick(st, ps, spec):
            if tuple(st.shape) == tuple(ps.shape):
                return spec
            model_axes = [ax for el in spec
                          for ax in (el if isinstance(el, tuple) else (el,))
                          if ax is not None and ax != axis_name]
            if model_axes:
                raise ValueError(
                    f"optimizer state leaf of shape {tuple(st.shape)} is "
                    f"shape-reduced relative to its param "
                    f"{tuple(ps.shape)} whose spec {spec} is model-"
                    f"parallel over {model_axes} — factored optimizers "
                    "(e.g. adafactor) do not compose with model-parallel "
                    "param shardings here; pass an explicit "
                    "opt_state_specs tree that shards the factored "
                    "moments to match, or use a non-factored optimizer")
            return default

        return jax.tree.map(pick, node, params_shapes, param_specs)

    def assign(node):
        try:
            matches = jax.tree.structure(node) == params_treedef
        except Exception:
            matches = False
        if matches:
            return match_specs(node)
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[assign(c) for c in node])
        if isinstance(node, tuple):
            return tuple(assign(c) for c in node)
        if isinstance(node, list):
            return [assign(c) for c in node]
        if isinstance(node, dict):
            return {k: assign(v) for k, v in node.items()}
        return default

    return assign(state_shapes)


def rank_spec_tree(tree, axis_name: str = "bf"):
    """PartitionSpec tree: leading rank axis on every leaf."""
    return jax.tree.map(lambda _: P(axis_name), tree)


def consensus_distance(params) -> jax.Array:
    """Mean squared distance of each rank's parameters from the rank-mean —
    the standard measure of decentralized disagreement.  ``params`` is
    rank-major."""
    leaves = jax.tree.leaves(params)
    total = 0.0
    count = 0
    for leaf in leaves:
        mean = jnp.mean(leaf, axis=0, keepdims=True)
        total = total + jnp.sum((leaf - mean) ** 2)
        count += leaf.size
    return total / count


def push_sum_weights(mesh: Mesh, axis_name: str = "bf") -> jax.Array:
    """Rank-major push-sum weight vector (init 1 per rank) — pair it with
    the base optimizer state for ``comm_mode='push_sum'``:
    ``opt_state = (base_opt_state, push_sum_weights(mesh))``."""
    n = mesh.shape[axis_name]
    return jax.device_put(jnp.ones((n,), jnp.float32),
                          NamedSharding(mesh, P(axis_name)))


def _pack_bucket(leaves, group):
    """Concatenate a bucket's leaves into one flat per-shard buffer (a
    single-leaf bucket keeps its shape: no reshape traffic, and compress
    stays per-tensor for it)."""
    if len(group) == 1:
        return leaves[group[0]]
    return jnp.concatenate(
        [jnp.reshape(leaves[i], (-1,)) for i in group])


def _unpack_bucket(buf, leaves, group, outs):
    """Slice a combined bucket buffer back into ``outs`` at the bucket's
    leaf indices (shapes/dtypes from the uncombined ``leaves``)."""
    if len(group) == 1:
        outs[group[0]] = buf
        return
    off = 0
    for i in group:
        k = leaves[i].size
        outs[i] = jnp.reshape(buf[off:off + k], leaves[i].shape)
        off += k


def _round_selector(comm_mode: str, specs: Sequence[CommSpec],
                    k_comm: int) -> Optional[Callable]:
    """``select(step)`` names the program one dispatch runs: the
    schedule's round ``step % len(specs)``, or ``None`` on an off-cycle
    step of ``num_steps_per_communication`` (the variant with no
    exchange in it).  The round is a STATIC argument of the step's one
    ``jax.jit``, so the host picks one of the step's executables and no
    ``conditional`` inside one executable does: a round's
    ``collective-permute``s are top-level asynchronous operations, each
    waiting for its own leaf alone, which can fly under compute (a
    ``conditional`` waits for its last operand, the backward pass's
    last gradient).  ``None`` for a step that is one program (a
    static topology exchanged every step, or no neighbor exchange at
    all): nothing of ``step`` is read on the host."""
    n_rounds = len(specs)
    if (comm_mode not in ("cta", "atc", "push_sum")
            or (n_rounds <= 1 and k_comm <= 1)):
        return None

    def select(step):
        try:
            i = int(step)
        except TypeError as e:
            raise TypeError(
                f"this train step is {n_rounds} round(s) of a schedule "
                f"with num_steps_per_communication={k_comm}: one compiled "
                "program a round, picked on the host from `step`, which "
                "must therefore be a concrete integer (a Python or NumPy "
                "integer; a device scalar costs one read a dispatch), not "
                f"{type(step).__name__}.  Under jit/scan around the step, "
                "build a one-round step (topology=schedule[r]) or call "
                "the step once a round from Python.") from e
        return i % n_rounds if i % k_comm == 0 else None

    return select


def _public_step(jitted: Callable, labels: dict, *,
                 select: Optional[Callable], has_aux: bool,
                 tail: tuple = (),
                 edge_traffic: Optional[tuple] = None,
                 prepare: Optional[Callable] = None) -> Callable:
    """The step a caller holds, over the jitted program ``jitted(params,
    aux, opt_state, batch, step, *more, round)``: the public signature
    leaves out ``aux`` (argument and output) unless ``has_aux``, never
    has ``tail`` (operands the builder supplies itself: the default
    combine weights of an unguarded step), and never the round,
    which ``select`` reads from ``step`` ONCE a dispatch, on the host,
    for the program and for the edge accounting alike.  ``.lower`` and
    ``.trace`` take the public arguments too (the round from the
    concrete ``step`` they are given), so AOT compilation (benchmarks)
    and jaxpr inspection (bluefog_tpu.analysis) see the program a call
    would run; ``.jitted`` is the one jitted object, with one cache
    entry a round.  ``prepare`` is called once, with the program's
    arguments, before the first program is made (a step of
    several programs traces the model there).

    Host-side observability: each dispatch increments
    ``bf_train_steps_total{comm_mode,overlap,guarded}`` and runs inside
    a ``train_step`` span on the ``train`` track (``bf.train.train_step``
    in a profiler trace, ``round=`` the program it picked, -1 off-cycle),
    the edge accounting in a ``record_edges`` span inside it.  Everything
    happens OUTSIDE the traced program, so jit cache sizes and step
    outputs are bit-identical with ``BLUEFOG_OBSERVE`` on or off
    (asserted in tests/test_observe.py).  The span measures host
    dispatch (jax is async); sync before reading it as a step time.

    ``edge_traffic`` — ``(specs, n_ranks, filtered, local_size)`` for
    the neighbor modes: per on-cycle dispatch, the round's edges each
    get the per-rank parameter payload added to
    ``bf_edge_bytes_total{src,dst}`` through
    ``observe.fleet.record_edge_traffic`` (logical bytes — wire
    compression is not folded in), the fleet-telemetry traffic account
    derived from the topology's shift classes.  ``filtered`` selects
    the weight-filtered push-sum edge set (``push_sum_mix`` only
    ppermutes nonzero-weight edges) instead of the declared one
    (``neighbor_allreduce`` moves bytes on every declared edge — its
    weights are traced operands).  Under a hierarchical exchange
    (``local_size`` set, ``specs`` machine-level) the two legs are
    billed SEPARATELY — the intra-machine ring edges as
    ``link="ici"`` and the expanded counterpart machine edges as
    ``link="dcn"`` — so ``PodSpec.from_telemetry`` can calibrate the
    inter-machine links without mistaking cheap ICI traffic for DCN
    load."""
    payload_cache: list = []
    pairs_cache: dict = {}
    todo = [prepare] if prepare is not None else []

    def record_edges(params, si: int) -> None:
        specs, n_ranks, filtered, local_size = edge_traffic
        if not payload_cache:
            payload_cache.append(sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree.leaves(params)) // max(n_ranks, 1))
        from bluefog_tpu.observe import fleet as _fleet

        if local_size:
            pairs = pairs_cache.get(si)
            if pairs is None:
                L = int(local_size)
                dcn = [(ms * L + j, md * L + j)
                       for (ms, md) in _fleet.edge_list(specs[si])
                       for j in range(L)]
                ici = []
                for g in C.machine_groups(n_ranks, L):
                    if len(g) > 1:
                        ici.extend((g[k], g[(k + 1) % len(g)])
                                   for k in range(len(g)))
                pairs = pairs_cache[si] = (ici, dcn)
            ici, dcn = pairs
            if ici:
                _fleet.record_edge_traffic(specs[si], payload_cache[0],
                                           pairs=ici, link="ici")
            _fleet.record_edge_traffic(specs[si], payload_cache[0],
                                       pairs=dcn, link="dcn")
            return
        pairs = pairs_cache.get(si)
        if pairs is None:
            pairs = pairs_cache[si] = (
                _fleet.gossip_edge_list(specs[si]) if filtered
                else _fleet.edge_list(specs[si]))
        _fleet.record_edge_traffic(specs[si], payload_cache[0],
                                   pairs=pairs)

    def adapt(*args):
        if not has_aux:
            args = args[:1] + ((),) + args[1:]
        args = args + tail + (select(args[4]) if select else 0,)
        if todo:
            todo.pop()(*args)   # once, before the first program is made
        return args

    def step(*args):
        from bluefog_tpu import observe

        args = adapt(*args)
        tr = observe.publish_tracer()
        if tr is None:
            out = jitted(*args)
        else:
            observe.get_registry().counter(
                "bf_train_steps_total", "train-step dispatches",
                **labels).inc()
            si = args[-1]
            with tr.span("train", "train_step",
                         round=-1 if si is None else si):
                # (a step traced by someone's jit is no dispatch: the
                # accounting waits for real ones)
                if (edge_traffic is not None and si is not None
                        and not isinstance(args[4], jax.core.Tracer)):
                    with tr.span("train", "record_edges"):
                        record_edges(args[0], si)
                out = jitted(*args)
        return out if has_aux else out[:1] + out[2:]

    step.jitted = jitted
    step.lower = lambda *args: jitted.lower(*adapt(*args))
    step.trace = lambda *args: jitted.trace(*adapt(*args))
    step.has_aux = has_aux
    return step


def _build_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    axis_name: str,
    comm_mode: str,
    specs: Sequence[CommSpec],
    k_comm: int,
    hierarchical_local_size: Optional[int],
    sp_axis: Optional[str],
    pp_axis: Optional[str],
    batch_specs: Any,
    param_specs: Any,
    opt_state_specs: Any,
    donate: bool,
    has_aux: bool,
    compress: Optional[str],
    n_buckets: Optional[int],
    guard: Optional[GuardConfig],
    health: Optional[HealthConfig],
    mix: Optional[MixCompressConfig] = None,
    moe: Optional[MoEConfig] = None,
) -> Callable:
    """The builder behind :func:`build_train_step` (see its docstring
    for the user contract, and the module docstring for the design);
    the arguments arrive validated.

    One builder serves every feature combination: the param tree is
    planned into fusion buckets (``EpiloguePlan`` — one bucket per leaf
    on the plain path, size-balanced under ``overlap='bucketed'``) and
    each bucket runs its epilogue stages (quantize → exchange →
    dequantize → guard-select → health-norm → consensus) as one
    composed pass, for every comm mode including push_sum.  The
    guard's isfinite reduce and the health norms accumulate as
    per-bucket partials in plan order; the consensus distance reuses
    the exchange's own pre/post bucket buffers.  The cta/atc combine
    weights are TRACED OPERANDS in the guarded AND unguarded builds, so
    both share one association order and topology healing swaps weight
    data without recompiling either variant."""
    guarded = guard is not None
    want_health = health is not None
    want_cons = want_health and health.consensus
    neighbor = comm_mode in ("cta", "atc") and bool(specs)
    # traced combine-weight operands for every neighbor exchange: flat
    # tables are rank-level, hierarchical tables are MACHINE-level (the
    # machine is the failure domain — healing/elastic swap the
    # inter-machine matrix as data); push_sum derives its
    # column-stochastic scales from the edge structure
    use_traced_w = neighbor
    # the step's programs: one a round of a schedule (and the off-cycle
    # one), picked on the host; None where the step is one program
    select = _round_selector(comm_mode, specs, k_comm)
    chained = select is not None
    wire = compress == "int8_sr"
    wire_compress = "int8" if wire else compress
    zero = lambda: jnp.zeros((), jnp.float32)
    # error-feedback compressed mixing: per-round sender refs +
    # per-in-edge receiver mirrors, laid out contiguously over the
    # schedule (round r's mirror rows live at [offset_r, offset_r+slots))
    mix_on = mix is not None
    mix_sr = mix_on and mix.values == "int8_sr"
    mix_slots = [C.mix_mirror_slots(s) for s in specs] if mix_on else []
    mix_offsets = list(np.cumsum([0] + mix_slots))
    stage_compress = compress if not mix_on else (
        "int8" if mix.values in ("int8", "int8_sr") else None)

    def _plan(leaves):
        return _fusion.EpiloguePlan.for_leaves(
            leaves, n_buckets, compress=stage_compress, guard=guarded,
            health=want_health, consensus=want_cons, mix=mix_on)

    def _bucket_exchange(pre, spec, key, b, w, mix_state, r_index, ci):
        """One bucket's exchange stage: the EF-compressed sparse wire
        for compressible buckets under a mix config (returning the
        advanced (ref, mirrors, err) slices), the ordinary dense
        exchange otherwise.  Returns (out, mix_update | None)."""
        cw, sw = w
        if mix_on and jnp.issubdtype(jnp.dtype(b.dtype), jnp.inexact):
            off = mix_offsets[r_index]
            rows = mix_slots[r_index]
            numel = int(np.prod(pre.shape))
            out, nr, nm, ne = C.mix_compress_exchange(
                pre, spec, axis_name,
                ref_row=mix_state.ref[ci][r_index],
                mirrors=mix_state.mirror[ci][off:off + rows],
                err=mix_state.err[ci],
                ratio=mix_state.ratio,
                k=_resolve_k(None, mix.ratio, numel),
                values=mix.values,
                error_feedback=mix.error_feedback,
                class_weights=cw, self_weights=sw,
                wire_key=(jax.random.fold_in(key, b.index)
                          if mix_sr else None),
                hierarchical_local_size=hierarchical_local_size)
            return out, (nr, nm, ne)
        if hierarchical_local_size is not None:
            out = C.hierarchical_neighbor_allreduce(
                pre, spec, hierarchical_local_size, axis_name,
                compress=wire_compress,
                wire_key=(jax.random.fold_in(key, b.index)
                          if wire else None),
                class_weights=cw, self_weights=sw)
        else:
            out = C.neighbor_allreduce(
                pre, spec, axis_name, compress=wire_compress,
                wire_key=(jax.random.fold_in(key, b.index)
                          if wire else None),
                class_weights=cw, self_weights=sw)
        return out, None

    def _advance_mix(mix_state, r_index, ci, upd, acc):
        """Fold one bucket's (ref, mirrors, err) advance into the
        accumulating (err, ref, mirror) lists."""
        nr, nm, ne = upd
        errs, refs, mirs = acc
        off = mix_offsets[r_index]
        rows = mix_slots[r_index]
        refs[ci] = refs[ci].at[r_index].set(nr)
        mirs[ci] = mirs[ci].at[off:off + rows].set(nm)
        errs[ci] = ne

    def _mix_result(mix_state, acc):
        if not mix_on:
            return mix_state
        errs, refs, mirs = acc
        return MixState(ratio=mix_state.ratio, err=tuple(errs),
                        ref=tuple(refs), mirror=tuple(mirs))

    def _fused_combine_branch(spec: CommSpec, r_index: int) -> Callable:
        """fn(tree, key, w, mix_state) -> (combined_tree, cons_sq,
        mix_state'): the per-bucket pipeline over an already-
        materialized param tree (cta pre-update; atc post-update).

        In a step of several programs the buckets' exchanges are
        CHAINED, largest bucket first: a bucket's buffer reaches its
        permute through a ``lax.optimization_barrier`` with the mixed
        output of the bucket before it.  Left alone, XLA's scheduler
        (which sinks the weight-gradient-with-update fusions below the
        backward chain) keeps a handful of permutes in flight and opens
        them largest first from the program's END: the smallest leaves
        get the fusions to fly under, the large ones start when nothing
        is left but the mixing.  Chained, each permute flies under the
        next bucket's fusion — about as long as the transfer, the
        buckets being sorted — and the round ends on its smallest
        transfers (PERF.md §6, PR 25: 43 ms of exchange exposed
        unchained, 6 chained, of 63).  The barrier is an identity:
        every value, and the order of every sum, is the unchained
        build's."""

        def fn(tree, key, w, mix_state):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            if not leaves:
                return tree, zero(), mix_state
            buckets = _plan(leaves).buckets
            pres = [_pack_bucket(leaves, list(b.leaves)) for b in buckets]
            inexact = [jnp.issubdtype(jnp.dtype(b.dtype), jnp.inexact)
                       for b in buckets]
            # a bucket's place among those that carry error-feedback state
            cis = [sum(inexact[:i]) if mix_on else 0
                   for i in range(len(buckets))]
            order = list(range(len(buckets)))
            if chained:
                order.sort(
                    key=lambda i: -pres[i].size * pres[i].dtype.itemsize)
            mixed, upds = [None] * len(buckets), [None] * len(buckets)
            for n, i in enumerate(order):
                pre = pres[i]
                if chained and n:
                    pre, mixed[order[n - 1]] = lax.optimization_barrier(
                        (pre, mixed[order[n - 1]]))
                mixed[i], upds[i] = _bucket_exchange(
                    pre, spec, key, buckets[i], w, mix_state, r_index,
                    cis[i])
            outs = [None] * len(leaves)
            cons = zero()
            acc = ([list(mix_state.err), list(mix_state.ref),
                    list(mix_state.mirror)] if mix_on else None)
            for i, b in enumerate(buckets):
                if upds[i] is not None:
                    _advance_mix(mix_state, r_index, cis[i], upds[i], acc)
                if want_cons and inexact[i]:
                    cons = cons + _bucket_cons_sq(pres[i], mixed[i])
                _unpack_bucket(mixed[i], leaves, list(b.leaves), outs)
            return (jax.tree_util.tree_unflatten(treedef, outs), cons,
                    _mix_result(mix_state, acc))

        return fn

    def _fused_push_sum_branch(spec: CommSpec) -> Callable:
        """fn((params, ps)) -> (debiased, mixed_ps, cons_sq): the
        push-sum pipeline — bias, mix, and de-bias run on bucket
        buffers (the extended payload [buckets ‖ ps] mixes as a unit,
        column-stochastic scales from the edge structure), with the
        consensus partial from the same pre/post buffers."""

        def fn(operand):
            params, ps = operand
            leaves, treedef = jax.tree_util.tree_flatten(params)
            if not leaves:
                return params, ps, zero()
            plan = _plan(leaves)
            bufs = [_pack_bucket(leaves, list(b.leaves))
                    for b in plan.buckets]
            # Push-sum state is the BIASED pair (x, w) with readout
            # z = x / w; the step carries (z, w) so the user-visible
            # params stay de-biased, and re-biases before every mix
            # (x = z * w): mixing z directly is only correct on doubly-
            # stochastic graphs and diverges on general digraphs
            # (reference optimizers.py:1151-1155).  The whole re-bias ->
            # mix -> de-bias round stays in f32, one cast back at the end.
            # push_sum_mix takes any pytree, so the bucket-buffer list
            # mixes as one extended payload [buckets ‖ ps] — column-
            # stochastic mixing distributes over concatenation, each
            # bucket its own independent collective
            biased = [buf.astype(jnp.float32) * ps for buf in bufs]
            mixed, mixed_ps = C.push_sum_mix(biased, ps, spec,
                                             axis_name)
            outs = [None] * len(leaves)
            cons = zero()
            for b, pre, mix in zip(plan.buckets, bufs, mixed):
                deb = (mix / mixed_ps).astype(jnp.dtype(b.dtype))
                if want_cons and jnp.issubdtype(jnp.dtype(b.dtype),
                                                jnp.inexact):
                    cons = cons + _bucket_cons_sq(pre, deb)
                _unpack_bucket(deb, leaves, list(b.leaves), outs)
            return (jax.tree_util.tree_unflatten(treedef, outs),
                    mixed_ps, cons)

        return fn

    branches = [_fused_combine_branch(s, r)
                for r, s in enumerate(specs)] \
        if neighbor else []
    ps_branches = [_fused_push_sum_branch(s) for s in specs] \
        if comm_mode == "push_sum" else []

    # ``r`` below is the program's STATIC round (``_round_selector``):
    # the round's branch is called directly, and an off-cycle program
    # (``r is None``) holds no collective and no epilogue stage riding
    # one — the mix state rides through untouched (no wire, no delta)
    @jax.named_scope(SCOPE_EXCHANGE)
    def fused_combine(params, step, comm_weights, mix_state, r):
        if not branches or r is None:
            return params, zero(), mix_state
        key = jax.random.fold_in(jax.random.PRNGKey(0x51EED), step)
        return branches[r](params, key,
                           comm_weights[r] if use_traced_w else (),
                           mix_state)

    if moe is not None:
        # Expert-sharded MoE: only the SHARED leaves ride the mixing
        # wire.  Wrapping here (a leaf LIST is itself a pytree, so the
        # branch machinery replans over it unchanged) covers every
        # fused_combine call site — cta, guarded atc, and the plain atc
        # fallback — with one partition; expert leaves pass through
        # untouched and never cost a byte of exchange.
        _dense_fused_combine = fused_combine

        def fused_combine(params, step, comm_weights, mix_state, r):
            leaves, treedef = jax.tree_util.tree_flatten(params)
            mask = _moe_shared_mask(params, moe)
            if not any(mask):
                raise ValueError(
                    f"MoEConfig.expert_path_tokens "
                    f"{moe.expert_path_tokens!r} match EVERY param "
                    "leaf — nothing left to mix, the fleet would "
                    "never reach consensus")
            shared = [l for l, m in zip(leaves, mask) if m]
            mixed, cons, mix_state = _dense_fused_combine(
                shared, step, comm_weights, mix_state, r)
            it = iter(mixed)
            out = [next(it) if m else l for l, m in zip(leaves, mask)]
            return (jax.tree_util.tree_unflatten(treedef, out), cons,
                    mix_state)

    @jax.named_scope(SCOPE_EXCHANGE)
    def fused_push_sum(params, ps, r):
        if r is None:
            return params, ps, zero()
        return ps_branches[r]((params, ps))

    def per_rank_step(r, params, aux, opt_state, batch, step,
                      comm_weights, fwd_bwd=None):
        mix_state = ()
        if mix_on:
            # the MixState rides opt_state as (base, MixState) — the
            # push_sum convention; the GUARD's pick below applies to
            # the base only (the exchange ran on the wire regardless of
            # a local skip, so ref/mirror/err must advance to stay
            # bitwise-consistent with what the neighbors received)
            opt_state, mix_state = opt_state
        loss, grads, new_aux = fwd_bwd or _loss_and_grads(
            loss_fn, has_aux, sp_axis, pp_axis, param_specs,
            params, aux, batch)
        groups = _plan(jax.tree.leaves(params)).groups \
            if (want_health or guarded) else None
        # local (pre-allreduce) gradient norm as per-bucket partials
        grad_sq = _grouped_sq_sum(jax.tree.leaves(grads), groups) \
            if want_health else None
        cons = zero()
        if comm_mode == "gradient_allreduce":
            # (guarded note: the allreduce mixes GRADIENTS, so one
            # rank's NaN reaches every rank — the guard skips globally;
            # the neighbor modes contain the blast radius)
            grads = _allreduce_grads(grads, axis_name)
        if comm_mode == "push_sum":
            base_state, ps = opt_state
            params, ps, cons = fused_push_sum(params, ps, r)
            updates, base_state = _opt_update(optimizer, grads, base_state,
                                              params)
            params = _apply_updates(params, updates)
            hv = _fused_health(loss, grad_sq, updates, groups, cons,
                               None) if want_health else None
            return params, new_aux, (base_state, ps), loss, None, hv
        if comm_mode == "cta":
            params, cons, mix_state = fused_combine(
                params, step, comm_weights, mix_state, r)
        updates, new_opt = _opt_update(optimizer, grads, opt_state, params)
        skipped = None
        if guarded:
            ok = _grouped_all_finite(
                loss, jax.tree_util.tree_flatten(updates)[0], groups)

            # The skip guard is per-rank arithmetic only: the collective
            # combine stays OUTSIDE it (a per-rank-divergent branch must
            # never contain a collective), and a skipping rank keeps
            # params/aux/opt_state, so the combine feeds its last-good
            # params to its neighbors.  An elementwise select over the
            # unconditionally applied update, NOT a lax.cond: a
            # traced-pred cond becomes a select anyway, but its branch
            # boundary would block XLA's mul+add contraction inside
            # apply_updates and cost the healthy path its bit-identity
            # with the unguarded step.  A discarded non-finite branch is
            # safe under select: it is elementwise, and nothing
            # differentiates through it here.
            def pick(new, old):
                return jnp.where(ok, new, old)

            params = jax.tree.map(
                pick, _apply_updates(params, updates), params)
            new_aux = jax.tree.map(pick, new_aux, aux)
            new_opt = jax.tree.map(pick, new_opt, opt_state)
            if comm_mode == "atc":
                params, cons, mix_state = fused_combine(
                    params, step, comm_weights, mix_state, r)
            skipped = jnp.where(ok, jnp.int32(0), jnp.int32(1))
        else:
            # (atc: every bucket's exchange depends on its own leaves'
            # apply alone, so a bucket's permute can launch before the
            # next bucket's update — the overlap engine's dataflow)
            params = _apply_updates(params, updates)
            if comm_mode == "atc":
                params, cons, mix_state = fused_combine(
                    params, step, comm_weights, mix_state, r)
        if mix_on:
            new_opt = (new_opt, mix_state)
        hv = _fused_health(loss, grad_sq, updates, groups, cons,
                           skipped) if want_health else None
        return params, new_aux, new_opt, loss, skipped, hv

    def _fused_health(loss, grad_sq, updates, groups, cons_sq, skipped):
        upd_leaves = jax.tree_util.tree_flatten(updates)[0]
        if skipped is None:
            ok = _grouped_all_finite(loss, upd_leaves, groups)
            skipped = jnp.where(ok, jnp.float32(0), jnp.float32(1))
        return HealthVector(
            loss=jnp.asarray(loss, jnp.float32),
            grad_norm=jnp.sqrt(grad_sq),
            update_norm=jnp.sqrt(_grouped_sq_sum(upd_leaves, groups)),
            skipped=jnp.asarray(skipped, jnp.float32),
            consensus=jnp.sqrt(cons_sq))

    squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
    expand = lambda t: jax.tree.map(lambda x: x[None], t)

    def per_shard(r, params, aux, opt_state, batch, step, comm_weights,
                  *fwd_bwd):
        params, aux, opt_state, loss, skipped, hv = per_rank_step(
            r, squeeze(params), squeeze(aux), squeeze(opt_state),
            squeeze(batch), step, comm_weights, squeeze(fwd_bwd))
        outs = (expand(params), expand(aux), expand(opt_state),
                jnp.reshape(loss, (1,)))
        if guarded:
            outs = outs + (jnp.reshape(skipped, (1,)),)
        if want_health:
            outs = outs + (HealthVector(
                *[jnp.reshape(x, (1,)) for x in hv]),)
        return outs

    p_rank = P(axis_name)
    # MixState layout: dim 0 is ranks; the packed/flat axis (last)
    # shards over every OTHER mesh axis, matching the per-device bucket
    # shards the exchange packs (see init_mix_state / _local_shapes)
    _mix_rest = tuple(a for a in mesh.axis_names if a != axis_name)
    p_mix = MixState(
        ratio=p_rank,
        err=P(axis_name, _mix_rest or None),
        ref=P(axis_name, None, _mix_rest or None),
        mirror=P(axis_name, None, _mix_rest or None))
    if batch_specs is None:
        batch_specs = p_rank
    p_params = param_specs if param_specs is not None else p_rank
    p_opt = opt_state_specs if opt_state_specs is not None else p_rank
    if mix_on:
        # opt_state = (base, MixState), a per-FIELD pytree-prefix spec:
        # the ratio is one scalar per rank, but err/ref/mirror hold one
        # flat EF row per DEVICE — their packed axis shards over every
        # non-rank mesh axis so a tp slice sees exactly its own bucket
        # shards (P(axis_name) alone would hand each device the full
        # per-rank row, 4x the bucket under tp=4)
        p_opt = (p_opt, p_mix)
    # comm weights ride replicated (every rank reads the full tables)
    p_comm = tuple((P(), P()) for _ in specs) if use_traced_w else ()
    out_specs = (p_params, p_rank, p_opt, p_rank)
    if guarded:
        out_specs = out_specs + (p_rank,)
    if want_health:
        out_specs = out_specs + (p_rank,)  # spec prefix over HealthVector

    # A step of several programs keeps the model out of them: forward
    # and backward are a jitted function of their own, traced ONCE, at
    # the top level (``prepare``, before the first program is), whose
    # jaxpr every round's program then calls; a program's own trace is
    # the optimizer and its round's exchange.  (Traced inside a
    # program's trace instead, three traces deep, the model costs twice
    # the seconds on the chip's host; and a model traced again for each
    # program gives the lowering new kernel objects to lower, where the
    # same ones are found lowered: PERF.md §6, PR 25.)  XLA inlines the
    # call: the program is one computation as before.
    fwd_bwd_all, prepare = None, None
    if select is not None:
        def _fwd_bwd_shard(params, aux, batch):
            loss, grads, new_aux = _loss_and_grads(
                loss_fn, has_aux, sp_axis, pp_axis, param_specs,
                squeeze(params), squeeze(aux), squeeze(batch))
            return jnp.reshape(loss, (1,)), expand(grads), expand(new_aux)

        fwd_bwd_all = jax.jit(jax.shard_map(
            _fwd_bwd_shard, mesh=mesh,
            in_specs=(p_params, p_rank, batch_specs),
            out_specs=(p_rank, p_params, p_rank), check_vma=False))

        def prepare(params, aux, opt_state, batch, *rest):
            fwd_bwd_all.trace(params, aux, batch)

    def wrapped(params, aux, opt_state, batch, step, comm_weights, r):
        fwd_bwd = () if fwd_bwd_all is None else \
            fwd_bwd_all(params, aux, batch)
        return jax.shard_map(
            partial(per_shard, r),
            mesh=mesh,
            in_specs=(p_params, p_rank, p_opt, batch_specs, P(), p_comm)
            + ((p_rank, p_params, p_rank) if fwd_bwd else ()),
            out_specs=out_specs,
            check_vma=False,
        )(params, aux, opt_state, batch, step, comm_weights, *fwd_bwd)

    donate_argnums = (0, 1, 2) if donate else ()
    jitted = jax.jit(wrapped, static_argnums=6,
                     donate_argnums=donate_argnums)
    default_w = comm_weight_inputs(specs) if use_traced_w else ()

    obs_labels = dict(
        comm_mode=comm_mode,
        overlap="bucketed" if n_buckets is not None else "none",
        guarded="true" if guarded else "false")
    needs_topo = comm_mode in ("cta", "atc", "push_sum")
    edge_traffic = (list(specs), int(mesh.shape[axis_name]),
                    comm_mode == "push_sum",
                    hierarchical_local_size if neighbor else None) \
        if (specs and needs_topo) else None

    stages = _fusion.epilogue_stages(
        compress=stage_compress, guard=guarded, health=want_health,
        consensus=want_cons, mix=mix_on)

    def _local_shapes(params):
        """Per-DEVICE leaf shapes exactly as the shard_map body sees
        them: the leading rank axis stripped, every other dim divided
        by the mesh axes its param spec shards over.  ``_plan`` buckets
        on these shapes inside the trace, so every MixState buffer must
        be sized by them too — under model parallelism (a
        ``param_specs`` tree naming other mesh axes) the EF state
        follows the SHARDS, one independent accumulator per device."""
        leaves = jax.tree.leaves(params)
        is_p = lambda s: s is None or isinstance(s, P)
        if param_specs is None:
            sp = [P(axis_name)] * len(leaves)
        elif is_p(param_specs):
            sp = [param_specs] * len(leaves)
        else:
            sp = jax.tree.leaves(param_specs, is_leaf=is_p)
        if len(sp) != len(leaves):
            raise ValueError(
                "compressed mixing needs param_specs to be None, one "
                "PartitionSpec, or a tree matching params exactly "
                f"(got {len(sp)} specs for {len(leaves)} leaves)")
        out = []
        for l, s in zip(leaves, sp):
            dims = list(np.shape(l))
            for i, names in enumerate(tuple(s or ())):
                if names is None:
                    continue
                for a in ((names,) if isinstance(names, str)
                          else tuple(names)):
                    dims[i] //= int(mesh.shape[a])
            out.append(jax.ShapeDtypeStruct(
                tuple(dims[1:]),
                getattr(l, "dtype", None) or jnp.asarray(l).dtype))
        return out

    def init_mix_state(params):
        """The MixState for rank-major ``params`` (attach it as
        ``opt_state = (base_opt_state, init_mix_state(params))``).

        ``ref``/``mirror`` start at each rank's OWN packed parameters:
        exact when every rank holds identical parameters at the start
        (the rank_major broadcast init — the normal case), so round
        one's wire already carries small deltas.  Ranks that start from
        DIVERGED states should zero ``ref``/``mirror`` instead (always
        bitwise-consistent, at the cost of sparse early rounds).
        Under a hierarchical exchange the same identical-init
        assumption makes the packed params equal the machine means.

        Built THROUGH a shard_map over the step's own mesh/specs, so
        the buffers are packed per device shard and land sharded as
        ``mix_state_specs`` — bitwise the layout the train step's
        exchange indexes into, whatever the model-parallel layout."""
        R = len(specs)
        G = int(sum(mix_slots))

        def body(p):
            leaves = [l[0] for l in jax.tree.leaves(p)]
            if moe is not None:
                # EF state exists only for leaves that ride the wire
                mask = _moe_shared_mask(p, moe)
                leaves = [l for l, m in zip(leaves, mask) if m]
            errs, refs, mirs = [], [], []
            for b in _plan(leaves).buckets:
                if not jnp.issubdtype(jnp.dtype(b.dtype), jnp.inexact):
                    continue
                flat = _pack_bucket(leaves, list(b.leaves)) \
                    .reshape(-1).astype(jnp.float32)
                errs.append(jnp.zeros((1, flat.size), jnp.float32))
                refs.append(jnp.broadcast_to(
                    flat[None, None, :], (1, R, flat.size)) + 0.0)
                mirs.append(jnp.broadcast_to(
                    flat[None, None, :], (1, G, flat.size)) + 0.0)
            return MixState(
                ratio=jnp.full((1,), jnp.float32(mix.ratio)),
                err=tuple(errs), ref=tuple(refs), mirror=tuple(mirs))

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(p_params,),
            out_specs=p_mix, check_vma=False))(params)

    def mix_wire_layout(params):
        """Per compressible bucket, the host-side wire facts the
        collectives contract audits against: ``(bucket_index, numel,
        k, wire_bytes)`` — ``wire_bytes`` being the single uint8
        payload each ppermute of that bucket moves per DCN pair.
        ``numel`` is the per-DEVICE packed size (model-parallel layouts
        exchange shards, so each tp slice moves its own wire)."""
        rows = []
        shapes = _local_shapes(params)
        if moe is not None:
            mask = _moe_shared_mask(params, moe)
            shapes = [s for s, m in zip(shapes, mask) if m]
        for b in _plan(shapes).buckets:
            if not jnp.issubdtype(jnp.dtype(b.dtype), jnp.inexact):
                continue
            numel = int(sum(
                int(np.prod(s.shape))
                for i, s in enumerate(shapes) if i in b.leaves))
            k = _resolve_k(None, mix.ratio, numel)
            rows.append(dict(bucket=b.index, numel=numel, k=k,
                             wire_bytes=C.mix_wire_bytes(
                                 numel, k, mix.values)))
        return tuple(rows)

    def set_mix_ratio(opt_state, ratio):
        """A new opt_state with every rank's LIVE compression ratio set
        to ``ratio`` — pure data (``k_live`` masking inside the traced
        program), so the swap never recompiles.  The control plane's
        sanctioned step-boundary producer
        (``topology.control.swap_mix_ratio``) feeds this."""
        base, ms = opt_state
        return (base, ms._replace(
            ratio=jnp.full_like(ms.ratio, jnp.float32(float(ratio)))))

    step_fn = _public_step(
        jitted, obs_labels, select=select, has_aux=has_aux,
        tail=() if guarded else (default_w,), edge_traffic=edge_traffic,
        prepare=prepare)
    step_fn.health_config = health
    step_fn.epilogue_stages = stages
    step_fn.hierarchical_local_size = \
        hierarchical_local_size if neighbor else None
    step_fn.mix_config = mix
    step_fn.moe_config = moe
    if mix_on:
        step_fn.init_mix_state = init_mix_state
        step_fn.mix_wire_layout = mix_wire_layout
        step_fn.set_mix_ratio = set_mix_ratio
        # pytree-prefix PartitionSpecs of the MixState (AOT callers
        # turn these into NamedShardings for abstract avals)
        step_fn.mix_state_specs = p_mix
    if guarded:
        step_fn.guard_config = guard
    if guarded or use_traced_w:
        step_fn.default_comm_weights = default_w
    return step_fn


def build_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    axis_name: str = "bf",
    comm_mode: str = "cta",
    topology: Optional[CommSpec] = None,
    schedule: Optional[Sequence[CommSpec]] = None,
    num_steps_per_communication: int = 1,
    hierarchical_local_size: Optional[int] = None,
    hierarchical: Any = None,
    sp_axis: Optional[str] = None,
    pp_axis: Optional[str] = None,
    batch_specs: Any = None,
    param_specs: Any = None,
    opt_state_specs: Any = None,
    donate: bool = True,
    has_aux: bool = False,
    compress: Union[str, MixCompressConfig, None] = None,
    overlap: str = "none",
    overlap_buckets: int = 4,
    guard: Optional[GuardConfig] = None,
    health: Optional[HealthConfig] = None,
    moe: Optional[MoEConfig] = None,
) -> Callable:
    """Compile one decentralized SGD/optax step over ``mesh``.

    loss_fn(params, batch) -> scalar loss, evaluated per rank on its local
    shard (under ``shard_map``; it may use ``sp_axis`` collectives, e.g.
    ring attention).  With ``has_aux=True`` the signature becomes
    ``loss_fn(params, aux, batch) -> (loss, new_aux)`` for mutable model
    state (e.g. batch-norm statistics), and the returned step takes and
    returns the rank-major ``aux`` tree:
    ``train_step(params, aux, opt_state, batch, step)``.

    comm_mode:
      * ``"cta"``  — combine-then-adapt (reference _DistributedReduceOptimizer)
      * ``"atc"``  — adapt-then-combine (reference _DistributedAdaptThenCombine)
      * ``"gradient_allreduce"`` — global gradient averaging (reference
        _DistributedOptimizer)
      * ``"push_sum"`` — bias-corrected directed averaging (reference
        _DistributedPushSumOptimizer, optimizers.py:1026-1177): column-
        stochastic mix of the extended payload [params ‖ ps_weight], then
        de-bias by the mixed weight.  The step's ``opt_state`` must be
        ``(base_opt_state, push_sum_weights(mesh))``.  Only the topology's
        edge structure is used — combine weights are replaced by the
        uniform ``1/(out_degree+1)`` push scales (see
        ``collectives.push_sum_mix``); hierarchical_local_size is not
        supported in this mode.
      * ``"none"`` — no communication (pure local SGD)

    Exactly one of ``topology`` (static) or ``schedule`` (dynamic) for the
    neighbor modes.  A schedule of ``P`` rounds builds ``P`` programs
    behind the one returned step (one ``jax.jit``, the round
    ``step % P`` its static argument, picked on the host at each call):
    ``P`` compiles, once, then none; a round's permutes are top-level
    asynchronous operations and no ``conditional`` holds them.
    ``num_steps_per_communication=k > 1`` adds the off-cycle program (no
    exchange in it) by the same selector.  So with a schedule or ``k > 1``
    ``step`` must be a concrete integer at every call (pass a Python or
    NumPy integer; a device scalar costs a device-to-host read): under
    someone's ``jit`` or ``scan`` it is a tracer and the step raises
    ``TypeError`` — build a one-round step (``topology=schedule[r]``) or
    call the step once a round.  A static topology exchanged every step,
    ``"none"`` and ``"gradient_allreduce"`` are one program and read
    nothing of ``step`` on the host.

    ``compress="int8"`` quantizes the cta/atc combine's wire payload
    (per-tensor absmax int8; see ``collectives.neighbor_allreduce``) —
    4x less ICI/DCN traffic at ~0.4% relative error per exchange.
    ``compress="int8_sr"`` is the same wire format with UNBIASED
    stochastic rounding (per-step, per-rank, per-leaf PRNG folding):
    round-to-nearest's deterministic snaps can accumulate into a
    consensus error floor in iterated averaging at pod rank counts,
    stochastic rounding's zero-mean noise averages out instead — the
    n=128 floor comparison is benchmarks/wire_quant_consensus.py.
    ``compress="bf16"`` rounds the wire payload to bfloat16 (2x less
    traffic for f32 params, self term stays full precision).

    ``compress="topk"`` (or an explicit :class:`MixCompressConfig`)
    is ERROR-FEEDBACK COMPRESSED MIXING — the sparsity rung below
    int8.  Each rank keeps a per-bucket reference copy of its
    last-exchanged state plus an error accumulator; the wire carries
    ``topk(x − ref + e)`` as a packed keep-mask + (int8-quantized)
    kept values (``collectives.mix_compress_exchange`` /
    ``mix_wire_bytes``), the residual folds into ``e``, and receivers
    reconstruct ``ref + delta`` so the mixing recursion stays
    contractive (consensus floor vs ratio: benchmarks/
    wire_quant_consensus.py's ratio sweep).  The step's ``opt_state``
    must then be ``(base_opt_state, train_step.init_mix_state(params))``
    — the ref/error state is ordinary rank-major pytree data, so
    checkpoints, healing rollbacks, and elastic swaps carry it with
    everything else.  k is FIXED at build time from the config ratio
    (static shapes — the zero-recompile contract); the LIVE ratio is
    ``MixState.ratio``, traced data the topology control plane
    tightens online under congestion (``topology.control.
    swap_mix_ratio`` → ``train_step.set_mix_ratio``) with zero
    recompiles.  ``ratio >= 1.0`` builds the ordinary uncompressed
    exchange (bit-identical by construction).  cta/atc only; under a
    hierarchical exchange the sparse wire rides the DCN leg only (the
    ICI machine reduce stays exact, ref/mirror state at machine-mean
    granularity).  Env defaults: ``BLUEFOG_MIX_COMPRESS`` /
    ``BLUEFOG_MIX_COMPRESS_RATIO`` (explicit arguments win).  Does
    not compose with the string wire modes (the int8 stage already
    rides the kept values).

    ``overlap="bucketed"`` (cta/atc only) is the overlap engine: the
    param tree is split into ``overlap_buckets`` size-balanced buckets
    (same trace-time planner as the eager wrappers' tensor fusion,
    ``optim.fusion``) and each bucket issues its OWN neighbor combine —
    for ATC, bucket *i*'s combine launches as soon as its optax update
    is applied, before bucket *i+1*'s update; for CTA, buckets combine
    in tree (= layer) order ahead of the forward that consumes them.
    Every bucket's collective is dataflow-independent of the other
    buckets' arithmetic, which is the program structure XLA's
    latency-hiding scheduler needs to run transfers concurrently with
    compute (the reference gets the same overlap from its background
    MPI thread + fusion buffers, operations.cc:943-1020); the HLO-level
    guarantee (>= K collective-permutes — leaf granularity permitting,
    see ``fusion.size_balanced_threshold`` — each with compute that
    does not depend on it) is regression-checked in
    tests/test_hlo_guarantees.py.
    Numerics match ``overlap="none"`` exactly except under
    ``compress="int8*"``, where the absmax scale becomes per-bucket.
    ``compress=`` and dynamic ``schedule=`` plumb through unchanged.

    ``guard=GuardConfig(...)`` compiles the RESILIENT variant of the
    step (the jitted half of ``bluefog_tpu.resilience``):

    * the optax apply is wrapped in a per-rank ``lax.cond`` on an
      in-graph ``jnp.isfinite`` health check over (loss, updates) — a
      rank whose step is non-finite SKIPS it (params, aux, and
      opt_state all keep their previous finite values) and contributes
      its pre-update params to the neighbor combine, so one poisoned
      rank never contaminates its neighbors; the returned per-rank
      ``skipped`` flags are the skip counter's per-step increments;
    * the cta/atc combine weights become a TRACED INPUT (the
      ``comm_weights`` pytree from :func:`comm_weight_inputs`, default
      exposed as ``train_step.default_comm_weights``): topology healing
      after a rank death swaps in new weight DATA over the same edge
      structure — shapes never change, nothing recompiles.

    With no faults present the guarded step's (params, opt_state,
    loss) are bit-identical to the unguarded step's.  Not supported
    with ``comm_mode='push_sum'`` (the (x, w) pair must mix as a unit).
    Under a hierarchical exchange the guard composes at MACHINE
    granularity: ``comm_weights`` are the machine-level tables and
    ``resilience.healing.healed_hierarchical_comm_weights`` collapses a
    rank-level dead mask to the machine failure domain.

    **Hierarchical exchange** — ``hierarchical=PodSpec(...)`` (or a
    plain int local size; equivalently ``hierarchical_local_size=``, or
    the ``BLUEFOG_HIER_LOCAL_SIZE`` env default) decomposes the cta/atc
    combine into ``W_dcn ⊗ exact-local-mean``: ONE exact intra-machine
    allreduce over the ICI submesh (``collectives.machine_groups``),
    then decentralized weighted mixing of the machine means over the
    (smaller) inter-machine schedule — ``topology=``/``schedule=`` are
    then MACHINE-level specs of size ``n_ranks / local_size`` (the
    hierarchical compiler emits them: ``topology.compiler.
    compile_topology(..., hierarchical=...)``).  ``compress=`` applies
    to the DCN leg only (the ICI reduce stays full precision), and the
    combine weights ride as traced MACHINE-level tables, so healing and
    elastic membership swap the inter-machine matrix as pure data —
    zero recompiles.  With ``local_size == 1`` the step is bitwise the
    flat exchange.

    ``health=HealthConfig(...)`` additionally emits a rank-major
    :class:`HealthVector` as the step's LAST output — loss, local grad
    norm, update norm, skip flag, and the consensus distance
    ``‖x_i − Σ_j w_ij x_j‖`` computed from tensors the neighbor
    exchange already materializes (both the plain and
    ``overlap="bucketed"`` paths).  The vector is fixed-shape — faults
    are inputs, nothing recompiles across fault patterns (same
    discipline as ``guard=``) — and ``health=None`` (default) leaves
    the step bit-identical to a pre-feature build.  Composes with
    ``guard=`` (``skipped`` then carries the guard's actual flags).

    **Per-bucket epilogue pipeline**: every feature above is emitted
    as a per-bucket stage of ONE composed pass per fusion-plan bucket —
    quantize → exchange → dequantize → guard-select → health-norm —
    instead of separate full-tree walks around the exchange (see the
    module docstring).  All comm modes ride it, including ``push_sum``
    (whose exchange also accepts ``overlap="bucketed"``); the cta/atc
    combine weights are traced operands in BOTH the guarded and
    unguarded builds, so the two share one association order (guarded
    == unguarded bitwise on every topology, including uniform-weight
    static CTA) and healing swaps weight data without recompiling
    either.

    Returns ``train_step(params, opt_state, batch, step) ->
    (params, opt_state, loss)`` — all rank-major, jit-compiled with
    params/opt_state donated.  Under ``guard=`` the signature is
    ``train_step(params, opt_state, batch, step, comm_weights) ->
    (params, opt_state, loss, skipped)`` with ``skipped`` a rank-major
    ``[n]`` int32 vector of this step's skip flags (``comm_weights`` is
    ``()`` for comm modes without neighbor weights).  Under ``health=``
    every variant appends the ``HealthVector`` of ``[n]`` f32 fields.
    """
    if comm_mode not in ("cta", "atc", "gradient_allreduce", "push_sum",
                         "none"):
        raise ValueError(f"unknown comm_mode {comm_mode!r}")
    needs_topo = comm_mode in ("cta", "atc", "push_sum")
    if needs_topo and (topology is None) == (schedule is None):
        raise ValueError(
            "neighbor modes need exactly one of topology= or schedule=")
    if hierarchical is not None:
        # a PodSpec (duck-typed: machines/chips_per_machine) or a plain
        # int local size — either way it resolves to the ICI group width
        hier_l = int(getattr(hierarchical, "chips_per_machine",
                             hierarchical))
        if (hierarchical_local_size is not None
                and int(hierarchical_local_size) != hier_l):
            raise ValueError(
                f"hierarchical={hierarchical!r} (local size {hier_l}) "
                f"conflicts with hierarchical_local_size="
                f"{hierarchical_local_size!r}")
        hierarchical_local_size = hier_l
    if hierarchical_local_size is None and comm_mode in ("cta", "atc"):
        hierarchical_local_size = _config.hier_local_size()
    if comm_mode == "push_sum" and hierarchical_local_size is not None:
        raise ValueError(
            "hierarchical_local_size is not supported with "
            "comm_mode='push_sum' (flat rank-level push-sum only)")
    if hierarchical_local_size is not None and comm_mode in ("cta", "atc"):
        n_ranks = int(mesh.shape[axis_name])
        hier_specs = ([topology] if topology is not None
                      else list(schedule or []))
        C.validate_machine_decomposition(
            n_ranks, hierarchical_local_size, hier_specs)
        machines = getattr(hierarchical, "machines", None)
        if machines is not None and \
                int(machines) * int(hierarchical_local_size) != n_ranks:
            raise ValueError(
                f"hierarchical pod of {machines} machines x "
                f"{hierarchical_local_size} chips does not cover the "
                f"{n_ranks}-rank mesh axis {axis_name!r}")
    if pp_axis is not None and param_specs is None:
        raise ValueError(
            "pp_axis requires param_specs: the spec tree is what tells "
            "pipeline-sharded leaves (layer stacks, NOT reduced over pp) "
            "apart from pp-replicated ones (embeddings/head, psum'd)")
    if compress is None and comm_mode in ("cta", "atc"):
        # BLUEFOG_MIX_COMPRESS supplies the default wire mode when the
        # builder did not choose one (explicit arguments always win)
        compress = _config.mix_compress()
    mix = None
    if isinstance(compress, MixCompressConfig):
        mix, compress = compress, None
    elif compress == "topk":
        env_ratio = _config.mix_compress_ratio()
        mix = (MixCompressConfig() if env_ratio is None
               else MixCompressConfig(ratio=env_ratio))
        compress = None
    if mix is not None:
        if comm_mode not in ("cta", "atc"):
            raise ValueError(
                "compress='topk' (error-feedback compressed mixing) "
                "rides the cta/atc combine only "
                f"(got comm_mode={comm_mode!r})")
        if mix.values not in ("int8", "int8_sr", "none"):
            raise ValueError(
                f"unknown MixCompressConfig values mode {mix.values!r}")
        if not mix.ratio > 0:
            raise ValueError(
                f"MixCompressConfig.ratio must be > 0, got {mix.ratio}")
        if mix.ratio >= 1.0:
            # keep-everything: build the ordinary uncompressed exchange
            # so ratio=1.0 is bit-identical to compress=None by
            # construction (no wire round-trip to be identical THROUGH)
            mix = None
    if compress is not None:
        if compress not in ("int8", "int8_sr", "bf16"):
            raise ValueError(f"unknown compress mode {compress!r}")
        if comm_mode not in ("cta", "atc"):
            raise ValueError(
                "compress= is only honored by the cta/atc combine "
                f"(got comm_mode={comm_mode!r})")
    if overlap not in ("none", "bucketed"):
        raise ValueError(f"unknown overlap mode {overlap!r}")
    if guard is not None:
        if comm_mode == "push_sum":
            raise ValueError(
                "guard= does not compose with comm_mode='push_sum': the "
                "(params, ps_weight) pair must mix as a unit, and a "
                "per-rank skip would break the column-stochastic "
                "sum(ps) == n invariant")
    if moe is not None and comm_mode not in ("cta", "atc"):
        raise ValueError(
            "moe= (expert-sharded MoE) partitions the NEIGHBOR combine "
            "into shared/expert leaves, so it needs comm_mode='cta' or "
            f"'atc' (got {comm_mode!r}); gradient_allreduce would "
            "average expert gradients across ranks hosting DIFFERENT "
            "experts, and push_sum's (x, w) pair cannot be split")
    if overlap == "bucketed":
        if comm_mode not in ("cta", "atc", "push_sum"):
            raise ValueError(
                "overlap='bucketed' buckets the cta/atc/push_sum "
                f"neighbor exchange only (got comm_mode={comm_mode!r}); "
                "gradient_allreduce relies on XLA's all-reduce combiner")
        if overlap_buckets < 1:
            raise ValueError(
                f"overlap_buckets must be >= 1, got {overlap_buckets}")
    bucketed = overlap == "bucketed"

    specs = list(schedule) if schedule is not None else (
        [topology] if topology is not None else [])
    return _build_step(
        loss_fn, optimizer, mesh, axis_name=axis_name,
        comm_mode=comm_mode, specs=specs,
        k_comm=int(num_steps_per_communication),
        hierarchical_local_size=hierarchical_local_size,
        sp_axis=sp_axis, pp_axis=pp_axis, batch_specs=batch_specs,
        param_specs=param_specs, opt_state_specs=opt_state_specs,
        donate=donate, has_aux=has_aux, compress=compress,
        n_buckets=overlap_buckets if bucketed else None,
        guard=guard, health=health, mix=mix, moe=moe)
