"""The plain training loop that a family's reference loss is driven
by: gradients by ``jax.value_and_grad``, the optimizer written out in
``optimizers/<name>.py`` (``rule``) and the exchange as a weighted sum
over ranks by the matrices of ``exchanges/<name>.py`` (``exchange``),
both found by the names a traffic file gives.  Nothing here is imported
from the program.  One rank a device; only per-leaf norms and per-rank
losses leave the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

try:
    from jax import shard_map
except ImportError:  # pragma: no cover
    from jax.experimental.shard_map import shard_map


def leaf_norms(tree):
    """Per-leaf L2 norms over everything but the leading rank axis."""
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                   axis=tuple(range(1, x.ndim)))), tree)


def rounds_of(exchange, n: int):
    """The exchange's mixing matrices, one a round; one rank mixes with
    nobody."""
    return list(exchange.matrices(n)) or [np.eye(n)]


def build(loss_fn, make_state, opt: dict, rule, exchange, n: int, devices):
    """The reference's three programs over ``n`` ranks, one a device:
    ``init(key) -> (p, aux, m, v)`` rank-major float32 state from the
    seed's key (an argument, not a constant: a new seed must not make a
    new program); ``step(p, aux, m, v, batch, t, w) -> (p, aux, m, v, losses,
    grad norms)``, one adapt-then-combine step with ``t`` the 1-based
    step and ``w`` the round's ``[n, n]`` mixing matrix sharded by rows
    (``exchange.MIXES`` says what it mixes: the updated ``parameters``,
    or the ``gradients`` before the update); ``update_norms(p, key)``,
    per-leaf norms of ``p - seeded p``.  Also returns the rounds'
    matrices and the rank sharding."""
    mesh = Mesh(np.array(devices[:n]), ("r",))
    rank = NamedSharding(mesh, P("r"))
    rounds = rounds_of(exchange, n)
    if exchange.MIXES not in ("parameters", "gradients"):
        raise ValueError(f"an exchange mixes parameters or gradients, not "
                         f"{exchange.MIXES!r}")
    on_gradients = exchange.MIXES == "gradients"
    # the offsets r - s that any round gives a weight (static: they
    # decide the ppermutes)
    offsets = sorted({(r - s) % n for w in rounds for r in range(n)
                      for s in range(n) if w[r, s] != 0} - {0})

    def stack(tree):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)

    @functools.partial(jax.jit, out_shardings=rank)
    def init(key):
        p, aux = make_state(key)
        p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        zeros = jax.tree.map(jnp.zeros_like, p)
        return stack(p), stack(aux), stack(zeros), stack(zeros)

    def local(p, aux, m, v, b, t, w_row):
        """One rank's step (leading axis of 1 under shard_map)."""
        sq = functools.partial(jax.tree.map, lambda x: x[0])
        p, aux, m, v, b = sq(p), sq(aux), sq(m), sq(v), sq(b)
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, aux, b)
        me = jax.lax.axis_index("r")

        def mix(x):
            # sum over offsets k of W[r, r - k] * x[r - k]
            out = w_row[0, me] * x
            for k in offsets:
                moved = jax.lax.ppermute(
                    x, "r", [(j, (j + k) % n) for j in range(n)])
                out = out + w_row[0, (me - k) % n] * moved
            return out

        if on_gradients:
            g = jax.tree.map(mix, g)
        flat_p, treedef = jax.tree.flatten(p)
        new_p, new_m, new_v = [], [], []
        for pi, gi, mi, vi in zip(flat_p, jax.tree.leaves(g),
                                  jax.tree.leaves(m), jax.tree.leaves(v)):
            mi, vi = rule.moments(opt, gi, mi, vi)
            pi = rule.apply(opt, pi, mi, vi, t, sqrt=jnp.sqrt)
            new_p.append(pi if on_gradients else mix(pi))
            new_m.append(mi)
            new_v.append(vi)

        def un(leaves):
            return jax.tree.map(lambda x: x[None],
                                jax.tree.unflatten(treedef, leaves))

        # the gradient as the optimizer gets it
        gn = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x))[None], g)
        return (un(new_p), jax.tree.map(lambda x: x[None], aux),
                un(new_m), un(new_v), loss[None], gn)

    spec = P("r")
    step = jax.jit(
        shard_map(local, mesh=mesh,
                  in_specs=(spec, spec, spec, spec, spec, P(), spec),
                  out_specs=spec, check_vma=False),
        donate_argnums=(0, 1, 2, 3))

    @jax.jit
    def update_norms(p, key):
        p0, _ = make_state(key)
        return leaf_norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32)[None], p, p0))

    return init, step, update_norms, rounds, rank


def follow(loss_fn, make_state, key, batch, opt: dict, rule, exchange,
           n_steps: int, devices):
    """Drive ``n_steps`` steps of adapt-then-combine from the seeded
    state.  ``loss_fn(params, aux, rank_batch) -> (loss, aux)``;
    ``make_state(key) -> (params, aux)`` (traceable; ``aux`` may be
    None);
    ``batch`` rank-major ``[ranks, ...]``.

    Returns numpy: ``losses [n_steps, ranks]``, ``grad_norms`` (per-leaf
    ``[ranks]`` norms of the first gradient) and ``update_norms``
    (per-leaf ``[ranks]`` norms of ``params after n_steps - params
    before``), the last two as flat lists in ``jax.tree.leaves`` order
    of the parameter tree."""
    n = jax.tree.leaves(batch)[0].shape[0]
    init, step, update_norms, rounds, rank = build(
        loss_fn, make_state, opt, rule, exchange, n, devices)
    p, aux, m, v = init(key)
    losses, grad_norms = [], None
    for i in range(n_steps):
        w = jax.device_put(
            jnp.asarray(rounds[i % len(rounds)], jnp.float32), rank)
        p, aux, m, v, loss, gn = step(p, aux, m, v, batch,
                                      jnp.float32(i + 1), w)
        losses.append(np.asarray(loss))
        if i == 0:
            grad_norms = [np.asarray(x) for x in jax.tree.leaves(gn)]
    upd = [np.asarray(x) for x in jax.tree.leaves(update_norms(p, key))]
    del p, aux, m, v
    return {"losses": np.stack(losses), "grad_norms": grad_norms,
            "update_norms": upd}


def worst_leaf_gap(got, want) -> float:
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero).
    ``got`` and ``want``: lists of ``[ranks]`` arrays."""
    want = [np.asarray(w, np.float64) for w in want]
    flat = np.concatenate([w.ravel() for w in want])
    # the median over the leaves that have a gradient at all: where a
    # block starts as the identity most leaves' first gradient is 0
    floor = float(np.median(flat[flat > 0])) if np.any(flat > 0) else 0.0
    worst = 0.0
    for g, w in zip(got, want):
        num = np.abs(np.asarray(g, np.float64) - w)
        den = np.maximum(w, floor)
        gap = np.where(num == 0, 0.0, num / np.where(den > 0, den, np.nan))
        if np.any(np.isnan(gap)):
            return float("inf")
        worst = max(worst, float(gap.max()))
    return worst


def mix_gap(opt: dict, rule, w, before, after, m, v, t: int) -> float:
    """How far, one element by one, the parameters after step ``t`` lie
    from the exchange's mix of every rank's own update: each rank's
    update is worked out again in float64 from the parameters before
    the step and that rank's OWN moments after it, and the ranks are
    mixed by ``w[dst, src]``.  Lists of ``[ranks, k]`` arrays, a leaf
    each; returns the largest absolute gap.  A wrong peer, weight or
    order of update and mix shows here at the size of an update; a
    sound step at the size of a float32 rounding."""
    w = np.asarray(w, np.float64)
    worst = 0.0
    for b, a, mi, vi in zip(before, after, m, v):
        f64 = [np.asarray(x, np.float64) for x in (b, mi, vi)]
        want = w @ rule.apply(opt, *f64, t)
        worst = max(worst, float(np.max(np.abs(np.asarray(a, np.float64)
                                               - want))))
    return worst
