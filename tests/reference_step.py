"""A plain decentralized train step, written from the definitions, for
the tests to hold ``build_train_step`` to.

It shares no code with ``bluefog_tpu.optim`` or
``bluefog_tpu.parallel``: ranks are a Python loop, each rank's loss,
gradient and optax update are taken on that rank's own slice, and a
round of mixing is ONE dense product with the round's matrix in float64
numpy, read off the spec's declared edges (not off its shift classes,
which are the product's own decomposition).  Order of operations per
mode, as ``optim/functional.py``'s docstring states it:

* ``cta``: mix, then adapt with gradients taken at the UNMIXED
  parameters;
* ``atc``: adapt, then mix;
* ``push_sum``: re-bias ``x = z * w``, mix the pair ``(x, w)`` with the
  column-stochastic matrix of the edge structure, de-bias ``z = x / w``,
  then adapt at the de-biased parameters with gradients taken before
  the mix;
* ``gradient_allreduce``: average the gradients, adapt;
* ``none``: adapt.

``every=k`` skips the mix on steps that are no multiple of ``k``; a
schedule of ``P`` rounds mixes by round ``step % P``.

State is a list of ``n`` per-rank pytrees (``unstack`` / ``stack`` move
between that and the product's rank-major arrays), so a test can hand
the reference the program's own state before a step and compare what
both make of it.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax


# ------------------------------------------------------------------ #
# mixing matrices, receiver-major: x_new = M @ x
# ------------------------------------------------------------------ #
def mixing_matrix(spec) -> np.ndarray:
    """``M[dst, src]``: the weight ``dst`` applies to ``src``'s value,
    the diagonal the self weights.  A static topology declares
    ``weights[src, dst]``; a dynamic round declares its edges one by
    one."""
    if hasattr(spec, "weights"):
        return np.array(spec.weights, np.float64).T
    M = np.diag(np.asarray(spec.self_weight_values, np.float64))
    for (src, dst), w in zip(spec.edges, spec.edge_weight_values):
        M[dst, src] += w
    return M


def hierarchical_matrix(machine_spec, local_size: int) -> np.ndarray:
    """Two-level round over machines of ``local_size`` consecutive
    ranks: the exact mean inside every machine, then the machine-level
    mix: ``M_machine (x) J_L / L``."""
    L = int(local_size)
    return np.kron(mixing_matrix(machine_spec), np.full((L, L), 1.0 / L))


def push_sum_matrix(spec) -> np.ndarray:
    """Column-stochastic push matrix of the spec's edge STRUCTURE: rank
    ``j`` keeps and sends to each out-neighbor ``1 / (out_degree + 1)``
    of its payload; an edge of weight 0 carries nothing."""
    W = mixing_matrix(spec)
    out = (W != 0.0) & ~np.eye(len(W), dtype=bool)     # out[dst, src]
    a = 1.0 / (out.sum(axis=0) + 1.0)
    return (out + np.eye(len(W))) * a[None, :]


# ------------------------------------------------------------------ #
# rank-major arrays <-> one pytree a rank
# ------------------------------------------------------------------ #
def unstack(tree, n: int) -> list:
    return [jax.tree.map(lambda x: np.asarray(x)[r], tree)
            for r in range(n)]


def stack(trees: list):
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *trees)


def _mix(M: np.ndarray, trees: list) -> list:
    """One dense product a leaf: every rank's leaf flattened to a row."""
    leaves = [jax.tree.flatten(t) for t in trees]
    treedef = leaves[0][1]
    mixed = []
    for col in zip(*(l for l, _ in leaves)):
        X = np.stack([np.asarray(x, np.float64).reshape(-1) for x in col])
        mixed.append((M @ X).reshape((len(col),) + np.shape(col[0])))
    return [jax.tree.unflatten(treedef, [m[r] for m in mixed])
            for r in range(len(trees))]


def _norm(tree) -> float:
    return float(np.sqrt(sum(np.sum(np.square(np.asarray(x, np.float64)))
                             for x in jax.tree.leaves(tree))))


class Health(NamedTuple):
    """What ``HealthVector`` reports, one value a rank, from the same
    arrays: loss, norm of the LOCAL gradient, norm of the optax update,
    the skip flag (0 on finite data), and the consensus distance
    ``||x_i - sum_j w_ij x_j||`` (0 where no mix ran)."""

    loss: np.ndarray
    grad_norm: np.ndarray
    update_norm: np.ndarray
    skipped: np.ndarray
    consensus: np.ndarray


class ReferenceStep:
    """``step(params, opt_state, batch, step, ps=None)`` over lists of
    per-rank pytrees -> ``(params, opt_state, losses, ps, health,
    premix)``; ``premix`` is what went on the wire (per rank; ``None``
    where no mix ran), for a bound on a quantized wire."""

    def __init__(self, loss_fn, optimizer, n: int, comm_mode: str, *,
                 specs=(), local_size=None, every: int = 1):
        self.n, self.comm_mode, self.every = n, comm_mode, int(every)
        self.optimizer = optimizer
        self._grad = jax.jit(jax.value_and_grad(loss_fn))
        self._update = jax.jit(optimizer.update)
        if comm_mode == "push_sum":
            self.matrices = [push_sum_matrix(s) for s in specs]
        elif local_size is not None:
            self.matrices = [hierarchical_matrix(s, local_size)
                             for s in specs]
        else:
            self.matrices = [mixing_matrix(s) for s in specs]

    def init(self, base):
        return ([base] * self.n, [self.optimizer.init(base)] * self.n,
                np.ones(self.n))

    def matrix(self, step: int):
        """The matrix this step mixes by, ``None`` off cycle."""
        if (self.comm_mode not in ("cta", "atc", "push_sum")
                or step % self.every):
            return None
        return self.matrices[step % len(self.matrices)]

    def _adapt(self, grads, opt_state, at):
        out = [self._update(g, o, p) for g, o, p in zip(grads, opt_state, at)]
        updates = [u for u, _ in out]
        return ([optax.apply_updates(p, u) for p, u in zip(at, updates)],
                [o for _, o in out], updates)

    def __call__(self, params, opt_state, batch, step: int, ps=None):
        n, M = self.n, self.matrix(int(step))
        vg = [self._grad(params[r], jnp.asarray(batch[r])) for r in range(n)]
        losses = np.array([float(l) for l, _ in vg])
        grads = [g for _, g in vg]
        grad_norm = np.array([_norm(g) for g in grads])
        # premix: what is mixed (and goes on the wire); post: what the
        # mix made of it
        premix = post = None
        at = params
        if self.comm_mode == "gradient_allreduce":
            grads = _mix(np.full((n, n), 1.0 / n), grads)
        if M is not None and self.comm_mode == "push_sum":
            premix = params
            biased = [jax.tree.map(lambda z, w=ps[r]: z * w, params[r])
                      for r in range(n)]
            ps = M @ ps
            at = post = [jax.tree.map(lambda x, w=ps[r]: x / w, t)
                         for r, t in enumerate(_mix(M, biased))]
        elif M is not None and self.comm_mode == "cta":
            premix, at = params, _mix(M, params)
            post = at
        new, opt_state, updates = self._adapt(grads, opt_state, at)
        if M is not None and self.comm_mode == "atc":
            premix, new = new, _mix(M, new)
            post = new
        consensus = np.zeros(n)
        if premix is not None:
            consensus = np.array([
                _norm(jax.tree.map(np.subtract, premix[r], post[r]))
                for r in range(n)])
        health = Health(losses, grad_norm,
                        np.array([_norm(u) for u in updates]),
                        np.zeros(n), consensus)
        return new, opt_state, losses, ps, health, premix


def wire_error_bound(M: np.ndarray, premix: list, groups, compress: str,
                     local_size=None) -> list:
    """Per rank and leaf, the most a quantized wire can move one mixed
    element away from the full-precision mix.

    The wire carries every sender's value rounded; the self term stays
    exact (under a two-level exchange what is sent is the machine's
    mean, and a machine's own mean stays exact).  A scale is shared by
    one group of leaves (``groups``: one leaf on the plain path, one
    bucket under ``overlap="bucketed"``), so an element received from
    ``j`` is off by at most a step ``q_j`` of that group on ``j``:

    * ``int8``: round to nearest on a grid of ``absmax_j / 127``:
      ``q_j = absmax_j / 254`` (half a quantum);
    * ``int8_sr``: floor of ``y + u``, ``u`` in ``[0, 1)``: a whole
      quantum, ``absmax_j / 127``;
    * ``bf16``: 8 significant bits, round to nearest: ``2**-8`` of the
      element, at most ``2**-8 * absmax_j``.

    Receiver ``i`` sums ``w_ij`` times what it receives, so its element
    is off by at most ``sum_{j != i} w_ij q_j``.  The quantizer works in
    float32: the cast and the division leave ``y = x / scale`` (at most
    127) off by ``127 * 2**-23 < 2**-16`` of a quantum before it is
    rounded, and the product back adds ``2**-24`` of the value: under
    ``2**-14`` of the half quantum in all, which the factor
    ``1 + 2**-13`` covers."""
    n = len(premix)
    L = 1 if local_size is None else int(local_size)
    m = n // L
    step = {"int8": 1 / 254.0, "int8_sr": 1 / 127.0, "bf16": 2.0 ** -8}
    # a unit is what sends: a rank, or a machine of L consecutive ranks
    # (its L equal columns of M folded into the machine's weight)
    W = M[::L].reshape(m, m, L).sum(-1)
    np.fill_diagonal(W, 0.0)
    leaves = [jax.tree.leaves(t) for t in premix]
    sent = [[np.mean([leaves[u * L + k][i] for k in range(L)], axis=0)
             for i in range(len(leaves[0]))] for u in range(m)]
    bounds = [[None] * len(leaves[0]) for _ in range(n)]
    for g in groups:
        q = np.array([step[compress] * max(float(np.max(np.abs(sent[u][i])))
                                           for i in g) for u in range(m)])
        for r in range(n):
            for i in g:
                bounds[r][i] = float(W[r // L] @ q) * (1 + 2.0 ** -13)
    return bounds
