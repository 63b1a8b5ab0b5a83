"""An expert layer with a shared expert that is TOLD WHICH EXPERTS IT
HOLDS, for every model under ``models/`` that has one (``models/afmoe.py``
and ``models/mla_moe.py``; imported lazily with them).

It routes every token over all ``n_experts`` in float32 and computes the
shared expert and the part of the routed sum that the experts it holds
give (``experts_held = (first, count)``), dropless: a loop over the
(token, expert) assignments the share holds, taken expert by expert in
tiles of ``EXPERT_TILE`` rows, one turn a tile (``held_experts``).  A
held expert costs what the rows that chose it cost: one that more rows
chose than a tile holds takes as many tiles as it needs, one that no row
chose takes none and is never read, padding makes no assignment; where
the whole call fits one tile (a decode step's slots) a hit expert's tile
is the call.  A taller call (a prefill chunk) orders its assignments by
expert once, and a turn then reads a tile of that order and its expert's
three matrices and writes a tile of results, nothing of the call's
height: a turn's operations run one after another on a TPU, and each
that is not a product with the expert's matrices adds to every turn
(``_experts_hit``).  The path follows from the call's shape alone.  What the
absent experts would add is left out; nothing stands in for their chips
or their traffic.  The sum of every share's routed part, plus the shared
expert once, is the whole layer (``tests/test_afmoe.py``,
``tests/test_mla_moe.py``).

The model's config says how a token's scores come from the router's
logits, the one thing the published models differ by (``score_func``)::

    "sigmoid": s = sigmoid(m Wr);  T = top_k(s + b)   a learned bias that
                                                      selects only
    "softmax": s = softmax(m Wr);  T = top_k(s)       no bias at all
    either:    w_e = route_scale * s_e / (sum_T s + 1e-20)

and, where it has ``n_group`` > 1, that ``T`` is chosen among the experts
of the ``topk_group`` best groups only (``route``).

In the serving layout the layer keeps two ``stat_*`` cache leaves (see
``serving/protocol.py``) for the ``bf_moe_*`` counters of
``serving/metrics.py``.

The config (any frozen dataclass) gives: ``n_experts``, ``top_k``,
``route_scale``, ``score_func``, ``held``, ``expert_hidden_dim``,
``dim``, ``initializer_range``, ``dtype``, ``decode``; and may give
``n_group`` and ``topk_group`` (absent: 1, no groups).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ExpertLayer", "SwiGLU", "route", "held_experts", "experts_cost",
           "EXPERT_TILE", "SCORE_FUNCS"]

SCOPE_MOE_ROUTER = "bf.moe.router"
SCOPE_MOE_SHARED = "bf.moe.shared"
SCOPE_MOE_EXPERTS = "bf.moe.experts"
SCORE_FUNCS = ("sigmoid", "softmax")
# rows of one turn of the expert loop (``_experts_hit``)
EXPERT_TILE = 128
# rows of a float32 tile of a TPU's memory (8 sublanes of 128 lanes);
# divides EXPERT_TILE
_ALIGN = 8


def _dense(cfg, feats: int, name: str):
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name,
                    kernel_init=nn.initializers.normal(
                        cfg.initializer_range))


class SwiGLU(nn.Module):
    cfg: Any
    hidden: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.hidden, "w1")(x)
        up = _dense(cfg, self.hidden, "w3")(x)
        return _dense(cfg, cfg.dim, "w2")(nn.silu(gate) * up)


def route(scores, bias, top_k: int, route_scale: float, n_group: int = 1,
          topk_group: int = 1):
    """``(chosen [N, top_k], weights [N, top_k])`` from the scores ``[N,
    E]``: the bias (``None``: the model has none) enters the choice and
    not the weights, which are the chosen scores over their sum, times
    ``route_scale``.  With ``n_group`` > 1 the outputs are that many
    groups of neighbours, a group scores the sum of its two largest
    biased scores, and the choice is among the ``topk_group`` best
    groups' experts alone."""
    biased = scores if bias is None else scores + bias
    if n_group > 1:
        rows, outputs = biased.shape
        grouped = biased.reshape(rows, n_group, outputs // n_group)
        _, kept = lax.top_k(lax.top_k(grouped, 2)[0].sum(-1), topk_group)
        keep = jax.nn.one_hot(kept, n_group, dtype=bool).any(1)
        biased = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
            rows, outputs)
    _, chosen = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * route_scale


def _tiles(combine):
    """How the expert loop cuts a call's held assignments (the non-zero
    entries of ``combine [N, held]``): ``(rows, chose, count, tiles)``,
    the rows of one turn (``EXPERT_TILE``, or the whole call where it
    fits one tile), the assignments, how many rows chose each held
    expert, and how many turns each takes: ``ceil(count / rows)``, none
    for an expert no row chose."""
    rows = min(combine.shape[0], EXPERT_TILE)
    chose = combine != 0
    count = chose.sum(0, dtype=jnp.int32)
    return rows, chose, count, -(-count // rows)


def _packed(combine, cut, top_k: int):
    """The call's held assignments in the order the tiled loop takes them,
    by expert and then by row, an expert's run next to the one before
    (no run is padded to a tile), and where their results land:
    ``(reads [held], writes [held], row_of [A + rows], share_of [A +
    rows], spot [k, N], held_at [k, N], room)`` with ``k = min(top_k,
    held)`` and ``A = N * k``, a static bound on the assignments (a row
    has at most ``top_k`` non-zero weights).

    The assignment at packed position ``p`` is row ``row_of[p]``'s with
    weight ``share_of[p]`` (zeros past the last assignment, and a tile's
    worth of them for the last turn's tail); turn ``i``, one of expert
    ``e``'s, takes the ``rows`` positions from ``reads[e] + i * rows``.
    Its results land at ``_ALIGN * (writes[e] + i * (rows // _ALIGN))``
    of ``room`` rows: the runs again, each started on a multiple of
    ``_ALIGN`` rows, because a product whose result starts on a whole
    tile of the chip's memory is written in place, and is copied once
    more where it does not.  Row ``n``'s ``j``-th held assignment lands
    at ``spot[j, n]`` where ``held_at[j, n]``.  ``cut`` is
    ``_tiles(combine)``."""
    rows, chose, count, tiles = cut
    n, held = combine.shape
    k = min(top_k, held)
    aligned = -(-count // _ALIGN) * _ALIGN
    start = jnp.cumsum(count, dtype=jnp.int32) - count
    lands = jnp.cumsum(aligned, dtype=jnp.int32) - aligned
    # a row's place among its expert's rows, and an assignment's among
    # its row's: running counts down a column and along a row
    place = jnp.cumsum(chose, axis=0, dtype=jnp.int32) - 1
    nth = jnp.cumsum(chose, axis=1, dtype=jnp.int32) - 1
    mine = chose & (nth == jnp.arange(k)[:, None, None])       # [k, N, held]
    of_row = lambda x: jnp.where(mine, x, 0).sum(2, dtype=x.dtype)   # [k, N]
    held_at = mine.any(2)
    # by expert, then by row: one sort of the rows' lists, a slot that
    # holds no assignment last
    key = jnp.where(held_at, of_row(jnp.arange(held, dtype=jnp.int32)) * n
                    + jnp.arange(n, dtype=jnp.int32), held * n)
    key, share_of = lax.sort((key.reshape(-1), of_row(combine).reshape(-1)),
                             num_keys=1)
    row_of = jnp.where(key < held * n, key % n, 0)
    # the turns before an expert's first took this many positions
    before = (jnp.cumsum(tiles, dtype=jnp.int32) - tiles) * rows
    room = -(-n * k // _ALIGN) * _ALIGN + _ALIGN * min(held, n * k) + rows
    return start - before, (lands - before) // _ALIGN, \
        jnp.pad(row_of, (0, rows)), jnp.pad(share_of, (0, rows)), \
        of_row(lands + place), held_at, room


def _experts_hit(m, combine, w1, w3, w2, top_k: int):
    """``sum_e combine[:, e] * expert_e(m)`` over the assignments this
    share holds, taken expert by expert in tiles of rows (``_tiles``),
    one loop turn a tile: an expert costs what the rows that chose it
    cost, one that more rows chose than a tile holds takes as many tiles
    as it needs, and one no row chose is never read.

    Where the whole call fits one tile (a decode step's slots) a hit
    expert's tile IS the call: the turn takes ``m`` as it stands and adds
    onto a ``[N, d]`` sum, which is small there.

    A taller call (a prefill chunk) is TILED, and a turn moves its tile
    and its expert, nothing of the call's height: the rows are gathered
    once before the loop into the order the turns take them
    (``_packed``), a turn slices its ``rows`` rows and weights out of
    that order, and its last product writes its ``[rows, d]`` result
    into a float32 buffer of landing places.  Turns run in ascending
    order, so what a part-full tile computes past its expert's run (the
    next expert's rows, under the wrong expert) is overwritten by the
    next expert's first tile, or lies past the last assignment and is
    never read: no mask, no read-modify-write.  After the loop a row sums
    its own ``k`` landing places.

    Why (TPU v5e, PR 39, the newest cell's chunk: N 512, d 2560, experts
    of 768): a turn's operations run one after another, so whatever is
    not one of the three products with the expert's matrices (23 us) adds
    to it in full.  Until PR 39 a turn gathered its rows with a one-hot
    ``[rows, N]`` product (1.9 us), scattered with its transpose at six
    passes into an ``[N, d]`` float32 sum carried through the loop (7.5
    us) and looked three numbers up (1.4 us): 34 us.

    m ``[N, d]``, combine ``[N, held]`` float32 with at most ``top_k``
    non-zero weights a row, w1 and w3 ``[held, d, f]``, w2 ``[held, f,
    d]``; float32 ``[N, d]``."""
    cut = _tiles(combine)
    rows, _, _, tiles = cut
    tiled = m.shape[0] > rows
    ends = jnp.cumsum(tiles, dtype=jnp.int32)
    expert_of = lambda i: jnp.sum(ends <= i)         # the expert of turn i

    def expert(e, x, share):
        pick = lambda w: lax.dynamic_index_in_dim(
            w, e, 0, keepdims=False).astype(m.dtype)
        gate = jnp.dot(x, pick(w1), preferred_element_type=jnp.float32)
        up = jnp.dot(x, pick(w3), preferred_element_type=jnp.float32)
        act = (nn.silu(gate) * up * share[:, None]).astype(m.dtype)
        return jnp.dot(act, pick(w2), preferred_element_type=jnp.float32)

    if not tiled:
        def turn(i, acc):
            e = expert_of(i)
            share = lax.dynamic_index_in_dim(combine, e, 1, keepdims=False)
            return acc + expert(e, m, share)

        return lax.fori_loop(
            0, ends[-1], turn,
            jnp.zeros((m.shape[0], w2.shape[-1]), jnp.float32))

    reads, writes, row_of, share_of, spot, held_at, room = _packed(
        combine, cut, top_k)
    x_of = m[row_of]

    def turn(i, landed):
        e = expert_of(i)
        first = reads[e] + i * rows
        tile = lambda x: lax.dynamic_slice_in_dim(x, first, rows)
        out = expert(e, tile(x_of), tile(share_of))
        return lax.dynamic_update_slice_in_dim(
            landed, out.reshape(rows // _ALIGN, _ALIGN, -1),
            writes[e] + i * (rows // _ALIGN), 0)

    landed = lax.fori_loop(
        0, ends[-1], turn,
        jnp.zeros((room // _ALIGN, _ALIGN, w2.shape[-1]), jnp.float32))
    return jnp.where(held_at[..., None], landed.reshape(room, -1)[spot],
                     0.0).sum(0)


def _joint(fn, axis_size, in_batched, *args):
    """``fn`` over the rows of every mapped sequence taken together:
    unmapped arguments are spread, the leading two axes folded."""
    spread = lambda x, batched: x if batched else jnp.broadcast_to(
        x, (axis_size,) + x.shape)
    args = [spread(x, b) for x, b in zip(args, in_batched)]
    return args[0].shape, fn(*(x.reshape((-1,) + x.shape[2:])
                               for x in args))


@functools.cache
def _held_experts(top_k: int):
    """``held_experts`` for a model of ``top_k`` experts a token (static:
    it bounds the tiled path's buffers)."""
    @jax.custom_batching.custom_vmap
    def held_experts(m, combine, w1, w3, w2):
        return _experts_hit(m, combine, w1, w3, w2, top_k)

    @held_experts.def_vmap
    def _held_experts_vmap(axis_size, in_batched, m, combine, w1, w3, w2):
        if any(in_batched[2:]):
            raise NotImplementedError("held_experts: vmap over the weights")
        shape, out = _joint(
            lambda x, c: _experts_hit(x, c, w1, w3, w2, top_k),
            axis_size, in_batched[:2], m, combine)
        return out.reshape(shape[:-1] + out.shape[-1:]), True

    return held_experts


def held_experts(m, combine, w1, w3, w2, top_k: int):
    """The routed part of an expert layer's output that the held
    experts give (``_experts_hit``); ``top_k`` (static) is the most
    non-zero weights a row of ``combine`` has.  Under ``vmap`` over
    sequences (the engine's decode step: one token a slot) the slots'
    tokens are taken TOGETHER, so that the loop still runs over the
    experts the whole step hit; ``vmap``'s own rule would turn the loop's
    bound into a mask and read every held expert for every slot."""
    return _held_experts(top_k)(m, combine, w1, w3, w2)


def _experts_cost(combine):
    rows, _, count, tiles = _tiles(combine)
    return jnp.stack([tiles.sum(dtype=jnp.int32) * rows,
                      count.sum(dtype=jnp.int32)])


@jax.custom_batching.custom_vmap
def experts_cost(combine):
    """What ``held_experts`` does for a call of these assignments, as
    ``[2]`` int32: the rows its expert matmuls compute (turns x rows a
    turn) and the held assignments they are computed for.  Apart from
    ``held_experts``, so that a call whose output nobody reads (the last
    layer of a prefill chunk) is still dropped whole.  Under ``vmap`` the
    joint call's cost is the first sequence's and the others' nothing:
    the sequences' costs add up to the call's."""
    return _experts_cost(combine)


@experts_cost.def_vmap
def _experts_cost_vmap(axis_size, in_batched, combine):
    _, cost = _joint(_experts_cost, axis_size, in_batched, combine)
    return jnp.zeros((axis_size, 2), cost.dtype).at[0].set(cost), True


class ExpertLayer(nn.Module):
    """The shared expert plus this share's part of the routed sum."""
    cfg: Any

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        if cfg.score_func not in SCORE_FUNCS:
            raise ValueError(f"score_func {cfg.score_func!r}: one of "
                             f"{SCORE_FUNCS}")
        b, t, d = x.shape
        first, count = cfg.held
        f = cfg.expert_hidden_dim
        init = nn.initializers.normal(cfg.initializer_range)
        m = x.reshape(b * t, d)
        with jax.named_scope(SCOPE_MOE_ROUTER):
            w_r = self.param("router", init, (d, cfg.n_experts),
                             jnp.float32)
            logits = jnp.dot(
                m.astype(jnp.float32), w_r.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
            if cfg.score_func == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                bias = self.param("router_bias", nn.initializers.zeros,
                                  (cfg.n_experts,), jnp.float32)
            else:
                scores, bias = jax.nn.softmax(logits, axis=-1), None
            chosen, weights = route(scores, bias, cfg.top_k,
                                    cfg.route_scale,
                                    getattr(cfg, "n_group", 1),
                                    getattr(cfg, "topk_group", 1))
            # [N, held]: a token's weight on each held expert, zero where
            # it chose another (one_hot of a row outside is all zeros)
            combine = (jax.nn.one_hot(chosen - first, count,
                                      dtype=jnp.float32)
                       * weights[..., None]).sum(1)
            if live is not None:
                # padding chooses nothing: its experts are not read
                combine = combine * live.reshape(b * t, 1)
        with jax.named_scope(SCOPE_MOE_SHARED):
            shared = SwiGLU(cfg, f, name="shared")(m)
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            w1 = self.param("w1", init, (count, d, f), jnp.float32)
            w3 = self.param("w3", init, (count, d, f), jnp.float32)
            w2 = self.param("w2", init, (count, f, d), jnp.float32)
            routed = held_experts(m.astype(cfg.dtype), combine, w1, w3, w2,
                                  cfg.top_k)
        if cfg.decode:
            stat = self.variable("cache", "stat_experts", jnp.zeros,
                                 (b, cfg.top_k), jnp.int32)
            stat.value = chosen.reshape(b, t, cfg.top_k)[:, -1].astype(
                jnp.int32)
            # what the sequence's calls have cost so far: it only grows
            rows = self.variable("cache", "stat_expert_rows", jnp.zeros,
                                 (2,), jnp.int32)
            rows.value = rows.value + experts_cost(combine)
        out = shared.astype(jnp.float32) + routed
        return out.astype(cfg.dtype).reshape(b, t, d)
