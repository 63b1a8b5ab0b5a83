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
is the call.  The path follows from the call's shape alone.  What the
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


def _experts_hit(m, combine, w1, w3, w2):
    """``sum_e combine[:, e] * expert_e(m)`` over the assignments this
    share holds, taken expert by expert in tiles of rows (``_tiles``),
    one loop turn a tile: an expert costs what the rows that chose it
    cost, one that more rows chose than a tile holds takes as many tiles
    as it needs, and one no row chose is never read.  A row's place
    among its expert's rows is a running count down the expert's column;
    a turn picks the rows whose place falls in its tile with a one-hot
    matrix (a matmul gathers them, exactly), and the transposed matrix
    adds the tile's result back onto their rows.  Where the whole call
    fits one tile a hit expert's tile IS the call, and the turn takes
    ``m`` as it stands.  m ``[N, d]``, combine ``[N, held]`` float32, w1
    and w3 ``[held, d, f]``, w2 ``[held, f, d]``; float32 ``[N, d]``."""
    rows, chose, _, tiles = _tiles(combine)
    tiled = m.shape[0] > rows
    ends = jnp.cumsum(tiles, dtype=jnp.int32)
    if tiled:
        place = jnp.cumsum(chose, axis=0, dtype=jnp.int32) - 1  # [N, held]

    def turn(i, acc):
        e = jnp.sum(ends <= i)                   # the expert of turn i
        column = lambda x: lax.dynamic_index_in_dim(x, e, 1, keepdims=False)
        pick = lambda w: lax.dynamic_index_in_dim(
            w, e, 0, keepdims=False).astype(m.dtype)
        x, share = m, column(combine)
        if tiled:
            # this turn's tile of the expert's rows: places first ..
            # first + rows - 1
            first = (i - ends[e] + tiles[e]) * rows
            take = (column(place) - first == jnp.arange(rows)[:, None]) \
                & column(chose)                                 # [rows, N]
            x = jnp.dot(take.astype(m.dtype), m,
                        precision=lax.Precision.HIGHEST)
            share = jnp.where(take, share, 0.0).sum(1)
        gate = jnp.dot(x, pick(w1), preferred_element_type=jnp.float32)
        up = jnp.dot(x, pick(w3), preferred_element_type=jnp.float32)
        act = (nn.silu(gate) * up * share[:, None]).astype(m.dtype)
        out = jnp.dot(act, pick(w2), preferred_element_type=jnp.float32)
        if tiled:
            out = jnp.einsum("rn,rd->nd", take.astype(jnp.float32), out,
                             precision=lax.Precision.HIGHEST)
        return acc + out

    return lax.fori_loop(0, ends[-1], turn,
                         jnp.zeros((m.shape[0], w2.shape[-1]), jnp.float32))


def _joint(fn, axis_size, in_batched, *args):
    """``fn`` over the rows of every mapped sequence taken together:
    unmapped arguments are spread, the leading two axes folded."""
    spread = lambda x, batched: x if batched else jnp.broadcast_to(
        x, (axis_size,) + x.shape)
    args = [spread(x, b) for x, b in zip(args, in_batched)]
    return args[0].shape, fn(*(x.reshape((-1,) + x.shape[2:])
                               for x in args))


@jax.custom_batching.custom_vmap
def held_experts(m, combine, w1, w3, w2):
    """The routed part of an expert layer's output that the held
    experts give (``_experts_hit``).  Under ``vmap`` over sequences
    (the engine's decode step: one token a slot) the slots' tokens are
    taken TOGETHER, so that the loop still runs over the experts the
    whole step hit; ``vmap``'s own rule would turn the loop's bound
    into a mask and read every held expert for every slot."""
    return _experts_hit(m, combine, w1, w3, w2)


@held_experts.def_vmap
def _held_experts_vmap(axis_size, in_batched, m, combine, w1, w3, w2):
    if any(in_batched[2:]):
        raise NotImplementedError("held_experts: vmap over the weights")
    shape, out = _joint(lambda x, c: _experts_hit(x, c, w1, w3, w2),
                        axis_size, in_batched[:2], m, combine)
    return out.reshape(shape[:-1] + out.shape[-1:]), True


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
            routed = held_experts(m.astype(cfg.dtype), combine, w1, w3, w2)
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
