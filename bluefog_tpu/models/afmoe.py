"""A decoder whose layers are of several kinds (``model_type: afmoe``):
window and full attention mixed, gated heads, sandwich norms, and an
expert layer with a shared expert that is TOLD WHICH EXPERTS IT HOLDS.

Imported lazily (nothing on ``import bluefog_tpu``'s path names it); it
reuses ``RMSNorm`` and ``rotary_embed`` of ``models/llama.py`` and is
served by the same ``ServingEngine`` through the protocol of
``serving/protocol.py``, which :class:`AfmoeConfig` implements.

One layer ``l`` on the residual stream ``h`` (``h0 = E[tok] *
sqrt(dim)``)::

    a = norm1(h);  q, k, v = a Wq, a Wk, a Wv
    q, k = rms(q), rms(k)            per head, learned scales
    sliding layers: q, k = rope(q, k, pos); keys 0 <= i - j < window
    full layers:    no rotation;            keys j <= i
    o = softmax(q k^T / sqrt(head_dim)) v * sigmoid(a Wg)
    h = h + norm2(o Wo)
    m = norm3(h)
    dense layers:  f = W2(silu(W1 m) * W3 m)
    expert layers: s = sigmoid(m Wr)              float32, all experts
                   T = top_k(s + b)               the bias selects only
                   w_e = route_scale * s_e / (sum_T s + 1e-20)
                   f = shared(m) + sum_{e in T, e held} w_e expert_e(m)
    h = h + norm4(f)

The expert layer routes every token over all ``n_experts`` and computes
the shared expert and the part of the sum that the experts it holds
give (``experts_held = (first, count)``), dropless: a loop over the
(token, expert) assignments the share holds, taken expert by expert in
tiles of ``EXPERT_TILE`` rows, one turn a tile (``held_experts``).  A
held expert costs what the rows that chose it cost: one that more rows
chose than a tile holds takes as many tiles as it needs, one that no
row chose takes none and is never read, padding makes no assignment;
where the whole call fits one tile (a decode step's slots) a hit
expert's tile is the call.  The path follows from the call's shape
alone.  What the absent experts would add is left out; nothing stands
in for their chips or their traffic.  On one chip it runs without an
exchange; the sum of every share's routed part, plus the shared expert
once, is the whole layer (``tests/test_afmoe.py``).

The cache of a served sequence is a tree of leaves per layer: a full
layer keeps ``cached_key``/``cached_value`` of ``max_len`` positions, a
window layer a RING ``window_key``/``window_value`` of ``window +
ring_slack`` positions (position ``p`` at row ``p % ring``), each with
its ``cache_index``; an expert layer keeps ``stat_experts``, the
experts its last token chose, and ``stat_expert_rows``, the rows its
expert loop has computed and the held assignments they were computed
for, summed over the sequence's calls (``experts_cost``), for the
``bf_moe_*`` counters of ``serving/metrics.py``.  A call of up to
``ring_slack`` tokens writes its keys first and attends afterwards, so
a chunk's first query still finds the ``window - 1`` keys behind it
and a wrapped ring is read through its positions, not its rows.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.models.llama import RMSNorm, rotary_embed

__all__ = ["AfmoeConfig", "Afmoe", "SLIDING", "FULL"]

SLIDING, FULL = "sliding_attention", "full_attention"
SCOPE_ATTN_WINDOW = "bf.attn.window"
SCOPE_ATTN_FULL = "bf.attn.full"
SCOPE_MOE_ROUTER = "bf.moe.router"
SCOPE_MOE_SHARED = "bf.moe.shared"
SCOPE_MOE_EXPERTS = "bf.moe.experts"
# query rows x key positions of one score block: a prefill chunk against
# a long full-attention cache is computed in row blocks under this size
SCORE_BLOCK = 1 << 21
# rows of one turn of the expert loop (``_experts_hit``)
EXPERT_TILE = 128


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 256
    dim: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    window: int = 8
    n_dense_layers: int = 1          # leading layers with a dense FFN
    dense_hidden_dim: int = 128
    expert_hidden_dim: int = 32
    n_experts: int = 16              # the router's outputs
    top_k: int = 4
    route_scale: float = 1.0
    # (first, count) of the experts this layer holds; None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    # the serving layout (``serving_layout``)
    decode: bool = False
    max_seq_len: int = 2048
    ring_slack: int = 1              # most tokens one cached call writes

    def __post_init__(self):
        for kind in self.layer_types:
            if kind not in (SLIDING, FULL):
                raise ValueError(f"unknown layer type {kind!r}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} lies "
                             f"outside the {self.n_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def ring_len(self) -> int:
        return self.window + self.ring_slack

    # -- the serving protocol (serving/protocol.py) -------------------- #
    def serving_layout(self, max_len: int, *, chunk: int = 1,
                       kv_quant: str = "none", weight_quant: str = "none",
                       decode_attn: str = "xla") -> "AfmoeConfig":
        if kv_quant != "none" or weight_quant != "none":
            raise NotImplementedError(
                "the afmoe model serves full-precision weights and caches "
                f"only (kv_quant={kv_quant!r}, weight_quant="
                f"{weight_quant!r})")
        # no fused decode kernel reads a ring: "auto" is the XLA lowering
        if decode_attn not in ("xla", "auto"):
            raise NotImplementedError(f"decode_attn={decode_attn!r}")
        return dataclasses.replace(self, decode=True, max_seq_len=max_len,
                                   ring_slack=max(int(chunk), 1))

    def init_cache(self, batch_size: int, max_len: int):
        """Zero caches of ``batch_size`` sequences, from shapes alone."""
        cfg = self if self.decode and self.max_seq_len == max_len \
            else self.serving_layout(max_len, chunk=self.ring_slack)
        shapes = jax.eval_shape(
            lambda: Afmoe(cfg).init(jax.random.PRNGKey(0),
                                    jnp.zeros((batch_size, 1), jnp.int32)))
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            shapes["cache"])

    def apply_cached(self, params, cache, tokens, all_logits=False,
                     live=None):
        """Append ``tokens [B, T]`` to ``cache`` and return ``(logits
        [B, 1 or T, vocab], cache')``.  ``live [B, T]``: False marks a
        token that is padding (a slot that does not decode, a chunk's
        tail); it chooses no expert, so no expert is read for it."""
        logits, mut = Afmoe(self).apply(
            {"params": params, "cache": cache}, tokens,
            all_logits=all_logits, live=live, mutable=["cache"])
        return logits, mut["cache"]

    def cache_kinds(self) -> dict:
        """``{kind: (layers, most positions a query attends or None)}``."""
        n_window = sum(k == SLIDING for k in self.layer_types)
        kinds = {}
        if n_window:
            kinds["window"] = (n_window, self.window)
        if n_window < self.n_layers:
            kinds["full"] = (self.n_layers - n_window, None)
        return kinds

    def streamed_positions(self, positions) -> tuple:
        """The XLA lowering reads every row of every leaf."""
        rows = {"window": self.ring_len, "full": self.max_seq_len}
        return tuple((kind, layers * len(positions) * rows[kind])
                     for kind, (layers, _) in self.cache_kinds().items())


def _dense(cfg: AfmoeConfig, feats: int, name: str):
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name,
                    kernel_init=nn.initializers.normal(
                        cfg.initializer_range))


def _row_blocks(t: int, s: int) -> int:
    """How many blocks of query rows keep a score block under
    ``SCORE_BLOCK``: the smallest divisor of ``t`` that does."""
    for n in range(1, t + 1):
        if t % n == 0 and (t // n) * s <= SCORE_BLOCK:
            return n
    return t


def attend(q, k_all, v_all, q_pos, key_pos, window: Optional[int]):
    """Grouped-query attention of queries at ``q_pos [T]`` over keys at
    ``key_pos [S]`` (a negative position marks an empty row): a key is
    visible where ``0 <= q_pos - key_pos`` and, under ``window``, ``<
    window``.  q ``[B, T, Hq, D]``; k_all, v_all KV-HEAD-MAJOR ``[B,
    Hkv, S, D]``.  Scores and probabilities are float32; long calls go
    in blocks of query rows."""
    b, t, n_q, d = q.shape
    n_kv, s = k_all.shape[1], k_all.shape[2]
    rep = n_q // n_kv
    k32, v32 = k_all.astype(jnp.float32), v_all.astype(jnp.float32)

    def rows(args):
        qb, pos = args
        q5 = qb.reshape(b, -1, n_kv, rep, d).astype(jnp.float32)
        scores = jnp.einsum("btkrd,bksd->bkrts", q5, k32) / math.sqrt(d)
        gap = pos[:, None] - key_pos[None, :]
        seen = (gap >= 0) & (key_pos[None, :] >= 0)
        if window is not None:
            seen &= gap < window
        scores = jnp.where(seen[None, None, None], scores, -1e30)
        # every query sees at least its own key
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bkrts,bksd->btkrd", p, v32).reshape(
            b, -1, n_q, d).astype(q.dtype)

    n = _row_blocks(t, s)
    if n == 1:
        return rows((q, q_pos))
    out = lax.map(rows, (jnp.moveaxis(q.reshape(b, n, t // n, n_q, d), 1, 0),
                         q_pos.reshape(n, t // n)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, n_q, d)


class Attention(nn.Module):
    cfg: AfmoeConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, _ = x.shape
        hd, n_q, n_kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        q = _dense(cfg, n_q * hd, "wq")(x).reshape(b, t, n_q, hd)
        k = _dense(cfg, n_kv * hd, "wk")(x).reshape(b, t, n_kv, hd)
        v = _dense(cfg, n_kv * hd, "wv")(x).reshape(b, t, n_kv, hd)
        gate = _dense(cfg, n_q * hd, "wg")(x)
        q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
        k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        sliding = self.kind == SLIDING
        with jax.named_scope(SCOPE_ATTN_WINDOW if sliding
                             else SCOPE_ATTN_FULL):
            if cfg.decode:
                out = self._cached(q, k, v, sliding)
            else:
                pos = jnp.arange(t)
                if sliding:
                    q = rotary_embed(q, pos, cfg.rope_theta)
                    k = rotary_embed(k, pos, cfg.rope_theta)
                out = attend(q, jnp.swapaxes(k, 1, 2),
                             jnp.swapaxes(v, 1, 2), pos, pos,
                             cfg.window if sliding else None)
        out = out.reshape(b, t, n_q * hd).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _dense(cfg, cfg.dim, "wo")(out.astype(cfg.dtype))

    def _cached(self, q, k, v, sliding: bool):
        """Write this call's keys and values at the cache index, then
        attend over the cache: a full layer's ``max_len`` rows, or a
        window layer's ring read through the position each row holds."""
        cfg = self.cfg
        b, t, n_kv, hd = k.shape
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        pos = idx + jnp.arange(t)
        if sliding:
            q = rotary_embed(q, pos, cfg.rope_theta)
            k = rotary_embed(k, pos, cfg.rope_theta)
        k = jnp.swapaxes(k, 1, 2).astype(cfg.dtype)   # [B, KV, T, D]
        v = jnp.swapaxes(v, 1, 2).astype(cfg.dtype)
        zero = jnp.zeros((), idx.dtype)
        if not sliding:
            size = cfg.max_seq_len
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (b, n_kv, size, hd), cfg.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (b, n_kv, size, hd), cfg.dtype)
            k_all = lax.dynamic_update_slice(ck.value, k,
                                             (zero, zero, idx, zero))
            v_all = lax.dynamic_update_slice(cv.value, v,
                                             (zero, zero, idx, zero))
            ck.value, cv.value, ci.value = k_all, v_all, idx + t
            # rows above the index hold positions no query has reached
            return attend(q, k_all, v_all, pos, jnp.arange(size), None)
        size = cfg.ring_len
        if t > cfg.ring_slack:
            raise ValueError(
                f"a cached call of {t} tokens needs ring_slack >= {t} "
                f"(it is {cfg.ring_slack}): the ring would lose keys the "
                "call's first query still sees")
        ck = self.variable("cache", "window_key", jnp.zeros,
                           (b, n_kv, size, hd), cfg.dtype)
        cv = self.variable("cache", "window_value", jnp.zeros,
                           (b, n_kv, size, hd), cfg.dtype)
        row = idx % size
        if t == 1:
            k_all = lax.dynamic_update_slice(ck.value, k,
                                             (zero, zero, row, zero))
            v_all = lax.dynamic_update_slice(cv.value, v,
                                             (zero, zero, row, zero))
        else:
            # rows row .. row + t - 1 (mod size): the call's tokens,
            # padded to the ring and rolled into place
            pad = ((0, 0), (0, 0), (0, size - t), (0, 0))
            fresh = (jnp.roll(jnp.arange(size), row) < t)[None, None, :,
                                                          None]
            k_all = jnp.where(fresh, jnp.roll(jnp.pad(k, pad), row, 2),
                              ck.value)
            v_all = jnp.where(fresh, jnp.roll(jnp.pad(v, pad), row, 2),
                              cv.value)
        ck.value, cv.value, ci.value = k_all, v_all, idx + t
        # row r holds the newest position below idx + t that is r modulo
        # the ring; negative: never written
        rows = jnp.arange(size)
        key_pos = rows + size * ((idx + t - 1 - rows) // size)
        return attend(q, k_all, v_all, pos, key_pos, cfg.window)


class SwiGLU(nn.Module):
    cfg: AfmoeConfig
    hidden: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.hidden, "w1")(x)
        up = _dense(cfg, self.hidden, "w3")(x)
        return _dense(cfg, cfg.dim, "w2")(nn.silu(gate) * up)


def route(scores, bias, top_k: int, route_scale: float):
    """``(chosen [N, top_k], weights [N, top_k])`` from the sigmoid
    scores ``[N, E]``: the bias enters the choice and not the weights,
    which are the chosen scores over their sum, times ``route_scale``."""
    _, chosen = lax.top_k(scores + bias, top_k)
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
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        b, t, d = x.shape
        first, count = cfg.held
        f = cfg.expert_hidden_dim
        init = nn.initializers.normal(cfg.initializer_range)
        m = x.reshape(b * t, d)
        with jax.named_scope(SCOPE_MOE_ROUTER):
            w_r = self.param("router", init, (d, cfg.n_experts),
                             jnp.float32)
            bias = self.param("router_bias", nn.initializers.zeros,
                              (cfg.n_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(
                m.astype(jnp.float32), w_r.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            chosen, weights = route(scores, bias.astype(jnp.float32),
                                    cfg.top_k, cfg.route_scale)
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


class Block(nn.Module):
    cfg: AfmoeConfig
    index: int

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, name=name)
        a = Attention(cfg, cfg.layer_types[self.index],
                      name="attention")(norm("attention_norm")(x))
        x = x + norm("attention_post_norm")(a)
        m = norm("ffn_norm")(x)
        if self.index < cfg.n_dense_layers:
            f = SwiGLU(cfg, cfg.dense_hidden_dim, name="feed_forward")(m)
        else:
            f = ExpertLayer(cfg, name="moe")(m, live)
        return x + norm("ffn_post_norm")(f)


class Afmoe(nn.Module):
    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, all_logits=False, live=None):
        """tokens ``[B, T]`` int32 -> float32 logits ``[B, T, vocab]``;
        in the serving layout the final position's alone unless
        ``all_logits``.  ``live [B, T]``: see ``apply_cached``."""
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="tok_embeddings",
                     embedding_init=nn.initializers.normal(
                         cfg.initializer_range))(tokens)
        x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(cfg.dtype)
        for i in range(cfg.n_layers):
            x = Block(cfg, i, name=f"layer_{i}")(x, live)
        x = RMSNorm(cfg.norm_eps, name="norm")(x)
        if cfg.decode and not all_logits:
            x = x[:, -1:]
        w_out = self.param("output", nn.initializers.normal(
            cfg.initializer_range), (cfg.dim, cfg.vocab_size), jnp.float32)
        return jnp.einsum("btd,dv->btv", x, w_out.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)
