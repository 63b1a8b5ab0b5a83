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

The expert layer is ``models/experts.py``'s, shared with
``models/mla_moe.py``: it routes every token over all ``n_experts``
(``score_func`` "sigmoid": this model's) and computes the shared expert
and the part of the sum that the experts it holds give (``experts_held
= (first, count)``), dropless, expert by expert in tiles of rows.  On
one chip it runs without an exchange; the sum of every share's routed
part, plus the shared expert once, is the whole layer
(``tests/test_afmoe.py``).

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
and a wrapped ring is read through its positions, not its rows.  A
single-token step scores every row of a leaf at once (``attend``); a
call of several tokens, the prefill chunk, walks the leaf in blocks of
``KEY_BLOCK`` rows with a running softmax, as far as a row is written
and no further (``blocked_attend``): the bound is worked out from the
cache index inside the program, so one program serves every position.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.models.experts import (  # noqa: F401  (re-exported)
    EXPERT_TILE, ExpertLayer, SwiGLU, _dense, experts_cost, held_experts,
    route)
from bluefog_tpu.models.llama import RMSNorm, rotary_embed

__all__ = ["AfmoeConfig", "Afmoe", "SLIDING", "FULL"]

SLIDING, FULL = "sliding_attention", "full_attention"
SCOPE_ATTN_WINDOW = "bf.attn.window"
SCOPE_ATTN_FULL = "bf.attn.full"
# key rows of one block of ``blocked_attend``: a cached call of several
# tokens reads a leaf block by block, as far as a row is written
KEY_BLOCK = 512


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
    score_func = "sigmoid"           # the expert layer's (models/experts.py)
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

    def _leaves(self):
        """``(kind, layers, rows of a leaf)`` of each kind of cache."""
        rows = {"window": self.ring_len, "full": self.max_seq_len}
        return [(kind, layers, rows[kind])
                for kind, (layers, _) in self.cache_kinds().items()]

    def streamed_positions(self, positions) -> tuple:
        """The single-token step reads every row of every leaf."""
        return tuple((kind, layers * len(positions) * rows)
                     for kind, layers, rows in self._leaves())

    def chunk_streamed_positions(self, start: int, tokens: int) -> tuple:
        """``((kind, rows), ...)`` that a call of ``tokens`` tokens at
        cache index ``start`` reads, summed over the kind's layers: the
        key blocks up to the last row written (``live_blocks``); every
        row for a single-token step."""
        if tokens == 1:
            return self.streamed_positions((start,))
        return tuple(
            (kind, layers * key_block(rows) * live_blocks(start + tokens,
                                                          rows))
            for kind, layers, rows in self._leaves())


def key_block(size: int) -> int:
    """The largest divisor of a leaf's ``size`` rows that is at most
    ``KEY_BLOCK``."""
    block = min(size, KEY_BLOCK)
    while size % block:
        block -= 1
    return block


def live_blocks(written, size: int):
    """Key blocks of a leaf of ``size`` rows that hold a written row
    once ``written`` positions are in it (an integer, or a traced
    scalar): the first ``ceil(written / block)``.  A full leaf never
    holds more than ``size``; a ring that has wrapped is live in every
    row, and before it wraps row ``r`` holds position ``r``."""
    kb = key_block(size)
    least = jnp.minimum if isinstance(written, jax.Array) else min
    return least((written + kb - 1) // kb, size // kb)


def _seen(q_pos, key_pos, window: Optional[int]):
    """``[T, S]``: the keys at ``key_pos`` that queries at ``q_pos``
    see (a negative key position marks an empty row)."""
    gap = q_pos[:, None] - key_pos[None, :]
    seen = (gap >= 0) & (key_pos[None, :] >= 0)
    if window is not None:
        seen &= gap < window
    return seen


def attend(q, k_all, v_all, q_pos, key_pos, window: Optional[int]):
    """Grouped-query attention of queries at ``q_pos [T]`` over keys at
    ``key_pos [S]`` (a negative position marks an empty row): a key is
    visible where ``0 <= q_pos - key_pos`` and, under ``window``, ``<
    window``.  q ``[B, T, Hq, D]``; k_all, v_all KV-HEAD-MAJOR ``[B,
    Hkv, S, D]``.  Scores and probabilities are float32, over every row
    at once: the single-token step's form, and the plain one that
    ``blocked_attend`` is held to."""
    b, t, n_q, d = q.shape
    n_kv = k_all.shape[1]
    k32, v32 = k_all.astype(jnp.float32), v_all.astype(jnp.float32)
    q5 = q.reshape(b, t, n_kv, n_q // n_kv, d).astype(jnp.float32)
    scores = jnp.einsum("btkrd,bksd->bkrts", q5, k32) / math.sqrt(d)
    seen = _seen(q_pos, key_pos, window)
    scores = jnp.where(seen[None, None, None], scores, -1e30)
    # every query sees at least its own key
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkrts,bksd->btkrd", p, v32).reshape(
        b, t, n_q, d).astype(q.dtype)


def blocked_attend(q, k_all, v_all, q_pos, key_pos, window: Optional[int],
                   blocks):
    """``attend`` over the first ``blocks`` key blocks (``key_block`` of
    the leaf's rows each; a traced scalar makes the bound data, so one
    program serves every cache index), block by block with a running
    softmax.  Only the block that is read is cast; scores, the running
    top, the sum and the probabilities are float32, as in ``attend``:
    what differs is the order in which the softmax is summed, and that
    rows past the bound are never read."""
    b, t, n_q, d = q.shape
    n_kv = k_all.shape[1]
    rep, kb = n_q // n_kv, key_block(k_all.shape[2])
    q5 = q.reshape(b, t, n_kv, rep, d).astype(jnp.float32)

    def turn(i, carry):
        top, total, acc = carry
        k32 = lax.dynamic_slice_in_dim(k_all, i * kb, kb, 2).astype(
            jnp.float32)
        v32 = lax.dynamic_slice_in_dim(v_all, i * kb, kb, 2).astype(
            jnp.float32)
        seen = _seen(q_pos, lax.dynamic_slice_in_dim(key_pos, i * kb, kb),
                     window)[None, None, None]
        scores = jnp.einsum("btkrd,bksd->bkrts", q5, k32) / math.sqrt(d)
        scores = jnp.where(seen, scores, -1e30)
        new_top = jnp.maximum(top, scores.max(-1))
        # a query may see no key of the blocks before its first visible
        # one (the rows a wrapped ring is about to lose): its top is then
        # still the mask's value and exp(masked - top) is 1.  The first
        # real score fades such a sum to exactly 0; the weights are
        # masked too, so that no sum ever holds them
        p = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        fade = jnp.exp(top - new_top)
        acc = acc * fade[..., None] + jnp.einsum("bkrts,bksd->bkrtd", p, v32)
        return new_top, total * fade + p.sum(-1), acc

    init = (jnp.full((b, n_kv, rep, t), -1e30, jnp.float32),
            jnp.zeros((b, n_kv, rep, t), jnp.float32),
            jnp.zeros((b, n_kv, rep, t, d), jnp.float32))
    _, total, acc = lax.fori_loop(0, blocks, turn, init)
    # every query sees at least its own key, inside the bound
    out = jnp.moveaxis(acc / total[..., None], 3, 1)      # [B, T, KV, R, D]
    return out.reshape(b, t, n_q, d).astype(q.dtype)


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
                out = blocked_attend(q, jnp.swapaxes(k, 1, 2),
                                     jnp.swapaxes(v, 1, 2), pos, pos,
                                     cfg.window if sliding else None,
                                     t // key_block(t))
        out = out.reshape(b, t, n_q * hd).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return _dense(cfg, cfg.dim, "wo")(out.astype(cfg.dtype))

    def _cached(self, q, k, v, sliding: bool):
        """Write this call's keys and values at the cache index, then
        attend over the cache: a full layer's ``max_len`` rows, or a
        window layer's ring read through the position each row holds.  A
        single-token step reads every row (``attend``); a call of several
        tokens the key blocks that hold a written row
        (``blocked_attend``)."""
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

        def over(k_all, v_all, key_pos, window):
            if t == 1:
                return attend(q, k_all, v_all, pos, key_pos, window)
            return blocked_attend(q, k_all, v_all, pos, key_pos, window,
                                  live_blocks(idx + t, k_all.shape[2]))

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
            return over(k_all, v_all, jnp.arange(size), None)
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
        return over(k_all, v_all, key_pos, cfg.window)


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
