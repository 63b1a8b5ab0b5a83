"""A decoder whose stack of layers runs several times over the SAME
weights (``model_type: ouro``; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): the weights are shared by the
passes, the key/value cache is not.

Imported lazily (nothing on ``import bluefog_tpu``'s path names it).
:class:`LoopedConfig` wraps the :class:`~bluefog_tpu.models.llama.
LlamaConfig` of one pass and reuses that file's attention projections
(``Attention``, through its ``attend`` argument), ``FeedForward``,
``RMSNorm``, ``rotary_embed``, ``_cached_attention`` and the fused
decode kernel (``parallel/pallas_decode.decode_attention``); it is
served by the same ``ServingEngine`` through ``serving/protocol.py``.
``loop_steps = 1`` with ``sandwich_norms`` and ``rope_halves`` off IS
``Llama(scan_layers=True)``, leaf for leaf (``tests/test_looped.py``).

A layer ``l`` on ``x [T, d]`` at positions ``p``, in pass ``t``::

    a = Attn_l(N1_l(x); p, cache[t, l]);  x = x + N2_l(a)
    m = W2_l(silu(W1_l n) * W3_l n), n = N3_l(x);  x = x + N4_l(m)

(``N2``, ``N4``: the ``sandwich_norms``; ``attention_post_norm`` and
``ffn_post_norm`` in the tree).  ``Attn_l`` is causal softmax attention
over the keys and values that pass ``t`` of layer ``l`` wrote: pass 2
never reads pass 1's keys.  The stack::

    h_0 = Embed(tokens);  h_t = N_f(Layers(h_{t-1}; pass t)), t = 1..T
    logits = W_out h_T

with the one final norm ``N_f`` applied after EVERY pass, its output
the next pass's input.  The exit gate ``g: d -> 1`` (with a bias) reads
every ``h_t``: ``lambda_t = sigmoid(g(h_t))``, ``p_t = lambda_t *
prod_{j<t} (1 - lambda_j)`` for ``t < T`` and ``p_T = prod_{j<T} (1 -
lambda_j)``.  The program serves the published exit threshold of 1,
where every token runs every pass and the head reads ``h_T``; the gate
is computed all the same and ``p_1..p_T`` of a sequence's last token
are left in the cache's ``stat_exit_pdf`` leaf.

**One rolled program.**  The layers are a ``lax.scan`` over their
stacked weights (``layers/block/...``, a leading ``[n_layers]`` axis,
the tree of ``Llama(scan_layers=True)``) inside a ``lax.fori_loop`` over
the passes: tracing and lowering hold one copy of the block whatever
``loop_steps`` is.  The cache of a sequence is ONE pair of leaves,
``cached_key``/``cached_value [B, loop_steps * n_layers, KV, max_len,
D]`` (leaf ``t * n_layers + l`` is pass ``t`` of layer ``l``), with one
``cache_index``.  The loops carry the pair and every layer application
updates it in place.  The single-token step hands the WHOLE pair and
its own key and value rows to the fused kernel, which finds its leaf
through a scalar-prefetched index, attends, and writes the rows into
the tile around the position (``decode_attention(..., leaf=,
fresh=)``): no slice of the stack is copied, and no scatter runs under
the engine's map over slots (written by XLA the rows cost 18 ms of a
57 ms step: PERF.md section 6, PR 40).  A call of several tokens (the
prefill chunk) writes with ``dynamic_update_slice`` at ``(leaf,
index)`` and slices its one leaf for the einsums of
``llama._cached_attention``.

``apply_in_pool`` is the same call on ONE SLOT of a pool's stacked
leaves (``[capacity, *leaf]``), writing the rows where they lie: a
sequence's cache is 8 KiB a token a layer application, at 192 of them
and 768 positions 1.125 GiB a slot, and a chunk program that cut a
slot's tree out of the pool and put it back would hold a second copy of
the slot beside a pool that fills the chip.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.models.llama import (Attention, FeedForward, LlamaConfig,
                                      RMSNorm, _cached_attention,
                                      rotary_embed)
from bluefog_tpu.parallel.ring_attention import full_attention

__all__ = ["LoopedConfig", "LoopBlock", "init_params", "SCOPE_LOOP",
           "SCOPE_LOOP_ATTN"]

# device-trace scopes: the passes (embedding, head, gate's pdf and
# sampling lie outside), and a layer application's attention inside them
SCOPE_LOOP = "bf.loop"
SCOPE_LOOP_ATTN = "bf.loop.attn"


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    block: LlamaConfig               # one pass: the layers, their widths
    loop_steps: int = 4              # passes a token makes (total_ut_steps)
    sandwich_norms: bool = True      # a norm AFTER each sublayer too
    rope_halves: bool = True         # rotate half against half
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps ({self.loop_steps}) must be >= 1")
        b = self.block
        if b.n_experts or b.tp_size > 1 or b.attn_mode != "full":
            raise ValueError(
                "the looped stack runs a dense block on one device with "
                "full attention (n_experts, tp_size and attn_mode of the "
                "block are the plain ones)")

    # what the engine, the benchmark and the tests read off any config
    vocab_size = property(lambda self: self.block.vocab_size)
    n_layers = property(lambda self: self.block.n_layers)
    max_seq_len = property(lambda self: self.block.max_seq_len)
    decode_attn = property(lambda self: self.block.decode_attn)

    @property
    def cache_leaves(self) -> int:
        """Layer applications a token makes: leaves of the stacked cache."""
        return self.loop_steps * self.block.n_layers

    # -- the serving protocol (serving/protocol.py) -------------------- #
    def serving_layout(self, max_len: int, *, chunk: int = 1,
                       kv_quant: str = "none", weight_quant: str = "none",
                       decode_attn: str = "xla") -> "LoopedConfig":
        if kv_quant != "none" or weight_quant != "none":
            raise NotImplementedError(
                "the looped model serves full-precision weights and caches "
                f"only (kv_quant={kv_quant!r}, weight_quant="
                f"{weight_quant!r}): the stacked leaf has no scale leaves")
        from bluefog_tpu.models.generate import decode_config

        return dataclasses.replace(self, block=decode_config(
            self.block, max_len, decode_attn=decode_attn))

    def init_cache(self, batch_size: int, max_len: int):
        """Zero caches of ``batch_size`` sequences, from shapes alone."""
        b = self.block
        kv = (batch_size, self.cache_leaves, b.n_kv_heads, max_len,
              b.head_dim)
        return {"cache_index": jnp.zeros((), jnp.int32),
                "cached_key": jnp.zeros(kv, b.dtype),
                "cached_value": jnp.zeros(kv, b.dtype),
                "stat_exit_pdf": jnp.zeros((batch_size, self.loop_steps),
                                           jnp.float32)}

    def apply_cached(self, params, cache, tokens, all_logits=False,
                     live=None):
        """Append ``tokens [B, T]`` to ``cache``: ``(logits [B, 1 or T,
        vocab], cache')``.  ``live [B, T]`` reaches the fused
        single-token attention, which fetches no cache block for a row
        that does not decode."""
        logits, _, cache = _forward(self, params, tokens, cache=cache,
                                    all_logits=all_logits, live=live)
        return logits, cache

    def apply_in_pool(self, params, pool, slot, tokens, live=None):
        """``apply_cached`` on slot ``slot`` of ``pool`` (the cache
        tree with a leading ``[capacity]`` axis on every leaf), the rows
        written where they lie: ``(logits, pool')``."""
        logits, _, pool = _forward(self, params, tokens, cache=pool,
                                   live=live, slot=slot)
        return logits, pool

    def apply(self, params, tokens):
        """The whole forward with no cache: ``(logits [B, T, vocab],
        exit_pdf [B, T, loop_steps])``."""
        logits, pdf, _ = _forward(self, params, tokens, all_logits=True)
        return logits, pdf

    def cache_kinds(self) -> dict:
        return {"full": (self.cache_leaves, None)}

    def streamed_positions(self, positions) -> tuple:
        from bluefog_tpu.parallel.pallas_decode import streamed_positions

        return (("full", self.cache_leaves * streamed_positions(
            positions, self.max_seq_len,
            fused=self.decode_attn == "pallas")),)


class LoopBlock(nn.Module):
    """One layer application.  ``attend(q, k, v)`` is the rotation, the
    cache and the attention between ``llama.Attention``'s projections."""

    cfg: LoopedConfig

    @nn.compact
    def __call__(self, x, attend, live=None):
        b = self.cfg.block
        norm = lambda name: RMSNorm(b.norm_eps, name=name)
        a = Attention(b, name="attention")(norm("attention_norm")(x), 0,
                                           live, attend=attend)
        if self.cfg.sandwich_norms:
            a = norm("attention_post_norm")(a)
        x = x + a
        m = FeedForward(b, name="feed_forward")(norm("ffn_norm")(x))
        if self.cfg.sandwich_norms:
            m = norm("ffn_post_norm")(m)
        return x + m


def _embed(b: LlamaConfig):
    return nn.Embed(b.vocab_size, b.dim, dtype=b.dtype,
                    param_dtype=jnp.float32)


def _head(b: LlamaConfig):
    head_dtype = jnp.float32 if b.logits_dot_in_fp32 else b.dtype
    return nn.Dense(b.vocab_size, use_bias=False, dtype=head_dtype,
                    param_dtype=jnp.float32)


def init_params(cfg: LoopedConfig, key):
    """The parameter tree: ``Llama(scan_layers=True)``'s names (the
    layers stacked under ``layers/block``), the extra norms where the
    config has them, and ``exit_gate``.  Every matrix ``normal(0,
    initializer_range)``, norm scales 1, the gate's bias 0."""
    b = cfg.block
    std = cfg.initializer_range
    shapes = jax.eval_shape(
        lambda: LoopBlock(cfg).init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 1, b.dim), b.dtype),
                                    lambda q, k, v: q)["params"])
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(leaves) + 3)
    normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)
    # a layer's vectors are norm scales, its matrices are drawn
    layers = [jnp.ones((b.n_layers,) + leaf.shape, jnp.float32)
              if leaf.ndim == 1 else normal(k, (b.n_layers,) + leaf.shape)
              for k, leaf in zip(keys[3:], leaves)]
    return {
        "tok_embeddings": {"embedding": normal(keys[0],
                                               (b.vocab_size, b.dim))},
        "layers": {"block": jax.tree.unflatten(treedef, layers)},
        "norm": {"scale": jnp.ones((b.dim,), jnp.float32)},
        "output": {"kernel": normal(keys[1], (b.dim, b.vocab_size))},
        "exit_gate": {"kernel": normal(keys[2], (b.dim, 1)),
                      "bias": jnp.zeros((1,), jnp.float32)},
    }


def _unfolded(*heads):
    """``heads`` (``[B, T, H, D]`` each, a projection's output split
    into heads) behind a barrier on their flat ``[B, T, H * D]`` form.
    Without it the TPU compiler folds the split into the projection (a
    convolution over the heads) and wants that matrix with its input
    axis minor: a transposed copy of wq, wk and wv of EVERY layer at the
    head of each program (sandbox, described v5e: 1.125 GiB of
    temporaries at the published sizes, read and written once a
    call)."""
    flat = lax.optimization_barrier(tuple(
        h.reshape(h.shape[:2] + (-1,)) for h in heads))
    return tuple(f.reshape(h.shape) for f, h in zip(flat, heads))


def _row_major(*arrays):
    from jax.experimental.layout import Layout, with_layout_constraint

    return tuple(with_layout_constraint(a, Layout(
        major_to_minor=tuple(range(a.ndim)))) for a in arrays)


def _cached_attend(b: LlamaConfig, q, k, v, kv, leaf, idx, live, slot):
    """Write this call's rotated keys and values at ``(leaf, idx)`` of
    the stacked pair ``kv`` and attend over that leaf: ``(out [B, T,
    n_q, D], kv')``.  With ``slot``, the pair is a pool's (a leading
    ``[capacity]`` axis) and the call is slot ``slot``'s."""
    t = q.shape[1]
    zero = jnp.zeros((), jnp.int32)
    at = (zero, leaf, zero, idx, zero)
    # KV-HEAD-MAJOR, as the cache lies: [B, 1, KV, T, D]
    k = jnp.swapaxes(k, 1, 2).astype(b.dtype)[:, None]
    v = jnp.swapaxes(v, 1, 2).astype(b.dtype)[:, None]
    if slot is None and b.decode_attn == "pallas" and t == 1:
        # the fused step writes the rows itself: under the engine's map
        # over slots XLA scatters them a (slot, leaf) at a time, which
        # cost more than the attention (PERF.md section 6, PR 40)
        from bluefog_tpu.parallel.pallas_decode import decode_attention

        out, ck, cv = decode_attention(
            q, *kv, idx, leaf=leaf, fresh=(k[:, 0, :, 0], v[:, 0, :, 0]),
            live=None if live is None else live[:, 0])
        return out, (ck, cv)
    if slot is not None:
        k, v, at = k[None], v[None], (slot,) + at
    ck, cv = (lax.dynamic_update_slice(c, new, at)
              for c, new in zip(kv, (k, v)))
    if slot is None:
        k_all, v_all = (lax.dynamic_index_in_dim(c, leaf, 1, keepdims=False)
                        for c in (ck, cv))
    else:
        one = (1,) + ck.shape[1:2] + (1,) + ck.shape[3:]
        k_all, v_all = (
            lax.dynamic_slice(c, (slot, zero, leaf, zero, zero, zero),
                              one)[0, :, 0] for c in (ck, cv))
    k_all, v_all = _row_major(k_all, v_all)
    # queries live at global positions [idx, idx + t); the causal mask
    # there also hides the leaf's unwritten tail
    return _cached_attention(q, k_all, v_all, idx), (ck, cv)


def _forward(cfg: LoopedConfig, params, tokens, *, cache=None,
             all_logits=False, live=None, slot=None):
    """``(logits, exit_pdf, cache')``.  ``cache`` None: the whole
    forward, every position's logits and exit distribution ``[B, T,
    loop_steps]``.  With a cache (one sequence batch's tree, or with
    ``slot`` a pool's): the tokens are appended at the cache index, the
    exit distribution is the last position's ``[B, loop_steps]`` and is
    left in ``stat_exit_pdf``."""
    b = cfg.block
    n_layers, steps = b.n_layers, cfg.loop_steps
    t = tokens.shape[1]
    at_slot = (lambda leaf: leaf) if slot is None else (
        lambda leaf: lax.dynamic_index_in_dim(leaf, slot, 0, keepdims=False))
    idx, kv = None, ()
    if cache is not None:
        idx = at_slot(cache["cache_index"])
        kv = (cache["cached_key"], cache["cached_value"])
    positions = jnp.arange(t, dtype=jnp.int32) + (0 if idx is None else idx)
    rotate = lambda a: rotary_embed(a, positions, b.rope_theta,
                                    b.rope_scaling, halves=cfg.rope_halves)
    block, final_norm = LoopBlock(cfg), RMSNorm(b.norm_eps)
    gate = jax.tree.map(lambda a: a.astype(jnp.float32),
                        params["exit_gate"])
    x = _embed(b).apply({"params": params["tok_embeddings"]}, tokens)

    def layer(carry, xs):
        x, kv = carry
        weights, leaf = xs
        held = [kv]

        def attend(q, k, v):
            with jax.named_scope(SCOPE_LOOP_ATTN):
                q, k, v = _unfolded(q, k, v)
                q, k = rotate(q), rotate(k)
                if cache is None:
                    return full_attention(q, k, v, causal=True)
                out, held[0] = _cached_attend(b, q, k, v, kv, leaf, idx,
                                              live, slot)
                return out

        x = block.apply({"params": weights}, x, attend, live)
        return (x, held[0]), None

    def one_pass(step, carry):
        x, kv, rest, pdf = carry
        step = jnp.asarray(step, jnp.int32)
        leaves = step * n_layers + jnp.arange(n_layers, dtype=jnp.int32)
        (x, kv), _ = lax.scan(layer, (x, kv),
                              (params["layers"]["block"], leaves))
        x = final_norm.apply({"params": params["norm"]}, x)
        lam = jax.nn.sigmoid(x.astype(jnp.float32) @ gate["kernel"]
                             + gate["bias"])[..., 0]
        # p_t = lambda_t prod_{j<t} (1 - lambda_j); the last pass takes
        # all that is left
        p = jnp.where(step == steps - 1, rest, lam * rest)
        return x, kv, rest * (1.0 - lam), lax.dynamic_update_index_in_dim(
            pdf, p, step, axis=2)

    with jax.named_scope(SCOPE_LOOP):
        x, kv, _, pdf = lax.fori_loop(
            0, steps, one_pass,
            (x, kv, jnp.ones(tokens.shape, jnp.float32),
             jnp.zeros(tokens.shape + (steps,), jnp.float32)))
    if cache is not None and not all_logits:
        # generation samples the final position alone
        x = x[:, -1:]
    logits = _head(b).apply({"params": params["output"]},
                            x).astype(jnp.float32)
    if cache is None:
        return logits, pdf, None
    # the small leaves: the sequence's own, or its slot of the pool's
    put = (lambda name, new: new) if slot is None else (
        lambda name, new: lax.dynamic_update_index_in_dim(
            cache[name], new, slot, 0))
    return logits, pdf[:, -1], {
        "cache_index": put("cache_index", idx + t),
        "cached_key": kv[0], "cached_value": kv[1],
        "stat_exit_pdf": put("stat_exit_pdf", pdf[:, -1])}
