"""Plain reference of the ``afmoe`` decoder (Trinity-Large-Preview,
https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json):
float32 ``jax.numpy`` with every contraction at ``Precision.HIGHEST``,
no kernels, no cache, no batching beyond one sequence, and no import
from ``bluefog_tpu``.  Weights come in as data, in the layout
``families/afmoe_decoder.make_params`` makes them.

The forward pass of one sequence (``sz``: the configuration's sizes
with the cut's overrides)::

    h = E[tok] * sqrt(hidden_size)                       (mup_enabled)
    for each layer l kept (its kind from layer_types):
      a = norm1(h);  q, k, v = a Wq, a Wk, a Wv          48 / 8 / 8 heads
      q, k = rms(q), rms(k)        per head of 128, learned scales
      sliding_attention: q, k = rope(q, k, pos; rope_theta), interleaved
        pairs; key j visible to query i where 0 <= i - j < sliding_window
      full_attention:    no rotation; key j visible where j <= i
      o = softmax(q k^T / sqrt(head_dim)) v
      o = o * sigmoid(a Wg)                              Wg: 3072 -> 6144
      h = h + norm2(o Wo)
      m = norm3(h)
      the first num_dense_layers: f = W2(silu(W1 m) * W3 m), width 12288
      the others: s = sigmoid(m Wr)                      router_outputs
                  T = top num_experts_per_tok of (s + b)
                  w_e = route_scale * s_e / (sum over T of s + 1e-20)
                  f = shared(m) + sum over e in T, e held, of
                      w_e * expert_e(m)                  SwiGLU, 3072
      h = h + norm4(f)
    logits = norm(h) W_out                               untied

``n_group = topk_group = 1``: no group limit.  ``load_balance_coeff``
is a training matter and plays no part.  The share: the experts
``experts_held_from .. + num_experts`` of ``router_outputs`` are held;
the router keeps every output and its ``num_experts_per_tok``, and what
an absent expert would have added is left out (the chip's partial
result goes on to the next layer, as in the deployment of the
configuration file).  The vocabulary is the slice the file states.

What the source's ``config.json`` does not itself state is listed in
the configuration file under ``assumed`` (the head norms, the gate's
place, which layers rotate, the order of the four norms, the embedding
scale, how the bias enters), each with the choice made here.

Attention is computed in blocks of query rows, and the expert layer in
blocks of tokens with a loop over the held experts, only so that
16,384 positions in float32 fit on one chip beside the weights;
neither changes a value.

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
TOKEN_BLOCK = 2048
SLIDING = "sliding_attention"
WIDTH = {"bfloat16": 2, "float32": 4}


def mm_highest(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fake_fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448), as
    an fp8 matmul path would, and back to float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm_fp8(spec: str, a, b):
    """The control: the precision below bfloat16.  Operands rounded to
    fp8, products accumulated exactly."""
    return jnp.einsum(spec, _fake_fp8(a), _fake_fp8(b), precision=HIGHEST)


mm_control = mm_fp8


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x ``[T, H, D]``: rotate the interleaved pairs ``(x[2i],
    x[2i+1])`` by ``positions * theta ** (-2i / D)``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _blocks(t: int, most: int) -> int:
    block = min(most, t)
    while t % block:
        block -= 1
    return block


def attention(q, k, v, mm, window=None):
    """Causal grouped-query attention of one sequence, under ``window``
    only over the ``window`` newest keys.  q ``[T, Hq, D]``, k and v
    ``[T, Hkv, D]``; query head ``h`` reads key head ``h // (Hq //
    Hkv)``."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    block = _blocks(t, Q_BLOCK)
    q = q.reshape(t // block, block, hkv, hq // hkv, d)
    key_pos = jnp.arange(t)

    def rows(args):
        qb, start = args
        s = mm("qkrd,skd->krqs", qb, k) / jnp.sqrt(jnp.float32(d))
        gap = (start + jnp.arange(block))[:, None] - key_pos[None, :]
        seen = gap >= 0
        if window is not None:
            seen &= gap < window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm("krqs,skd->qkrd", p, v)

    out = jax.lax.map(rows, (q, jnp.arange(0, t, block)))
    return out.reshape(t, hq * d)


def swiglu(x, w, mm):
    gate = mm("td,df->tf", x, w["w1"]["kernel"])
    up = mm("td,df->tf", x, w["w3"]["kernel"])
    return mm("tf,fd->td", jax.nn.silu(gate) * up, w["w2"]["kernel"])


def route(m, moe, sz, mm):
    """``(chosen [T, k], weights [T, k])``: the bias enters the choice,
    the weights come from the unbiased scores."""
    s = jax.nn.sigmoid(mm("td,de->te", m, moe["router"]))
    _, chosen = jax.lax.top_k(s + moe["router_bias"].astype(jnp.float32),
                              sz["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    if sz["route_norm"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * sz["route_scale"]


def routed_part(m, moe, sz, mm):
    """The held experts' part of the routed sum, ``[T, dim]``: token
    blocks, and inside each a loop over the held experts, every expert
    applied to the whole block and weighted by zero where a token did
    not choose it."""
    t, d = m.shape
    first, held = sz.get("experts_held_from", 0), sz["num_experts"]
    chosen, weights = route(m, moe, sz, mm)
    block = _blocks(t, TOKEN_BLOCK)

    def tokens(args):
        mb, cb, wb = args

        def expert(acc, xs):
            e, w1, w3, w2 = xs
            share = jnp.sum(jnp.where(cb == e, wb, 0.0), -1)   # [block]
            act = jax.nn.silu(mm("td,df->tf", mb, w1)) \
                * mm("td,df->tf", mb, w3)
            return acc + mm("tf,fd->td", act * share[:, None], w2), None

        out, _ = jax.lax.scan(
            expert, jnp.zeros((block, d), jnp.float32),
            (first + jnp.arange(held), moe["w1"], moe["w3"], moe["w2"]))
        return out

    k = chosen.shape[-1]
    out = jax.lax.map(tokens, (m.reshape(t // block, block, d),
                               chosen.reshape(t // block, block, k),
                               weights.reshape(t // block, block, k)))
    return out.reshape(t, d)


def layer_kinds(sz: dict):
    """The ``layer_types`` of the layers kept, in order."""
    kept = sz.get("layers_kept") or range(sz["num_hidden_layers"])
    return [sz["layer_types"][i] for i in kept]


def block(x, lp, sz, kind, dense, positions, mm):
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    eps = sz["rms_norm_eps"]
    t = x.shape[0]
    att = lp["attention"]
    a = rmsnorm(x, lp["attention_norm"]["scale"], eps)
    q = mm("td,df->tf", a, att["wq"]["kernel"]).reshape(t, nq, hd)
    k = mm("td,df->tf", a, att["wk"]["kernel"]).reshape(t, nkv, hd)
    v = mm("td,df->tf", a, att["wv"]["kernel"]).reshape(t, nkv, hd)
    q = rmsnorm(q, att["q_norm"]["scale"], eps)
    k = rmsnorm(k, att["k_norm"]["scale"], eps)
    if kind == SLIDING:
        q = rope(q, positions, sz["rope_theta"])
        k = rope(k, positions, sz["rope_theta"])
        o = attention(q, k, v, mm, sz["sliding_window"])
    else:
        o = attention(q, k, v, mm)
    o = o * jax.nn.sigmoid(mm("td,df->tf", a, att["wg"]["kernel"]))
    x = x + rmsnorm(mm("tf,fd->td", o, att["wo"]["kernel"]),
                    lp["attention_post_norm"]["scale"], eps)
    m = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
    if dense:
        f = swiglu(m, lp["feed_forward"], mm)
    else:
        f = swiglu(m, lp["moe"]["shared"], mm) \
            + routed_part(m, lp["moe"], sz, mm)
    return x + rmsnorm(f, lp["ffn_post_norm"]["scale"], eps)


def hidden(params, tokens, sz, mm):
    """Final-norm hidden states ``[T, dim]`` of one sequence."""
    x = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32) \
        * jnp.sqrt(jnp.float32(sz["hidden_size"]))
    positions = jnp.arange(tokens.shape[0])
    for i, kind in enumerate(layer_kinds(sz)):
        x = block(x, params[f"layer_{i}"], sz, kind,
                  i < sz["num_dense_layers"], positions, mm)
    return rmsnorm(x, params["norm"]["scale"], sz["rms_norm_eps"])


def logits(params, tokens, sz, mm=mm_highest, rows=None):
    """Logits ``[T, vocab]`` of one sequence, or of its ``rows`` only."""
    h = hidden(params, tokens, sz, mm)
    if rows is not None:
        h = h[rows]
    return mm("td,dv->tv", h, params["output"])


# ------------------------------------------------------------------ #
# bytes, from shapes alone
# ------------------------------------------------------------------ #
def decode_weight_params(sz: dict, experts_hit: float) -> float:
    """Parameters one decode step must read: every projection of every
    layer kept, the dense FFN, and in each expert layer the router, the
    shared expert and ``experts_hit`` routed experts (the mean number of
    held experts that at least one token of the step chose), and the
    head's slice; the embedding is a lookup of a few rows."""
    d, hd = sz["hidden_size"], sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    attention = 3 * d * nq * hd + 2 * d * nkv * hd   # Wq, Wg, Wo; Wk, Wv
    expert = 3 * d * sz["moe_intermediate_size"]
    n_dense = sz["num_dense_layers"]
    n_moe = sz["num_hidden_layers"] - n_dense
    return (sz["num_hidden_layers"] * attention
            + n_dense * 3 * d * sz["intermediate_size"]
            + n_moe * (d * sz["router_outputs"]
                       + sz["num_shared_experts"] * expert
                       + experts_hit * expert)
            + d * sz["vocab_size"])


def cache_bytes_per_position(sz: dict) -> int:
    """Keys and values of one position of one layer."""
    return 2 * sz["num_key_value_heads"] * sz["head_dim"] \
        * WIDTH[sz["compute_dtype"]]


def moe_decode_step_bytes(sz: dict, experts_hit: float,
                          attended_positions: float) -> float:
    """HBM bytes one decode step must read at the least: the weights of
    ``decode_weight_params`` in the held dtype and the keys and values
    of the ``attended_positions`` (positions times layers, all slots)
    its queries see."""
    return (decode_weight_params(sz, experts_hit)
            * WIDTH[sz["param_dtype"]]
            + attended_positions * cache_bytes_per_position(sz))
