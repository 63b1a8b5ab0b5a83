"""Plain reference of the latent-attention decoder with routed experts
(Mistral-Small-4-119B-2603's language model,
https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/config.json,
``model_type: mistral4``; the layer's keys are DeepSeek-V3's to the
letter and are read that way): float32 ``jax.numpy`` with every
contraction at ``Precision.HIGHEST``, EXPANDED keys and values only (no
absorbed form, no cache, no kernels), no batching beyond one sequence,
and no import from ``bluefog_tpu``.  Weights come in as data, in the
layout ``families/mla_moe_decoder.make_params`` makes them.

The forward pass of one sequence (``sz``: the configuration's sizes with
the cut's overrides; ``H`` = num_attention_heads 32, position ``p``)::

    h = E[tok]
    for each of the layers kept, all alike (first_k_dense_replace 0):
      a = rms(h)                                          rms_norm_eps
      c_q = rms(a W_dq)                   hidden_size -> q_lora_rank 1024
      q = c_q W_uq                        -> H x (qk_nope 64 + qk_rope 64)
      per head q = [q_n ; q_r]
      [c ; k_r] = a W_dkv                 -> kv_lora_rank 256 + qk_rope 64
      c = rms(c)
      k_r = rope(k_r, p)                  ONE rotated key for all heads
      [k_n ; v] = c W_ukv                 -> H x (qk_nope 64 + v_head 128)
      q_r = rope(q_r, p);  k = [k_n ; k_r]
      q = q (1 + beta ln(1 + floor(p / original_max)))
                       beta = llama_4_scaling_beta 0.1, original_max 8192
      o = softmax(s q k^T, j <= i) v
          s = (64 + 64)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
      h = h + [o_1 .. o_H] W_o
      m = rms(h)
      g = softmax(m W_r)                  float32, all router_outputs 128
      T = top num_experts_per_tok 4 of g           no bias, no groups
      w_e = routed_scaling_factor g_e / (sum over T of g + 1e-20)
                                                   (norm_topk_prob)
      f = shared(m) + sum over e in T, e held, of w_e expert_e(m)
          each W2(silu(W1 x) * W3 x) at moe_intermediate_size 2048
      h = h + f
    logits = rms(h) W_out                          untied

``rope``: interleaved pairs ``(x[2i], x[2i+1])`` over the 64 rotated
columns (``rope_interleave``) with YaRN's frequencies
(``rope_parameters``)::

    f_i = rope_theta^(-2i/64),  i = 0 .. 31
    d(r) = 64 ln(original_max / (2 pi r)) / (2 ln rope_theta)
    low = max(floor(d(beta_fast)), 0),  high = min(ceil(d(beta_slow)), 63)
    ramp_i = clip((i - low) / (high - low), 0, 1)
    freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i

and cos and sin times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
mscale_all_dim)``, which is 1 for this model.

``intermediate_size`` 12288 belongs to no layer (every layer is an
expert layer) and plays no part.  The share: the experts
``experts_held_from .. + n_routed_experts`` of ``router_outputs`` are
held; the router keeps every output and its 4 experts a token, and what
an absent expert would have added is left out.  The vocabulary is the
slice the file states.

What the source's ``config.json`` does not itself state is listed in the
configuration file under ``assumed`` (the softmax score function and the
absent bias, the ``m^2`` on the softmax scale, where the two inner norms
sit and their ``eps``, the query scale's formula and that it multiplies
the whole query), each with the choice made here.

Attention is computed in blocks of query rows, and the expert layer in
blocks of tokens with a loop over the held experts, only so that 16,384
positions in float32 fit on one chip beside the weights; neither changes
a value.

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
TOKEN_BLOCK = 2048
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


# ------------------------------------------------------------------ #
# positions
# ------------------------------------------------------------------ #
def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(rope: dict, dim: int):
    """``(low, high)``: the pairs between which the ramp runs."""
    def d(rotations):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope["rope_theta"]))

    return (max(math.floor(d(rope["beta_fast"])), 0),
            min(math.ceil(d(rope["beta_slow"])), dim - 1))


def yarn_freqs(rope: dict, dim: int):
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = jnp.float32(rope["rope_theta"]) ** (-2.0 * i / dim)
    low, high = yarn_range(rope, dim)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1 - ramp) + plain / rope["factor"] * ramp


def rope_pairs(x, positions, rope: dict):
    """x ``[T, H, D]``: rotate the interleaved pairs ``(x[2i],
    x[2i+1])`` by ``positions * freq_i``."""
    ang = positions.astype(jnp.float32)[:, None] \
        * yarn_freqs(rope, x.shape[-1])[None, :]
    turn = yarn_mscale(rope["factor"], rope["mscale"]) \
        / yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    cos, sin = jnp.cos(ang)[:, None, :] * turn, jnp.sin(ang)[:, None, :] * turn
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def query_scale(positions, sz: dict):
    """``1 + beta ln(1 + floor(p / original_max))``, ``[T]``."""
    rope = sz["rope_parameters"]
    return 1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        positions.astype(jnp.float32)
        / rope["original_max_position_embeddings"]))


def softmax_scale(sz: dict) -> float:
    rope = sz["rope_parameters"]
    m = yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    return m * m / math.sqrt(sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"])


# ------------------------------------------------------------------ #
# the layer
# ------------------------------------------------------------------ #
def _blocks(t: int, most: int) -> int:
    block = min(most, t)
    while t % block:
        block -= 1
    return block


def attention(q, k, v, scale, mm):
    """Causal attention of one sequence over EXPANDED keys and values.
    q, k ``[T, H, D]``, v ``[T, H, Dv]``; ``[T, H * Dv]``."""
    t, h, d = q.shape
    block = _blocks(t, Q_BLOCK)
    key_pos = jnp.arange(t)

    def rows(args):
        qb, start = args
        s = mm("qhd,shd->hqs", qb, k) * scale
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm("hqs,shv->qhv", p, v)

    out = jax.lax.map(rows, (q.reshape(t // block, block, h, d),
                             jnp.arange(0, t, block)))
    return out.reshape(t, -1)


def swiglu(x, w, mm):
    gate = mm("td,df->tf", x, w["w1"]["kernel"])
    up = mm("td,df->tf", x, w["w3"]["kernel"])
    return mm("tf,fd->td", jax.nn.silu(gate) * up, w["w2"]["kernel"])


def route(m, moe, sz, mm):
    """``(chosen [T, k], weights [T, k])``: softmax over every router
    output, the top ``num_experts_per_tok``, their scores over their
    sum, times ``routed_scaling_factor``.  No bias."""
    g = jax.nn.softmax(mm("td,de->te", m, moe["router"]), axis=-1)
    picked, chosen = jax.lax.top_k(g, sz["num_experts_per_tok"])
    if sz["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * sz["routed_scaling_factor"]


def routed_part(m, moe, sz, mm):
    """The held experts' part of the routed sum, ``[T, dim]``: token
    blocks, and inside each a loop over the held experts, every expert
    applied to the whole block and weighted by zero where a token did
    not choose it."""
    t, d = m.shape
    first, held = sz.get("experts_held_from", 0), sz["n_routed_experts"]
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


def latent_attention(a, att, sz, positions, mm):
    """The attention sublayer's output ``[T, hidden]`` from its normed
    input ``a``: keys and values EXPANDED from the latent."""
    t = a.shape[0]
    h, dc = sz["num_attention_heads"], sz["kv_lora_rank"]
    dn, dr, dv = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                  sz["v_head_dim"])
    eps, rope = sz["rms_norm_eps"], sz["rope_parameters"]
    c_q = rmsnorm(mm("td,dr->tr", a, att["wq_a"]["kernel"]),
                  att["q_norm"]["scale"], eps)
    q = mm("tr,rf->tf", c_q, att["wq_b"]["kernel"]).reshape(t, h, dn + dr)
    ckr = mm("td,dc->tc", a, att["wkv_a"]["kernel"])
    c = rmsnorm(ckr[:, :dc], att["kv_norm"]["scale"], eps)
    k_r = rope_pairs(ckr[:, None, dc:], positions, rope)        # [T, 1, dr]
    kv = mm("tc,chf->thf", c, att["wkv_b"])                     # [T, H, dn+dv]
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], positions,
                                                 rope)], -1)
    q = q * query_scale(positions, sz)[:, None, None]
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (t, h, dr))],
                        -1)
    o = attention(q, k, kv[..., dn:], softmax_scale(sz), mm)
    return mm("tf,fd->td", o, att["wo"]["kernel"])


def block(x, lp, sz, positions, mm):
    eps = sz["rms_norm_eps"]
    x = x + latent_attention(
        rmsnorm(x, lp["attention_norm"]["scale"], eps), lp["attention"],
        sz, positions, mm)
    m = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
    return x + swiglu(m, lp["moe"]["shared"], mm) \
        + routed_part(m, lp["moe"], sz, mm)


def hidden(params, tokens, sz, mm):
    """Final-norm hidden states ``[T, dim]`` of one sequence."""
    x = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])
    for i in range(sz["num_hidden_layers"]):
        x = block(x, params[f"layer_{i}"], sz, positions, mm)
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
    layer kept, the router, the shared expert and ``experts_hit`` routed
    experts a layer (the mean number of held experts that at least one
    token of the step chose), and the head's slice; the embedding is a
    lookup of a few rows."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    dc, dn, dr, dv = (sz["kv_lora_rank"], sz["qk_nope_head_dim"],
                      sz["qk_rope_head_dim"], sz["v_head_dim"])
    rq = sz["q_lora_rank"]
    attention = d * rq + rq * h * (dn + dr) + d * (dc + dr) \
        + dc * h * (dn + dv) + h * dv * d
    expert = 3 * d * sz["moe_intermediate_size"]
    return (sz["num_hidden_layers"] * (
        attention + d * sz["router_outputs"]
        + (sz["n_shared_experts"] + experts_hit) * expert)
        + d * sz["vocab_size"])


def cache_bytes_per_position(sz: dict) -> int:
    """The normed latent and the one rotated key of one position of one
    layer: all a position leaves behind."""
    return (sz["kv_lora_rank"] + sz["qk_rope_head_dim"]) \
        * WIDTH[sz["compute_dtype"]]


def moe_decode_step_bytes(sz: dict, experts_hit: float,
                          attended_positions: float) -> float:
    """HBM bytes one decode step must read at the least: the weights of
    ``decode_weight_params`` in the held dtype and the latent rows of
    the ``attended_positions`` (positions times layers, all slots) its
    queries see, each once."""
    return (decode_weight_params(sz, experts_hit)
            * WIDTH[sz["param_dtype"]]
            + attended_positions * cache_bytes_per_position(sz))
