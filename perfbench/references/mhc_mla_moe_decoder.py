"""Plain reference of the latent-attention decoder whose residual path is
FOUR STREAMS mixed a token at a time by manifold-constrained
hyper-connections (Xing4.0-29B-A4B's language model,
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json,
``model_type: xing4_0``; the layer's keys are DeepSeek-V3's and the
``hc_*`` / ``mhc_*`` keys those of arXiv:2512.24880 over
arXiv:2409.19606): float32 ``jax.numpy`` with every contraction at
``Precision.HIGHEST``, EXPANDED keys and values only (no absorbed form,
no cache, no kernels), no batching beyond one sequence, and no import
from ``bluefog_tpu``.  Weights come in as data, in the layout
``families/mhc_mla_moe_decoder.make_params`` makes them.

The latent attention ``A`` is the accepted reference's
(``references/mla_moe_decoder.latent_attention``, imported, with this
model's rotation keys handed to it under the names it reads:
``base_sizes``): queries through ``q_lora_rank`` 768, 32 heads of 128
unrotated + 64 rotated columns, one normed latent of 512 and one rotated
key of 64 a position, values of 128; YaRN factor 64 over 4096 with
``mscale`` = ``mscale_all_dim`` = 1, so the score scale is ``192^-1/2 x
(0.1 ln 64 + 1)^2`` and cos and sin carry 1; NO position scale on the
query (this model has no such key: beta 0).

The forward pass of one sequence (``sz``: the configuration's sizes with
the cut's overrides; ``n`` = hc_mult 4, ``C`` = hidden_size 3584; a
token's residual is ``X`` in ``R^{n x C}``)::

    X[i] = E[tok]                                   for all n streams
    for each of the layers kept, for F in (A, then FF):
      xh        = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
      [p, q, R] = xh phi                  phi [nC, 2n + n^2], R row-major
      H_pre     = sigmoid(alpha_pre p + b_pre)
      H_post    = 2 sigmoid(alpha_post q + b_post)
      M         = exp(clip(alpha_res R + b_res, mhc_h_res_clamp_min,
                                               mhc_h_res_clamp_max))
      hc_sinkhorn_iters (20) times:
          M = M / (rowsum(M) + hc_eps);  M = M / (colsum(M) + hc_eps)
      y         = F(rms(sum_i H_pre[i] X[i]))       F's own learned norm
      X'[i]     = sum_j M[i, j] X[j] + H_post[i] y
    logits = rms(sum_i X[i]) W_out                             untied

    FF, the first first_k_dense_replace layers:
      W2(silu(W1 m) * W3 m) at intermediate_size 9216
    FF, the layers after them:
      g = sigmoid(m W_r)                  float32, all router_outputs 64
      T = top num_experts_per_tok 4 of g + b      the bias selects only
      w_e = routed_scaling_factor g_e / (sum over T of g + 1e-20)
      shared(m) + sum over e in T, e held, of w_e expert_e(m)
          each W2(silu(W1 x) * W3 x) at moe_intermediate_size 1024

The multi-token-prediction module (``num_nextn_predict_layers`` 1) is a
draft head the main model's logits do not depend on and is left out.
The share: every one of the 64 experts is held
(``experts_held_from`` 0, ``n_routed_experts`` = ``router_outputs``);
the code still takes a share, as the accepted reference does.  What the
source's ``config.json`` does not itself state is listed in the
configuration file under ``assumed``, each with the choice made here.

The expert layer is computed in blocks of tokens with a loop over the
held experts only so that 4,096 positions in float32 fit on one chip
beside the weights; it changes no value.

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references.mla_moe_decoder import (  # noqa: F401
    TOKEN_BLOCK, WIDTH, _blocks, cache_bytes_per_position, latent_attention,
    mm_control, mm_highest, rmsnorm, swiglu)


def base_sizes(sz: dict) -> dict:
    """``sz`` with the rotation's keys under the names the accepted
    reference reads (``rope_parameters`` with ``rope_theta`` inside):
    this config has them as ``rope_scaling`` and ``rope_theta``, and has
    no query scale (``llama_4_scaling_beta`` 0: the factor is 1)."""
    return dict(sz, rope_parameters=dict(
        sz["rope_scaling"], rope_theta=sz["rope_theta"],
        llama_4_scaling_beta=0.0))


# ------------------------------------------------------------------ #
# the residual path
# ------------------------------------------------------------------ #
def mixing(x, hp, sz, mm):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the
    streams ``x [T, n, C]`` for one sublayer's parameters ``hp``."""
    t, n, _ = x.shape
    flat = x.reshape(t, -1)
    xh = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                         + sz["rms_norm_eps"])
    proj = mm("tk,kf->tf", xh, hp["phi"])
    a_pre, a_post, a_res = hp["alpha"]
    h_pre = jax.nn.sigmoid(a_pre * proj[:, :n] + hp["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a_post * proj[:, n:2 * n] + hp["b_post"])
    m = jnp.exp(jnp.clip(
        a_res * proj[:, 2 * n:].reshape(t, n, n) + hp["b_res"],
        sz["mhc_h_res_clamp_min"], sz["mhc_h_res_clamp_max"]))
    for _ in range(sz["hc_sinkhorn_iters"]):
        m = m / (m.sum(2, keepdims=True) + sz["hc_eps"])    # rows
        m = m / (m.sum(1, keepdims=True) + sz["hc_eps"])    # columns
    return h_pre, h_post, m


def mixed(x, hp, scale, f, sz, mm):
    """One sublayer ``f`` around the streams ``x [T, n, C]``."""
    h_pre, h_post, h_res = mixing(x, hp, sz, mm)
    y = f(rmsnorm(jnp.sum(h_pre[:, :, None] * x, 1), scale,
                  sz["rms_norm_eps"]))
    return jnp.sum(h_res[:, :, :, None] * x[:, None, :, :], 2) \
        + h_post[:, :, None] * y[:, None, :]


# ------------------------------------------------------------------ #
# the expert layer
# ------------------------------------------------------------------ #
def route(m, moe, sz, mm):
    """``(chosen [T, k], weights [T, k])``: sigmoid scores over every
    router output, the top ``num_experts_per_tok`` of scores + bias,
    their scores (not the bias) over their sum, times
    ``routed_scaling_factor``."""
    g = jax.nn.sigmoid(mm("td,de->te", m, moe["router"]))
    _, chosen = jax.lax.top_k(g + moe["router_bias"],
                              sz["num_experts_per_tok"])
    picked = jnp.take_along_axis(g, chosen, -1)
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


def feed_forward(m, lp, sz, mm):
    if "feed_forward" in lp:
        return swiglu(m, lp["feed_forward"], mm)
    return swiglu(m, lp["moe"]["shared"], mm) \
        + routed_part(m, lp["moe"], sz, mm)


# ------------------------------------------------------------------ #
# the model
# ------------------------------------------------------------------ #
def block(x, lp, sz, positions, mm):
    attention_sizes = base_sizes(sz)
    x = mixed(x, lp["attention_hc"], lp["attention_norm"]["scale"],
              lambda a: latent_attention(a, lp["attention"],
                                         attention_sizes, positions, mm),
              sz, mm)
    return mixed(x, lp["ffn_hc"], lp["ffn_norm"]["scale"],
                 lambda m: feed_forward(m, lp, sz, mm), sz, mm)


def hidden(params, tokens, sz, mm):
    """Final-norm hidden states ``[T, dim]`` of one sequence."""
    e = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32)
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], sz["hc_mult"],
                                         e.shape[1]))
    positions = jnp.arange(tokens.shape[0])
    for i in range(sz["num_hidden_layers"]):
        x = block(x, params[f"layer_{i}"], sz, positions, mm)
    return rmsnorm(x.sum(1), params["norm"]["scale"], sz["rms_norm_eps"])


def logits(params, tokens, sz, mm=mm_highest, rows=None):
    """Logits ``[T, vocab]`` of one sequence, or of its ``rows`` only."""
    h = hidden(params, tokens, sz, mm)
    if rows is not None:
        h = h[rows]
    return mm("td,dv->tv", h, params["output"])


# ------------------------------------------------------------------ #
# bytes, from shapes alone
# ------------------------------------------------------------------ #
def mixing_params(sz: dict) -> int:
    """One sublayer's mixing parameters (float32): ``phi``, the three
    ``alpha``, the two bias vectors and the bias matrix."""
    n = sz["hc_mult"]
    return n * sz["hidden_size"] * (2 * n + n * n) + 3 + 2 * n + n * n


def decode_step_weight_bytes(sz: dict, experts_hit: float) -> float:
    """Bytes of weights one decode step must read: every projection of
    every layer kept and the head in the held dtype; the leading dense
    layers' feed-forward; in the expert layers the shared expert and
    ``experts_hit`` routed experts a layer (the mean number of held
    experts that at least one token of the step chose); the router and
    the mixing parameters of both sublayers in float32.  The embedding
    is a lookup of a few rows."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    dc, dn, dr, dv = (sz["kv_lora_rank"], sz["qk_nope_head_dim"],
                      sz["qk_rope_head_dim"], sz["v_head_dim"])
    rq = sz["q_lora_rank"]
    layers, dense = sz["num_hidden_layers"], sz["first_k_dense_replace"]
    attention = d * rq + rq * h * (dn + dr) + d * (dc + dr) \
        + dc * h * (dn + dv) + h * dv * d
    expert = 3 * d * sz["moe_intermediate_size"]
    held = layers * attention + dense * 3 * d * sz["intermediate_size"] \
        + (layers - dense) * (sz["n_shared_experts"] + experts_hit) * expert \
        + d * sz["vocab_size"]
    float32 = (layers - dense) * d * sz["router_outputs"] \
        + 2 * layers * mixing_params(sz)
    return held * WIDTH[sz["param_dtype"]] + float32 * WIDTH["float32"]


def moe_decode_step_bytes(sz: dict, experts_hit: float,
                          attended_positions: float) -> float:
    """HBM bytes one decode step must read at the least: the weights of
    ``decode_step_weight_bytes`` and the latent rows (576 wide at the
    published widths: 1,152 bytes) of the ``attended_positions``
    (positions times layers, all slots) its queries see, each once.  The
    streams of the step's few tokens are left out: counted low, never
    high."""
    return decode_step_weight_bytes(sz, experts_hit) \
        + attended_positions * cache_bytes_per_position(sz)


def hc_mix_bytes_per_token(sz: dict) -> int:
    """Bytes the mixing of ONE token around ONE sublayer must move at
    the least, whatever implements it, at the streams' width: ``hc_pre``
    reads the n streams once (a token tile's ``X`` stays on chip between
    the statistics and the weighted sum: ``xh phi = (vec(X) phi) /
    rms``), ``hc_post`` reads the n streams and ``y`` and writes the n
    streams: ``(n + n + 1 + n) x hidden_size`` values; 93,184 at the
    published widths in bfloat16.  The coefficients (a few dozen floats
    a token) and ``y_in`` (written by ``hc_pre``, read by the sublayer's
    norm, which a fused form keeps on chip) are left out: counted low,
    never high."""
    n = sz["hc_mult"]
    return (3 * n + 1) * sz["hidden_size"] * WIDTH[sz["compute_dtype"]]


def hc_chunk_bytes(sz: dict, executions: int, tokens: int) -> float:
    """The least bytes of mixing of ``executions`` calls of the
    PREFILL-CHUNK program over ``tokens`` tokens each (the chunk's
    width, padding included: the device mixes it too).  A chunk discards
    its logits, so the last layer's feed-forward feeds nothing the
    program returns and the compiler drops it (``PERF.md`` section 5
    shows the same in both other expert cells) with the ``hc_post``
    around it; but the last layer's ROUTER still runs, because the
    expert statistics it gives are leaves of the cache a chunk returns,
    and the router reads the streams through that sublayer's ``H_pre``.
    So a chunk program executes every sublayer whole but the last, and
    of the last its ``hc_pre``'s weighted sum alone (the n streams read
    once: n of the 3n + 1 units; read off the compiled program's
    operations by scope and layer, sandbox, PR 32).  No work is counted
    that the program does not do."""
    n = sz["hc_mult"]
    pre_alone = n * sz["hidden_size"] * WIDTH[sz["compute_dtype"]]
    per_token = (2 * sz["num_hidden_layers"] - 1) \
        * hc_mix_bytes_per_token(sz) + pre_alone
    return float(executions) * tokens * per_token
