"""Plain reference of the decoder whose layers are of two kinds, KIMI
DELTA ATTENTION five to one latent attention (Ling-3.0-flash-VL's
language model,
https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json;
the recurrent mixer is arXiv:2510.26692 section 3 in the form of
``fla``'s ``KimiDeltaAttention``, the latent layer's keys DeepSeek-V3's):
float32 ``jax.numpy`` with every contraction at ``Precision.HIGHEST``,
the recurrence TOKEN BY TOKEN, expanded keys and values only, no cache,
no kernels, no batching beyond one sequence, and no import from
``bluefog_tpu``.  Weights come in as data, in the layout
``families/kda_mla_moe_decoder.make_params`` makes them.

The forward pass of one sequence (``sz``: the configuration's sizes with
the cut's overrides; ``H`` = num_attention_heads 32, ``D`` = head_dim
128; published layer ``i`` is LATENT where ``(i + 1) % layer_group_size
== 0``, else KDA)::

    h = E[tok]
    for each of the layers kept:
      a = rms(h)                                          rms_norm_eps
      KDA:
        q~, k~, v~ = a W_q, a W_k, a W_v                  each H x D
        q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
              y_t = sum_j filter[j] x_(t - 3 + j), j = 0 .. 3
              (short_conv_kernel_size 4, one filter a channel, zeros
              before the sequence)
        q = q / sqrt(|q|^2 + 1e-6) * D^-1/2;  k = k / sqrt(|k|^2 + 1e-6)
        g_t = kda_lower_bound * sigmoid(exp(A_log_h) (a W_f + dt_bias))
        beta_t = sigmoid(a W_beta)
        S_t = Diag(e^g_t) S_(t-1);  S_t += beta_t k_t (v_t - S_t^T k_t)^T
        o_t = S_t^T q_t                              S_0 = 0, D x D a head
        h = h + [sigmoid(a W_g) * rms_head(o_t)] W_o
      LATENT:
        q = a W_q                          H x (qk_nope 128 + qk_rope 64)
        [c ; k_r] = a W_dkv;  c = rms(c)   kv_lora_rank 512 + 64
        q_r, k_r = rope(q_r, p), rope(k_r, p)   rope_theta 6e6, no scaling
        [k_n ; v] = c W_ukv                H x (128 + 128)
        o = softmax(192^-1/2 [q_n ; q_r] [k_n ; k_r]^T, j <= i) v
        o_h = sigmoid(a W_gate)_h o_h      head_wise, W_gate 2560 x 32
        h = h + [o_1 .. o_H] W_o
      m = rms(h)
      the first first_k_dense_replace layers kept:
        h = h + W2(silu(W1 m) * W3 m)      intermediate_size 6144
      the others:
        g = sigmoid(m W_r)                 float32, all router_outputs 512
        n_group 8 groups of 64 neighbours; a group scores the sum of its
        two largest g + b; the best topk_group 4 groups are kept
        T = top num_experts_per_tok 8 of g + b among the kept groups
        w_e = routed_scaling_factor 2.5 g_e / (sum over T of g + 1e-20)
        h = h + shared(m) + sum over e in T, e held, of w_e expert_e(m)
            each W2(silu(W1 x) * W3 x) at moe_intermediate_size 768
    logits = rms(h) W_out                  untied

The share: the experts ``experts_held_from .. + num_experts`` of
``router_outputs`` are held; the router keeps every output, its groups
and its 8 experts a token, and what an absent expert would have added is
left out.  The vocabulary is the slice the file states.  What the
source's ``config.json`` does not itself state is listed in the
configuration file under ``assumed``, each with the choice made here.
The vision tower and the multi-token-prediction head are left out.

Two things are done only so that 16,384 padded positions in float32
stay under ten seconds a request, and neither changes a value that is
read.  (1) With ``rows`` given, the recurrence stops after the last row
read (the model is causal: no later position reaches a row).  (2) The
routed sum applies an expert TO THE ROWS THAT CHOSE IT: inside a block
of tokens, for each held expert, the rows that chose it are gathered
``EXPERT_ROWS`` at a time, as many times as it takes (dropless), and
their results added back; 128 experts applied densely to every row
would be 148 TFLOP a request.

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references.mla_moe_decoder import (  # noqa: F401
    TOKEN_BLOCK, WIDTH, _blocks, attention, cache_bytes_per_position,
    mm_control, mm_highest, rmsnorm, rope_pairs, softmax_scale, swiglu)

EXPERT_ROWS = 256
L2_EPS = 1e-6


def layer_types(sz: dict) -> tuple:
    """The mixer of each layer kept, from its PUBLISHED index."""
    kept = sz.get("published_layers", range(sz["num_hidden_layers"]))
    return tuple("latent" if (i + 1) % sz["layer_group_size"] == 0
                 else "kda" for i in kept)


def rope_sizes(sz: dict) -> dict:
    """``sz`` with the rotation's keys under the names the accepted
    reference reads: plain rotary frequencies (factor 1: no ramp, cos
    and sin carry 1, the softmax's scale is ``192^-1/2``)."""
    return dict(sz, rope_parameters={
        "rope_theta": sz["rope_theta"], "factor": 1.0, "mscale": 1.0,
        "mscale_all_dim": 1.0, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": sz["max_position_embeddings"]})


# ------------------------------------------------------------------ #
# Kimi Delta Attention, token by token
# ------------------------------------------------------------------ #
def short_conv(x, filters):
    """Depthwise causal convolution of ``x [T, C]`` with ``filters [K,
    C]``, zeros before the sequence; the last tap meets ``x_t``."""
    taps, t = filters.shape[0], x.shape[0]
    behind = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(behind[j:j + t] * filters[j] for j in range(taps))


def delta_rule(q, k, v, g, beta, steps, mm):
    """The recurrence over the first ``steps`` positions of one
    sequence, one position a turn.  q, k, g ``[T, H, D]``, v ``[T, H,
    Dv]``, beta ``[T, H]``; ``[T, H, Dv]`` (zeros from ``steps`` on)."""
    t, h, d = q.shape

    def turn(i, carry):
        s, out = carry
        s = jnp.exp(g[i])[:, :, None] * s
        u = beta[i][:, None] * (v[i] - mm("hkv,hk->hv", s, k[i]))
        s = s + k[i][:, :, None] * u[:, None, :]
        return s, out.at[i].set(mm("hkv,hk->hv", s, q[i]))

    _, out = jax.lax.fori_loop(
        0, steps, turn, (jnp.zeros((h, d, v.shape[-1]), jnp.float32),
                         jnp.zeros(v.shape, jnp.float32)))
    return out


def kda_attention(a, att, sz, steps, mm):
    """The recurrent mixer's output ``[T, hidden]`` from its normed
    input ``a``."""
    t = a.shape[0]
    h, d = sz["num_attention_heads"], sz["head_dim"]
    q, k, v = (jax.nn.silu(short_conv(
        mm("td,df->tf", a, att[f"w{c}"]["kernel"]), att[f"conv_{c}"])
        ).reshape(t, h, d) for c in "qkv")
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * d ** -0.5, unit(k)
    g = sz["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(att["A_log"])[:, None]
        * (mm("td,df->tf", a, att["wf"]["kernel"])
           + att["dt_bias"]).reshape(t, h, d))
    beta = jax.nn.sigmoid(mm("td,dh->th", a, att["wbeta"]["kernel"]))
    o = delta_rule(q, k, v, g, beta, steps, mm)
    o = rmsnorm(o, att["o_norm"]["scale"], sz["rms_norm_eps"])
    gate = jax.nn.sigmoid(mm("td,df->tf", a, att["wg"]["kernel"]))
    return mm("tf,fd->td", gate * o.reshape(t, h * d), att["wo"]["kernel"])


# ------------------------------------------------------------------ #
# latent attention: no query latent, a gate a head
# ------------------------------------------------------------------ #
def latent_attention(a, att, sz, positions, mm):
    t = a.shape[0]
    h, dc = sz["num_attention_heads"], sz["kv_lora_rank"]
    dn, dr, dv = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                  sz["v_head_dim"])
    rope = rope_sizes(sz)["rope_parameters"]
    q = mm("td,df->tf", a, att["wq"]["kernel"]).reshape(t, h, dn + dr)
    ckr = mm("td,dc->tc", a, att["wkv_a"]["kernel"])
    c = rmsnorm(ckr[:, :dc], att["kv_norm"]["scale"], sz["rms_norm_eps"])
    k_r = rope_pairs(ckr[:, None, dc:], positions, rope)        # [T, 1, dr]
    kv = mm("tc,chf->thf", c, att["wkv_b"])                     # [T, H, dn+dv]
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], positions,
                                                 rope)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (t, h, dr))],
                        -1)
    o = attention(q, k, kv[..., dn:], softmax_scale(rope_sizes(sz)), mm)
    gate = jax.nn.sigmoid(mm("td,dh->th", a, att["wgate"]["kernel"]))
    o = (o.reshape(t, h, dv) * gate[:, :, None]).reshape(t, h * dv)
    return mm("tf,fd->td", o, att["wo"]["kernel"])


# ------------------------------------------------------------------ #
# the expert layer
# ------------------------------------------------------------------ #
def route(m, moe, sz, mm):
    """``(chosen [T, k], weights [T, k])``: sigmoid scores over every
    router output; the groups' scores (the sum of a group's two largest
    biased scores) keep ``topk_group`` of ``n_group`` groups; the top
    ``num_experts_per_tok`` of the biased scores inside them; their
    scores (not the bias) over their sum, times
    ``routed_scaling_factor``."""
    g = jax.nn.sigmoid(mm("td,de->te", m, moe["router"]))
    biased = g + moe["router_bias"]
    t, e = biased.shape
    groups = sz["n_group"]
    grouped = biased.reshape(t, groups, e // groups)
    score = jnp.sort(grouped, -1)[..., -2:].sum(-1)             # [T, groups]
    order = jnp.argsort(-score, -1, stable=True)[:, :sz["topk_group"]]
    kept = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], order].set(True)
    masked = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e)
    _, chosen = jax.lax.top_k(masked, sz["num_experts_per_tok"])
    picked = jnp.take_along_axis(g, chosen, -1)
    if sz["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * sz["routed_scaling_factor"]


def routed_part(m, moe, sz, mm):
    """The held experts' part of the routed sum, ``[T, dim]``: token
    blocks; inside each a loop over the held experts; each expert
    applied to the rows of the block that chose it, ``EXPERT_ROWS`` at a
    time, as many times as it takes."""
    t, d = m.shape
    first, held = sz.get("experts_held_from", 0), sz["num_experts"]
    chosen, weights = route(m, moe, sz, mm)
    block = _blocks(t, TOKEN_BLOCK)
    tile = min(EXPERT_ROWS, block)

    def tokens(args):
        mb, cb, wb = args
        padded = jnp.concatenate([mb, jnp.zeros((1, d), mb.dtype)])

        def expert(acc, xs):
            e, w1, w3, w2 = xs
            # [block + 1]: a row's weight on this expert, zero past the end
            share = jnp.concatenate(
                [jnp.sum(jnp.where(cb == e, wb, 0.0), -1),
                 jnp.zeros((1,), wb.dtype)])
            chose = jnp.any(cb == e, -1)
            place = jnp.cumsum(chose) - 1

            def rows(j, acc):
                mine = chose & (place >= j * tile) & (place < (j + 1) * tile)
                at = jnp.nonzero(mine, size=tile, fill_value=block)[0]
                x = padded[at]
                act = jax.nn.silu(mm("td,df->tf", x, w1)) \
                    * mm("td,df->tf", x, w3)
                return acc.at[at].add(
                    mm("tf,fd->td", act * share[at][:, None], w2))

            turns = (chose.sum() + tile - 1) // tile
            return jax.lax.fori_loop(0, turns, rows, acc), None

        out, _ = jax.lax.scan(
            expert, jnp.zeros((block + 1, d), jnp.float32),
            (first + jnp.arange(held), moe["w1"], moe["w3"], moe["w2"]))
        return out[:block]

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
def block(x, lp, kind, sz, positions, steps, mm):
    eps = sz["rms_norm_eps"]
    a = rmsnorm(x, lp["attention_norm"]["scale"], eps)
    if kind == "kda":
        x = x + kda_attention(a, lp["attention"], sz, steps, mm)
    else:
        x = x + latent_attention(a, lp["attention"], sz, positions, mm)
    return x + feed_forward(rmsnorm(x, lp["ffn_norm"]["scale"], eps), lp,
                            sz, mm)


def hidden(params, tokens, sz, mm, steps=None):
    """Final-norm hidden states ``[T, dim]`` of one sequence; the
    recurrent layers run over the first ``steps`` positions (all)."""
    x = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])
    steps = tokens.shape[0] if steps is None else steps
    for i, kind in enumerate(layer_types(sz)):
        x = block(x, params[f"layer_{i}"], kind, sz, positions, steps, mm)
    return rmsnorm(x, params["norm"]["scale"], sz["rms_norm_eps"])


def logits(params, tokens, sz, mm=mm_highest, rows=None):
    """Logits ``[T, vocab]`` of one sequence, or of its ``rows`` only."""
    h = hidden(params, tokens, sz, mm,
               None if rows is None else jnp.max(rows) + 1)
    if rows is not None:
        h = h[rows]
    return mm("td,dv->tv", h, params["output"])


# ------------------------------------------------------------------ #
# bytes and operations, from shapes alone
# ------------------------------------------------------------------ #
def kda_layers(sz: dict) -> int:
    return layer_types(sz).count("kda")


def kda_state_bytes_per_layer(sz: dict) -> int:
    """The float32 matrix of state of every head of one layer of one
    sequence: 2 MiB at 32 x 128 x 128."""
    return sz["num_attention_heads"] * sz["head_dim"] ** 2 * WIDTH["float32"]


def kda_state_bytes_per_slot(sz: dict) -> int:
    """What a sequence leaves in the recurrent layers' cache, whatever
    its length: the state and the convolution's last inputs (three rows
    of q~, k~ and v~ in the compute dtype)."""
    conv = (sz["short_conv_kernel_size"] - 1) * 3 \
        * sz["num_attention_heads"] * sz["head_dim"] \
        * WIDTH[sz["compute_dtype"]]
    return kda_layers(sz) * (kda_state_bytes_per_layer(sz) + conv)


def kda_step_bytes(sz: dict, decoding_slot_layers: float) -> float:
    """HBM bytes the recurrence of one decode step must move at the
    least, whatever implements it: every decoding slot's state of every
    recurrent layer read once and written once
    (``decoding_slot_layers``: decoding slots x recurrent layers).  A
    slot that does not decode needs neither; the token's q, k, v, g of a
    few KiB are left out: counted low, never high."""
    return 2.0 * decoding_slot_layers * kda_state_bytes_per_layer(sz)


def kda_chunk_flops(sz: dict, tokens: int) -> float:
    """Operations of the recurrence of ONE recurrent layer over a call
    of ``tokens`` positions in chunked form at blocks of ``r`` = 16: a
    block and a head cost the two triangular matrices and the two
    products with the system's inverse (``4 x 2 r^2 D``), ``B U`` (``2
    r^2 D``) and the three products with the state (``3 x 2 r D^2``);
    the inverse itself (``r^3``) is left out."""
    r, d = 16, sz["head_dim"]
    return float(tokens) * sz["num_attention_heads"] \
        * (10.0 * r * d + 6.0 * d * d)


def kda_chunk_bytes(sz: dict, tokens: int) -> float:
    """HBM bytes the same call must move at the least: q, k, v, g in and
    o out at the compute dtype's width (a fused form reads them once),
    and the state read and written once."""
    wide = sz["num_attention_heads"] * sz["head_dim"]
    return float(tokens) * 5 * wide * WIDTH[sz["compute_dtype"]] \
        + 2.0 * kda_state_bytes_per_layer(sz)


def decode_step_weight_bytes(sz: dict, experts_hit: float) -> float:
    """Bytes of weights one decode step must read: every projection of
    every layer kept by its kind and the head in the held dtype; the
    leading dense layers' feed-forward; in the expert layers the shared
    expert and ``experts_hit`` routed experts a layer; the router (and
    its bias) in float32.  The embedding is a lookup of a few rows."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    dc, dn, dr, dv = (sz["kv_lora_rank"], sz["qk_nope_head_dim"],
                      sz["qk_rope_head_dim"], sz["v_head_dim"])
    wide = h * sz["head_dim"]
    kinds = layer_types(sz)
    kda = 6 * d * wide + d * h
    latent = d * h * (dn + dr) + d * (dc + dr) + dc * h * (dn + dv) \
        + d * h + h * dv * d
    layers, dense = len(kinds), sz["first_k_dense_replace"]
    expert = 3 * d * sz["moe_intermediate_size"]
    held = kinds.count("kda") * kda + kinds.count("latent") * latent \
        + dense * 3 * d * sz["intermediate_size"] \
        + (layers - dense) * (1 + experts_hit) * expert \
        + d * sz["vocab_size"]
    float32 = (layers - dense) * (d + 1) * sz["router_outputs"]
    return held * WIDTH[sz["param_dtype"]] + float32 * WIDTH["float32"]


def moe_decode_step_bytes(sz: dict, experts_hit: float,
                          attended_positions: float) -> float:
    """HBM bytes one decode step must read at the least: the weights of
    ``decode_step_weight_bytes`` and the latent rows of the
    ``attended_positions`` (positions times LATENT layers, all slots)
    its queries see, each once.  The recurrent layers' state is NOT in
    it (the caller knows no count of decoding slots; ``kda_step_bytes``
    counts it for ``kda_state_roofline``): counted low, never high."""
    return decode_step_weight_bytes(sz, experts_hit) \
        + attended_positions * cache_bytes_per_position(sz)
