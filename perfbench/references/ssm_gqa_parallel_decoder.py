"""Plain reference of the decoder whose every layer holds a MAMBA-2
state-space mixer and grouped-query attention SIDE BY SIDE
(Falcon-H1-34B-Instruct,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json;
the state-space mixer is arXiv:2405.21060): float32 ``jax.numpy`` with
every contraction at ``Precision.HIGHEST``, the recurrence a literal
``lax.scan`` over positions, no cache, no chunks, no kernels, no
batching beyond one sequence, and no import from ``bluefog_tpu``.
Weights come in as data, in the layout
``families/ssm_gqa_parallel_decoder.make_params`` makes them.

The forward pass of one sequence (``sz``: the configuration's sizes with
the cut's overrides; ``d`` hidden_size 5120; ``H`` mamba_n_heads 32 of
``P`` mamba_d_head 128 channels, a state of ``N`` mamba_d_state 256 a
channel, ``G`` mamba_n_groups 2)::

    h = E[tok] * embedding_multiplier
    for each of the layers kept:
      a = rms(h)                                          rms_norm_eps
      attention:
        q, k, v = (a * attention_in_multiplier) W_q, W_k, W_v
                    20 query / 4 key-value heads of head_dim 128, no bias
        k = k * key_multiplier
        q, k = rope(q, p), rope(k, p)          rope_theta 1e11, no scaling
        A = (softmax(128^-1/2 q k^T, j <= i) v) W_o * attention_out_multiplier
      state space, on u = a * ssm_in_multiplier:
        [z | x | B | C | dt] = u W_in      4096 | 4096 | G N | G N | H columns
            each segment times its entry of ssm_multipliers (z, x, B, C, dt)
        [x | B | C] = silu(conv([x | B | C]) + bias)
            y_t = sum_j filter[j] x_(t - 3 + j), j = 0 .. 3 (mamba_d_conv 4,
            one filter a channel, zeros before the sequence)
        dt_h = softplus(dt_h + dt_bias_h);  alpha_t,h = exp(-dt_t,h exp(A_log_h))
        S_t,h = alpha_t,h S_(t-1),h + dt_t,h x_t,h (x) B_t,g(h)
            S_0 = 0, P x N a head; heads 0 .. H/G - 1 read group 0's B and C
        y_t,h = S_t,h C_t,g(h) + D_h x_t,h
        y = rms_grouped(y * silu(z))       G groups of 4096 / G channels,
                                           one learned scale of 4096
        M = (y W_out) * ssm_out_multiplier
      h = h + A + M
      m = rms(h)
      h = h + ((silu((m W_1) * mlp_multipliers[0]) * (m W_3)) W_2)
              * mlp_multipliers[1]                        intermediate_size
    logits = (rms(h) W_head) * lm_head_multiplier         untied

What the source's ``config.json`` does not itself state is listed in the
configuration file under ``assumed``, each with the choice made here.

Two things are done only so that the float32 computation fits beside
10.5 GB of bfloat16 weights on one chip, and neither changes a value:
the layers run one after another behind a barrier (a layer's matrices
are widened to float32 when its turn comes, not all at once), and the
head is applied in blocks of ``VOCAB_BLOCK`` columns (its float32 copy
whole is 5.3 GB).

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_BLOCK = 32768
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


def attention(a, att, sz, positions, mm):
    """The attention mixer's output ``[T, hidden]`` from the layer's
    normed input ``a``, before its output multiplier."""
    t = a.shape[0]
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    a = a * sz["attention_in_multiplier"]
    q = mm("td,df->tf", a, att["wq"]["kernel"]).reshape(t, nq, hd)
    k = mm("td,df->tf", a, att["wk"]["kernel"]).reshape(t, nkv, hd)
    v = mm("td,df->tf", a, att["wv"]["kernel"]).reshape(t, nkv, hd)
    k = k * sz["key_multiplier"]
    theta = float(sz["rope_theta"])
    q = rope(q, positions, theta).reshape(t, nkv, nq // nkv, hd)
    k = rope(k, positions, theta)
    s = mm("qkrd,skd->krqs", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(positions[None, :] <= positions[:, None], s, -jnp.inf)
    o = mm("krqs,skd->qkrd", jax.nn.softmax(s, axis=-1), v)
    return mm("tf,fd->td", o.reshape(t, nq * hd), att["wo"]["kernel"])


def short_conv(x, filters, bias):
    """Depthwise causal convolution of ``x [T, C]`` with ``filters [K,
    C]`` and ``bias [C]``, zeros before the sequence; the last tap meets
    ``x_t``."""
    taps, t = filters.shape[0], x.shape[0]
    behind = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(behind[j:j + t] * filters[j] for j in range(taps)) + bias


def selective_scan(x, dt, a, bmat, cmat, mm):
    """The recurrence of one sequence, one position a turn.  x ``[T, H,
    P]``, dt ``[T, H]``, a ``[H]`` (negative), bmat and cmat ``[T, H,
    N]`` (a head's group's): ``S_t C_t`` as ``[T, H, P]``."""
    h, p, n = x.shape[1], x.shape[2], bmat.shape[2]

    def turn(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, mm("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(turn, jnp.zeros((h, p, n), jnp.float32),
                        (x, dt, bmat, cmat))
    return y


def gated_norm(y, z, scale, groups, eps):
    """``rms(y * silu(z))`` of ``[T, C]`` with the mean square taken
    over each of ``groups`` runs of ``C / groups`` channels, times one
    learned ``scale [C]``."""
    t, width = y.shape
    y = (y * jax.nn.silu(z)).reshape(t, groups, width // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y.reshape(t, width) * scale


def state_space(a, ssm, sz, mm):
    """The state-space mixer's output ``[T, hidden]`` from the layer's
    normed input ``a``, before its output multiplier."""
    t = a.shape[0]
    h, p, n, g = (sz["mamba_n_heads"], sz["mamba_d_head"],
                  sz["mamba_d_state"], sz["mamba_n_groups"])
    inner = h * p
    mz, mx, mb, mc, mdt = sz["ssm_multipliers"]
    f32 = lambda v: v.astype(jnp.float32)
    proj = mm("td,df->tf", a * sz["ssm_in_multiplier"],
              ssm["in_proj"]["kernel"])
    z, x, bmat, cmat, dt = jnp.split(
        proj, [inner, 2 * inner, 2 * inner + g * n, 2 * inner + 2 * g * n],
        axis=-1)
    mixed = jax.nn.silu(short_conv(
        jnp.concatenate([x * mx, bmat * mb, cmat * mc], -1),
        f32(ssm["conv_kernel"]), f32(ssm["conv_bias"])))
    x, bmat, cmat = jnp.split(mixed, [inner, inner + g * n], axis=-1)
    x = x.reshape(t, h, p)
    # heads 0 .. H/G - 1 read group 0's B and C, and so on
    per_head = lambda v: jnp.repeat(v.reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt * mdt + f32(ssm["dt_bias"]))
    y = selective_scan(x, dt, -jnp.exp(f32(ssm["A_log"])), per_head(bmat),
                       per_head(cmat), mm)
    y = y + f32(ssm["D"])[:, None] * x
    y = gated_norm(y.reshape(t, inner), z * mz, f32(ssm["norm"]), g,
                   sz["rms_norm_eps"])
    return mm("tf,fd->td", y, ssm["out_proj"]["kernel"])


def block(x, lp, sz, positions, mm):
    eps = sz["rms_norm_eps"]
    a = rmsnorm(x, lp["attention_norm"]["scale"], eps)
    x = x + attention(a, lp["attention"], sz, positions, mm) \
        * sz["attention_out_multiplier"] \
        + state_space(a, lp["mamba"], sz, mm) * sz["ssm_out_multiplier"]
    m = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
    gate_by, down_by = sz["mlp_multipliers"]
    gate = mm("td,df->tf", m, lp["w1"]["kernel"]) * gate_by
    up = mm("td,df->tf", m, lp["w3"]["kernel"])
    return x + mm("tf,fd->td", jax.nn.silu(gate) * up,
                  lp["w2"]["kernel"]) * down_by


def hidden(params, tokens, sz, mm):
    """Final-norm hidden states ``[T, dim]`` of one sequence."""
    x = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32) \
        * sz["embedding_multiplier"]
    positions = jnp.arange(tokens.shape[0])
    for i in range(sz["num_hidden_layers"]):
        # a layer's matrices are widened when its turn comes
        x, lp = jax.lax.optimization_barrier((x, params[f"layer_{i}"]))
        x = block(x, lp, sz, positions, mm)
    return rmsnorm(x, params["norm"]["scale"], sz["rms_norm_eps"])


def logits(params, tokens, sz, mm=mm_highest, rows=None):
    """Logits ``[T, vocab]`` of one sequence, or of its ``rows`` only."""
    h = hidden(params, tokens, sz, mm)
    if rows is not None:
        h = h[rows]
    kernel = params["output"]["kernel"]
    out = []
    for start in range(0, kernel.shape[1], VOCAB_BLOCK):
        part = mm("td,dv->tv", h, kernel[:, start:start + VOCAB_BLOCK])
        # one block's float32 copy at a time
        h, part = jax.lax.optimization_barrier((h, part))
        out.append(part)
    return jnp.concatenate(out, -1) * sz["lm_head_multiplier"]


# ------------------------------------------------------------------ #
# bytes and operations, from shapes alone: the same work whatever
# implements it
# ------------------------------------------------------------------ #
def layer_matmul_params(sz: dict) -> int:
    """Parameters of one layer that a token is multiplied by: the
    attention's four projections, the state-space mixer's two and the
    SwiGLU's three."""
    d, f, hd = sz["hidden_size"], sz["intermediate_size"], sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    inner = sz["mamba_n_heads"] * sz["mamba_d_head"]
    width = 2 * inner + 2 * sz["mamba_n_groups"] * sz["mamba_d_state"] \
        + sz["mamba_n_heads"]
    return (2 * d * nq * hd + 2 * d * nkv * hd + d * width + inner * d
            + 3 * d * f)


def total_params(sz: dict) -> int:
    """Every parameter: a layer's matrices, its convolution's filters
    and bias, ``A_log``, ``D`` and ``dt_bias`` a head, the gated norm's
    scale and the two norms; embedding, head and the final norm."""
    d = sz["hidden_size"]
    inner = sz["mamba_n_heads"] * sz["mamba_d_head"]
    conv = inner + 2 * sz["mamba_n_groups"] * sz["mamba_d_state"]
    small = (sz["mamba_d_conv"] + 1) * conv + 3 * sz["mamba_n_heads"] \
        + inner + 2 * d
    return (sz["num_hidden_layers"] * (layer_matmul_params(sz) + small)
            + 2 * sz["vocab_size"] * d + d)


def ssd_layers(sz: dict) -> int:
    """Every layer has the state-space mixer."""
    return sz["num_hidden_layers"]


def ssd_state_bytes_per_layer(sz: dict) -> int:
    """The float32 state of every head of one layer of one sequence:
    4 MiB at 32 x 128 x 256."""
    return sz["mamba_n_heads"] * sz["mamba_d_head"] * sz["mamba_d_state"] \
        * WIDTH["float32"]


def state_bytes_per_slot(sz: dict) -> int:
    """What a sequence leaves in the layers' recurrent leaves, whatever
    its length: the state and the convolution's last ``mamba_d_conv -
    1`` inputs (rows of x, B and C in the compute dtype)."""
    conv = (sz["mamba_d_conv"] - 1) * (
        sz["mamba_n_heads"] * sz["mamba_d_head"]
        + 2 * sz["mamba_n_groups"] * sz["mamba_d_state"]) \
        * WIDTH[sz["compute_dtype"]]
    return ssd_layers(sz) * (ssd_state_bytes_per_layer(sz) + conv)


def cache_bytes_per_token(sz: dict) -> int:
    """Keys and values of one position in every layer."""
    return (sz["num_hidden_layers"] * 2 * sz["num_key_value_heads"]
            * sz["head_dim"] * WIDTH[sz["compute_dtype"]])


def ssd_step_bytes(sz: dict, decoding_slot_layers: float) -> float:
    """HBM bytes the recurrence of one decode step must move at the
    least, whatever implements it: every decoding slot's state of every
    layer read once and written once (``decoding_slot_layers``: decoding
    slots x layers; 8 MiB each).  A slot that does not decode needs
    neither; the token's x, B, C and dt of a few KiB are left out:
    counted low, never high."""
    return 2.0 * decoding_slot_layers * ssd_state_bytes_per_layer(sz)


def ssd_chunk_flops(sz: dict, tokens: int) -> float:
    """Operations of the recurrence of ONE layer over a call of
    ``tokens`` positions in block form at blocks of ``Q`` =
    mamba_chunk_size: a block costs ``C B^T`` a group (``2 Q^2 N G``),
    the masked product with ``dt x`` a head (``2 Q^2 P H``), and the two
    products with the state a head (``C S_0`` and what the block adds:
    ``2 x 2 Q P N H``); the decays and the mask are left out."""
    q, g = sz["mamba_chunk_size"], sz["mamba_n_groups"]
    h, p, n = sz["mamba_n_heads"], sz["mamba_d_head"], sz["mamba_d_state"]
    return float(tokens) * (2.0 * q * n * g + 2.0 * q * p * h
                            + 4.0 * p * n * h)


def ssd_chunk_bytes(sz: dict, tokens: int) -> float:
    """HBM bytes the same call must move at the least: x, B, C and dt in
    and y out at the compute dtype's width (a fused form reads them
    once), and the state read and written once."""
    h, p = sz["mamba_n_heads"], sz["mamba_d_head"]
    row = 2 * h * p + 2 * sz["mamba_n_groups"] * sz["mamba_d_state"] + h
    return float(tokens) * row * WIDTH[sz["compute_dtype"]] \
        + 2.0 * ssd_state_bytes_per_layer(sz)


def decode_step_bytes(sz: dict, live_tokens: float,
                      decoding_slots: float = 1.0) -> float:
    """HBM bytes one decode step must move at the least: every
    projection and the head read once, in the held dtype; the keys and
    values of the ``live_tokens`` cache positions in use; and the state
    of ``decoding_slots`` slots read and written in every layer.  A
    caller that knows only the positions in use gets the state of ONE
    slot, the least a decode step can hold: counted low, never high."""
    weights = (sz["num_hidden_layers"] * layer_matmul_params(sz)
               + sz["hidden_size"] * sz["vocab_size"]) \
        * WIDTH[sz["param_dtype"]]
    return weights + live_tokens * cache_bytes_per_token(sz) \
        + ssd_step_bytes(sz, decoding_slots * ssd_layers(sz))
