"""Plain reference of the looped dense decoder (Ouro-2.6B,
``model_type: ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741): float32 ``jax.numpy`` with every
contraction at ``Precision.HIGHEST``, no kernels, no cache, no batching
beyond one sequence, and no import from ``bluefog_tpu``.  Weights come
in as data, in the layout ``families/looped_dense_decoder.make_params``
makes them (the layers stacked under ``layers/block``, a leading
``[num_hidden_layers]`` axis).

The model, at its published sizes: ``d = 2048``, 48 layers, 16 query and
16 key/value heads of 128, SwiGLU of width 5,632, vocabulary 49,152,
untied head, no biases, RMSNorm with ``eps = 1e-6`` and a learned scale,
RoPE with ``theta = 1e6`` over the whole head, rotating half against
half (``x1 = x[..., :64]``, ``x2 = x[..., 64:]``, ``[x1 cos - x2 sin,
x2 cos + x1 sin]``), ``total_ut_steps = 4``, ``early_exit_threshold =
1``.

A layer ``l`` on ``x [T, d]`` at positions ``p``, in pass ``t``::

    a = Attn_l(N1_l(x); p);          x = x + N2_l(a)
    m = W2_l(silu(W1_l n) * W3_l n), n = N3_l(x);   x = x + N4_l(m)

four norms a layer (the published ``input_layernorm``,
``input_layernorm_2``, ``post_attention_layernorm``,
``post_attention_layernorm_2``; here ``attention_norm``,
``attention_post_norm``, ``ffn_norm``, ``ffn_post_norm``).  ``Attn_l``
is causal softmax attention with scale ``1/sqrt(128)`` over the keys
and values of the SAME pass: this reference keeps no cache, so a pass
can read no other pass's keys.  The stack::

    h_0 = Embed(tokens)
    h_t = N_f(Layers(h_{t-1}))   t = 1..4, the SAME 48 layers each pass
    logits = W_out h_4

with the one final norm ``N_f`` after every pass, its output the next
pass's input.  The exit gate is one linear map ``g: d -> 1`` with a
bias, read on every ``h_t``: ``lambda_t = sigmoid(g(h_t))``, ``p_t =
lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < 4`` and ``p_4 =
prod_{j<4} (1 - lambda_j)`` (``exit_pdf``).  A token leaves at the first
pass whose cumulative ``p`` reaches the threshold; at the published
threshold of 1 that is the last pass: every token runs all four and the
head reads ``h_4``.

Assumed, where ``config.json`` leaves a size or a place open (the
configuration file's ``assumed`` says the same): the gate has a bias
and reads the normed state; ``N_f`` sits inside the loop; rotation by
halves; every matrix ``normal(0, 0.02)`` from the seed, norm scales 1,
the gate's bias 0.  The paper's cache shared or averaged across passes
is an approximation it evaluates, not what the published model
computes: not used.

Departures: none in the mathematics.  The layers are walked with
``lax.scan`` over their stacked weights, one layer's matrices cast to
float32 at a time, only so that 768 positions fit beside the 5 GB of
bf16 weights on one chip.

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


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


def loop_steps(sz: dict) -> int:
    return sz["total_ut_steps"]


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x ``[T, H, D]``: rotate the pairs ``(x[i], x[i + D/2])``, half
    against half, by ``positions * theta ** (-2i / D)``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mm):
    """Causal attention of one sequence.  q ``[T, Hq, D]``, k and v
    ``[T, Hkv, D]``; query head ``h`` reads key head ``h // (Hq //
    Hkv)``."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    q = q.reshape(t, hkv, hq // hkv, d)
    s = mm("qkrd,skd->krqs", q, k) / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(t)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    return mm("krqs,skd->qkrd", jax.nn.softmax(s, axis=-1),
              v).reshape(t, hq * d)


def block(x, lp, sz, positions, mm):
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    eps, theta = sz["rms_norm_eps"], sz["rope_theta"]
    t = x.shape[0]
    att, ff = lp["attention"], lp["feed_forward"]
    h = rmsnorm(x, lp["attention_norm"]["scale"], eps)
    q = mm("td,df->tf", h, att["wq"]["kernel"]).reshape(t, nq, hd)
    k = mm("td,df->tf", h, att["wk"]["kernel"]).reshape(t, nkv, hd)
    v = mm("td,df->tf", h, att["wv"]["kernel"]).reshape(t, nkv, hd)
    a = attention(rope(q, positions, theta), rope(k, positions, theta), v,
                  mm)
    a = mm("tf,fd->td", a, att["wo"]["kernel"])
    x = x + rmsnorm(a, lp["attention_post_norm"]["scale"], eps)
    n = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
    m = mm("tf,fd->td",
           jax.nn.silu(mm("td,df->tf", n, ff["w1"]["kernel"]))
           * mm("td,df->tf", n, ff["w3"]["kernel"]), ff["w2"]["kernel"])
    return x + rmsnorm(m, lp["ffn_post_norm"]["scale"], eps)


def hidden_states(params, tokens, sz, mm):
    """``[h_1, .., h_T]``, each ``[T, dim]``: the normed state after
    every pass of one sequence."""
    x = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])
    out = []
    for _ in range(loop_steps(sz)):
        x, _ = jax.lax.scan(
            lambda x, lp: (block(x, lp, sz, positions, mm), None), x,
            params["layers"]["block"])
        x = rmsnorm(x, params["norm"]["scale"], sz["rms_norm_eps"])
        out.append(x)
    return out


def logits(params, tokens, sz, mm=mm_highest, rows=None):
    """Logits ``[T, vocab]`` of one sequence, or of its ``rows`` only:
    the head on the last pass's state."""
    h = hidden_states(params, tokens, sz, mm)[-1]
    if rows is not None:
        h = h[rows]
    return mm("td,dv->tv", h, params["output"]["kernel"])


def exit_pdf(params, tokens, sz, mm=mm_highest):
    """``[T, total_ut_steps]``: the exit gate's distribution over the
    passes at every position of one sequence."""
    gate = params["exit_gate"]
    rest, out = 1.0, []
    states = hidden_states(params, tokens, sz, mm)
    for t, h in enumerate(states):
        lam = jax.nn.sigmoid(mm("td,do->to", h, gate["kernel"])[:, 0]
                             + gate["bias"].astype(jnp.float32)[0])
        out.append(rest if t == len(states) - 1 else lam * rest)
        rest = rest * (1.0 - lam)
    return jnp.stack(out, -1)


# ------------------------------------------------------------------ #
# operations and bytes, from shapes alone
# ------------------------------------------------------------------ #
WIDTH = {"bfloat16": 2, "float32": 4}


def layer_matmul_params(sz: dict) -> int:
    """Parameters of one layer that a token is multiplied by."""
    d, f, hd = sz["hidden_size"], sz["intermediate_size"], sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * f


def total_params(sz: dict) -> int:
    """Every parameter: the layers with their four norms, embedding and
    head, the final norm, the gate and its bias."""
    d = sz["hidden_size"]
    return (sz["num_hidden_layers"] * (layer_matmul_params(sz) + 4 * d)
            + 2 * sz["vocab_size"] * d + d + d + 1)


def cache_bytes_per_token(sz: dict) -> int:
    """Keys and values of one position: every pass of every layer keeps
    its own."""
    return (loop_steps(sz) * sz["num_hidden_layers"] * 2
            * sz["num_key_value_heads"] * sz["head_dim"]
            * WIDTH[sz["compute_dtype"]])


def decode_step_bytes(sz: dict, live_tokens: float) -> float:
    """HBM bytes one decode step must read: the layers' matrices once A
    PASS (5 GB of weights do not stay on the chip between passes), the
    head once, in the held dtype, and the keys and values of the
    ``live_tokens`` cache positions in use."""
    weights = (loop_steps(sz) * sz["num_hidden_layers"]
               * layer_matmul_params(sz)
               + sz["hidden_size"] * sz["vocab_size"]) \
        * WIDTH[sz["param_dtype"]]
    return weights + live_tokens * cache_bytes_per_token(sz)
