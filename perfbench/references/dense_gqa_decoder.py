"""Plain reference of the pre-norm GQA decoder (Mistral-7B-v0.1's
block, arXiv:2310.06825): float32 ``jax.numpy`` with every contraction
at ``Precision.HIGHEST``, no kernels, no cache, no batching beyond a
loop over sequences, and no import from ``bluefog_tpu``.  Weights come
in as data, in the layout ``families/dense_gqa_decoder.make_params``
makes them.

Departures from the published model: the 4096-token sliding window is
left out (every cell keeps contexts at or under 4096 positions, where
it equals full causal attention).  Attention is computed in blocks of
query rows, and each layer is rematerialised in the backward pass, only
so that the float32 computation fits beside nothing else on one chip;
neither changes a value.

``mm(spec, a, b)`` is the one contraction everything goes through, so
that the output check can put a lower precision in its place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def mm_highest(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fake_fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448), as
    an fp8 matmul path would, and back to float32.  The gradient passes
    straight through the rounding (a cast's own transpose would round
    the unscaled cotangent to fp8, which flushes it to zero)."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


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


def attention(q, k, v, mm):
    """Causal grouped-query attention of one sequence.  q ``[T, Hq, D]``,
    k and v ``[T, Hkv, D]``; query head ``h`` reads key head
    ``h // (Hq // Hkv)``."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    block = min(Q_BLOCK, t)
    assert t % block == 0, (t, block)
    q = q.reshape(t // block, block, hkv, hq // hkv, d)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        qb, start = args
        s = mm("qkrd,skd->krqs", qb, k) / jnp.sqrt(jnp.float32(d))
        q_pos = start + jnp.arange(block)
        s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("krqs,skd->qkrd", p, v)

    out = jax.lax.map(rows, (q, jnp.arange(0, t, block)))
    return out.reshape(t, hq * d)


def block(x, lp, sz, positions, mm):
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    eps, theta = sz["rms_norm_eps"], sz["rope_theta"]
    t = x.shape[0]
    att = lp["attention"]
    h = rmsnorm(x, lp["attention_norm"]["scale"], eps)
    q = mm("td,df->tf", h, att["wq"]["kernel"]).reshape(t, nq, hd)
    k = mm("td,df->tf", h, att["wk"]["kernel"]).reshape(t, nkv, hd)
    v = mm("td,df->tf", h, att["wv"]["kernel"]).reshape(t, nkv, hd)
    a = attention(rope(q, positions, theta), rope(k, positions, theta), v,
                  mm)
    x = x + mm("tf,fd->td", a, att["wo"]["kernel"])
    ff = lp["feed_forward"]
    h = rmsnorm(x, lp["ffn_norm"]["scale"], eps)
    gate = mm("td,df->tf", h, ff["w1"]["kernel"])
    up = mm("td,df->tf", h, ff["w3"]["kernel"])
    return x + mm("tf,fd->td", jax.nn.silu(gate) * up, ff["w2"]["kernel"])


def hidden(params, tokens, sz, mm):
    """Final-norm hidden states ``[T, dim]`` of one sequence."""
    x = params["tok_embeddings"]["embedding"][tokens].astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])
    for i in range(sz["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            block, sz=sz, positions=positions, mm=mm))(
                x, params[f"layer_{i}"])
    return rmsnorm(x, params["norm"]["scale"], sz["rms_norm_eps"])


def logits(params, tokens, sz, mm=mm_highest, rows=None):
    """Logits ``[T, vocab]`` of one sequence, or of its ``rows`` only."""
    h = hidden(params, tokens, sz, mm)
    if rows is not None:
        h = h[rows]
    return mm("td,dv->tv", h, params["output"]["kernel"])


def loss(params, aux, batch, sz, mm=mm_highest):
    """Mean next-token cross-entropy of one rank's ``[batch, seq+1]``
    ids, sequence by sequence.  ``(loss, aux)``; ``aux`` passes through
    (the model has no mutable state)."""
    def one(seq):
        lg = logits(params, seq[:-1], sz, mm)
        picked = jnp.take_along_axis(lg, seq[1:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, -1) - picked)

    # a Python loop, not lax.map: a scanned backward pass would carry
    # (and double-buffer) a second copy of every parameter's gradient
    return sum(one(batch[i]) for i in range(batch.shape[0])) \
        / batch.shape[0], aux


# ------------------------------------------------------------------ #
# operations and bytes, from shapes alone
# ------------------------------------------------------------------ #
def matmul_params(sz: dict) -> int:
    """Parameters that a token is multiplied by: every projection and
    the head; the embedding is a lookup."""
    d, f = sz["hidden_size"], sz["intermediate_size"]
    hd = sz["head_dim"]
    nq, nkv = sz["num_attention_heads"], sz["num_key_value_heads"]
    per_layer = d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * f
    return sz["num_hidden_layers"] * per_layer + d * sz["vocab_size"]


def total_params(sz: dict) -> int:
    d = sz["hidden_size"]
    return (matmul_params(sz) + sz["vocab_size"] * d
            + (2 * sz["num_hidden_layers"] + 1) * d)


def attention_flops_forward(sz: dict, seq: int, layers: int = 1) -> float:
    """Causal attention of ``layers`` layers over one sequence: QK^T and
    PV, 2 FLOPs a multiply-add, over the ``seq (seq + 1) / 2`` pairs
    that the mask keeps."""
    pairs = seq * (seq + 1) / 2
    return layers * 2 * 2 * sz["num_attention_heads"] * sz["head_dim"] \
        * pairs


def train_flops_per_item(sz: dict, traffic: dict) -> float:
    """Required forward + backward FLOPs a token at the cell's
    sequence length: backward is twice forward; recomputation is not
    counted."""
    seq = traffic["seq_len"]
    forward = 2 * matmul_params(sz) + attention_flops_forward(
        sz, seq, sz["num_hidden_layers"]) / seq
    return 3 * forward


def flash_kernel_cost(sz: dict, batch: int, seq: int):
    """(FLOPs, HBM bytes) that causal attention needs for one layer's
    forward and backward over ``batch`` sequences, however many kernels
    carry it: forward is 2 score-sized products (QK^T, PV), backward 5
    (QK^T again, dP, dV, dQ, dK); the tensors q, k, v, o, do, dq, dk,
    dv cross HBM once per pass that needs them, in bf16."""
    unit = attention_flops_forward(sz, seq) / 2 * batch
    flops = 7 * unit
    hd = sz["head_dim"]
    q_bytes = batch * seq * sz["num_attention_heads"] * hd * 2
    kv_bytes = batch * seq * sz["num_key_value_heads"] * hd * 2
    forward = 2 * q_bytes + 2 * kv_bytes            # q k v -> o
    backward = 4 * q_bytes + 4 * kv_bytes           # q k v o do -> dq dk dv
    return flops, forward + backward


def decode_step_bytes(sz: dict, live_tokens: float) -> float:
    """HBM bytes one decode step must read: every projection and the
    head once, in the held dtype, and the keys and values of the
    ``live_tokens`` cache positions in use."""
    width = {"bfloat16": 2, "float32": 4}
    weights = matmul_params(sz) * width[sz["param_dtype"]]
    cache_per_token = (sz["num_hidden_layers"] * 2
                       * sz["num_key_value_heads"] * sz["head_dim"]
                       * width[sz["compute_dtype"]])
    return weights + live_tokens * cache_per_token
