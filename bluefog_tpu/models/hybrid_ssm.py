"""A decoder whose every layer holds TWO mixers side by side, a Mamba-2
state-space mixer (arXiv:2405.21060) and grouped-query softmax attention,
both reading the same normed input, their outputs added (``model_type:
falcon_h1``).  A sequence leaves two kinds of memory behind in ONE layer:
keys and values that grow with it, and a recurrent state that does not.

:class:`HybridSsmConfig` wraps a :class:`~bluefog_tpu.models.llama.
LlamaConfig` for the widths the two files share and reuses that file's
``Attention`` (through its ``attend`` argument: the rotation, the cache
and the fused decode kernel of ``_decode_attend``), ``RMSNorm`` and
``_dense``, and ``models/kda.py``'s carried-inputs convolution; it is
served by the same ``ServingEngine`` through ``serving/protocol.py``.

One layer on ``h [T, d]`` (``H`` state-space heads of ``P`` channels, a
state of ``N`` a channel, ``G`` groups of heads sharing ``B`` and ``C``;
the multipliers are the config's, each a scalar on the tensor named)::

    a = rmsnorm(h)
    attention:  q, k, v = (a * attention_in) Wq, Wk, Wv
                k = k * key_multiplier;  rotary on q and k
                A = (softmax(q k^T / sqrt(D), causal) v) Wo * attention_out
    state space, on a * ssm_in:
        [z | x | B | C | dt] = (.) W_in, each segment times its own
                                         ssm_multipliers entry
        [x | B | C] = silu(conv([x | B | C]) + bias)     depthwise, causal
        dt = softplus(dt + dt_bias);  alpha = exp(-dt exp(A_log))   a head
        S_t = alpha_t S_(t-1) + dt_t x_t (x) B_t        S in R^(P x N) a head
        y_t = S_t C_t + D x_t
        y = rmsnorm_grouped(y * silu(z))                G groups, one scale
        M = (y W_out) * ssm_out
    h = h + A + M
    m = rmsnorm(h)
    h = h + ((silu((m W1) * mlp[0]) * (m W3)) W2) * mlp[1]

and ``logits = (rmsnorm(h) W_head) * lm_head_multiplier`` over
``embedding[tokens] * embedding_multiplier``.

What a sequence leaves behind in a layer: ``cached_key``/``cached_value``
(full leaves, ``llama.py``'s), ``state_ssm [H, P, N]`` float32 and
``state_conv [taps - 1, H P + 2 G N]`` (the convolution's last inputs),
under the attention's one ``cache_index``.  The two ``state_*`` leaves
keep ``serving/protocol.py``'s rule: **a state leaf is what the model
left after the LIVE tokens behind ``cache_index``, and a call that
starts at index 0 starts from zero state whatever the leaf holds.**  A
token that is not live (a chunk's padded tail, a slot that sits a decode
step out) leaves both as they were: ``dt`` 0, so alpha 1 and no input,
and no shift of the convolution's inputs.  Within one call the live
tokens come first.

The recurrence is computed in one of two forms, equal in exact
arithmetic, and the call's length says which:

* STEP, a single token (the engine's decode program, one token a slot
  under ``vmap``): ``S' = alpha S + (dt x) (x) B`` and ``y = S' C + D x``
  as a multiply and a sum over the state: exact float32, one read of
  the state and one write.  Mapped over the engine's slots it moves
  EVERY slot's state, whatever decodes (``state_streamed_steps``).
* CHUNKED, a call of several tokens (a prefill chunk, the training
  layout): Mamba-2's block form over blocks of ``ssm_chunk`` positions.
  With ``c`` the running sum of ``-dt exp(A_log)`` from the block's
  start and ``L[t, j] = exp(c_t - c_j)`` (``j <= t``), a block's output
  is ``((C B^T) o L) (dt x) + e^c (C S_0)`` and it leaves ``S' =
  e^(c_last) S_0 + (e^(c_last - c) dt x)^T B``.  Everything that does
  not read ``S_0`` is computed for all blocks together; two products a
  block wait for the state the block before left.  Every exponent is at
  most 0.

The state, the decay sums and every product of the scan are float32 at
``Precision.HIGHEST`` (a TPU would otherwise round the state to bfloat16
at every read); the projections around it run in the config's dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.models.kda import causal_conv
from bluefog_tpu.models.llama import (Attention, LlamaConfig, RMSNorm,
                                      _dense, rotary_embed)
from bluefog_tpu.parallel.ring_attention import full_attention

__all__ = ["HybridSsmConfig", "HybridSsm", "StatedHeads", "SsdMixer",
           "ssd_step", "ssd_chunked", "SCOPE_SSD", "SCOPE_SSD_STATE",
           "SCOPE_SSD_CHUNK"]

# device-trace scopes: the whole state-space mixer; inside it the
# single-token recurrence and the chunked scan (projections, convolution
# and the gated norm lie outside the inner two)
SCOPE_SSD = "bf.attn.ssd"
SCOPE_SSD_STATE = "bf.attn.ssd_state"
SCOPE_SSD_CHUNK = "bf.attn.ssd_chunk"
HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class StatedHeads(LlamaConfig):
    """A ``LlamaConfig`` whose heads' width is stated, where it is not
    ``dim / n_heads`` (20 heads of 128 on a hidden size of 5,120)."""
    head_size: int = 128

    @property
    def head_dim(self) -> int:
        return self.head_size


@dataclasses.dataclass(frozen=True)
class HybridSsmConfig:
    block: StatedHeads               # the widths shared with llama.py
    ssm_heads: int = 32              # mamba_n_heads
    ssm_head_dim: int = 128          # mamba_d_head
    ssm_state: int = 256             # mamba_d_state
    ssm_groups: int = 2              # mamba_n_groups: heads sharing B, C
    ssm_conv: int = 4                # mamba_d_conv, taps
    ssm_chunk: int = 128             # mamba_chunk_size, the scan's block
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5    # z, x, B, C, dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)  # gate, down
    initializer_range: float = 0.02

    def __post_init__(self):
        b = self.block
        if b.n_experts or b.tp_size > 1 or b.attn_mode != "full" \
                or b.scan_layers:
            raise ValueError(
                "the hybrid block is dense, on one device, with full "
                "attention and unrolled layers (n_experts, tp_size, "
                "attn_mode and scan_layers of the block are the plain ones)")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"ssm_heads ({self.ssm_heads}) must divide by "
                             f"ssm_groups ({self.ssm_groups})")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, "
                             "dt) and mlp_multipliers 2 (gate, down)")

    # what the engine, the benchmark and the tests read off any config
    vocab_size = property(lambda self: self.block.vocab_size)
    n_layers = property(lambda self: self.block.n_layers)
    max_seq_len = property(lambda self: self.block.max_seq_len)
    decode_attn = property(lambda self: self.block.decode_attn)
    # every layer keeps a recurrent state beside its keys and values
    state_layers = n_layers

    @property
    def ssm_inner(self) -> int:
        """Channels of x, y and z (mamba_d_ssm)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """Channels of the convolution: x, B and C together."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    # -- the serving protocol (serving/protocol.py) -------------------- #
    def serving_layout(self, max_len: int, *, chunk: int = 1,
                       kv_quant: str = "none", weight_quant: str = "none",
                       decode_attn: str = "xla") -> "HybridSsmConfig":
        if kv_quant != "none" or weight_quant != "none":
            raise NotImplementedError(
                "the hybrid model serves full-precision weights and caches "
                f"only (kv_quant={kv_quant!r}, weight_quant="
                f"{weight_quant!r})")
        from bluefog_tpu.models.generate import decode_config

        return dataclasses.replace(self, block=decode_config(
            self.block, max_len, decode_attn=decode_attn))

    def init_cache(self, batch_size: int, max_len: int):
        """Zero caches of ``batch_size`` sequences, from shapes alone."""
        model = HybridSsm(self.serving_layout(
            max_len, decode_attn=self.decode_attn))
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((batch_size, 1), jnp.int32)))
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            shapes["cache"])

    def apply_cached(self, params, cache, tokens, all_logits=False,
                     live=None):
        """Append ``tokens [B, T]`` to ``cache``: ``(logits [B, 1 or T,
        vocab], cache')``.  ``live [B, T]`` freezes both state leaves
        for a token that is padding, and reaches the fused single-token
        attention, which fetches no cache block for it."""
        logits, mut = HybridSsm(self).apply(
            {"params": params, "cache": cache}, tokens,
            all_logits=all_logits, live=live, mutable=["cache"])
        return logits, mut["cache"]

    def cache_kinds(self) -> dict:
        return {"full": (self.n_layers, None)}

    def streamed_positions(self, positions) -> tuple:
        from bluefog_tpu.parallel.pallas_decode import streamed_positions

        return (("full", self.n_layers * streamed_positions(
            positions, self.max_seq_len,
            fused=self.decode_attn == "pallas")),)

    def state_streamed_steps(self, decoding: int, capacity: int) -> int:
        """The step is mapped over the pool: it reads (and writes) every
        slot's state in every layer, whatever decodes."""
        return capacity * self.n_layers


# ------------------------------------------------------------------ #
# the recurrence, in two forms
# ------------------------------------------------------------------ #
def ssd_step(x, dt, a, bmat, cmat, skip, state):
    """One token a sequence.  x ``[B, H, P]``, dt ``[B, H]`` (0 for a
    token that is not live), a ``[H]`` (negative), bmat and cmat ``[B,
    G, N]``, skip ``[H]``, all float32; state ``[B, H, P, N]``.  Returns
    ``(y [B, H, P], state')``."""
    b, h, p = x.shape
    g = bmat.shape[1]
    # heads of a group side by side: B and C broadcast over them
    grouped = lambda v: v.reshape((b, g, h // g) + v.shape[2:])
    alpha = jnp.exp(dt * a)
    s = grouped(state)
    new = grouped(alpha)[..., None, None] * s \
        + grouped(dt[..., None] * x)[..., None] \
        * bmat[:, :, None, None, :]
    # the read-out as a multiply and a sum: exact float32
    y = jnp.sum(new * cmat[:, :, None, None, :], axis=-1)
    return y.reshape(b, h, p) + skip[:, None] * x, new.reshape(state.shape)


def ssd_chunked(x, dt, a, bmat, cmat, skip, state, block: int):
    """``T`` tokens a sequence, block by block.  x ``[B, T, H, P]``, dt
    ``[B, T, H]`` (0 for a token that is not live), a ``[H]``, bmat and
    cmat ``[B, T, G, N]``, skip ``[H]``, all float32; state ``[B, H, P,
    N]``.  Returns ``(y [B, T, H, P], state')``."""
    b, t, h, p = x.shape
    g, n = bmat.shape[2:]
    q = block
    pad = -t % q
    if pad:
        # a padded position is not live: it decays nothing, writes nothing
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)]
                                  * (v.ndim - 2))
        x, dt, bmat, cmat = (widen(v) for v in (x, dt, bmat, cmat))
    c = (t + pad) // q
    # [B, C, Q, G, H / G, ...]: heads of a group side by side
    x = x.reshape(b, c, q, g, h // g, p)
    dt = dt.reshape(b, c, q, g, h // g)
    bmat, cmat = bmat.reshape(b, c, q, g, n), cmat.reshape(b, c, q, g, n)
    run = jnp.cumsum(dt * a.reshape(g, h // g), axis=2)   # c, <= 0
    last = run[:, :, -1]                                  # [B, C, G, R]
    mm = lambda spec, u, v: jnp.einsum(spec, u, v, precision=HIGHEST)
    dx = dt[..., None] * x
    # inside a block: ((C B^T) o L) (dt x), for all blocks at once
    row, col = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    decay = run[:, :, :, None] - run[:, :, None, :]       # [B, C, t, j, G, R]
    lower = jnp.exp(jnp.where((row >= col)[..., None, None], decay,
                              -jnp.inf))
    scores = mm("bctgn,bcjgn->bctjg", cmat, bmat)
    y = mm("bctjgr,bcjgrp->bctgrp", scores[..., None] * lower, dx)
    # what a block adds to the state it leaves
    added = mm("bcjgrp,bcjgn->bcgrpn",
               jnp.exp(last[:, :, None] - run)[..., None] * dx, bmat)

    def one(s, xs):
        added, keep, cmat, grow = xs
        # the two products that wait for the block before
        out = grow[..., None] * mm("btgn,bgrpn->btgrp", cmat, s)
        return keep[..., None, None] * s + added, out

    blocks = lambda v: jnp.moveaxis(v, 1, 0)
    state, carried = lax.scan(
        one, state.reshape(b, g, h // g, p, n),
        (blocks(added), blocks(jnp.exp(last)), blocks(cmat),
         blocks(jnp.exp(run))))
    y = y + jnp.moveaxis(carried, 0, 1) + skip.reshape(g, h // g, 1) * x
    return (y.reshape(b, c * q, h, p)[:, :t],
            state.reshape(b, h, p, n))


class SsdMixer(nn.Module):
    """The state-space mixer of a layer, on the layer's normed input.
    ``start``: the layer's cache index before this call (decode
    layout)."""
    cfg: HybridSsmConfig

    @nn.compact
    def __call__(self, x, live=None, start=None):
        cfg, b = self.cfg, self.cfg.block
        bsz, t, _ = x.shape
        h, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_groups)
        inner, conv, taps = cfg.ssm_inner, cfg.conv_channels, cfg.ssm_conv
        f32 = jnp.float32
        if live is None:
            live = jnp.ones((bsz, t), bool)
        with jax.named_scope(SCOPE_SSD):
            proj = _dense(b, 2 * inner + 2 * g * n + h, "in_proj")(
                x * jnp.asarray(cfg.ssm_in_multiplier, x.dtype))
            z, xbc, dt = jnp.split(proj, [inner, inner + conv], axis=-1)
            mz, mx, mb, mc, mdt = cfg.ssm_multipliers
            z = z * jnp.asarray(mz, z.dtype)
            xbc = xbc * jnp.concatenate(
                [jnp.full((width,), m, xbc.dtype) for width, m in
                 ((inner, mx), (g * n, mb), (g * n, mc))])
            filters = self.param("conv_kernel", nn.initializers.normal(
                cfg.initializer_range), (taps, conv), f32)
            conv_bias = self.param("conv_bias", nn.initializers.zeros,
                                   (conv,), f32)
            a_log = self.param("A_log", nn.initializers.zeros, (h,), f32)
            skip = self.param("D", nn.initializers.ones, (h,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,), f32)
            dt = jax.nn.softplus(dt.astype(f32) * mdt + dt_bias)
            # a token that is not live: alpha 1 and no input
            dt = jnp.where(live[..., None], dt, 0.0)
            state = jnp.zeros((bsz, h, p, n), f32)
            history = jnp.zeros((bsz, taps - 1, conv), b.dtype)
            if b.decode:
                ss = self.variable("cache", "state_ssm", jnp.zeros,
                                   state.shape, f32)
                sc = self.variable("cache", "state_conv", jnp.zeros,
                                   history.shape, b.dtype)
                # a call at index 0 starts from nothing, whatever the
                # slot's last request left in the leaves
                fresh = start == 0
                state = jnp.where(fresh, 0.0, ss.value)
                history = jnp.where(fresh, 0, sc.value).astype(b.dtype)
            mixed, history = causal_conv(
                xbc, history, filters,
                live.sum(-1, dtype=jnp.int32))
            mixed = nn.silu(mixed + conv_bias)
            xs, bmat, cmat = jnp.split(mixed, [inner, inner + g * n], -1)
            xs = xs.reshape(bsz, t, h, p)
            bmat, cmat = (v.reshape(bsz, t, g, n) for v in (bmat, cmat))
            decay = -jnp.exp(a_log)
            if t == 1:
                with jax.named_scope(SCOPE_SSD_STATE):
                    y, state = ssd_step(xs[:, 0], dt[:, 0], decay,
                                        bmat[:, 0], cmat[:, 0], skip,
                                        state)
                    y = y[:, None]
            else:
                with jax.named_scope(SCOPE_SSD_CHUNK):
                    y, state = ssd_chunked(xs, dt, decay, bmat, cmat,
                                           skip, state, cfg.ssm_chunk)
            if b.decode:
                ss.value, sc.value = state, history
            # the gated norm: a group's channels share one mean square
            y = y.reshape(bsz, t, inner) * nn.silu(z.astype(f32))
            scale = self.param("norm", nn.initializers.ones, (inner,), f32)
            y = y.reshape(bsz, t, g, inner // g)
            y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + b.norm_eps)
            y = (y.reshape(bsz, t, inner) * scale).astype(b.dtype)
            out = _dense(b, b.dim, "out_proj")(y)
            return out * jnp.asarray(cfg.ssm_out_multiplier, out.dtype)


class HybridBlock(nn.Module):
    cfg: HybridSsmConfig

    @nn.compact
    def __call__(self, x, live=None):
        cfg, b = self.cfg, self.cfg.block
        t = x.shape[1]
        a = RMSNorm(b.norm_eps, name="attention_norm")(x)
        attention = Attention(b, name="attention")

        def attend(q, k, v):
            """``llama.Attention``'s own paths behind the key scale."""
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
            if b.decode:
                return attention._decode_attend(q, k, v, live)
            positions = jnp.arange(t)
            return full_attention(
                rotary_embed(q, positions, b.rope_theta, b.rope_scaling),
                rotary_embed(k, positions, b.rope_theta, b.rope_scaling),
                v, causal=True)

        att = attention(a * jnp.asarray(cfg.attention_in_multiplier, a.dtype),
                        0, live, attend=attend)
        att = att * jnp.asarray(cfg.attention_out_multiplier, att.dtype)
        # the layer's one index, as the attention left it
        start = attention.get_variable("cache", "cache_index") - t \
            if b.decode else None
        ssm = SsdMixer(cfg, name="mamba")(a, live, start)
        x = x + att + ssm
        m = RMSNorm(b.norm_eps, name="ffn_norm")(x)
        gate_by, down_by = cfg.mlp_multipliers
        gate = _dense(b, b.ffn_dim, "w1")(m)
        up = _dense(b, b.ffn_dim, "w3")(m)
        down = _dense(b, b.dim, "w2")(
            nn.silu(gate * jnp.asarray(gate_by, gate.dtype)) * up)
        return x + down * jnp.asarray(down_by, down.dtype)


class _Head(nn.Module):
    """The untied head: operands in the compute dtype, float32 sums and
    logits, and no float32 copy of a ``[dim, vocab]`` matrix."""
    vocab: int
    dtype: jnp.dtype
    std: float

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.normal(self.std),
                            (x.shape[-1], self.vocab), jnp.float32)
        return jnp.einsum("btd,dv->btv", x.astype(self.dtype),
                          kernel.astype(self.dtype),
                          preferred_element_type=jnp.float32)


class HybridSsm(nn.Module):
    cfg: HybridSsmConfig

    @nn.compact
    def __call__(self, tokens, all_logits=False, live=None):
        """tokens ``[B, T]`` int32 -> logits ``[B, T, vocab]`` float32;
        in decode layout the last position's alone unless
        ``all_logits``."""
        cfg, b = self.cfg, self.cfg.block
        assert tokens.shape[1] <= b.max_seq_len, (tokens.shape, b.max_seq_len)
        x = nn.Embed(b.vocab_size, b.dim, dtype=b.dtype,
                     param_dtype=jnp.float32, name="tok_embeddings")(tokens)
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        for i in range(b.n_layers):
            x = HybridBlock(cfg, name=f"layer_{i}")(x, live)
        x = RMSNorm(b.norm_eps, name="norm")(x)
        if b.decode and not all_logits:
            x = x[:, -1:]
        logits = _Head(b.vocab_size, b.dtype, cfg.initializer_range,
                       name="output")(x)
        return logits * cfg.lm_head_multiplier
