"""A decoder with LATENT attention and routed experts beside a shared
one (the layer's keys are DeepSeek-V3's and are read that way).  Three
published models are computed here, and the config's fields select each
path, nothing else does:

* ``model_type: mistral4`` (Mistral-Small-4-119B-2603's language model):
  ``score_func="softmax"`` (no bias), a plain residual (``hc_mult`` 1),
  no dense layer (``n_dense_layers`` 0), ``query_scale_beta`` 0.1.
* ``model_type: xing4_0`` (Xing4.0-29B-A4B): ``score_func="sigmoid"``
  (a bias that selects only), ``n_dense_layers`` leading layers whose
  feed-forward is a SwiGLU of ``dense_hidden_dim``, and a residual of
  ``hc_mult`` = 4 streams mixed a token at a time around every sublayer
  (``models/hyper_connections.py``); no position scale on the query.
* ``Ling-3.0-flash-VL``'s language model: ``layer_types`` names each
  layer's mixer, ``"kda"`` (the recurrent mixer of ``models/kda.py``,
  whose cache is a state and not positions) or ``"latent"``; the latent
  layers take the query straight from the input (``q_lora_rank`` None)
  and gate each head's output (``head_gate``); the experts are chosen
  inside the best ``topk_group`` of ``n_group`` groups
  (``models/experts.py:route``).

Imported lazily (nothing on ``import bluefog_tpu``'s path names it); it
reuses ``RMSNorm`` of ``models/llama.py`` and the expert layer of
``models/experts.py``, and is served by the same ``ServingEngine``
through the protocol of ``serving/protocol.py``, which
:class:`MlaMoeConfig` implements.

One layer on the residual stream ``h`` (``h0 = E[tok]``), ``H`` heads,
position ``p``::

    a = norm1(h)
    c_q = rms(a W_dq);  q = c_q W_uq        per head [q_n (dn) ; q_r (dr)]
    [c ; k_r] = a W_dkv;  c = rms(c)        the latent (dc) and ONE key
                                            of dr columns for all heads
    q_r, k_r = rope(q_r, p), rope(k_r, p)   YaRN frequencies, interleaved
    [k_n ; v] = c W_ukv                     per head (dn + dv)
    q = q * (1 + beta ln(1 + floor(p / original_max)))
    o = softmax(s [q_n ; q_r] [k_n ; k_r]^T, j <= i) v
    h = h + [o_1 .. o_H] W_o
    m = norm2(h);  h = h + shared(m) + sum_{e in top_k, e held} w_e expert_e(m)
                   (a leading dense layer: h = h + W2(silu(W1 m) * W3 m))

with ``s = (dn + dr)^-1/2 * yarn_mscale(factor, mscale_all_dim)^2``.
With ``hc_mult`` = n > 1 the residual is n streams ``X`` (all of them
``E[tok]`` at the entry, summed before the final norm), and each of the
two sublayers ``F`` above (its norm included) reads and writes them
through the token's own coefficients (``hyper_connections.hc_pre`` /
``hc_post``)::

    y = F(sum_i H_pre[i] X[i]);  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

The streams live and die inside one call: the cache, and so the serving
layer, never sees one.

What a position leaves in the cache is ``[c ; k_r]``: ``dc + dr``
values, 640 bytes in bfloat16 at the published widths where the
expanded keys and values of 32 heads are 16 KiB.  No leaf holds an
expanded key or value.  Attention reads the latent in one of two forms,
equal in exact arithmetic, and the call's length says which:

* ABSORBED, a single-token step (the engine's decode program, one token
  a slot under ``vmap``): ``qa_h = q_n,h W_uk,h^T`` scores the latent
  directly, ``score_j = s ([qa_h ; q_r,h] . [c_j ; k_r,j])``, the
  weighted latent ``u_h = sum_j p_j c_j`` goes through ``W_uv,h``.
  Nothing is expanded: every head reads the one ``dc + dr`` row a
  position holds.  Which rows are read is the layout's ``decode_attn``:
  the einsums (``"xla"``: what a CPU and the tests run, and the
  kernel's reference) read every reserved row behind a mask; the kernel
  of ``parallel/pallas_decode.py`` (``"pallas"``: what ``"auto"``
  resolves to on a TPU) fetches the blocks at or before the slot's
  position, once for both contractions, and nothing for a slot that
  does not decode.
* EXPANDED, a call of several tokens (a prefill chunk, the training
  layout): ``[k_n ; v] = c W_ukv`` is rebuilt for the cached rows and
  attention is the plain one.  It walks the cache in blocks of
  ``key_block`` positions with a running softmax, only as far as the
  call's last position: its cost follows the context that is live, not
  the rows reserved.  (A chunk that absorbed instead was timed slower on
  the chip and does more work at every context: PERF.md section 6, PR 30.)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.models import hyper_connections as hc
from bluefog_tpu.models.experts import ExpertLayer, SwiGLU, _dense
from bluefog_tpu.models.llama import RMSNorm

__all__ = ["MlaMoeConfig", "MlaMoe", "yarn_frequencies", "yarn_mscale",
           "query_scale"]

SCOPE_ATTN_LATENT = "bf.attn.latent"
SCOPE_ATTN_ABSORB = "bf.attn.latent_absorb"
SCOPE_ATTN_EXPAND = "bf.attn.latent_expand"
LAYER_TYPES = ("latent", "kda")


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 256
    dim: int = 64
    n_layers: int = 2
    n_heads: int = 4
    q_lora_rank: Optional[int] = 32   # None: q = a W_q, no query latent
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    expert_hidden_dim: int = 32
    n_experts: int = 16              # the router's outputs
    top_k: int = 4
    route_scale: float = 1.0
    score_func: str = "softmax"      # the expert layer's (models/experts.py)
    # the experts are chosen among the best ``topk_group`` of ``n_group``
    # groups of the router's outputs (1: among all of them)
    n_group: int = 1
    topk_group: int = 1
    # each layer's mixer, "latent" or "kda"; None: every layer latent
    layer_types: Optional[Tuple[str, ...]] = None
    # the latent layer scales head h's output by sigmoid(a W_gate)_h
    head_gate: bool = False
    # the recurrent mixer (models/kda.py): keys and values of a head,
    # taps of the convolution, the floor of the log decay
    kda_head_dim: int = 8
    kda_conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    n_dense_layers: int = 0          # leading layers with a dense FFN
    dense_hidden_dim: int = 128
    # the residual path: streams (1: the plain ``h + f(norm(h))``), and
    # how their mixing matrix is made (models/hyper_connections.py)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # (first, count) of the experts this layer holds; None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # rope_parameters (rope_type yarn)
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the query at p is scaled by 1 + beta ln(1 + floor(p / original_max))
    query_scale_beta: float = 0.0
    norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    # the cached positions a block of a several-token call's attention holds
    key_block: int = 1024
    # the serving layout (``serving_layout``)
    decode: bool = False
    max_seq_len: int = 2048
    # how a single-token step reads the cached latent: "xla" (einsums
    # over every reserved row) or "pallas" (parallel/pallas_decode.py:
    # the blocks at or before the position); ``serving_layout`` chooses
    decode_attn: str = "xla"

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} lies "
                             f"outside the {self.n_experts} experts")
        if self.hc_mult < 1 or not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"hc_mult {self.hc_mult} must be at least 1 and "
                f"n_dense_layers {self.n_dense_layers} at most n_layers")
        if self.decode_attn not in ("xla", "pallas"):
            raise ValueError(f"decode_attn {self.decode_attn!r} not in "
                             "('xla', 'pallas')")
        if self.layer_types is not None and (
                len(self.layer_types) != self.n_layers
                or set(self.layer_types) - set(LAYER_TYPES)):
            raise ValueError(
                f"layer_types {self.layer_types} must name one of "
                f"{LAYER_TYPES} for each of the {self.n_layers} layers")
        if self.n_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group \
                or self.topk_group * (self.n_experts // self.n_group) \
                < self.top_k:
            raise ValueError(
                f"n_group {self.n_group} must divide the {self.n_experts} "
                f"experts and topk_group {self.topk_group} groups must "
                f"hold the {self.top_k} experts of a token")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def latent_layers(self) -> int:
        """Layers whose cache is positions of the latent."""
        return self.n_layers if self.layer_types is None \
            else self.layer_types.count("latent")

    @property
    def state_layers(self) -> int:
        """Layers whose cache is a recurrent state (optional in the
        serving protocol: the engine counts the live tokens through
        them)."""
        return self.n_layers - self.latent_layers

    @property
    def latent_width(self) -> int:
        """Values one position leaves in one layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)

    @property
    def mixed_sublayers(self) -> int:
        """Sublayers a token's streams are mixed around (optional in the
        serving protocol: 0 for a plain residual, which counts none)."""
        return 2 * self.n_layers if self.hc_mult > 1 else 0

    @property
    def residual_streams(self) -> int:
        return self.hc_mult

    # -- the serving protocol (serving/protocol.py) -------------------- #
    def serving_layout(self, max_len: int, *, chunk: int = 1,
                       kv_quant: str = "none", weight_quant: str = "none",
                       decode_attn: str = "xla") -> "MlaMoeConfig":
        del chunk  # a call of any length writes max_len leaves alike
        if kv_quant != "none" or weight_quant != "none":
            raise NotImplementedError(
                "the latent-attention model serves full-precision weights "
                f"and caches only (kv_quant={kv_quant!r}, weight_quant="
                f"{weight_quant!r})")
        if decode_attn == "auto":
            # as ``generate.decode_config`` decides for the dense model,
            # from what is known when the program is traced: the kernel
            # on a real TPU (off one it is interpreted) where it reads
            # the leaf as the chip lays it out; how much of the cache is
            # live is the kernel's business at run time
            from bluefog_tpu.parallel.pallas_decode import latent_tileable

            decode_attn = ("pallas" if jax.default_backend() == "tpu"
                           and latent_tileable(max_len, self.latent_width)
                           else "xla")
        return dataclasses.replace(self, decode=True, max_seq_len=max_len,
                                   decode_attn=decode_attn)

    def init_cache(self, batch_size: int, max_len: int):
        """Zero caches of ``batch_size`` sequences, from shapes alone."""
        cfg = self if self.decode and self.max_seq_len == max_len \
            else self.serving_layout(max_len)
        shapes = jax.eval_shape(
            lambda: MlaMoe(cfg).init(jax.random.PRNGKey(0),
                                     jnp.zeros((batch_size, 1), jnp.int32)))
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            shapes["cache"])

    def apply_cached(self, params, cache, tokens, all_logits=False,
                     live=None):
        """Append ``tokens [B, T]`` to ``cache`` and return ``(logits
        [B, 1 or T, vocab], cache')``.  ``live [B, T]``: False marks a
        token that is padding; it chooses no expert."""
        logits, mut = MlaMoe(self).apply(
            {"params": params, "cache": cache}, tokens,
            all_logits=all_logits, live=live, mutable=["cache"])
        return logits, mut["cache"]

    def cache_kinds(self) -> dict:
        """A latent position is a position: every latent layer is
        "full"; a recurrent layer attends no position."""
        return {"full": (self.latent_layers, None)}

    def streamed_positions(self, positions) -> tuple:
        """Rows of the latent a single-token step fetches, summed over
        layers: the blocks the kernel's plan names, or every reserved
        row of every slot under the einsums, whatever is live."""
        from bluefog_tpu.parallel import pallas_decode

        return (("full", self.latent_layers * pallas_decode.streamed_positions(
            positions, self.max_seq_len,
            fused=self.decode_attn == "pallas",
            block_s=pallas_decode.latent_block(self.max_seq_len))),)

    def state_streamed_steps(self, decoding: int, capacity: int) -> int:
        """Slots whose recurrent state a single-token step reads, times
        the layers that keep one: the ``decoding`` slots where the step
        is the kernel over the live rows, every one of the pool's
        ``capacity`` under the XLA step, whoever decodes."""
        from bluefog_tpu.models import kda

        return self.state_layers * (decoding if kda.steps_in_kernel(self)
                                    else capacity)

    def rebuilt_positions(self, start: int, tokens: int) -> int:
        """Cached positions whose keys and values a call of ``tokens``
        tokens at cache index ``start`` rebuilds from the latent, summed
        over layers: the key blocks up to the call's last position; none
        for a single-token step, which absorbs."""
        if tokens == 1:
            return 0
        kb = _divisor(self.max_seq_len, self.key_block)
        return self.latent_layers * ((start + tokens - 1) // kb + 1) * kb


# ------------------------------------------------------------------ #
# positions: YaRN's frequencies and the query's scale
# ------------------------------------------------------------------ #
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotation frequencies, float32: pair ``i`` turns at
    ``f_i = theta^(-2i/dim)`` where it makes more than ``beta_fast``
    turns over the original context (``i <= low``), at ``f_i / factor``
    where it makes fewer than ``beta_slow`` (``i >= high``), and on the
    straight ramp between the two in between."""
    i = np.arange(dim // 2, dtype=np.float32)
    plain = np.float32(theta) ** (-2.0 * i / np.float32(dim))

    def turns_at(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / np.float32(factor) * ramp
            ).astype(np.float32)


def rotate_pairs(x, positions, freqs, scale: float = 1.0):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of ``x [..., T,
    H, D]`` by ``positions [T] * freqs [D/2]`` (``rotary_embed``'s
    pairing), cos and sin times ``scale``."""
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1 = x[..., ::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def query_scale(positions, beta: float, original_max: int):
    """``1 + beta ln(1 + floor(p / original_max))``, float32 ``[T]``."""
    return 1.0 + beta * jnp.log1p(jnp.floor(
        positions.astype(jnp.float32) / original_max))


# ------------------------------------------------------------------ #
# attention over the latent
# ------------------------------------------------------------------ #
def _divisor(s: int, most: int) -> int:
    """The largest divisor of ``s`` that is at most ``most``."""
    block = min(s, most)
    while s % block:
        block -= 1
    return block


def absorbed_step(q_n, q_r, latent, pos, w_ukv, dc: int, dn: int,
                  live=None, fused: bool = False):
    """One query a sequence over its cache, absorbed.  q_n ``[B, 1, H,
    dn]`` and q_r ``[B, 1, H, dr]`` (scaled); latent ``[B, S, dc +
    dr]``; pos ``[1]``; w_ukv ``[dc, H, dn + dv]``.  Rows above ``pos``
    hold positions no query has reached: the einsums read every row
    behind a mask; ``fused`` hands the weighted latent to the kernel of
    ``parallel/pallas_decode.py``, which fetches the blocks at or before
    ``pos`` and none for a sequence whose ``live [B]`` is False (its
    output is zeros: nobody reads it).  ``[B, 1, H, dv]``."""
    dtype = latent.dtype
    # [q_n W_uk^T ; q_r]: a head's query against the latent's own columns
    qa = jnp.einsum("bthn,chn->bthc", q_n, w_ukv[..., :dn],
                    preferred_element_type=jnp.float32)
    qcat = jnp.concatenate([qa.astype(dtype), q_r], axis=-1)
    if fused:
        from bluefog_tpu.parallel.pallas_decode import latent_decode_attention

        u = latent_decode_attention(qcat, latent, pos[0], dc=dc, live=live)
    else:
        scores = jnp.einsum("bthc,bsc->bhts", qcat, latent,
                            preferred_element_type=jnp.float32)
        seen = jnp.arange(latent.shape[1])[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30),
                           axis=-1)
        u = jnp.einsum("bhts,bsc->bthc", p.astype(dtype), latent,
                       preferred_element_type=jnp.float32)[..., :dc]
    return jnp.einsum("bthc,chv->bthv", u.astype(dtype), w_ukv[..., dn:],
                      preferred_element_type=jnp.float32)


def blocked_attention(q_n, q_r, latent, pos, w_ukv, dc: int, dn: int,
                      kb: int, blocks=None):
    """Queries at ``pos [T]`` (ascending from 0 or from the cache index)
    of ONE call over its cache, block of ``kb`` keys by block, with a
    running softmax, over the first ``blocks`` blocks (``None``: up to
    the block that holds the last query's position, a bound only the
    device knows).  Shapes as ``absorbed_step`` with ``T`` queries.  A
    block's keys and values are expanded from its ``[K, dc + dr]`` rows."""
    b, t, h, _ = q_n.shape
    dtype = latent.dtype
    query = jnp.concatenate([q_n, q_r], axis=-1)

    def turn(i, carry):
        top, total, acc = carry
        rows = lax.dynamic_slice_in_dim(latent, i * kb, kb, axis=1)
        with jax.named_scope(SCOPE_ATTN_EXPAND):
            kv = jnp.einsum("bsc,chf->bshf", rows[..., :dc], w_ukv,
                            preferred_element_type=jnp.float32
                            ).astype(dtype)
            k_r = jnp.broadcast_to(rows[:, :, None, dc:],
                                   (b, kb, h, rows.shape[-1] - dc))
            keys = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
            scores = jnp.einsum("bthd,bshd->bhts", query, keys,
                                preferred_element_type=jnp.float32)
            values = kv[..., dn:]
        key_pos = i * kb + jnp.arange(kb)
        seen = key_pos[None, :] <= pos[:, None]
        scores = jnp.where(seen[None, None], scores, -1e30)
        new_top = jnp.maximum(top, scores.max(-1))
        # every query sees position 0, in the first block: its top is a
        # real score from then on and a masked key's weight is exactly 0
        p = jnp.exp(scores - new_top[..., None])
        fade = jnp.exp(top - new_top)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhts,bshv->bhtv", p.astype(dtype), values,
            preferred_element_type=jnp.float32)
        return new_top, total * fade + p.sum(-1), acc

    init = (jnp.full((b, h, t), -1e30, jnp.float32),
            jnp.zeros((b, h, t), jnp.float32),
            jnp.zeros((b, h, t, w_ukv.shape[-1] - dn), jnp.float32))
    if blocks is None:
        blocks = pos[-1] // kb + 1
    _, total, acc = lax.fori_loop(0, blocks, turn, init)
    return jnp.swapaxes(acc / total[..., None], 1, 2)        # [B, T, H, dv]


class LatentAttention(nn.Module):
    cfg: MlaMoeConfig

    @nn.compact
    def __call__(self, x, live=None):
        """``live [B, T]``: False where a token is padding (its output
        is never read); only the fused single-token step looks at it."""
        cfg = self.cfg
        b, t, _ = x.shape
        h, dc = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        with jax.named_scope(SCOPE_ATTN_LATENT):
            if cfg.q_lora_rank is None:
                q = _dense(cfg, h * (dn + dr), "wq")(x)
            else:
                c_q = RMSNorm(cfg.norm_eps, name="q_norm")(
                    _dense(cfg, cfg.q_lora_rank, "wq_a")(x))
                q = _dense(cfg, h * (dn + dr), "wq_b")(c_q)
            q = q.reshape(b, t, h, dn + dr)
            ckr = _dense(cfg, dc + dr, "wkv_a")(x)
            c = RMSNorm(cfg.norm_eps, name="kv_norm")(ckr[..., :dc])
            w_ukv = self.param(
                "wkv_b", nn.initializers.normal(cfg.initializer_range),
                (dc, h, dn + dv), jnp.float32).astype(cfg.dtype)
            idx = jnp.zeros((), jnp.int32)
            if cfg.decode:
                ci = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((), jnp.int32))
                idx = ci.value
            pos = idx + jnp.arange(t)
            freqs = jnp.asarray(yarn_frequencies(
                dr, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
                cfg.rope_beta_fast, cfg.rope_beta_slow))
            turn = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
                / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
            k_r = rotate_pairs(ckr[:, :, None, dc:], pos, freqs, turn)[:, :, 0]
            # the softmax's scale and the position's ride on the query
            scale = cfg.softmax_scale * query_scale(
                pos, cfg.query_scale_beta, cfg.rope_original_max)
            scale = scale[None, :, None, None]
            q_n = (q[..., :dn].astype(jnp.float32) * scale).astype(cfg.dtype)
            q_r = (rotate_pairs(q[..., dn:], pos, freqs, turn).astype(
                jnp.float32) * scale).astype(cfg.dtype)
            latent = jnp.concatenate([c, k_r], axis=-1).astype(cfg.dtype)
            if cfg.decode:
                cl = self.variable("cache", "cached_latent", jnp.zeros,
                                   (b, cfg.max_seq_len, dc + dr), cfg.dtype)
                zero = jnp.zeros((), idx.dtype)
                latent = lax.dynamic_update_slice(cl.value, latent,
                                                  (zero, idx, zero))
                cl.value, ci.value = latent, idx + t
            if cfg.decode and t == 1:
                with jax.named_scope(SCOPE_ATTN_ABSORB):
                    out = absorbed_step(
                        q_n, q_r, latent, pos, w_ukv, dc, dn,
                        live=None if live is None else live[:, 0],
                        fused=cfg.decode_attn == "pallas")
            else:
                # the training layout's cache is the call: every block
                kb = _divisor(latent.shape[1], cfg.key_block)
                out = blocked_attention(
                    q_n, q_r, latent, pos, w_ukv, dc, dn, kb,
                    blocks=None if cfg.decode else t // kb)
            if cfg.head_gate:
                gate = jax.nn.sigmoid(
                    _dense(cfg, h, "wgate")(x).astype(jnp.float32))
                out = out * gate[..., None]
            out = out.astype(cfg.dtype).reshape(b, t, h * dv)
            return _dense(cfg, cfg.dim, "wo")(out)


class Block(nn.Module):
    cfg: MlaMoeConfig
    index: int = 0

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.norm_eps, name=name)

        def attend(a):
            if cfg.layer_types is not None \
                    and cfg.layer_types[self.index] == "kda":
                from bluefog_tpu.models.kda import KimiDeltaAttention

                return KimiDeltaAttention(cfg, name="attention")(a, live)
            return LatentAttention(cfg, name="attention")(a, live)

        def feed(m):
            if self.index < cfg.n_dense_layers:
                return SwiGLU(cfg, cfg.dense_hidden_dim,
                              name="feed_forward")(m)
            return ExpertLayer(cfg, name="moe")(m, live)

        if cfg.hc_mult == 1:
            x = x + attend(norm("attention_norm")(x))
            return x + feed(norm("ffn_norm")(x))
        # x: the token's streams, flat [B, T, hc_mult * dim]
        mixing = dict(n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters,
                      eps=cfg.hc_eps, clamp=cfg.hc_res_clamp,
                      norm_eps=cfg.norm_eps)
        for sublayer, name in ((attend, "attention"), (feed, "ffn")):
            y_in, h_post, h_res = hc.hc_pre(
                x, hc.Mixing(cfg, name=f"{name}_hc")(), **mixing)
            x = hc.hc_post(x, sublayer(norm(f"{name}_norm")(y_in)), h_post,
                           h_res, n=cfg.hc_mult)
        return x


class MlaMoe(nn.Module):
    cfg: MlaMoeConfig

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
        if cfg.hc_mult > 1:
            x = hc.expand(x, cfg.hc_mult)
        for i in range(cfg.n_layers):
            x = Block(cfg, i, name=f"layer_{i}")(x, live)
        if cfg.hc_mult > 1:
            x = hc.collapse(x, cfg.hc_mult)
        x = RMSNorm(cfg.norm_eps, name="norm")(x)
        if cfg.decode and not all_logits:
            x = x[:, -1:]
        w_out = self.param("output", nn.initializers.normal(
            cfg.initializer_range), (cfg.dim, cfg.vocab_size), jnp.float32)
        return jnp.einsum("btd,dv->btv", x, w_out.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)
