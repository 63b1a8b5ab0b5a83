"""Llama-style decoder-only transformer, TPU-first.

Capability target: BASELINE.json's "Llama-3-8B decentralized SGD with
neighbor_allreduce" stress config.  Fresh flax.linen implementation —
RMSNorm + rotary embeddings + grouped-query attention + SwiGLU — designed
for the MXU (bf16 compute, f32 params, static shapes) and for sequence
parallelism: ``attn_mode='ring'`` shards the sequence over a mesh axis and
runs :func:`bluefog_tpu.parallel.ring_attention.ring_attention`, making
long-context first-class (the reference has none — SURVEY.md §5).

The module itself never touches the mesh; under ``shard_map`` the caller
passes ``pos_offset = axis_index * T_local`` so rotary phases line up across
sequence shards.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from flax import linen as nn

from bluefog_tpu.parallel.ring_attention import (
    blockwise_attention,
    full_attention,
    ring_attention,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: Optional[int] = None  # default 8/3 * dim rounded to 256
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    attn_mode: str = "full"  # full | blockwise | ring | ulysses
    attn_impl: str = "xla"  # xla | flash (Pallas kernel; composes with
    #                         attn_mode="ring" incl. training — the ring
    #                         VJP re-runs the Pallas bwd per ring step)
    #                         | splash (library fused-bwd kernel; plain
    #                         causal full-sequence train path only —
    #                         +10% measured end-to-end tokens/s at
    #                         200M/1B, parallel/splash.py)
    attn_block_size: int = 512  # for blockwise/ring/ulysses modes
    # Llama-3.1-style rope scaling (HF rope_type='llama3'): "none" or
    # "llama3".  Flat fields keep the config hashable (it is a jit
    # static argument); reference semantics in _llama3_scaled_freqs.
    rope_scaling_kind: str = "none"  # none | llama3
    rope_scaling_factor: float = 8.0
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_len: int = 8192
    # Tile sizes for the full-sequence Pallas flash kernel (q tile /
    # k tile; both clamped to t).  Measured on v5e (round 3): 1024 q
    # tiles beat 512 by +18% tokens/s at 200M and +13% at 1B end-to-end.
    # Round 5 added causal BLOCK SKIPPING (fully-masked k blocks execute
    # nothing, pallas_attention._block_live), which flips the k-tile
    # optimum: a k block spanning the whole sequence never skips, while
    # 1024-wide k blocks skip a quarter of the grid at seq 2048 —
    # re-measured end-to-end, q1024/k1024 beats the round-3 q1024/k2048
    # at BOTH 200M (+1.9%) and 1B (+2.3%).  The backward pass
    # auto-shrinks its q tile to keep its two score-sized f32
    # intermediates inside the 16 MB scoped VMEM (_flash_bwd_impl).
    attn_flash_block_size: int = 1024
    attn_flash_block_k: int = 1024
    sp_axis: Optional[str] = None  # mesh axis for ring mode
    # Tensor (Megatron-style) parallelism: heads + FFN hidden sharded over
    # ``tp_axis`` (``tp_size`` shards, static).  Column-parallel kernels
    # (wq/wk/wv/w1/w3) shard their output dim, row-parallel ones (wo/w2)
    # their input dim with one psum each per block; activations stay
    # replicated over tp.  The param TREE is identical to tp_size=1 (the
    # global kernels keep full logical shapes — sharding happens in the
    # PartitionSpecs, see ``llama_param_specs``), so checkpoints move
    # freely between TP layouts.  A capability beyond the reference
    # (SURVEY.md §2.3: TP absent there).
    tp_axis: Optional[str] = None
    tp_size: int = 1
    # Mixture-of-Experts FFN with expert parallelism (Mixtral-style;
    # another capability past the reference's DP-only scope).
    # ``n_experts > 0`` replaces the dense FFN with ``moe_top_k``-routed
    # experts; experts shard over ``ep_axis`` (``ep_size`` shards), each
    # shard evaluating its local experts on the replicated token stream
    # and the outputs merging through ONE psum per layer (the same f/g
    # conjugate pair as TP keeps the backward exact).  Static capacity
    # ``capacity_factor * tokens * top_k / n_experts`` per expert keeps
    # shapes XLA-friendly; overflow tokens fall through the residual.
    n_experts: int = 0
    moe_top_k: int = 2
    ep_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25
    # Routing group size: tokens are routed within fixed-size groups with
    # per-group expert capacity (flaxformer/MaxText-style), keeping the
    # dispatch/combine tensors O(s * group) instead of O(s^2) — without
    # grouping, capacity grows with s and the [s, E, cap] one-hots blow
    # up at benchmark sequence lengths.  0 = one group over all tokens
    # (exact original behavior); otherwise the effective group is the
    # largest divisor of the token count <= this value.
    moe_group_size: int = 4096
    # Router flavor: "topk" (token-choice, autoregressive-safe, the
    # Mixtral/Switch default) or "expert_choice" (each expert takes its
    # top-capacity tokens per group — dropless and perfectly balanced by
    # construction, but NOT causal; for encoder/bidirectional stacks).
    moe_router: str = "topk"
    # Expert-choice routing conditions each token's expert assignment on
    # the OTHER tokens in its group — including future ones — so on this
    # causal decoder stack train-time logits are not reproducible
    # autoregressively.  Selecting it requires this explicit
    # acknowledgement (e.g. for representation learning, distillation
    # teachers, or ablations where autoregressive deployment is not the
    # goal); otherwise __post_init__ refuses the combination.
    allow_noncausal_router: bool = False
    # Weight of the Switch-style load-balance auxiliary loss.  The loss
    # is always sown under "intermediates" (scan included); the shipped
    # loss builders (llama_benchmark, llama_pp_loss_fn) ADD
    # moe_aux_weight * total_aux to the objective when this is > 0 —
    # without it routers can collapse onto few experts and capacity
    # drops silently bypass the FFN.
    moe_aux_weight: float = 0.0
    remat: bool = False
    # Compile the decoder stack as ONE nn.scan'd block instead of L unrolled
    # copies: params gain a leading [n_layers] axis, trace/compile time goes
    # O(L) -> O(1), and remat composes per scan step (the standard TPU
    # recipe for deep LLMs; the reference has no analogue — torch eager
    # re-executes Python per layer).
    scan_layers: bool = False
    remat_policy: str = "none"  # none | dots | everything (with remat)
    # Autoregressive decoding: attention layers keep [B, max_seq_len]
    # K/V caches (flax "cache" collection) and attend incrementally —
    # see models/generate.py.  Training configs leave this False; the
    # param tree is identical either way, so trained params decode
    # directly.
    decode: bool = False
    # Final logits matmul precision (MaxText's logits_dot_in_fp32): True
    # runs the [*, dim] x [dim, vocab] head in f32 (stablest; the
    # default), False runs it in the compute dtype with the logits cast
    # to f32 afterwards — ~2x faster head at bf16-rounded logits.
    logits_dot_in_fp32: bool = True
    # Inference-time quantization (decode is HBM-bound: every step
    # streams all params + the K/V cache once, so bytes ARE time).
    # kv_quant="int8": the decode K/V caches store int8 with one f32
    # scale per (batch, kv_head, position) vector; both scales commute
    # out of the attention contractions (over head_dim for scores, over
    # positions via the probabilities for values), so dequantization
    # fuses into the matmul operand reads and HBM traffic halves.
    # param_quant="int8": every projection kernel (wq/wk/wv/wo/w1/w2/w3
    # and the logits head) stores int8 with a per-output-channel f32
    # scale applied to the matmul OUTPUT ((x @ W_q) * s == x @ (W_q * s)
    # exactly, since s is constant along the contraction) — see
    # QuantDense.  Both are decode-only knobs (set via llama_generate);
    # training stays full precision.
    kv_quant: str = "none"  # none | int8
    param_quant: str = "none"  # none | int8
    # decode_attn="pallas": single-token decode steps run the fused
    # Pallas attention kernel (parallel/pallas_decode.py: one launch per
    # layer, in-kernel int8 cache dequant, probabilities kept float),
    # which fetches only the cache blocks at or before each row's
    # position.  "xla" keeps the einsum lowering, which reads every
    # reserved position behind its mask.
    # ``llama_generate(decode_attn="auto")`` chooses by platform, cache
    # dtype and tiling (PERF.md section 6, PR 27: the readings on a
    # v5e at 32 slots x 2048 positions).  Prefill (t > 1) always XLA.
    decode_attn: str = "xla"  # xla | pallas
    # Megatron-style vocab parallelism: the token embedding shards its
    # VOCAB rows and the logits head its VOCAB columns over ``tp_axis``,
    # so the two [128k x 4096] matrices stop being replicated per chip —
    # at Llama-3-8B scale they are ~4.2 GB of f32 params per chip (plus
    # the same again in momentum and gradients), the difference between
    # fitting a 16 GB v5e chip and not (benchmarks/llama_8b_structural).
    # The model then RETURNS VOCAB-SHARDED logits [B, T, V/tp]; train
    # with ``vocab_parallel_xent`` (exact vocab-parallel cross-entropy,
    # one pmax + two psums per step).  Training-only: decode keeps the
    # replicated head (no optimizer state there to dominate memory).
    vocab_parallel: bool = False
    # Megatron sequence-parallel ACTIVATIONS (their "sequence
    # parallelism" paper, distinct from ring/Ulysses attention SP): the
    # residual stream, norms, and remat-saved layer boundaries live
    # SEQ-SHARDED [B, T/tp, D] per chip; entering a tp region
    # all-gathers the rows and leaving it reduce-scatters them (the
    # conjugate pair _sp_region_in/_sp_region_out — same total bytes as
    # the f/g identity/psum pair, but activation memory divides by tp).
    # At 8B this is what lets an 8-CHIP tp group fit 16 GB v5e HBM
    # (benchmarks/llama_8b_structural.py).  Training-only; composes
    # with vocab_parallel (the head re-gathers rows once).
    tp_seq_shard: bool = False

    def __post_init__(self):
        if self.decode and self.attn_mode != "full":
            raise ValueError(
                f"decode=True requires attn_mode='full' (got "
                f"{self.attn_mode!r}); incremental K/V caching and "
                "ring/blockwise attention do not compose")
        if self.decode and self.n_experts:
            # capacity-dropped routing depends on how many tokens are
            # processed together, so a cached decode (one token at a
            # time) could not reproduce a capacity-dropped forward
            # token-for-token.  DROPLESS routing removes the coupling:
            # with per-group capacity >= group_tokens * top_k
            # (capacity_factor >= n_experts — exact for ANY group
            # size), every token gets its full top-k combine no matter
            # what it is co-batched with, so the cached decode matches
            # the dropless full forward exactly
            # (tests/test_moe_decode.py).  llama_generate raises the
            # capacity automatically; grouping stays as configured (it
            # keeps prefill dispatch memory linear in prompt length).
            if self.moe_router != "topk":
                raise ValueError(
                    "decode=True supports only moe_router='topk' "
                    "(expert_choice is non-causal)")
            if self.capacity_factor < self.n_experts:
                raise ValueError(
                    "decode=True with MoE requires DROPLESS routing: "
                    "capacity_factor >= n_experts (per-group capacity "
                    ">= group_tokens * top_k), so the cached "
                    "one-token-at-a-time decode reproduces the "
                    "dropless forward exactly — llama_generate "
                    "configures this automatically")
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant {self.kv_quant!r} not in ('none', 'int8')")
        if self.param_quant not in ("none", "int8", "w8a8"):
            raise ValueError(
                f"param_quant {self.param_quant!r} not in "
                "('none', 'int8', 'w8a8')")
        if self.kv_quant != "none" and not self.decode:
            raise ValueError(
                "kv_quant is a decode-time knob (it shapes the K/V cache "
                "layout); training/eval forward passes have no cache — "
                "set it through llama_generate")
        if self.param_quant != "none" and not self.decode:
            raise ValueError(
                "param_quant is inference-only (int8 kernels are not "
                "differentiable); set it through llama_generate and "
                "convert params with quantize_llama_params")
        if self.attn_impl not in ("xla", "flash", "splash"):
            raise ValueError(
                f"attn_impl {self.attn_impl!r} not in "
                "('xla', 'flash', 'splash')")
        if self.attn_impl == "splash":
            if self.attn_mode != "full":
                raise ValueError(
                    "attn_impl='splash' serves the plain full-sequence "
                    "causal path only (no LSE output to merge across "
                    "ring/ulysses steps) — use attn_impl='flash' with "
                    f"attn_mode={self.attn_mode!r}")
            if self.decode:
                raise ValueError(
                    "attn_impl='splash' is a train-time knob; decode "
                    "uses decode_attn ('xla' | 'pallas')")
        if self.decode_attn not in ("xla", "pallas"):
            raise ValueError(
                f"decode_attn {self.decode_attn!r} not in "
                "('xla', 'pallas')")
        if self.decode_attn == "pallas" and not self.decode:
            raise ValueError(
                "decode_attn='pallas' is a decode-time knob (the fused "
                "kernel serves single-token cached steps); set it "
                "through llama_generate")
        if self.vocab_parallel:
            if self.tp_size <= 1 or self.tp_axis is None:
                raise ValueError("vocab_parallel requires tensor "
                                 "parallelism (tp_axis + tp_size > 1)")
            if self.vocab_size % self.tp_size:
                raise ValueError(
                    f"vocab_size ({self.vocab_size}) must divide by "
                    f"tp_size ({self.tp_size}) for vocab_parallel")
            if self.decode:
                raise ValueError(
                    "vocab_parallel is a training-time memory layout "
                    "(it shards the optimizer-state-bearing vocab "
                    "matrices); decode keeps the replicated head — drop "
                    "vocab_parallel from the decode config")
        if self.tp_seq_shard:
            if self.tp_size <= 1 or self.tp_axis is None:
                raise ValueError("tp_seq_shard requires tensor "
                                 "parallelism (tp_axis + tp_size > 1)")
            if self.decode:
                raise ValueError(
                    "tp_seq_shard is a training-time activation layout; "
                    "drop it from the decode config (llama_generate "
                    "does this automatically)")
            if self.n_experts:
                raise ValueError(
                    "tp_seq_shard + MoE is not supported (experts use "
                    "the ep region operators; MoE configs exclude tp "
                    "anyway)")
            if self.attn_mode in ("ring", "ulysses"):
                raise ValueError(
                    "tp_seq_shard already shards the sequence over tp; "
                    "composing it with ring/ulysses attention "
                    "(sp_axis) is redundant — pick one")
            if not self.vocab_parallel:
                raise ValueError(
                    "tp_seq_shard requires vocab_parallel=True: a "
                    "REPLICATED logits head consumed by seq-sharded "
                    "rows would get per-shard partial gradients (each "
                    "shard only sees its own rows), while the "
                    "vocab-parallel head re-gathers the rows once and "
                    "stays exact — and at the scales where "
                    "tp_seq_shard matters the vocab matrices dominate "
                    "memory anyway")
        if self.rope_scaling_kind not in ("none", "llama3"):
            raise ValueError(
                f"rope_scaling_kind {self.rope_scaling_kind!r} not in "
                "('none', 'llama3')")
        valid = ("none", "dots", "everything")
        if self.remat_policy not in valid:
            raise ValueError(
                f"remat_policy {self.remat_policy!r} not in {valid}")
        if self.remat_policy != "none" and not self.remat:
            raise ValueError("remat_policy requires remat=True")
        if self.tp_size > 1:
            if self.tp_axis is None:
                raise ValueError("tp_size > 1 requires tp_axis")
            for name, val in (("n_heads", self.n_heads),
                              ("n_kv_heads", self.n_kv_heads),
                              ("ffn_dim", self.ffn_dim)):
                if val % self.tp_size:
                    raise ValueError(
                        f"{name} ({val}) must divide by tp_size "
                        f"({self.tp_size})")
        if self.ep_size > 1:
            if self.ep_axis is None:
                raise ValueError("ep_size > 1 requires ep_axis")
            if not self.n_experts:
                raise ValueError("ep_size > 1 requires n_experts > 0")
        if self.moe_router not in ("topk", "expert_choice"):
            raise ValueError(f"moe_router {self.moe_router!r} not in "
                             "('topk', 'expert_choice')")
        if self.moe_router == "expert_choice" \
                and not self.allow_noncausal_router:
            raise ValueError(
                "moe_router='expert_choice' is non-causal (each token's "
                "routing depends on later tokens in its group) but this "
                "stack is a causal decoder: trained logits would not be "
                "reproducible autoregressively.  Pass "
                "allow_noncausal_router=True to acknowledge this "
                "explicitly, or use moe_router='topk'.")
        if self.n_experts:
            if self.n_experts % self.ep_size:
                raise ValueError(
                    f"n_experts ({self.n_experts}) must divide by ep_size "
                    f"({self.ep_size})")
            if self.moe_top_k > self.n_experts:
                raise ValueError("moe_top_k exceeds n_experts")
            if self.tp_size > 1:
                raise ValueError(
                    "MoE + tensor parallelism in one config is not "
                    "supported yet (experts are not tp-sharded)")

    @property
    def rope_scaling(self):
        """The ``rotary_embed`` scaling tuple, or None when disabled."""
        if self.rope_scaling_kind == "none":
            return None
        return (self.rope_scaling_factor,
                self.rope_scaling_low_freq_factor,
                self.rope_scaling_high_freq_factor,
                self.rope_scaling_original_max_len)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn_dim(self) -> int:
        if self.hidden_dim is not None:
            return self.hidden_dim
        h = int(8 * self.dim / 3)
        return ((h + 255) // 256) * 256

    # -- the serving protocol (serving/protocol.py): the engine, the
    # slot pool and the prefix cache reach the model through these ---- #
    def serving_layout(self, max_len: int, *, chunk: int = 1,
                       kv_quant: str = "none", weight_quant: str = "none",
                       decode_attn: str = "xla") -> "LlamaConfig":
        """The decode layout (``generate.decode_config``); every layer
        caches ``max_len`` positions, so ``chunk`` changes nothing."""
        from bluefog_tpu.models.generate import decode_config

        return decode_config(self, max_len, kv_quant=kv_quant,
                             weight_quant=weight_quant,
                             decode_attn=decode_attn)

    def init_cache(self, batch_size: int, max_len: int):
        from bluefog_tpu.models.generate import init_cache

        return init_cache(self, batch_size, max_len,
                          kv_quant=self.kv_quant)

    def apply_cached(self, params, cache, tokens, all_logits=False,
                     live=None):
        """Append ``tokens [B, T]``'s K/V to ``cache`` (the call
        ``generate.prefill_cache`` makes): ``(logits, cache')``, the
        final position's logits alone unless ``all_logits`` (a
        speculative step's verify window keeps every position's).
        ``live`` (which tokens are no padding) reaches the fused
        single-token attention, which fetches no cache block for a row
        that does not decode; every token costs a dense layer the
        same."""
        logits, mut = Llama(self).apply(
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

    @staticmethod
    def llama3_8b(**overrides) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, hidden_dim=14336, rope_theta=500000.0, **overrides)

    @staticmethod
    def llama_1b(**overrides) -> "LlamaConfig":
        """The repo's "1b" benchmark widths (TinyLlama-class: 32 query /
        8 KV heads of 64, SwiGLU 5632): the largest configuration whose
        batch-4 x 2048 SGD+momentum train step fits one 16 GB v5e chip.
        The ONE definition examples/ and chip_smoke.py share."""
        base = dict(vocab_size=32000, dim=2048, n_layers=16, n_heads=32,
                    n_kv_heads=8, hidden_dim=5632, max_seq_len=8192)
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Test-scale config."""
        base = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, hidden_dim=128, max_seq_len=256)
        base.update(overrides)
        return LlamaConfig(**base)


def _remat_policies():
    return {
        "none": None,
        "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        "everything": jax.checkpoint_policies.nothing_saveable,
    }


class RMSNorm(nn.Module):
    eps: float = 1e-5
    # tp_seq_shard: the scale is a REPLICATED param consumed by
    # seq-sharded rows, so its per-shard gradient is partial (each
    # shard only sees its own rows); routing the param through the f
    # operator (identity forward, psum backward) restores the full
    # gradient on every shard — Megatron all-reduces its layernorm
    # grads across the tp group for exactly this reason.
    grad_psum_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        if self.grad_psum_axis is not None:
            scale = _tp_region_in(scale, self.grad_psum_axis)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


def _llama3_scaled_freqs(freqs: jax.Array, factor: float,
                         low_freq_factor: float, high_freq_factor: float,
                         original_max_len: int) -> jax.Array:
    """Llama-3.1's ``rope_type='llama3'`` frequency scaling (the HF
    implementation's piecewise rule): wavelengths shorter than the
    high-freq cutoff keep their frequency, longer than the low-freq
    cutoff divide by ``factor``, and the band between interpolates
    smoothly — long-context extension without hurting local attention."""
    low_wavelen = original_max_len / low_freq_factor
    high_wavelen = original_max_len / high_freq_factor
    wavelen = 2.0 * jnp.pi / freqs
    smooth = (original_max_len / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    interp = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(
        wavelen < high_wavelen, freqs,
        jnp.where(wavelen > low_wavelen, freqs / factor, interp))


def rotary_embed(x: jax.Array, positions: jax.Array, theta: float,
                 scaling=None, halves: bool = False) -> jax.Array:
    """Apply rotary position embedding.  x: [B, T, H, D], positions: [T].
    ``scaling``: optional ``(factor, low_freq_factor, high_freq_factor,
    original_max_len)`` tuple enabling llama3-style frequency scaling.
    ``halves``: the pair that frequency ``i`` rotates is ``(x[i], x[i +
    D/2])``, half against half (the Hugging Face layout), and not the
    interleaved ``(x[2i], x[2i+1])``."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is not None:
        freqs = _llama3_scaled_freqs(freqs, *scaling)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # [T, D/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if halves:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1).astype(x.dtype)
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out1 = x1 * cos - x2 * sin
    out2 = x1 * sin + x2 * cos
    out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# Megatron's conjugate communication operators.  Under shard_map every tp
# shard computes an IDENTICAL copy of the loss and differentiates it with
# seed 1, so the raw lax.psum is wrong in reverse (its transpose is
# another psum: sharded-kernel cotangents get multiplied by tp_size and
# the activation cotangent entering a parallel region is left partial).
# The fix is the f/g pair from the Megatron-LM paper:
#   f: identity forward, psum backward  (enter a parallel region)
#   g: psum forward, identity backward  (leave a parallel region)
# With them, TP gradients equal the unsharded model's exactly
# (tests/test_tp.py::test_tp_gradients_match_single_shard).
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_region_in(x, axis_name):
    return x


def _tp_region_in_fwd(x, axis_name):
    return x, None


def _tp_region_in_bwd(axis_name, _, g):
    return (jax.lax.psum(g, axis_name),)


_tp_region_in.defvjp(_tp_region_in_fwd, _tp_region_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tp_region_out(x, axis_name):
    return jax.lax.psum(x, axis_name)


def _tp_region_out_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _tp_region_out_bwd(axis_name, _, g):
    return (g,)


_tp_region_out.defvjp(_tp_region_out_fwd, _tp_region_out_bwd)


# Sequence-parallel-activation variants (cfg.tp_seq_shard): the residual
# stream is SEQ-SHARDED [B, T/tp, D]; a tp region is entered by
# all-gathering the rows and left by reduce-scattering the partial
# outputs.  The pair is exactly conjugate (all_gather^T = reduce-scatter
# and vice versa), so gradients equal the unsharded model's the same way
# the f/g identity/psum pair's do (tests/test_tp_seq_shard.py).
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _sp_region_in(x, axis_name):
    return jax.lax.all_gather(x, axis_name, axis=1, tiled=True)


def _sp_region_in_fwd(x, axis_name):
    return _sp_region_in(x, axis_name), None


def _sp_region_in_bwd(axis_name, _, g):
    return (jax.lax.psum_scatter(g, axis_name, scatter_dimension=1,
                                 tiled=True),)


_sp_region_in.defvjp(_sp_region_in_fwd, _sp_region_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _sp_region_out(y, axis_name):
    return jax.lax.psum_scatter(y, axis_name, scatter_dimension=1,
                                tiled=True)


def _sp_region_out_fwd(y, axis_name):
    return _sp_region_out(y, axis_name), None


def _sp_region_out_bwd(axis_name, _, g):
    return (jax.lax.all_gather(g, axis_name, axis=1, tiled=True),)


_sp_region_out.defvjp(_sp_region_out_fwd, _sp_region_out_bwd)


def _enter_tp_region(x, cfg: LlamaConfig):
    """Bring the (possibly seq-sharded) residual stream into a tp
    parallel region: full rows out, conjugate backward."""
    if cfg.tp_seq_shard:
        return _sp_region_in(x, cfg.tp_axis)
    return _tp_region_in(x, cfg.tp_axis)


def _leave_tp_region(y, cfg: LlamaConfig):
    """Merge the shards' partial outputs back onto the residual stream
    layout (full psum, or summed seq shards under tp_seq_shard)."""
    if cfg.tp_seq_shard:
        return _sp_region_out(y, cfg.tp_axis)
    return _tp_region_out(y, cfg.tp_axis)




def _amax_quantize(x, eps: float = 1e-8):
    """Dynamic symmetric int8 quantization along the LAST axis: returns
    ``(q_int8, scale_f32)`` with ``scale = max(amax(|x|), eps) / 127``
    and ``q = round(x / scale)``.  ``|q| <= 127`` by construction (the
    amax element maps to exactly ±127), so no clip is needed — unlike
    the offline kernel quantizer (quant.py), whose per-output-channel
    scale divides elements from OTHER rows.  One definition for all four
    runtime uses (activations, K/V writes, queries, probabilities)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True),
                        eps) / 127.0
    return jnp.round(x32 / scale).astype(jnp.int8), scale


class QuantDense(nn.Module):
    """Int8 linear layer for HBM-bound decode.

    Params: ``kernel`` int8 ``[in, out]`` + ``scale`` f32 ``[out]``
    (produced by :func:`bluefog_tpu.models.quant.quantize_llama_params`
    from a trained ``nn.Dense`` kernel).  The per-output-channel scale is
    constant along the contraction, so it commutes out of the matmul:
    ``x @ (W_q * s) == (x @ W_q) * s`` exactly.

    Two execution modes, measured on v5e (docs/performance.md round 4):

    * ``act_quant=False`` (weight-only, ``param_quant='int8'``): the dot
      runs in the compute dtype, so every weight element passes through
      an int8->bf16 convert on its way into the MXU — HBM streams 1 B/el
      but the convert path feeds matmuls at only ~280 GB/s effective.
    * ``act_quant=True`` (W8A8, ``param_quant='w8a8'``): activations
      quantize dynamically per token (one f32 amax scale per row — VPU
      work linear in the TINY activation, not the weights) and the dot
      runs natively s8 x s8 -> s32 on the MXU, which consumes int8
      weights at ~590-690 GB/s — ~2x the weight-only mode's wall-clock.
      Exact integer accumulation; the only extra rounding vs weight-only
      is the activations' int8 snap.

    ``out_f32`` returns f32 activations (the logits head).
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16
    out_f32: bool = False
    act_quant: bool = False

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.zeros,
                            (x.shape[-1], self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        if self.act_quant:
            xq, xs = _amax_quantize(x)
            y = lax.dot_general(
                xq, kernel, (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            out = y.astype(jnp.float32) * xs * scale
            return out if self.out_f32 else out.astype(self.dtype)
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))
        if self.out_f32:
            return y.astype(jnp.float32) * scale
        # scale in f32 then cast the product: keeps the module's
        # 'x @ (W_q * s) == (x @ W_q) * s exactly' contract — casting
        # the scale itself to bf16 first would add ~0.4% scale-rounding
        # error on top of the int8 snap.
        return (y.astype(jnp.float32) * scale).astype(self.dtype)


def _dense(cfg: LlamaConfig, feats: int, name: str):
    """The projection layer the config asks for: trained-precision
    ``nn.Dense`` or the int8 ``QuantDense`` (``param_quant='int8'``
    weight-only / ``'w8a8'`` native-int8-matmul)."""
    if cfg.param_quant != "none":
        return QuantDense(feats, dtype=cfg.dtype,
                          act_quant=cfg.param_quant == "w8a8", name=name)
    return nn.Dense(feats, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name)


class VocabParallelEmbed(nn.Module):
    """Token embedding with VOCAB rows sharded over ``tp_axis``.

    Each shard holds ``vocab/tp`` rows; out-of-range token ids look up a
    clamped row and are masked to zero, and the shards' partial results
    merge through ONE psum (the Megatron ``g`` operator, so the
    backward is identity and each shard's table gradient is exactly its
    own rows' — gradient parity in tests/test_vocab_parallel.py).
    Param path matches ``nn.Embed`` (``embedding``), so checkpoints move
    freely between layouts (the global array keeps the full
    ``[vocab, dim]`` shape; sharding happens in ``llama_param_specs``).
    """

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        v_local = cfg.vocab_size // cfg.tp_size
        table = self.param(
            "embedding", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", out_axis=0),
            (v_local, cfg.dim), jnp.float32)
        lo = lax.axis_index(cfg.tp_axis) * v_local
        local = tokens - lo
        valid = (local >= 0) & (local < v_local)
        x = jnp.take(table.astype(cfg.dtype),
                     jnp.clip(local, 0, v_local - 1), axis=0)
        x = jnp.where(valid[..., None], x, 0)
        # under tp_seq_shard this reduce-scatters straight to the
        # seq-sharded stream layout [B, T/tp, D] (half the wire bytes
        # of a full psum followed by a slice; the backward all-gathers
        # the disjoint row cotangents, so the table gradient still
        # covers every row)
        return _leave_tp_region(x, cfg)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _pmax_nograd(x, axis_name):
    """``lax.pmax`` with a zero tangent (pmax has no differentiation
    rule in JAX; as the logsumexp shift its gradient is exactly zero
    anyway — the shift cancels in ``logz - tlogit``)."""
    return lax.pmax(x, axis_name)


@_pmax_nograd.defjvp
def _pmax_nograd_jvp(axis_name, primals, tangents):
    (x,) = primals
    out = lax.pmax(x, axis_name)
    return out, jnp.zeros_like(out)


def vocab_parallel_xent(local_logits, targets, axis_name: str):
    """Exact next-token cross-entropy over VOCAB-SHARDED logits.

    ``local_logits``: ``[..., vocab/tp]`` (this shard's columns, in
    shard-index order — what a ``vocab_parallel`` Llama returns);
    ``targets``: ``[...]`` GLOBAL token ids.  Communicates one ``pmax``
    (stop-gradded — the standard logsumexp shift, exact either way) and
    two psums via the Megatron ``g`` operator so the backward stays
    per-shard (each shard's logit cotangent is the usual
    ``softmax - onehot`` restricted to its columns).  Every shard
    returns the IDENTICAL scalar mean loss, matching this framework's
    replicated-loss SPMD convention (optim/functional.py).
    """
    v_local = local_logits.shape[-1]
    logits32 = local_logits.astype(jnp.float32)
    m = _pmax_nograd(jnp.max(logits32, -1), axis_name)
    se = _tp_region_out(jnp.sum(jnp.exp(logits32 - m[..., None]), -1),
                        axis_name)
    logz = m + jnp.log(se)
    lo = lax.axis_index(axis_name) * v_local
    local = targets - lo
    valid = (local >= 0) & (local < v_local)
    tlogit = jnp.take_along_axis(
        logits32, jnp.clip(local, 0, v_local - 1)[..., None], -1)[..., 0]
    tlogit = _tp_region_out(jnp.where(valid, tlogit, 0.0), axis_name)
    return jnp.mean(logz - tlogit)


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, pos_offset, live=None, attend=None):
        """``attend(q, k, v) -> [B, T, n_q, D]``, where given, stands in
        for the rotation and the attention between the projections (a
        model that keeps its own cache: ``models/looped.py``)."""
        cfg = self.cfg
        hd = cfg.head_dim
        dense = lambda feats, name: _dense(cfg, feats, name)
        # under TP this module runs per-shard: local head counts; wo's
        # partial output merges below (Megatron column->row pattern,
        # entered through the 'f' operator — or the all-gather variant
        # under tp_seq_shard — so the backward is exact)
        tp = cfg.tp_axis is not None and cfg.tp_size > 1
        if tp:
            x = _enter_tp_region(x, cfg)
        b, t, _ = x.shape  # full rows (post-gather under tp_seq_shard)
        n_q = cfg.n_heads // cfg.tp_size
        n_kv = cfg.n_kv_heads // cfg.tp_size
        q = dense(n_q * hd, "wq")(x).reshape(b, t, n_q, hd)
        k = dense(n_kv * hd, "wk")(x).reshape(b, t, n_kv, hd)
        v = dense(n_kv * hd, "wv")(x).reshape(b, t, n_kv, hd)
        if attend is not None:
            out = attend(q, k, v)
        elif cfg.decode:
            # rotary happens inside, at the cache-index positions
            out = self._decode_attend(q, k, v, live)
        else:
            positions = pos_offset + jnp.arange(t)
            q = rotary_embed(q, positions, cfg.rope_theta,
                             cfg.rope_scaling)
            k = rotary_embed(k, positions, cfg.rope_theta,
                             cfg.rope_scaling)
            if cfg.attn_mode == "ring":
                assert cfg.sp_axis is not None, "ring attention needs sp_axis"
                out = ring_attention(q, k, v, cfg.sp_axis, causal=True,
                                     impl=cfg.attn_impl)
            elif cfg.attn_mode == "ulysses":
                from bluefog_tpu.parallel.ulysses import ulysses_attention

                assert cfg.sp_axis is not None, \
                    "ulysses attention needs sp_axis"
                out = ulysses_attention(q, k, v, cfg.sp_axis, causal=True,
                                        impl=cfg.attn_impl,
                                        block_size=cfg.attn_block_size)
            elif cfg.attn_impl == "flash":
                from bluefog_tpu.parallel.pallas_attention import (
                    flash_attention)

                out = flash_attention(
                    q, k, v, causal=True,
                    block_q=min(cfg.attn_flash_block_size, t),
                    block_k=min(cfg.attn_flash_block_k, t))
            elif cfg.attn_impl == "splash":
                from bluefog_tpu.parallel.splash import splash_attention

                out = splash_attention(
                    q, k, v, causal=True,
                    block_q=min(cfg.attn_flash_block_size, t),
                    block_kv=min(cfg.attn_flash_block_k, t))
            elif cfg.attn_mode == "blockwise":
                out = blockwise_attention(q, k, v, cfg.attn_block_size,
                                          causal=True)
            else:
                out = full_attention(q, k, v, causal=True)
        out = out.reshape(b, t, n_q * hd)
        proj = dense(cfg.dim, "wo")(out)
        if tp:
            proj = _leave_tp_region(proj, cfg)
        return proj

    def _decode_attend(self, q, k, v, live=None):
        """Incremental attention against the layer's K/V cache.

        Appends this call's K/V at the cache index (rotary applied at the
        true absolute positions), then attends the queries over the whole
        cache with the causal mask in global coordinates
        (``_block_scores`` with ``q_offset=index``).  Works for both the
        multi-token prefill call and the one-token decode steps.
        ``live [B, T]``: False where a token is padding (its output is
        never read); only the fused single-token step looks at it.
        """
        cfg = self.cfg
        b, t, n_kv, hd = k.shape
        max_len = cfg.max_seq_len
        row_live = None if live is None else live[:, 0]
        # the fused single-token step (parallel/pallas_decode.py)
        fused = cfg.decode_attn == "pallas" and t == 1
        if fused:
            from bluefog_tpu.parallel import pallas_decode
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        positions = idx + jnp.arange(t)
        q = rotary_embed(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = rotary_embed(k, positions, cfg.rope_theta, cfg.rope_scaling)
        zero = jnp.zeros((), idx.dtype)
        # caches live KV-HEAD-MAJOR [B, KV, S, D] — the batch-dim layout
        # the attention dot_generals want, so no step pays a transpose
        # of the whole cache (measured: the [B, S, KV, D] layout cost
        # two cache-sized transposes per layer per decode step)
        k = jnp.swapaxes(k, 1, 2)  # [B, KV, T, D] (tiny: T=1 in decode)
        v = jnp.swapaxes(v, 1, 2)
        if cfg.kv_quant == "int8":
            # int8 cache, one f32 scale per (batch, kv_head, position)
            # vector.  Both scales commute out of the contractions (the
            # key scale is constant over head_dim, the value scale folds
            # into the probabilities), so the dequant below fuses into
            # the attention matmul reads — HBM streams int8.
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (b, n_kv, max_len, hd), jnp.int8)
            cks = self.variable("cache", "cached_key_scale", jnp.zeros,
                                (b, n_kv, max_len), jnp.float32)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (b, n_kv, max_len, hd), jnp.int8)
            cvs = self.variable("cache", "cached_value_scale", jnp.zeros,
                                (b, n_kv, max_len), jnp.float32)

            kq, ks = _amax_quantize(k)
            vq, vs = _amax_quantize(v)
            ks, vs = ks[..., 0], vs[..., 0]  # scale per (b, kv_head, t)
            kq_all = lax.dynamic_update_slice(ck.value, kq,
                                              (zero, zero, idx, zero))
            ks_all = lax.dynamic_update_slice(cks.value, ks,
                                              (zero, zero, idx))
            vq_all = lax.dynamic_update_slice(cv.value, vq,
                                              (zero, zero, idx, zero))
            vs_all = lax.dynamic_update_slice(cvs.value, vs,
                                              (zero, zero, idx))
            ck.value, cks.value = kq_all, ks_all
            cv.value, cvs.value = vq_all, vs_all
            ci.value = idx + t
            if fused:
                # in-kernel dequant, probabilities kept float
                return pallas_decode.decode_attention_int8(
                    q, kq_all, ks_all, vq_all, vs_all, idx, live=row_live)
            if cfg.param_quant == "w8a8" and max_len <= 1024:
                # fully-integer attention: both contractions run s8xs8
                # on the MXU against the raw int8 cache — the cache
                # streams at native-dot rates (~600 GB/s measured)
                # instead of the ~280 GB/s convert-into-dot path.
                # LONG CONTEXT (static gate on the cache length) takes
                # the dequant path below instead: the integer path's
                # per-step probability re-quantization is VPU work
                # linear in S x heads and LOSES past ~1k positions
                # (round 5 measured, benchmarks/decode_200m_v5e1_r05:
                # w8a8 8.1k vs weight-only 10.1k tok/s at prompt 2048
                # before this gate; 10.9k after) — the round-4
                # "rule of thumb" is now the code's own dispatch.
                return _cached_attention_int8(q, kq_all, ks_all, vq_all,
                                              vs_all, idx)
            k_all = kq_all.astype(jnp.float32) * ks_all[..., None]
            v_all = vq_all.astype(jnp.float32) * vs_all[..., None]
        else:
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (b, n_kv, max_len, hd), cfg.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (b, n_kv, max_len, hd), cfg.dtype)
            if fused and pallas_decode.writable(max_len):
                # the kernel writes the step's rows itself (a row that
                # is not live writes nothing): under the engine's map
                # over slots XLA scatters them a slot at a time, a loop
                # for K and one for V a layer (4.4 of 14.8 ms a step,
                # PERF.md section 6, PR 41)
                out, ck.value, cv.value = pallas_decode.decode_attention(
                    q, ck.value, cv.value, idx, live=row_live,
                    fresh=(k[:, :, 0], v[:, :, 0]))
                ci.value = idx + 1
                return out
            k_all = lax.dynamic_update_slice(
                ck.value, k.astype(cfg.dtype), (zero, zero, idx, zero))
            v_all = lax.dynamic_update_slice(
                cv.value, v.astype(cfg.dtype), (zero, zero, idx, zero))
            ck.value, cv.value, ci.value = k_all, v_all, idx + t
        if fused:
            return pallas_decode.decode_attention(q, k_all, v_all, idx,
                                                  live=row_live)
        # queries live at global positions [idx, idx+t); the causal mask
        # there also excludes the cache's unwritten (zero) tail
        return _cached_attention(q, k_all, v_all, idx)


def _cached_attention(q, k_all, v_all, idx):
    """Grouped-query attention over the whole K/V cache WITHOUT
    materializing repeated K/V heads.

    ``full_attention`` tiles K/V up to the query head count
    (``_repeat_kv``) — fine for training where the score matmul
    dominates, but decode is HBM-bound and the tiled cache multiplies
    its per-step attention traffic by ``n_heads / n_kv_heads`` (4x for
    Llama GQA).  Here the query heads reshape into ``[n_kv, group]``
    and both contractions run against the cache at its NATIVE kv-head
    count; any dequantization expression feeding ``k_all``/``v_all``
    (the int8 cache path) fuses into the dot operand reads.

    q: [B, T, n_q, D] (global positions ``idx + arange(T)``),
    k_all/v_all: KV-HEAD-MAJOR [B, n_kv, S, D] (the cache layout — the
    dots' batch dims lead, so no per-step transpose of the cache).
    Returns [B, T, n_q, D] in q's dtype.
    """
    b, t, n_q, d = q.shape
    n_kv, s = k_all.shape[1], k_all.shape[2]
    rep = n_q // n_kv
    q5 = q.reshape(b, t, n_kv, rep, d).astype(jnp.float32)
    scores = jnp.einsum("btkrd,bksd->bkrts", q5,
                        k_all.astype(jnp.float32)) * (1.0 / d ** 0.5)
    q_pos = idx + jnp.arange(t)
    mask = jnp.arange(s)[None, :] <= q_pos[:, None]  # [T, S]
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    # every query row sees at least its own key (just written), so no
    # fully-masked-row guard is needed
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrts,bksd->btkrd", p, v_all.astype(jnp.float32))
    return out.reshape(b, t, n_q, d).astype(q.dtype)


def _cached_attention_int8(q, kq_all, ks_all, vq_all, vs_all, idx):
    """Grouped-query cached attention with BOTH contractions as native
    s8 x s8 -> s32 MXU dots (the ``param_quant='w8a8'`` +
    ``kv_quant='int8'`` decode path).

    The per-vector cache scales commute exactly: the key scale is
    constant along the head_dim contraction so it multiplies the score
    columns afterwards; the value scale varies along the position
    contraction so it folds INTO the probabilities before they are
    dynamically quantized (one amax scale per row — the same trick
    QuantDense plays on activations).  Rounding beyond the cache's own
    int8 snap: the queries' and probabilities' per-row int8 quant.

    q: [B, T, n_q, D] (positions ``idx + arange(T)``), kq_all/vq_all:
    int8 KV-HEAD-MAJOR [B, n_kv, S, D], ks_all/vs_all: f32
    [B, n_kv, S] (the cache layout — batch dims lead the dots, no
    per-step cache transpose).
    """
    b, t, n_q, d = q.shape
    n_kv, s = kq_all.shape[1], kq_all.shape[2]
    # the value contraction accumulates s8 x s8 into int32 with
    # worst-case magnitude 127*127*S, which crosses INT32_MAX near
    # S ~ 133k — refuse silently-overflowing cache lengths (chunk the
    # position contraction if longer contexts are ever needed)
    if s > 131072:
        raise ValueError(
            f"kv_quant='int8' + w8a8 decode supports cache length <= "
            f"131072 (int32 accumulator overflow at ~133k); got {s}")
    rep = n_q // n_kv
    qq, qs = _amax_quantize(q.reshape(b, t, n_kv, rep, d))
    s32 = jnp.einsum("btkrd,bksd->bkrts", qq, kq_all,
                     preferred_element_type=jnp.int32)
    # scales: q per row [B,T,KV,R,1] -> [B,KV,R,T,1]; k per position
    # [B,KV,S] broadcasts directly
    scores = (s32.astype(jnp.float32)
              * jnp.transpose(qs, (0, 2, 3, 1, 4))
              * ks_all[:, :, None, None, :]
              * (1.0 / d ** 0.5))
    q_pos = idx + jnp.arange(t)
    mask = jnp.arange(s)[None, :] <= q_pos[:, None]  # [T, S]
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)  # [B,KV,R,T,S]
    pv = p * vs_all[:, :, None, None, :]
    # eps far below any realistic row amax: a probability row sums to 1,
    # so amax >= 1/S — the tiny eps only guards fully-padded rows
    pq, ps = _amax_quantize(pv, eps=1e-30)
    o32 = jnp.einsum("bkrts,bksd->btkrd", pq, vq_all,
                     preferred_element_type=jnp.int32)
    out = o32.astype(jnp.float32) * jnp.transpose(ps, (0, 3, 1, 2, 4))
    return out.reshape(b, t, n_q, d).astype(q.dtype)


class FeedForward(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: _dense(cfg, feats, name)
        tp = cfg.tp_axis is not None and cfg.tp_size > 1
        if tp:
            x = _enter_tp_region(x, cfg)
        local_ffn = cfg.ffn_dim // cfg.tp_size
        gate = dense(local_ffn, "w1")(x)
        up = dense(local_ffn, "w3")(x)
        down = dense(cfg.dim, "w2")(nn.silu(gate) * up)
        if tp:
            down = _leave_tp_region(down, cfg)
        return down


def moe_combine_weights(probs: jax.Array, top_k: int, cap: int,
                        router: str = "topk") -> jax.Array:
    """Routing combine weights ``[g, G, E, cap]`` from per-group expert
    probabilities ``probs [g, G, E]`` — a pure function so the routing
    contract is unit-testable in isolation (tests/test_moe.py asserts
    the occupancy/drop accounting directly on it).

    ``router="topk"``: token-choice — each token takes its ``top_k``
    experts, bounded by the per-expert per-group capacity ``cap``
    (overflow tokens are dropped to the residual).  Autoregressive-safe.

    ``router="expert_choice"`` (Zhou et al. 2022): each expert takes its
    top-``cap`` tokens per group — dropless and perfectly load-balanced
    BY CONSTRUCTION (no aux loss needed), but NOT causal (which earlier
    tokens an expert keeps depends on later tokens in the group); for
    encoder/bidirectional stacks.  ``cap`` is clamped to the group size.
    """
    g, G, E = probs.shape
    if router == "expert_choice":
        cap = min(cap, G)  # an expert cannot take more than G tokens
        scores = jnp.swapaxes(probs, 1, 2)          # [g, E, G]
        gate_vals, idx = lax.top_k(scores, cap)     # [g, E, cap]
        onehot = jax.nn.one_hot(idx, G, dtype=jnp.float32)
        # combine[g, s, e, c] = gate of token s in expert e's slot c
        return jnp.einsum("gecs,gec->gsec", onehot, gate_vals)
    # top-k selection: k rounds of argmax with masking (k is tiny)
    masked = probs
    combine = jnp.zeros((g, G, E, cap), jnp.float32)
    counts = jnp.zeros((g, E), jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)               # [g, G]
        # gate from MASKED probs: if the softmax tail underflowed to
        # exact zero, a later round's argmax re-picks an earlier expert —
        # reading the unmasked prob would double-count it with full
        # weight; the masked value is 0 for re-picks.
        gate = jnp.take_along_axis(masked, idx[..., None],
                                   axis=-1)[..., 0]     # [g, G]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        # position of each token within its expert's per-group queue,
        # offset by what previous rounds already enqueued
        pos = jnp.cumsum(onehot, axis=1) - onehot + counts[:, None, :]
        pos_tok = jnp.sum(pos * onehot, axis=-1)        # [g, G]
        keep = pos_tok < cap
        combine = combine + (
            gate[..., None, None]
            * jax.nn.one_hot(idx, E)[..., None]
            * jax.nn.one_hot(pos_tok, cap)[..., None, :]
            * keep[..., None, None])
        counts = counts + jnp.sum(
            onehot * keep[..., None].astype(jnp.int32), axis=1)
        masked = masked * (1.0 - onehot.astype(masked.dtype))
    return combine


class MoEFeedForward(nn.Module):
    """Top-k routed mixture-of-experts SwiGLU FFN with expert parallelism.

    TPU-first design: routing is computed identically on every ep shard
    (tokens are replicated over ``ep_axis``), dispatch/combine are static
    einsums against a capacity-bounded one-hot tensor (no dynamic shapes,
    no host round trips), each shard evaluates only its LOCAL experts as
    one batched ``[local_E, slots, d]`` einsum on the MXU, and the
    shards' partial outputs merge with ONE psum (through the Megatron-
    style g operator; the token stream enters through f so gradients are
    exact — see ``_tp_region_in/_tp_region_out``).  Tokens over an
    expert's capacity are dropped (they ride the residual), the standard
    static-shape MoE contract.

    Routing is GROUPED (``cfg.moe_group_size``): tokens route within
    fixed-size groups with per-group capacity, so the one-hot
    dispatch/combine tensors are ``[g, G, E, cap]`` with
    ``g*G*E*cap = capacity_factor*top_k*s*G`` elements — LINEAR in the
    token count ``s`` for fixed ``G`` (an ungrouped capacity grows with
    ``s`` and the tensors are O(s^2), which OOMs at real sequence
    lengths).
    """

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, t, d = x.shape
        E = cfg.n_experts
        local_E = E // cfg.ep_size
        ep = cfg.ep_axis is not None and cfg.ep_size > 1
        s = b * t
        # effective group: the largest divisor of s <= moe_group_size
        # (static Python arithmetic — shapes stay compile-time constants)
        G = s
        if 0 < cfg.moe_group_size < s:
            G = cfg.moe_group_size
            while s % G:
                G -= 1
        g = s // G
        # Two independent paths enter the expert region, each wrapped in
        # its OWN f operator (identity fwd / psum bwd) so every backward
        # contribution is summed over ep exactly once: the token stream
        # (expert inputs) and the router logits.  The router itself runs
        # on the raw x OUTSIDE the region — it is a replicated param, and
        # wrapping its output (not its input) is what makes its gradient
        # the full cross-expert sum instead of a per-shard partial.
        flat_raw = x.reshape(s, d)
        if ep:
            x = _tp_region_in(x, cfg.ep_axis)
        flat = x.reshape(g, G, d)
        cap = max(1, int(cfg.capacity_factor * G * cfg.moe_top_k / E))

        logits_raw = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              param_dtype=jnp.float32, name="router")(
                                  flat_raw.astype(jnp.float32))
        logits = _tp_region_in(logits_raw, cfg.ep_axis) if ep else logits_raw
        probs = jax.nn.softmax(logits, axis=-1).reshape(g, G, E)

        combine = moe_combine_weights(probs, cfg.moe_top_k, cap,
                                      cfg.moe_router)
        cap = combine.shape[-1]  # expert_choice clamps cap to G
        if 0 < cfg.moe_group_size < s and G < cfg.moe_group_size // 2:
            # awkward token counts (odd/prime b*t) can collapse the
            # divisor far below the requested group — per-group capacity
            # shrinks with it and routing quality degrades silently;
            # surface it (pad b*t to a rounder count to fix)
            from bluefog_tpu.logging_util import get_logger
            get_logger().warning(
                "MoE grouped routing: token count %d has no divisor "
                "near moe_group_size=%d; effective group collapsed to "
                "%d (capacity %d tokens/expert/group). Pad the "
                "batch*seq token count to a multiple of the group size "
                "to restore routing quality.", s, cfg.moe_group_size,
                G, cap)
        dispatch = (combine > 0.0).astype(cfg.dtype)  # [g, G, E, cap]
        # my shard's expert slice
        if ep:
            e_lo = jax.lax.axis_index(cfg.ep_axis) * local_E
        else:
            e_lo = 0
        disp_local = lax.dynamic_slice_in_dim(dispatch, e_lo, local_E, 2)
        comb_local = lax.dynamic_slice_in_dim(
            combine.astype(cfg.dtype), e_lo, local_E, 2)

        # gather each expert's slots across all groups into one MXU batch
        expert_in = jnp.einsum("gsec,gsd->egcd", disp_local,
                               flat.astype(cfg.dtype))
        expert_in = expert_in.reshape(local_E, g * cap, d)
        h = cfg.ffn_dim
        w1 = self.param("w1", nn.initializers.lecun_normal(
            in_axis=-2, out_axis=-1), (local_E, d, h), jnp.float32)
        w3 = self.param("w3", nn.initializers.lecun_normal(
            in_axis=-2, out_axis=-1), (local_E, d, h), jnp.float32)
        w2 = self.param("w2", nn.initializers.lecun_normal(
            in_axis=-2, out_axis=-1), (local_E, h, d), jnp.float32)
        gate_h = jnp.einsum("ecd,edh->ech", expert_in, w1.astype(cfg.dtype))
        up_h = jnp.einsum("ecd,edh->ech", expert_in, w3.astype(cfg.dtype))
        expert_out = jnp.einsum("ech,ehd->ecd", nn.silu(gate_h) * up_h,
                                w2.astype(cfg.dtype))
        expert_out = expert_out.reshape(local_E, g, cap, d)
        out = jnp.einsum("egcd,gsec->gsd", expert_out, comb_local)
        if ep:
            out = _tp_region_out(out, cfg.ep_axis)
        # load-balancing auxiliary loss (Switch Transformer eq. 4) —
        # always sown (the scanned stack declares an intermediates axis);
        # trainers add cfg.moe_aux_weight * total to the objective (the
        # shipped loss builders do — see llama_pp_loss_fn and
        # examples/llama_benchmark.py).  Computed from the UNWRAPPED
        # logits: the aux term is a replicated computation outside the
        # expert region, so adding it to the loss gives the unsharded
        # router gradient exactly (through the f-wrapped logits its
        # backward psum would scale the aux contribution by ep_size).
        probs_all = jax.nn.softmax(logits_raw, axis=-1)
        frac_tokens = jnp.mean(
            jax.nn.one_hot(jnp.argmax(probs_all, -1), E,
                           dtype=jnp.float32), axis=0)
        frac_probs = jnp.mean(probs_all, axis=0)
        self.sow("intermediates", "moe_aux_loss",
                 E * jnp.sum(frac_tokens * frac_probs))
        return out.reshape(b, t, d).astype(x.dtype)


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, pos_offset, live=None):
        cfg = self.cfg
        naxis = cfg.tp_axis if cfg.tp_seq_shard else None
        x = x + Attention(cfg, name="attention")(
            RMSNorm(cfg.norm_eps, grad_psum_axis=naxis,
                    name="attention_norm")(x), pos_offset, live)
        ffn_cls = MoEFeedForward if cfg.n_experts else FeedForward
        name = "moe_ffn" if cfg.n_experts else "feed_forward"
        x = x + ffn_cls(cfg, name=name)(
            RMSNorm(cfg.norm_eps, grad_psum_axis=naxis,
                    name="ffn_norm")(x))
        return x


class _ScanBlock(nn.Module):
    """nn.scan adapter: Block with a (carry, out) return signature."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, pos_offset, live=None):
        return Block(self.cfg, name="block")(x, pos_offset, live), None


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, pos_offset=0, return_hidden=False,
                 all_logits=False, live=None):
        """tokens: [B, T_local] int32 -> logits [B, T_local, vocab] f32
        (with ``cfg.vocab_parallel``: [B, T_local, vocab/tp] — this
        shard's columns; train against ``vocab_parallel_xent``).

        ``return_hidden=True`` stops after the final RMSNorm and returns
        the [B, T_local, dim] hidden states instead of logits — the
        entry point for the chunked head+cross-entropy path
        (``llama_chunked_xent_loss_fn``), which never materializes the
        full [B, T, vocab] logits.  Init with the default so the head
        params exist; apply-with-return_hidden simply leaves them
        unused.

        ``all_logits=True`` keeps every position's logits in decode
        layout (normally only the final position survives — generation
        samples nothing else).  Speculative decoding's verify step needs
        it: ONE multi-token cached forward scores a whole draft window,
        so acceptance reads the target distribution at each drafted
        position.  No-op outside decode layout.

        ``live [B, T]`` (decode layout): False where a token is padding
        whose output nobody reads (``serving/protocol.py``)."""
        cfg = self.cfg
        assert tokens.shape[1] <= cfg.max_seq_len, (
            f"sequence shard {tokens.shape[1]} exceeds max_seq_len "
            f"{cfg.max_seq_len}")
        if cfg.tp_seq_shard:
            assert tokens.shape[1] % cfg.tp_size == 0, (
                f"sequence length {tokens.shape[1]} must divide by "
                f"tp_size ({cfg.tp_size}) under tp_seq_shard")
        if cfg.vocab_parallel:
            # with tp_seq_shard the embed reduce-scatters straight to
            # this shard's rows [B, T/tp, D] — the layout the whole
            # residual stream lives in between tp regions
            x = VocabParallelEmbed(cfg, name="tok_embeddings")(tokens)
        else:
            x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=jnp.float32,
                         name="tok_embeddings")(tokens)
        policy = _remat_policies()[cfg.remat_policy]
        if cfg.scan_layers:
            # one compiled block, scanned n_layers times; params get a
            # leading [n_layers] axis under "layers" — trace/compile cost
            # stops growing with depth
            body = _ScanBlock
            if cfg.remat:
                # prevent_cse=False: XLA's loop lowering already blocks the
                # problematic CSE under scan; the default True would insert
                # an opt-barrier per scanned layer
                body = nn.checkpoint(body, static_argnums=(), policy=policy,
                                     prevent_cse=False)
            scan_cls = nn.scan(
                body,
                variable_axes={"params": 0, "intermediates": 0, "cache": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=cfg.n_layers,
                metadata_params={nn.meta.PARTITION_NAME: None},
            )
            x, _ = scan_cls(cfg, name="layers")(x, pos_offset, live)
        else:
            block_cls = Block
            if cfg.remat:
                block_cls = nn.checkpoint(Block, static_argnums=(),
                                          policy=policy)
            for i in range(cfg.n_layers):
                x = block_cls(cfg, name=f"layer_{i}")(x, pos_offset, live)
        x = RMSNorm(cfg.norm_eps,
                    grad_psum_axis=cfg.tp_axis if cfg.tp_seq_shard
                    else None, name="norm")(x)
        if cfg.decode and not all_logits:
            # generation only ever samples from the final position — skip
            # the other T-1 head matmuls and the [B, T, vocab] logits
            # buffer (at 8k prompt x 128k vocab that is ~4 GB of f32)
            x = x[:, -1:]
        if return_hidden:
            return x
        if cfg.param_quant != "none":
            # int8 head: HBM streams the int8 kernel, the per-channel
            # scale lands in f32 — the logits keep f32 dynamic range
            # around int8-rounded products
            logits = QuantDense(cfg.vocab_size, dtype=cfg.dtype,
                                out_f32=True,
                                act_quant=cfg.param_quant == "w8a8",
                                name="output")(x)
        elif cfg.vocab_parallel:
            # column-parallel over VOCAB: each shard emits its own
            # logits columns [B, T, vocab/tp] — NOT psum-merged (the
            # full matrix would be the memory the layout exists to
            # avoid); train against vocab_parallel_xent.  x enters the
            # parallel region through f so the backward psum is exact
            # (under tp_seq_shard the entry re-gathers the rows ONCE,
            # since each shard's vocab columns are needed for EVERY
            # row's softmax).
            head_dtype = jnp.float32 if cfg.logits_dot_in_fp32 else cfg.dtype
            logits = nn.Dense(cfg.vocab_size // cfg.tp_size,
                              use_bias=False, dtype=head_dtype,
                              param_dtype=jnp.float32, name="output")(
                                  _enter_tp_region(x, cfg))
        else:
            head_dtype = jnp.float32 if cfg.logits_dot_in_fp32 else cfg.dtype
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              dtype=head_dtype, param_dtype=jnp.float32,
                              name="output")(x)
        return logits.astype(jnp.float32)


def llama_circular_layout(variables, n_stages: int, n_loops: int,
                          inverse: bool = False):
    """Permute the scanned block's layer axis into (or, with
    ``inverse=True``, back out of) the circular-pipeline storage order —
    apply BEFORE ``rank_major`` when training with
    ``llama_pp_loss_fn(..., n_loops>1)``, and inversely when exporting a
    checkpoint to the natural layer order.  See
    ``parallel.pipeline.circular_layer_permutation``."""
    from bluefog_tpu.parallel.pipeline import circular_layer_permutation

    block = variables["params"]["layers"]["block"]
    n_layers = jax.tree.leaves(block)[0].shape[0]
    perm = circular_layer_permutation(n_layers, n_stages, n_loops)
    if inverse:
        perm = np.argsort(perm)
    permuted = jax.tree.map(lambda a: jnp.take(a, perm, axis=0), block)
    out = dict(variables)
    out["params"] = dict(variables["params"])
    out["params"]["layers"] = {"block": permuted}
    return out


def llama_pp_loss_fn(cfg: LlamaConfig, *, pp_axis: str, n_stages: int,
                     n_micro: int, n_loops: int = 1):
    """Build a next-token cross-entropy ``loss_fn(params, (inputs,
    targets))`` that runs the decoder stack as a GPipe pipeline over
    ``pp_axis`` (see ``bluefog_tpu.parallel.pipeline.gpipe``) — pipeline
    parallelism, a capability past the reference's DP-only scope
    (SURVEY.md §2.3: PP absent there).

    Requires ``cfg.scan_layers=True``: the scanned parameter layout gives
    every block leaf a leading ``[n_layers]`` axis, which
    ``llama_param_specs(pp_axis=...)`` shards over the pipeline axis so
    each stage holds ``n_layers / n_stages`` layers.  The param TREE is
    identical to the plain scanned model — checkpoints move freely
    between pipeline layouts.

    The returned loss is per-shard MASKED: only the last stage's value is
    the real loss (other stages return 0).  Feed it to
    ``build_train_step(pp_axis=...)``, which psums the loss over the
    pipeline axis and reduces gradients for pp-replicated leaves
    (embeddings / final norm / head).

    Composes with sequence parallelism (``cfg.attn_mode='ring'``): rotary
    offsets are derived from the sp shard index internally, and each sp
    shard's partial loss is averaged by the train step's ``sp_axis``
    reduction.  Batch size must divide by ``n_micro``.

    ``n_loops > 1`` switches to the circular (interleaved) schedule:
    each stage holds ``n_loops`` round-robin layer chunks and
    microbatches ride the ring ``n_loops`` times, shrinking the bubble
    to ``(S-1)/(n_loops*M + S-1)``.  Params must be permuted into the
    circular storage order first (``llama_circular_layout``) and
    ``n_micro >= n_stages`` is required.
    """
    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True "
                         "(the stacked-layer param layout is what shards "
                         "over the pipeline axis)")
    if cfg.n_layers % (n_stages * n_loops):
        raise ValueError(f"n_layers ({cfg.n_layers}) must divide by "
                         f"n_stages*n_loops ({n_stages}*{n_loops})")
    if cfg.tp_seq_shard:
        raise ValueError(
            "tp_seq_shard is not supported in the pipeline loss builder "
            "yet (the stage boundary would have to carry seq-sharded "
            "activations through the pp permute); use it with the plain "
            "stack, or pp without tp_seq_shard")

    from bluefog_tpu.parallel.pipeline import gpipe, gpipe_circular

    # the exact modules Llama.__call__ uses — applied to param subtrees,
    # so the pp path cannot diverge from the plain model's math
    block = Block(cfg)
    final_norm = RMSNorm(cfg.norm_eps)
    head_dtype = jnp.float32 if cfg.logits_dot_in_fp32 else cfg.dtype
    if cfg.vocab_parallel:
        embed = VocabParallelEmbed(cfg)
        head = nn.Dense(cfg.vocab_size // cfg.tp_size, use_bias=False,
                        dtype=head_dtype, param_dtype=jnp.float32)
    else:
        embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=jnp.float32)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=head_dtype,
                        param_dtype=jnp.float32)
    want_aux = cfg.n_experts > 0 and cfg.moe_aux_weight > 0.0

    def loss_fn(params, batch):
        import optax

        inp, tgt = batch  # [B, T_local] int32
        p = params["params"]
        b, t = inp.shape
        if b % n_micro:
            raise ValueError(f"batch size {b} must divide by n_micro "
                             f"({n_micro})")
        x = embed.apply({"params": p["tok_embeddings"]}, inp)  # [B, T, D]
        pos_offset = 0
        if cfg.attn_mode in ("ring", "ulysses"):
            assert cfg.sp_axis is not None, "sequence parallelism needs " \
                "sp_axis"
            pos_offset = lax.axis_index(cfg.sp_axis) * t
        bm = b // n_micro
        x_micro = x.reshape(n_micro, bm, t, cfg.dim)
        layer_p = p["layers"]["block"]  # per-shard: leaves [L/S, ...]

        def per_layer(x, lp):
            if want_aux:
                y, mut = block.apply({"params": lp}, x, pos_offset,
                                     mutable=["intermediates"])
                aux = sum(jnp.sum(v) for v in
                          jax.tree.leaves(mut["intermediates"]))
                return y, aux
            return block.apply({"params": lp}, x, pos_offset), jnp.float32(0)

        body = per_layer
        if cfg.remat:
            body = jax.checkpoint(per_layer,
                                  policy=_remat_policies()[cfg.remat_policy],
                                  prevent_cse=False)

        def stage_fn(lp, x):
            y, aux = lax.scan(body, x, lp)
            return y, jnp.sum(aux)

        if n_loops > 1:
            # circular layout: this shard's [L/S] layers are its n_loops
            # chunks in loop order (params permuted by
            # llama_circular_layout before sharding)
            chunks = jax.tree.map(
                lambda a: a.reshape((n_loops, a.shape[0] // n_loops)
                                    + a.shape[1:]), layer_p)
            outs, aux_sum = gpipe_circular(
                stage_fn, chunks, x_micro, pp_axis, n_stages, n_loops,
                with_aux=True)
        else:
            outs, aux_sum = gpipe(stage_fn, layer_p, x_micro, pp_axis,
                                  n_stages, with_aux=True)
        h = outs.reshape(b, t, cfg.dim)
        # final norm + head are pp-replicated params; every stage runs
        # them (SPMD lockstep — no extra wall-clock) but only the last
        # stage's loss survives the mask, so their gradients are nonzero
        # exactly once across the axis and the train step's pp psum
        # restores the replicated update.
        h = final_norm.apply({"params": p["norm"]}, h)
        if cfg.vocab_parallel:
            hl = _tp_region_in(h, cfg.tp_axis)
            logits = head.apply({"params": p["output"]},
                                hl).astype(jnp.float32)
            loss = vocab_parallel_xent(logits, tgt, cfg.tp_axis)
        else:
            logits = head.apply({"params": p["output"]},
                                h).astype(jnp.float32)
            loss = jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, tgt))
        stage = lax.axis_index(pp_axis)
        loss = jnp.where(stage == n_stages - 1, loss, 0.0)
        if want_aux:
            # each stage owns its layers' routers, so its aux rides its
            # OWN loss term (unmasked — the train step's pp psum then
            # totals CE + every stage's aux).  aux_sum is over the M real
            # microbatch ticks; /M gives the per-microbatch mean — the
            # grouped-routing analogue of the unsharded full-batch aux
            # (identical to it when n_micro == 1).
            loss = loss + cfg.moe_aux_weight * aux_sum / n_micro
        return loss

    return loss_fn


def chunked_xent(h, w_kernel, targets, *, n_chunks: int = 8,
                 dot_in_fp32: bool = True):
    """Next-token cross-entropy computed CHUNK BY CHUNK over the sequence
    so the full ``[B, T, vocab]`` logits never materialize.

    ``h``: [B, T, dim] final-norm hidden states (``Llama.__call__`` with
    ``return_hidden=True``); ``w_kernel``: [dim, vocab] head kernel;
    ``targets``: [B, T] int32.  Each of the ``n_chunks`` sequence chunks
    computes its logits, log-sum-exp and target gather inside a
    ``jax.checkpoint`` region iterated by ``lax.map``: forward holds one
    [B, T/n_chunks, vocab] block at a time, backward recomputes it — at
    8B scale (seq 4096, vocab 128k) that is 16 GB of f32 logits (+ the
    same again for their cotangent) that never exist at once.  Exact:
    same f32 softmax math as the monolithic head (parity in
    tests/test_models.py)."""
    b, s, _ = h.shape
    if s % n_chunks:
        raise ValueError(f"seq len {s} % n_chunks {n_chunks} != 0")
    import optax

    c = s // n_chunks
    dtype = jnp.float32 if dot_in_fp32 else h.dtype
    hc = jnp.swapaxes(h.reshape(b, n_chunks, c, h.shape[-1]), 0, 1)
    tc = jnp.swapaxes(targets.reshape(b, n_chunks, c), 0, 1)

    @jax.checkpoint
    def one(args):
        hx, t = args
        logits = jnp.dot(hx.astype(dtype),
                         w_kernel.astype(dtype)).astype(jnp.float32)
        return jnp.sum(
            optax.softmax_cross_entropy_with_integer_labels(logits, t))

    return jnp.sum(lax.map(one, (hc, tc))) / (b * s)


def llama_chunked_xent_loss_fn(cfg: LlamaConfig, *, n_chunks: int = 8):
    """Build ``loss_fn(params, (inputs, targets))`` that runs the decoder
    stack normally but the head + cross-entropy through ``chunked_xent``
    (the fused/blockwise head path — the full logits tensor is the
    single largest activation of the train step at every size).  Not
    compatible with ``vocab_parallel`` (which has its own exact sharded
    xent) or MoE-aux configs (use the plain loss with intermediates)."""
    if cfg.vocab_parallel:
        raise ValueError("chunked xent: use vocab_parallel_xent with "
                         "vocab_parallel configs")
    if cfg.tp_seq_shard:
        raise ValueError("chunked xent: hidden states are seq-sharded "
                         "under tp_seq_shard but targets are not")
    if cfg.n_experts and cfg.moe_aux_weight > 0.0:
        raise ValueError("chunked xent does not collect MoE aux "
                         "intermediates; use the plain loss")
    model = Llama(cfg)

    def loss_fn(params, batch):
        inp, tgt = batch
        h = model.apply(params, inp, return_hidden=True)
        w = params["params"]["output"]["kernel"]
        return chunked_xent(h, w, tgt, n_chunks=n_chunks,
                            dot_in_fp32=cfg.logits_dot_in_fp32)

    return loss_fn


def llama_param_specs(params_or_shapes, rank_axis: Optional[str] = "bf",
                      tp_axis: Optional[str] = "tp",
                      ep_axis: Optional[str] = "ep",
                      pp_axis: Optional[str] = None,
                      vocab_axis: Optional[str] = None):
    """PartitionSpec tree for rank-major Llama params under model
    parallelism: column-parallel kernels (wq/wk/wv/w1/w3) shard their
    OUTPUT (last) dim over ``tp_axis``, row-parallel kernels (wo/w2)
    their INPUT (second-to-last) dim; MoE expert tensors (under
    ``moe_ffn``) shard their EXPERT dim over ``ep_axis``; with
    ``pp_axis`` (pipeline parallelism — requires the scanned-layer
    layout) every leaf under the scanned block additionally shards its
    leading ``[n_layers]`` axis over the pipeline axis, so each stage
    holds only its own layers.  The router and everything outside the
    decoder stack (embeddings, final norm, logits head) stay replicated
    — unless ``vocab_axis`` is given (``cfg.vocab_parallel`` models):
    then the embedding shards its VOCAB rows (dim 0) and the logits
    head its VOCAB columns (last dim) over that axis.
    Works for both unrolled and scanned layouts (the kernel rank decides
    where the sharded dim sits).  Feed the result to
    ``optim.functional.build_train_step(param_specs=...)``."""
    from jax.sharding import PartitionSpec as P

    column = ("wq", "wk", "wv", "w1", "w3")
    row = ("wo", "w2")

    def spec_for(path, leaf):
        names = "/".join(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path)
        tagged = f"/{names}/"
        # leaf shapes come WITHOUT the leading rank axis (pass the tree
        # that model.init returned); the produced specs are for the
        # rank-major global arrays, so the rank axis is prepended here
        nd = len(leaf.shape)
        leaf_name = str(getattr(path[-1], "key",
                                getattr(path[-1], "name", path[-1])))
        # QuantDense per-output-channel scales ([.., out]) shard exactly
        # like their kernel's OUTPUT dim: over tp for column-parallel
        # layers, replicated for row-parallel ones (whose tp-sharded dim
        # is the input)
        is_scale = leaf_name == "scale"
        dims = [None] * nd
        # scanned decoder stack: leading dim is the layer axis
        if pp_axis is not None and "/layers/" in tagged and nd >= 1:
            dims[0] = pp_axis
        if vocab_axis is not None and "/tok_embeddings/" in tagged \
                and nd >= 2:
            dims[0] = vocab_axis  # [V, D]: shard the vocab rows
        elif vocab_axis is not None and "/output/" in tagged and nd >= 1:
            dims[-1] = vocab_axis  # kernel [D, V] / scale [V]: columns
        elif "/moe_ffn/" in tagged:
            if ep_axis is not None and "/router/" not in tagged and nd >= 3:
                dims[-3] = ep_axis  # [.., E, in, out]: shard E
        elif any(f"/{k}/" in tagged for k in column) \
                and (nd >= 2 or (is_scale and nd >= 1)):
            if tp_axis is not None:
                dims[-1] = tp_axis
        elif any(f"/{k}/" in tagged for k in row) and nd >= 2 \
                and not is_scale:
            if tp_axis is not None:
                dims[-2] = tp_axis
        while dims and dims[-1] is None:  # canonical: no trailing Nones
            dims.pop()
        if rank_axis is None:
            # non-rank-major trees (e.g. replicated decode params whose
            # only sharded axis is tp): specs without the rank dim
            return P(*dims)
        return P(rank_axis, *dims)

    return jax.tree_util.tree_map_with_path(spec_for, params_or_shapes)
