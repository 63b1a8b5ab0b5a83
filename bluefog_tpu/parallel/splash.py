"""Splash-attention train backend (library kernel, fused backward).

Round-5 A/B at the 1B per-layer train shapes (benchmarks/splash_ab.py,
v5e-1, [B=4, H=32, KV=8, S=2048, D=64] bf16, causal, chained-loop
timing; earlier installation, record removed) measured
``jax.experimental.pallas.ops.tpu.splash_attention``
with its fused one-pass dq/dk/dv backward at **6.37 ms fwd+bwd** per
layer vs **8.72 ms** for our ``pallas_attention`` kernel (forward is a
wash: 2.63 vs 2.71 ms — the win is the fused backward).  End-to-end
(examples/llama_benchmark.py): **+10.0% tokens/s at 1B (58.5% MFU) and
+10.5% at 200M (50.0%)**, loss identical.  ``LlamaConfig(
attn_impl="splash")`` opts the plain causal full-sequence train path
into it; at the 8B tp8_seqshard shard shapes the whole-layer chain
still favored our flash kernel in that round's tile sweep (earlier
installation, record removed), so the 8B composition keeps ``flash``.

Our kernel remains the default and the only backend with an LSE output
(ring/blockwise composition, ``flash_attention_with_lse``) and
``q_offset``/``kv_offset`` support (decode); splash is a train-time
throughput knob.  GQA is native on both (q heads grouped over kv heads,
never materialized).  Precision note: splash downcasts its Q/K/V VMEM
scratch to bf16 (``downcast_smem_data=True``), the same precision class
as our bf16 train path; measured f32-input deltas vs our kernel are
~7e-4 (fwd) / ~9e-4 (dq).

Reference parity note: the reference framework has no attention kernels
at all (it is a DP communication library); this module is part of the
beyond-parity model stack.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from bluefog_tpu.parallel.pallas_attention import _fit_block

__all__ = ["splash_attention"]


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=64)
def _make_kernel(n_heads: int, seq: int, block_q: int, block_kv: int,
                 interpret: bool):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    mask = sa.MultiHeadMask([sa.CausalMask((seq, seq))
                             for _ in range(n_heads)])
    # q blocks must be whole 8-row sublane tiles (the library kernel's
    # grid math otherwise fails deep inside Mosaic with an opaque
    # layout error); seq is a multiple of 128 here (checked by the
    # wrapper), so fitting over seq//8 then scaling back up keeps every
    # candidate divisor tile-aligned — the same construction bkv uses
    # for whole 128-lane tiles below.
    bq = _fit_block(seq // 8, max(block_q // 8, 1)) * 8
    # kv blocks must be whole 128-lane tiles (kernel NUM_LANES check)
    bkv = _fit_block(seq // 128, max(block_kv // 128, 1)) * 128
    sizes = sa.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        # fused backward: block_q_dq/block_kv_dq must stay unset
        use_fused_bwd_kernel=True)
    return sa.make_splash_mha(mask=mask, block_sizes=sizes,
                              head_shards=1, q_seq_shards=1,
                              interpret=interpret)


def splash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool = True, scale: Optional[float] = None,
                     block_q: int = 1024, block_kv: int = 1024,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Causal self-attention via the splash kernel.

    Same contract as ``pallas_attention.flash_attention``'s train path:
    ``q [B, T, H, D]``, ``k/v [B, T, H_kv, D]`` -> ``[B, T, H, D]``,
    softmax(scale * q k^T + causal mask) v, differentiable.  The kernel
    wants head-major operands and pre-scaled queries; this wrapper
    adapts both and vmaps over the batch.
    """
    if not causal:
        raise NotImplementedError(
            "attn_impl='splash' supports the causal train path only; "
            "use attn_impl='flash' or 'xla' for non-causal attention")
    if jax.config.read("jax_enable_x64"):
        # the library's index maps mix int32 program ids with Python
        # ints, which promote to int64 under x64 and fail lax.div/rem
        # dtype checks (in backward traces too, beyond any local scope)
        raise NotImplementedError(
            "attn_impl='splash' is incompatible with jax_enable_x64; "
            "scope it off around the train step: "
            "`with jax.enable_x64(False): ...`")
    b, t, h, d = q.shape
    if t % 128:
        raise NotImplementedError(
            f"attn_impl='splash' needs the sequence length to be a "
            f"multiple of 128 (kv blocks are whole 128-lane tiles; "
            f"got {t}) — use attn_impl='flash' for odd lengths")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    kernel = _make_kernel(h, t, block_q, block_kv,
                          _auto_interpret(interpret))
    qh = jnp.swapaxes(q * jnp.asarray(scale, q.dtype), 1, 2)  # [B,H,T,D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = jax.vmap(kernel)(qh, kh, vh)  # [B,H,T,D]
    return jnp.swapaxes(out, 1, 2)
