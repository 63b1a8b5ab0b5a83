"""A/B attention kernels at the Llama train shapes (round 5).

Compares, on the real chip, fwd and fwd+bwd wall time of:
  * ours      — bluefog_tpu.parallel.pallas_attention.flash_attention
  * jaxflash  — jax.experimental.pallas.ops.tpu.flash_attention (reference)
  * splash    — jax.experimental.pallas.ops.tpu.splash_attention (GQA-native,
                fused one-pass dq/dk/dv backward)

Timing uses benchutil.chain_time / fwd_bwd_time — the jitted
fori_loop data-dependent-chain harness (benchmarks/llama_roofline.py).
A host loop of independent calls is NOT a timing of the kernel: the
calls pipeline on the device and each pays a host dispatch.  The
decision evidence for adopting splash is END-TO-END
(examples/llama_benchmark.py); this script's isolated numbers locate
where a difference comes from.

Usage: python benchmarks/splash_ab.py [--model 1b|200m|8b_shard]
"""

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu.benchutil import chain_time, chip_peak_flops, fwd_bwd_time
from bluefog_tpu.parallel.pallas_attention import flash_attention as ours_flash

SHAPES = {
    # batch, q_heads, kv_heads, seq, head_dim  (per-chip train shapes,
    # matching benchmarks/llama_roofline.py CONFIGS)
    "1b": (4, 32, 8, 2048, 64),
    "200m": (8, 16, 4, 2048, 64),
    # 8B tp8_seqshard shard: 4 q heads / 1 kv head per chip, seq 4096,
    # batch-per-dp-rank 2 (benchmarks/llama_8b_measured.py train layout)
    "8b_shard": (2, 4, 1, 4096, 128),
}


def attn_flops(b, h, s, d, causal=True):
    # QK^T + PV, fwd only; bwd adds 2x (dq, dk, dv, dS recompute).
    f = 2 * 2 * b * h * s * s * d
    return f // 2 if causal else f


_ITERS = 20


def _bench(f, q0, kv0):
    """(fwd_s, fwd_bwd_s) of out = f((k, v), q) via the chained harness.

    fwd_bwd_time's grads wrt (params, x) = (dk, dv, dq) — the full
    attention backward, every gradient consumed.
    """
    return (chain_time(f, kv0, q0, n=_ITERS),
            fwd_bwd_time(f, kv0, q0, n=_ITERS))


def bench_ours(b, h, kv, s, d, dtype, block=1024):
    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(b, s, h, d) * 0.02, dtype)
    kv0 = (jnp.asarray(rng.randn(b, s, kv, d) * 0.02, dtype),
           jnp.asarray(rng.randn(b, s, kv, d) * 0.02, dtype))

    def attn(p, q):
        return ours_flash(q, p[0], p[1], causal=True,
                          block_q=block, block_k=block)

    return _bench(attn, q0, kv0)


def bench_jaxflash(b, h, kv, s, d, dtype, block=1024):
    from jax.experimental.pallas.ops.tpu import flash_attention as jf
    rng = np.random.RandomState(0)
    # reference kernel is MHA [B, H, S, D]; kv heads broadcast to h
    q0 = jnp.asarray(rng.randn(b, h, s, d) * 0.02, dtype)
    kv0 = (jnp.asarray(rng.randn(b, h, s, d) * 0.02, dtype),
           jnp.asarray(rng.randn(b, h, s, d) * 0.02, dtype))
    blk = min(block, s)
    bs = jf.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk,
        block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk,
    )

    def attn(p, q):
        return jf.flash_attention(q, p[0], p[1], causal=True,
                                  sm_scale=1.0 / d ** 0.5, block_sizes=bs)

    return _bench(attn, q0, kv0)


def bench_splash(b, h, kv, s, d, dtype, block=1024):
    from bluefog_tpu.parallel.splash import splash_attention
    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(b, s, h, d) * 0.02, dtype)
    kv0 = (jnp.asarray(rng.randn(b, s, kv, d) * 0.02, dtype),
           jnp.asarray(rng.randn(b, s, kv, d) * 0.02, dtype))

    def attn(p, q):
        return splash_attention(q, p[0], p[1], causal=True,
                                block_q=block, block_kv=block)

    # warm the kernel's mask-info conversion cache OUTSIDE any trace:
    # first-called inside fori_loop it caches tracers and the second
    # trace dies with UnexpectedTracerError
    jax.block_until_ready(attn(kv0, q0))
    return _bench(attn, q0, kv0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="1b", choices=sorted(SHAPES))
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--block", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=20,
                    help="chain length; raise for sub-ms kernels (the "
                         "8B shard shapes need ~200 to rise above the "
                         "fetch-overhead noise)")
    args = ap.parse_args()
    global _ITERS
    _ITERS = args.iters
    assert jax.default_backend() == "tpu", "run on the real chip"
    b, h, kv, s, d = SHAPES[args.model]
    dtype = jnp.dtype(args.dtype)
    fl_fwd = attn_flops(b, h, s, d)
    peak = chip_peak_flops()
    results = {}
    for name, fn in [("ours", bench_ours), ("jaxflash", bench_jaxflash),
                     ("splash", bench_splash)]:
        try:
            tf, tb = fn(b, h, kv, s, d, dtype, block=args.block)
            results[name] = {
                "fwd_ms": round(tf * 1e3, 3),
                "fwd_bwd_ms": round(tb * 1e3, 3),
                "mfu_fwd": round(fl_fwd / tf / peak, 3),
                "mfu_fwd_bwd": round(3 * fl_fwd / tb / peak, 3),
            }
        except Exception as e:  # noqa: BLE001 — record kernel-level failures
            results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        print(name, json.dumps(results[name]), flush=True)
    print(json.dumps({"model": args.model, "shapes": [b, h, kv, s, d],
                      "dtype": str(dtype), "block": args.block,
                      "results": results}))


if __name__ == "__main__":
    main()
