"""K/V-cached decode throughput (tokens/sec) — the inference-side
counterpart of llama_benchmark.py.

Measures `llama_generate` end-to-end (prefill + scan decode, one
compiled program) at a given batch/prompt/new-token budget, and
reports per-sequence and aggregate decode tokens/sec plus the
decode-step bandwidth utilization (decode is HBM-bound: every step
reads all params + the K/V cache once).

Quantization levers (round 4): ``--kv-quant int8`` stores the K/V cache
as int8 + per-vector scales, ``--weight-quant int8`` streams int8
projection kernels (params quantized ONCE before timing, the serving
pattern), and ``--head bf16`` runs the logits matmul in the compute
dtype instead of f32.  Each shrinks bytes/step, which RAISES the
analytic ceiling — the floor below is computed from the actual stream
dtype of every leaf, so the utilization denominator moves with the
config.

  PYTHONPATH=. python examples/decode_benchmark.py --model 200m \
      --batch-size 8 --prompt-len 128 --new-tokens 256 \
      --kv-quant int8 --weight-quant int8
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu import models
from bluefog_tpu.benchutil import (chip_hbm_bandwidth, device_fetch,
                                   fetch_overhead)
from bluefog_tpu.config import configure_compilation_cache
from bluefog_tpu.models import llama_generate, quantize_llama_params
from bluefog_tpu.models.quant import QUANT_KERNELS

parser = argparse.ArgumentParser()
parser.add_argument("--model", default="200m", choices=["tiny", "200m", "1b"])
parser.add_argument("--batch-size", type=int, default=8)
parser.add_argument("--prompt-len", type=int, default=128)
parser.add_argument("--new-tokens", type=int, default=256)
parser.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
parser.add_argument("--kv-quant", default="none", choices=["none", "int8"])
parser.add_argument("--weight-quant", default="none",
                    choices=["none", "int8", "w8a8"])
parser.add_argument("--head", default="f32", choices=["f32", "bf16"],
                    help="logits matmul precision (ignored whenever "
                    "--weight-quant is not 'none': the int8 head "
                    "streams 1 B/el either way)")
parser.add_argument("--decode-attn", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="decode-step attention lowering: XLA einsums, "
                    "the fused Pallas kernel (parallel/pallas_decode.py), "
                    "or auto (the kernel on a TPU for a full-precision "
                    "cache, xla otherwise)")
parser.add_argument("--repeats", type=int, default=3)
args = parser.parse_args()


def make_config():
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    extra = dict(logits_dot_in_fp32=args.head == "f32")
    if args.model == "tiny":
        return models.LlamaConfig.tiny(dtype=dtype, **extra)
    if args.model == "200m":
        return models.LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=12, n_heads=16,
            n_kv_heads=4, hidden_dim=2816, max_seq_len=8192, dtype=dtype,
            **extra)
    return models.LlamaConfig.llama_1b(dtype=dtype, **extra)


def stream_bytes_per_step(variables, cfg, batch_size) -> int:
    """HBM bytes one decode step reads for parameters: every leaf in its
    STREAM dtype — int8 kernels 1 B/el, f32 QuantDense scales 4 B/el,
    full-precision params the casted compute-dtype copy XLA streams
    (2 B/el at bf16), except the logits head which streams f32 when
    ``logits_dot_in_fp32`` (the dot itself runs in f32 — there is no
    casted copy to stream).  The token-embedding table is NOT streamed
    whole: decode gathers ``batch_size`` rows per step, so only those
    rows count (the table is ~16% of params at 200M — charging it fully
    would understate the ceiling and inflate utilization)."""
    compute_bytes = 2 if cfg.dtype == jnp.bfloat16 else 4
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            variables["params"]):
        names = [str(getattr(p, "key", p)) for p in path]
        if "tok_embeddings" in names:
            # gather of B rows, in the leaf's storage dtype
            row_bytes = leaf.size // leaf.shape[0] * leaf.dtype.itemsize
            total += batch_size * row_bytes
        elif leaf.dtype == jnp.int8:
            total += leaf.size
        elif names[-1] == "scale" and names[-2] in QUANT_KERNELS:
            total += leaf.size * 4
        elif names[-2] == "output" and cfg.logits_dot_in_fp32:
            total += leaf.size * 4
        else:
            total += leaf.size * compute_bytes
    return total


def main():
    configure_compilation_cache()
    cfg = make_config()
    model = models.Llama(cfg)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch_size, args.prompt_len)),
        jnp.int32)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((args.batch_size, 8), jnp.int32))
    n_params = sum(p.size for p in jax.tree.leaves(variables["params"]))
    if args.weight_quant != "none":
        # once, offline — the serving pattern (quantize_llama_params doc)
        variables = jax.jit(quantize_llama_params)(variables)
        device_fetch(variables)

    def timed_generate(n_new):
        # same cache size both runs, so the prefill programs match and
        # the difference isolates the decode steps
        gen = lambda: llama_generate(
            variables, cfg, prompt, n_new,
            max_len=args.prompt_len + args.new_tokens,
            kv_quant=args.kv_quant, weight_quant=args.weight_quant,
            decode_attn=args.decode_attn)
        device_fetch(gen())  # compile + run once
        rtt = fetch_overhead()
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            device_fetch(gen())
            times.append(max(time.perf_counter() - t0 - rtt, 1e-9))
        return float(np.median(times))

    total_s = timed_generate(args.new_tokens)
    prefill_s = timed_generate(1)  # prefill + one step
    # decode-only: the remaining new_tokens - 1 scan steps
    decode_s = max(total_s - prefill_s, 1e-9)
    decode_steps = args.new_tokens - 1
    toks_per_sec = args.batch_size * decode_steps / decode_s

    # decode-step HBM floor: params once, in their stream dtype, plus
    # the written K/V cache (mean over the decode phase)
    param_bytes = stream_bytes_per_step(variables, cfg, args.batch_size)
    kv_vec = cfg.head_dim * (1 if args.kv_quant == "int8" else
                             (2 if args.dtype == "bf16" else 4)) \
        + (4 if args.kv_quant == "int8" else 0)  # + the f32 scale
    kv_bytes_mean = (2 * cfg.n_layers * cfg.n_kv_heads * args.batch_size
                     * (args.prompt_len + args.new_tokens / 2) * kv_vec)
    hbm = chip_hbm_bandwidth()
    step_floor_s = (param_bytes + kv_bytes_mean) / hbm if hbm else 0.0
    print(json.dumps({
        "model": args.model, "params": int(n_params),
        "batch": args.batch_size, "prompt_len": args.prompt_len,
        "new_tokens": args.new_tokens, "dtype": args.dtype,
        "kv_quant": args.kv_quant, "weight_quant": args.weight_quant,
        "decode_attn": args.decode_attn,
        "head": "int8" if args.weight_quant != "none" else args.head,
        "decode_tokens_per_sec": round(toks_per_sec, 1),
        "per_seq_tokens_per_sec": round(toks_per_sec / args.batch_size, 1),
        "end_to_end_s": round(total_s, 3),
        "prefill_plus_one_s": round(prefill_s, 3),
        "stream_bytes_per_step": int(param_bytes + kv_bytes_mean),
        "hbm_bound_tokens_per_sec": round(
            args.batch_size / step_floor_s, 1) if step_floor_s else None,
        "hbm_utilization": round(
            (decode_steps * step_floor_s) / decode_s, 3)
        if step_floor_s else None,
    }))


if __name__ == "__main__":
    main()
