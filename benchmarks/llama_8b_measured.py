"""Llama-3-8B MEASURED on the real chip — per-component timings composed
into a projected pod MFU, plus a real end-to-end 8B quantized decode.

Round-5 closure of the verdict's top item: the BASELINE stress config
(Llama-3-8B decentralized SGD) had only a compile-time structural audit
(`llama_8b_structural.json`); nothing at 8B scale had ever been TIMED.
One 16 GB v5e chip cannot hold the 8B train state, but it CAN hold —
and this script times —

* **the exact tp8 per-shard decoder layer** of the shipped
  `tp8_seqshard` layout (d_model 4096, per-shard heads 4q/1kv at
  head_dim 128, per-shard ffn 1792, seq 4096, batch-per-dp-rank 2,
  flash attention with a tile sweep), forward AND backward;
* **the unsharded 8B layer** (32q/8kv, ffn 14336) — the tp=1 reference
  the tp-efficiency claim is judged against;
* **the vocab-parallel head + cross-entropy shard** (f32 [B, S, 16032]
  logits per chip) and its round-5 chunked-xent variant;
* **the embedding gather** and **the SGD+momentum update** on this
  chip's 1.004B param shard (an HBM-bound 20 bytes/param sweep);
* **end-to-end 8B w8a8 decode**: the int8-quantized 8B model FITS one
  chip (~9.7 GB kernels+embed) — generate runs for real, no
  extrapolation.

Composition (stated here, reproduced in docs/performance.md):

    t_chip = n_layers * t_layer + t_embed + t_head_xent + t_opt
    t_layer(remat=everything) = t_fwd + t_grad   (bwd recomputes fwd)
    t_step(no overlap)   = t_chip + t_ici
    t_step(full overlap) = max(t_chip, t_ici)

with t_ici from the scaling projection's machinery: per layer the
tp_seq_shard layout enters/leaves 2 tp regions (all-gather + reduce-
scatter of the [B, S, D] bf16 activation, ring cost (n-1)/n x bytes
over tp), and the dp axis pays one params-sized neighbor exchange per
step (int8 wire, congestion from `topology.default_pod_schedule`).
MFU uses the analytic 6N + causal-attention FLOPs over the v5e peak.

Run ALONE on the chip's machine (other load on the host's cores
poisons host-clock timings):

  PYTHONPATH=.:$PYTHONPATH python -u benchmarks/llama_8b_measured.py \
      [--part train|decode|all]
"""

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bluefog_tpu import models
from bluefog_tpu.benchutil import (chip_hbm_bandwidth, chip_peak_flops,
                                   device_fetch, fetch_overhead)
from bluefog_tpu.models.llama import Block

TP = 8
B, S = 2, 4096
V5E_LINK_GBPS = 200.0  # per-link one-way, the scaling projection's figure
OUT = "benchmarks/llama_8b_measured.json"


import dataclasses as _dc


@_dc.dataclass(frozen=True)
class _ShardConfig(models.LlamaConfig):
    """Per-shard compute twin: head_dim must stay the REAL 8B 128
    (dim // n_heads would give 4096/4 = 1024 — 8x the attention work;
    under tp the Attention module divides head COUNTS by tp_size while
    each head keeps its width)."""

    @property
    def head_dim(self) -> int:  # type: ignore[override]
        return 128


def shard_cfg(**over):
    """The tp8 per-shard COMPUTE twin of LlamaConfig.llama3_8b: heads,
    kv heads and ffn divided by tp; dim stays 4096 (activations are
    full-width between regions), head_dim stays 128.  Collectives are
    excluded on purpose — the composition adds them analytically (they
    cannot run on one chip)."""
    base = dict(vocab_size=256, dim=4096, n_layers=1, n_heads=32 // TP,
                n_kv_heads=8 // TP, hidden_dim=14336 // TP,
                max_seq_len=S, dtype=jnp.bfloat16, attn_impl="flash",
                rope_scaling_kind="llama3")
    base.update(over)
    return _ShardConfig(**base)


def unsharded_cfg(**over):
    base = dict(vocab_size=256, dim=4096, n_layers=1, n_heads=32,
                n_kv_heads=8, hidden_dim=14336, max_seq_len=S,
                dtype=jnp.bfloat16, attn_impl="flash",
                rope_scaling_kind="llama3")
    base.update(over)
    return models.LlamaConfig(**base)


def time_chain(fn, x0, n=8, overhead=None):
    """Median per-iteration seconds of a data-dependent chain of ``fn``
    (each iteration consumes the previous output, so XLA cannot
    parallelize or elide the chain)."""
    x = fn(x0)
    device_fetch(jnp.sum(x[0] if isinstance(x, tuple) else x))  # compile
    if overhead is None:
        overhead = fetch_overhead()
    times = []
    for _ in range(3):
        x = x0
        t0 = time.perf_counter()
        for _ in range(n):
            x = fn(x)
        device_fetch(jnp.sum(x[0] if isinstance(x, tuple) else x))
        times.append((time.perf_counter() - t0 - overhead) / n)
    return float(np.median(times))


def measure_layer(cfg, block_q=None, block_k=None):
    """fwd and fwd+bwd seconds of ONE decoder layer at [B, S, dim]."""
    if block_q:
        cfg = dataclasses.replace(cfg, attn_flash_block_size=block_q)
    if block_k:
        cfg = dataclasses.replace(cfg, attn_flash_block_k=block_k)
    layer = Block(cfg)
    x0 = jnp.asarray(
        np.random.RandomState(0).randn(B, S, cfg.dim) * 0.02, cfg.dtype)
    params = layer.init(jax.random.PRNGKey(0), x0, 0)

    # params ride as ARGUMENTS everywhere: a closure-captured 0.87 GB
    # param tree would be baked into the module as constants
    fwd = jax.jit(lambda p, x: layer.apply(p, x, 0))
    t_fwd = time_chain(lambda x: fwd(params, x), x0)

    def loss(p, x):
        return jnp.sum(layer.apply(p, x, 0).astype(jnp.float32) ** 2)

    # gradient wrt params AND input: training backward includes the dW
    # matmuls (a third of the backward FLOPs), not just dx
    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))

    def chain(x):
        _, dx = grad(params, x)
        return dx * 1e-30 + x0

    t_grad = time_chain(chain, x0)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    return t_fwd, t_grad, n_params


def measure_head_xent(chunks=0):
    """Vocab-parallel head shard + xent: h [B, S, 4096] -> f32 logits
    [B, S, 128256/8] (+ local lse/gather parts of vocab_parallel_xent;
    the two tiny psums ride the ICI term)."""
    v_local = 128256 // TP
    rng = np.random.RandomState(1)
    h0 = jnp.asarray(rng.randn(B, S, 4096) * 0.02, jnp.bfloat16)
    w = jnp.asarray(rng.randn(4096, v_local) * 0.02, jnp.float32)
    tgt = jnp.asarray(rng.randint(0, v_local, (B, S)), jnp.int32)

    if chunks:
        def fwd(h, w):
            return models.chunked_xent(h, w, tgt, n_chunks=chunks)
    else:
        def fwd(h, w):
            logits = jnp.dot(h.astype(jnp.float32), w)
            m = jnp.max(logits, -1)
            se = jnp.sum(jnp.exp(logits - m[..., None]), -1)
            hit = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
            return jnp.mean(m + jnp.log(se) - hit)

    # dW included: the head backward's [D, V] gradient matmul is half
    # its backward FLOPs
    g = jax.jit(jax.grad(fwd, argnums=(0, 1)))

    def chain(h):
        dh, _ = g(h, w)
        return dh * 1e-30 + h0

    return time_chain(chain, h0, n=4)


def measure_embed():
    v_local = 128256 // TP
    table = jnp.asarray(
        np.random.RandomState(2).randn(v_local, 4096) * 0.02, jnp.float32)
    tok0 = jnp.asarray(
        np.random.RandomState(3).randint(0, v_local, (B, S)), jnp.int32)
    # table as an argument (not a 262 MB jaxpr constant — see
    # measure_layer's note)
    f = jax.jit(lambda tbl, t: (jnp.take(tbl, t, axis=0), t))

    def step(carry):
        _, t = carry if isinstance(carry, tuple) else (None, carry)
        out, t = f(table, t if t is not None else tok0)
        return (out, (t + 1) % v_local)

    return time_chain(lambda c: step(c), (None, tok0), n=8)


def measure_opt_update(n_params=1_004_000_000):
    """SGD+momentum over this chip's param shard: pure HBM sweep,
    ~20 B/param (read p, m, g; write p, m)."""
    n = n_params // 4
    leaves = [jnp.ones((n,), jnp.float32) for _ in range(4)]
    opt = optax.sgd(1e-3, momentum=0.9)
    state = opt.init(leaves)

    # donate params+state: without donation the in+out copies of the
    # 4 GB params and 4 GB momentum alone exceed the 16 GB chip
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def update(params, state, seed):
        grads = [p * 1e-9 + seed for p in params]
        upd, state = opt.update(grads, state, params)
        return optax.apply_updates(params, upd), state

    params, st = update(leaves, state, jnp.float32(0.0))
    device_fetch(jnp.sum(params[0][:1]))
    overhead = fetch_overhead()
    times = []
    for rep in range(3):
        t0 = time.perf_counter()
        for i in range(4):
            params, st = update(params, st, jnp.float32(i))
        device_fetch(jnp.sum(params[0][:1]))
        times.append((time.perf_counter() - t0 - overhead) / 4)
    return float(np.median(times))


def flops_8b(seq=S, batch=B):
    """Analytic train FLOPs per step for the FULL 8B model on this dp
    rank: 6N over matmul params (head included, embedding excluded —
    it is a gather) + the causal attention term."""
    n_matmul = 8_030_000_000 - 128256 * 4096  # minus the embed table
    tokens = seq * batch
    base = 6 * n_matmul * tokens
    # causal attention: 12 * L * H * hd * S^2 * B / 2 (fwd+bwd, masked)
    attn = 12 * 32 * 32 * 128 * seq * seq * batch // 2
    return base + attn


def ici_terms():
    """Analytic ICI time per step for the tp8_seqshard x dp layout."""
    link = V5E_LINK_GBPS * 1e9 / 8  # bytes/s one-way
    act_bytes = B * S * 4096 * 2  # bf16 [B, S, D]
    # per layer: 2 tp regions x (all-gather + reduce-scatter), ring
    # cost (tp-1)/tp x bytes each
    per_layer = 4 * (TP - 1) / TP * act_bytes / link
    tp_total = 32 * per_layer
    # dp: one params-size neighbor exchange per step (int8 wire on the
    # default pod schedule: bytes/4, mean congestion 16/7)
    params_chip = 8_030_000_000 / TP * 4  # f32 bytes per chip
    dp_f32 = params_chip * (16 / 7) / link
    dp_int8 = dp_f32 / 4
    return {
        "tp_allgather_reducescatter_s_per_step": round(tp_total, 4),
        "dp_neighbor_exchange_f32_s": round(dp_f32, 4),
        "dp_neighbor_exchange_int8_s": round(dp_int8, 4),
        "note": "ring collective cost (n-1)/n x bytes at "
                f"{V5E_LINK_GBPS} Gbps/link one-way; dp uses the "
                "default_pod_schedule mean congestion 16/7 with int8 "
                "wire (scaling_projection_r05.json); overlap discounts "
                "come from the defended fractions, not a spread",
    }


def run_train_part(result, save):
    partial = result.setdefault("train_partial", {})
    # seed the resume cache from a previous completed run so already-
    # measured layer timings survive a re-run that only adds new rows
    prior = result.get("train", {})
    sweep = partial.setdefault(
        "flash_tile_sweep",
        dict(prior.get("flash_tile_sweep_shard_layer", {})))
    if "unsharded_layer" not in partial and "unsharded_layer" in prior:
        partial["unsharded_layer"] = {
            k: prior["unsharded_layer"][k] for k in ("fwd_s", "fwd_bwd_s")}
    print("[train] flash tile sweep on the tp8 shard layer", flush=True)
    # head_dim is 128 here (vs 64 at 200M/1B) — the f32 score buffer is
    # [block_q, block_k]; 2048-class tiles exceed the 16 MB scoped VMEM
    # and are excluded up front (q1024/k2048 measured 20.4M > 16M)
    for bq, bk in ((512, 1024), (512, 2048), (1024, 1024), (1024, 2048)):
        key = f"q{bq}_k{bk}"
        if "fwd_bwd_s" in sweep.get(key, {}):
            continue  # resumed run: keep measured rows
        try:
            t_fwd, t_grad, n_p = measure_layer(shard_cfg(), bq, bk)
        except Exception as e:  # VMEM OOM at this tile combo
            sweep[key] = {"error": str(e)[:160]}
            print(f"  q{bq}/k{bk}: FAILED ({str(e)[:80]})", flush=True)
            continue
        sweep[key] = {"fwd_s": round(t_fwd, 4),
                      "fwd_bwd_s": round(t_grad, 4)}
        print(f"  q{bq}/k{bk}: fwd {t_fwd*1e3:.1f} ms "
              f"grad {t_grad*1e3:.1f} ms", flush=True)
        save()  # a later tile may fail; keep what we have
    # round-5 final lever: the splash backend (fused-bwd library
    # kernel, parallel/splash.py) at the config's own block sizes —
    # the row key is DERIVED from the measured config, not hardcoded
    # (round-5 advice: a changed default would silently mislabel the row)
    splash_cfg = shard_cfg(attn_impl="splash")
    skey = (f"splash_q{splash_cfg.attn_flash_block_size}"
            f"_kv{splash_cfg.attn_flash_block_k}")
    if "fwd_bwd_s" not in sweep.get(skey, {}):
        print("[train] splash shard layer", flush=True)
        try:
            ts_fwd, ts_grad, _ = measure_layer(splash_cfg)
            sweep[skey] = {"fwd_s": round(ts_fwd, 4),
                           "fwd_bwd_s": round(ts_grad, 4)}
        except Exception as e:  # noqa: BLE001 — record, keep flash
            sweep[skey] = {"error": str(e)[:160]}
        save()
    ok = {k: v for k, v in sweep.items() if "fwd_bwd_s" in v}
    best_key = min(ok, key=lambda k: ok[k]["fwd_s"] + ok[k]["fwd_bwd_s"])
    flash_ok = {k: v for k, v in ok.items() if not k.startswith("splash")}
    flash_best = min(flash_ok,
                     key=lambda k: flash_ok[k]["fwd_s"]
                     + flash_ok[k]["fwd_bwd_s"])
    bq, bk = (int(x[1:]) for x in flash_best.split("_"))
    t_fwd = ok[best_key]["fwd_s"]
    t_grad = ok[best_key]["fwd_bwd_s"]
    shard_params = sum(
        p.size for p in jax.tree.leaves(jax.eval_shape(
            lambda: Block(shard_cfg()).init(
                jax.random.PRNGKey(0),
                jnp.zeros((B, S, 4096), jnp.bfloat16), 0))))

    print("[train] unsharded 8B layer (same tiles)", flush=True)
    if "unsharded_layer" not in partial:
        tu_fwd, tu_grad, _ = measure_layer(unsharded_cfg(), bq, bk)
        partial["unsharded_layer"] = {
            "fwd_s": round(tu_fwd, 4), "fwd_bwd_s": round(tu_grad, 4)}
        save()
    if best_key.startswith("splash") and \
            "unsharded_layer_splash" not in partial:
        # tp efficiency must compare same-impl layers
        print("[train] unsharded 8B layer (splash)", flush=True)
        tu_fwd, tu_grad, _ = measure_layer(
            unsharded_cfg(attn_impl="splash"))
        partial["unsharded_layer_splash"] = {
            "fwd_s": round(tu_fwd, 4), "fwd_bwd_s": round(tu_grad, 4)}
        save()
    unsh_key = ("unsharded_layer_splash" if best_key.startswith("splash")
                else "unsharded_layer")
    tu_fwd = partial[unsh_key]["fwd_s"]
    tu_grad = partial[unsh_key]["fwd_bwd_s"]
    full_params = sum(
        p.size for p in jax.tree.leaves(jax.eval_shape(
            lambda: Block(unsharded_cfg()).init(
                jax.random.PRNGKey(0),
                jnp.zeros((B, S, 4096), jnp.bfloat16), 0))))

    print("[train] head/xent, embed, optimizer", flush=True)
    t_head = measure_head_xent()
    t_head_chunked = measure_head_xent(chunks=8)
    save()
    t_embed = measure_embed()
    t_opt = measure_opt_update()

    result.pop("train_partial", None)
    t_layer = t_fwd + t_grad  # remat=everything: bwd recomputes fwd
    head_best = min(t_head, t_head_chunked)
    t_chip = 32 * t_layer + t_embed + head_best + t_opt
    ici = ici_terms()
    flops = flops_8b()
    peak = chip_peak_flops()
    result["train"] = {
        "layout": "tp8_seqshard (llama_8b_structural.json: fits 14.92 "
                  "GB/chip), batch_per_dp_rank 2, seq 4096",
        "flash_tile_sweep_shard_layer": sweep,
        "best_tiles": best_key,
        "attn_impl": ("splash" if best_key.startswith("splash")
                      else "flash"),
        "shard_layer": {"fwd_s": round(t_fwd, 4),
                        "fwd_bwd_s": round(t_grad, 4),
                        "remat_layer_s": round(t_layer, 4),
                        "params": int(shard_params)},
        "unsharded_layer": {"fwd_s": round(tu_fwd, 4),
                            "fwd_bwd_s": round(tu_grad, 4),
                            "params": int(full_params)},
        "tp_compute_efficiency": round(
            (tu_fwd + tu_grad) / (TP * t_layer), 4),
        "head_xent_shard_s": round(t_head, 4),
        "head_xent_shard_chunked8_s": round(t_head_chunked, 4),
        "embed_shard_s": round(t_embed, 5),
        "sgd_momentum_1B_params_s": round(t_opt, 4),
        "ici_analytic": ici,
        "composition": {
            "formula": "t_chip = 32*(fwd+fwd_bwd) + embed + "
                       "min(head, head_chunked) + opt; t_step = t_chip "
                       "+ (1-f_tp)*t_tp + (1-f_dp)*t_dp with f_* the "
                       "DEFENDED overlap fractions (overlap record; "
                       "benchmarks/llama_8b_overlap.py)",
            "t_chip_s": round(t_chip, 4),
        },
        "projected": {
            "flops_per_step_per_dp_rank": flops,
            "chip_peak_flops": peak,
        },
    }
    compose_defended(result)


def compose_defended(result):
    """Single defended-MFU composition: the overlap record's
    overlappable-bytes fractions discount each ICI term.  With no
    overlap record yet (run ``--part overlap`` or
    benchmarks/llama_8b_overlap.py) the fractions default to 0.0 —
    conservative, but still ONE number, not a spread."""
    if "overlap" not in result:
        result["overlap"] = {
            "note": "no overlap audit yet — fractions conservatively "
                    "0.0; run benchmarks/llama_8b_overlap.py (or "
                    "--part overlap) for the defended fractions",
            "dp_neighbor_exchange": {"fraction": 0.0,
                                     "basis": "unaudited"},
            "tp_allgather_reducescatter": {"fraction": 0.0,
                                           "basis": "unaudited"},
        }
    try:
        from llama_8b_overlap import rebase_projection
    except ImportError:  # imported as a package module
        from benchmarks.llama_8b_overlap import rebase_projection
    rebase_projection(result)


def run_decode_part(result, batch=4, prompt_len=256, new_tokens=256):
    """END-TO-END 8B w8a8+int8kv decode on the one chip: the int8 tree
    (~9.7 GB) fits, so this is a real generate, not an extrapolation."""
    print("[decode] building int8 8B param tree on-chip", flush=True)
    cfg = models.LlamaConfig.llama3_8b(
        dtype=jnp.bfloat16, rope_scaling_kind="llama3",
        scan_layers=True,  # O(1) compile in depth; cached-decode parity
        max_seq_len=prompt_len + new_tokens)  # with scan is tested
    dcfg = dataclasses.replace(cfg, decode=True, param_quant="w8a8",
                               kv_quant="int8")
    model = models.Llama(dcfg)
    # init directly in the quantized layout: int8 kernels + f32 scales
    # + f32 embed/norms — ~9.7 GB, never a f32 8B tree.  Init + fill in
    # ONE jit (a separate tree_map would hold old+new trees = ~19 GB);
    # non-zero kernels so the matmuls do real work
    def build():
        v = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((batch, 1), jnp.int32))
        return jax.tree.map(
            lambda p: (jnp.full(p.shape, 3, p.dtype)
                       if p.dtype == jnp.int8 else p), v["params"])

    variables = {"params": jax.jit(build)()}
    device_fetch(jax.tree.leaves(variables)[0][..., :1])
    n_bytes = sum(p.size * p.dtype.itemsize
                  for p in jax.tree.leaves(variables["params"]))
    print(f"  param bytes on chip: {n_bytes/1e9:.2f} GB", flush=True)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, prompt_len)), jnp.int32)

    rows = []
    for decode_attn in ("xla", "pallas"):
        def gen(n_new):
            return models.llama_generate(
                variables, cfg, prompt, n_new,
                max_len=prompt_len + new_tokens, kv_quant="int8",
                weight_quant="w8a8", decode_attn=decode_attn)
        print(f"[decode] {decode_attn}: compile + measure", flush=True)
        device_fetch(gen(new_tokens))
        overhead = fetch_overhead()
        t0 = time.perf_counter()
        device_fetch(gen(new_tokens))
        total = time.perf_counter() - t0 - overhead
        device_fetch(gen(1))
        t0 = time.perf_counter()
        device_fetch(gen(1))
        prefill = time.perf_counter() - t0 - overhead
        decode_s = max(total - prefill, 1e-9)
        tps = batch * (new_tokens - 1) / decode_s
        # stream floor: int8 kernels + f32 scales/norms + B embed rows
        # + mean cache
        kv_mean = (2 * 32 * 8 * batch * (prompt_len + new_tokens / 2)
                   * (128 + 4))
        floor = (n_bytes - 128256 // 1 * 4096 * 4
                 + batch * 4096 * 4 + kv_mean) / chip_hbm_bandwidth()
        rows.append({
            "decode_attn": decode_attn, "batch": batch,
            "prompt_len": prompt_len, "new_tokens": new_tokens,
            "decode_tokens_per_sec": round(tps, 1),
            "hbm_bound_tokens_per_sec": round(batch / floor, 1),
            "hbm_utilization": round(tps / (batch / floor), 3),
        })
        print(f"  {decode_attn}: {tps:.1f} tok/s", flush=True)
    result["decode_8b_w8a8_real"] = {
        "note": "END-TO-END measured 8B decode on one v5e chip "
                "(int8 param tree fits; synthetic weights, real "
                "program). kv int8 + w8a8, f32 embedding gather.",
        "param_bytes_gb": round(n_bytes / 1e9, 2),
        "rows": rows,
    }


def run_overlap_part(args):
    """Delegate the overlap audit to benchmarks/llama_8b_overlap.py in
    a FRESH process: the audit AOT-compiles on a 16-virtual-device CPU
    mesh, which needs XLA_FLAGS/JAX_PLATFORMS pinned before jax
    initializes (impossible in this already-initialized process).  This
    process holds the chip, so the child's environment pins the CPU
    before it imports jax: a child that reached for the chip would fail
    or hang."""
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "llama_8b_overlap.py")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    # absolute paths: the child runs with cwd=repo_root, the parent's
    # relative --out must still mean the SAME file in both processes
    subprocess.run(
        [sys.executable, script, "--out", os.path.abspath(args.out)],
        check=True, env=env, cwd=repo_root)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part", default="all",
                    choices=["train", "decode", "overlap", "all"])
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()
    if args.part != "overlap":
        assert jax.default_backend() == "tpu", "run on the real chip"
    result = {}
    if os.path.exists(args.out):  # resume an interrupted run
        with open(args.out) as fh:
            result = json.load(fh)
    result.update({
        "model": "llama3_8b", "chip": "v5e-1",
        "method": "per-component wall timings on the real chip "
                  "(data-dependent chains, fetch-overhead subtracted), "
                  "composed per the stated formula; ICI analytic; "
                  "overlap fractions from the scheduled-HLO "
                  "overlappable-bytes audit (llama_8b_overlap.py)",
    })
    def save():
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)

    if args.part in ("train", "all"):
        run_train_part(result, save)
        save()
    if args.part in ("decode", "all"):
        run_decode_part(result)
        save()
    if args.part in ("overlap", "all"):
        save()
        run_overlap_part(args)  # writes/updates args.out itself
        with open(args.out) as fh:
            result = json.load(fh)
    print(json.dumps(result.get("train", {}).get("projected", {}))
          if "train" in result else "")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
