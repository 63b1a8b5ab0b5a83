"""Autoregressive generation with K/V caching.

The reference framework is training-only; users of an LLM framework also
need inference.  This is the TPU-native decode loop: one prefill call
writes the prompt's K/V into per-layer caches (flax "cache" collection),
then a single ``lax.scan`` emits tokens one at a time — the whole
generation is jittable (static prompt length / token budget / cache
size), with no per-token host round trips beyond the final fetch.

Trained parameters decode directly: ``decode=True`` changes no param
shapes (``LlamaConfig.decode``), and both layer layouts (unrolled and
``scan_layers``) carry caches (the scanned stack declares a cache axis).
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.models.llama import Llama, LlamaConfig

__all__ = ["init_cache", "llama_generate", "decode_config",
           "prefill_cache", "decode_token_step"]


def _decode_cfg(cfg: LlamaConfig, max_len: int, keep_tp: bool = False,
                kv_quant: str = "none",
                weight_quant: str = "none",
                decode_attn: str = "xla") -> LlamaConfig:
    """Decode layout: sequence/expert mesh knobs are cleared (they are
    training-time layouts); tensor parallelism is KEPT when requested —
    a tp-sharded K/V-cached decode serves checkpoints too big for one
    chip (each shard holds its own heads' cache; outputs merge through
    the same f/g psum pair as training).

    MoE configs decode with DROPLESS routing (capacity_factor raised to
    n_experts, so per-group capacity >= group_tokens * top_k): train-
    time capacity drops depend on which tokens are co-batched, so a
    cached one-token-at-a-time decode could never reproduce them —
    dropless routing removes the coupling entirely (each token always
    gets its full top-k combine, making the output grouping-invariant),
    and the cached decode matches the dropless full forward
    token-for-token (tests/test_moe_decode.py).  This is the standard
    inference treatment: capacity is a training-throughput knob, not
    part of the learned function.  The training config's
    ``moe_group_size`` is KEPT: grouped dropless routing is exact too,
    and it is what keeps the prefill's dispatch/combine tensors linear
    in the prompt length."""
    moe = {}
    if cfg.n_experts:
        if cfg.moe_router != "topk":
            raise NotImplementedError(
                "llama_generate supports only moe_router='topk' "
                "(expert_choice is non-causal and cannot decode)")
        moe = dict(capacity_factor=max(cfg.capacity_factor,
                                       float(cfg.n_experts)))
    if decode_attn == "auto":
        # Decided from what is known when the program is traced; how
        # much of the cache is live is the kernel's business at run
        # time (it fetches the blocks at or before each row's position,
        # parallel/pallas_decode.py).  The fused step serves a
        # full-precision cache at any length (PERF.md section 6, PR 27,
        # has the readings); an int8 cache stays on the XLA lowering,
        # which no cell of the benchmark runs and PR 27 did not
        # measure.  The kernel needs a viable S tiling (awkward cache
        # lengths fall back instead of erroring) and a REAL TPU: off
        # one it would run in Pallas interpret mode, orders of
        # magnitude slower than the einsums.
        from bluefog_tpu.parallel.pallas_decode import tileable

        decode_attn = ("pallas" if kv_quant == "none" and tileable(max_len)
                       and jax.default_backend() == "tpu" else "xla")
    tp = {} if keep_tp else {"tp_axis": None, "tp_size": 1}
    # vocab_parallel is a training-time memory layout (it shards the
    # optimizer-state-bearing vocab matrices); decode clears it like the
    # other training-only knobs — the param TREE is identical, so a
    # vocab_parallel-trained checkpoint serves through the replicated
    # head directly.
    return dataclasses.replace(
        cfg, decode=True, max_seq_len=max_len, attn_mode="full",
        attn_impl="xla", sp_axis=None, ep_axis=None, ep_size=1,
        remat=False, remat_policy="none", kv_quant=kv_quant,
        param_quant=weight_quant, decode_attn=decode_attn,
        vocab_parallel=False, tp_seq_shard=False, **moe, **tp)


def decode_config(cfg: LlamaConfig, max_len: int, *, keep_tp: bool = False,
                  kv_quant: str = "none", weight_quant: str = "none",
                  decode_attn: str = "xla") -> LlamaConfig:
    """Public form of the decode-layout transform: the config a K/V-cached
    decode program runs under (``decode=True``, cache length ``max_len``,
    training-time mesh knobs cleared; see :func:`_decode_cfg`).  The
    serving engine (``bluefog_tpu.serving``) builds its resident model
    from this, so engine steps and :func:`llama_generate` share one
    definition of "the decode layout" — and therefore one numerics."""
    return _decode_cfg(cfg, max_len, keep_tp=keep_tp, kv_quant=kv_quant,
                       weight_quant=weight_quant, decode_attn=decode_attn)


def prefill_cache(model: Llama, params, cache, tokens: jax.Array):
    """Cache-writing prefill: one multi-token forward writes ``tokens``'s
    K/V into ``cache`` at its current index.  Returns ``(logits, cache')``
    with ``logits [B, T, V]``.  ``params`` is the bare param tree (not the
    ``{"params": ...}`` wrapper).  Shared by :func:`llama_generate`'s
    one-shot path and the serving engine's chunked prefill — both are
    this exact call, so their numerics agree token for token."""
    logits, mut = model.apply({"params": params, "cache": cache}, tokens,
                              mutable=["cache"])
    return logits, mut["cache"]


def decode_token_step(model: Llama, params, cache, tok: jax.Array):
    """One incremental decode step: append ``tok [B, 1]``'s K/V and return
    ``(last_logits [B, V], cache')``.  The single-token twin of
    :func:`prefill_cache`, shared by the one-shot scan body and the
    serving engine's slot-batched step."""
    logits, mut = model.apply({"params": params, "cache": cache}, tok,
                              mutable=["cache"])
    return logits[:, -1], mut["cache"]


def init_cache(cfg: LlamaConfig, batch_size: int, max_len: int,
               keep_tp: bool = False, kv_quant: str = "none"):
    """Zero K/V caches for ``batch_size`` sequences of up to ``max_len``
    tokens — built from shapes only (``jax.eval_shape``), no forward
    pass, no params needed.  With ``keep_tp`` the shapes are PER-SHARD
    (local kv heads) for the tp-sharded decode path; ``kv_quant='int8'``
    yields the int8 + per-vector-scale cache layout."""
    model = Llama(_decode_cfg(cfg, max_len, keep_tp=keep_tp,
                              kv_quant=kv_quant))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((batch_size, 1), jnp.int32)))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def llama_generate(variables, cfg: LlamaConfig, prompt: jax.Array,
                   max_new_tokens: int, *, temperature: float = 0.0,
                   rng: Optional[jax.Array] = None,
                   max_len: Optional[int] = None,
                   mesh=None, kv_quant: str = "none",
                   weight_quant: str = "none",
                   decode_attn: str = "auto",
                   eos_id: Optional[int] = None) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    Args:
      variables: ``{"params": ...}`` from training / HF import (any
        layer layout; model-parallel shardings are the caller's concern —
        pass replicated params here).
      cfg: the model's config (its ``decode``/``max_seq_len`` are
        overridden internally).
      prompt: ``[B, T_prompt]`` int32 token ids.
      max_new_tokens: number of tokens to emit (static, >= 1).
      temperature: 0 = greedy argmax; otherwise softmax sampling at this
        temperature (needs ``rng``).  Traced — changing the temperature
        does NOT recompile (only switching greedy <-> sampling does).
      max_len: cache length; defaults to ``T_prompt + max_new_tokens``.
      kv_quant: "int8" stores the K/V cache as int8 with per-vector f32
        scales — half the cache HBM traffic (decode is bandwidth-bound).
      weight_quant: "int8" (weight-only) or "w8a8" (also quantizes
        activations per token and runs native s8xs8 MXU dots) run every
        projection + the logits head from int8 kernels with
        per-output-channel scales.  The faster mode is SCALE-DEPENDENT
        (measured, docs/performance.md round 4): "w8a8" wins at ~200M
        (the weight-only convert path is VPU-bound there), "int8" wins
        at ~1B+ (larger contractions amortize the convert and w8a8's
        activation-quant overhead flips the ordering) — benchmark both
        with examples/decode_benchmark.py.  ``variables`` must already
        be the quantized tree
        (:func:`bluefog_tpu.models.quant.quantize_llama_params` — do it
        once offline, not per call).
      decode_attn: "pallas" runs single-token decode steps through the
        fused Pallas attention kernel (one launch per layer, in-kernel
        int8 cache dequant, float probabilities —
        parallel/pallas_decode.py); "xla" keeps the einsum lowering;
        "auto" (default) takes the kernel on a TPU for a
        full-precision cache whose length it can tile, and the einsums
        otherwise (``_decode_cfg``).  Measure:
        examples/decode_benchmark.py ``--decode-attn``.
      eos_id: early-stop token id.  Once a row emits ``eos_id`` its
        remaining positions are frozen to ``eos_id`` (the done mask rides
        the ``lax.scan`` carry, so finished rows stop emitting sampled
        tokens); rows that never emit it are bit-identical to the
        unstopped path.  ``None`` (default) disables the check.  Static:
        switching eos ids compiles a new program (one id per served
        model in practice).

    Returns ``[B, T_prompt + max_new_tokens]`` int32: prompt ‖ generation.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens ({max_new_tokens}) must be >= 1")
    b, t_prompt = prompt.shape
    total = t_prompt + max_new_tokens
    max_len = max_len or total
    if max_len < total:
        raise ValueError(f"max_len ({max_len}) < prompt + new tokens "
                         f"({total})")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng=")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    from bluefog_tpu.models.quant import is_quantized_params

    if (weight_quant != "none") != is_quantized_params(variables):
        raise ValueError(
            "weight_quant='int8'/'w8a8' requires params converted by "
            "quantize_llama_params (and full-precision params require "
            "weight_quant='none'); got a mismatched tree")
    quant = dict(kv_quant=kv_quant, weight_quant=weight_quant,
                 decode_attn=decode_attn)
    if cfg.tp_size > 1 and mesh is not None:
        # tp-sharded decode: run the whole generate program under
        # shard_map over the tp axis — params shard by the Megatron
        # column/row layout, each shard keeps its own heads' K/V cache,
        # and the psum-merged logits are replicated so every shard
        # samples the same token (same rng).  Without mesh= the tp knobs
        # are cleared and decode runs replicated (the original
        # single-chip behavior).
        dcfg = _decode_cfg(cfg, max_len, keep_tp=True, **quant)
        fn = _tp_generate_program(dcfg, max_new_tokens,
                                  temperature == 0.0, max_len, mesh,
                                  eos_id)
        return fn(variables["params"], prompt, jnp.float32(temperature),
                  rng)
    return _generate_impl(
        variables, prompt, jnp.float32(temperature), rng,
        cfg=_decode_cfg(cfg, max_len, **quant),
        max_new_tokens=max_new_tokens,
        greedy=temperature == 0.0, max_len=max_len, eos_id=eos_id)


def _generate_body(variables, prompt, temperature, rng, *,
                   cfg: LlamaConfig, max_new_tokens: int, greedy: bool,
                   max_len: int,
                   eos_id: Optional[int] = None) -> jax.Array:
    b = prompt.shape[0]
    model = Llama(cfg)
    params = variables["params"]
    # cfg here is already the decode layout; keep_tp preserves its tp
    # knobs so the cache shapes are per-shard under the tp shard_map
    cache = init_cache(cfg, b, max_len, keep_tp=cfg.tp_size > 1,
                       kv_quant=cfg.kv_quant)

    def sample(logits_last, rng):
        if greedy:
            return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits_last / temperature, axis=-1).astype(jnp.int32)

    # prefill: one multi-token call writes the prompt K/V
    logits, cache = prefill_cache(model, params, cache, prompt)
    rng, sub = jax.random.split(rng)
    tok = sample(logits[:, -1], sub)

    def step(carry, _):
        cache, tok, rng, done = carry
        last, cache = decode_token_step(model, params, cache, tok[:, None])
        rng, sub = jax.random.split(rng)
        nxt = sample(last, sub)
        if eos_id is not None:
            # a row is done once it has EMITTED eos; its later positions
            # freeze to eos_id (the already-emitted tok passes through
            # untouched — the first eos itself is part of the output)
            done = done | (tok == eos_id)
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
        return (cache, nxt, rng, done), tok

    done0 = jnp.zeros((b,), bool)
    (_, last, _, _), toks = lax.scan(step, (cache, tok, rng, done0), None,
                                     length=max_new_tokens - 1)
    generated = jnp.concatenate(
        [jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1) \
        if max_new_tokens > 1 else tok[:, None]
    return jnp.concatenate([prompt, generated], axis=1)


_generate_impl = partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "greedy", "max_len", "eos_id"))(_generate_body)


@functools.lru_cache(maxsize=8)
def _tp_generate_program(dcfg: LlamaConfig, max_new_tokens: int,
                         greedy: bool, max_len: int, mesh,
                         eos_id: Optional[int] = None):
    """Cached jitted shard_map program for tp-sharded decode — a serving
    loop reuses ONE compilation per (config, token budget, mesh).  The
    param partition specs derive from the config alone (via eval_shape),
    so the cache key never needs the concrete params."""
    from jax.sharding import PartitionSpec as P

    from bluefog_tpu.models.llama import llama_param_specs

    # structure-only init of the tp-CLEARED twin (identical param paths
    # and ranks — including QuantDense's scale leaves, so weight_quant
    # carries over; tracing the tp model outside shard_map would hit
    # unbound-axis psums)
    plain = _decode_cfg(dcfg, dcfg.max_seq_len,
                        weight_quant=dcfg.param_quant)
    abstract = jax.eval_shape(
        lambda: Llama(plain).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 1), jnp.int32)))
    pspecs = llama_param_specs(abstract["params"], rank_axis=None,
                               tp_axis=dcfg.tp_axis, ep_axis=None)

    def body(params, prompt, temperature, rng):
        return _generate_body(
            {"params": params}, prompt, temperature, rng, cfg=dcfg,
            max_new_tokens=max_new_tokens, greedy=greedy, max_len=max_len,
            eos_id=eos_id)

    sm = jax.shard_map(body, mesh=mesh, in_specs=(pspecs, P(), P(), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(sm)
