"""Fused Pallas decode-attention kernel (parallel/pallas_decode.py):
exactness against the XLA cached-attention lowerings, and end-to-end
token parity through llama_generate.

The reference has no decode path at all (generation is a new capability,
docs/parity.md); the exactness bar here is the repo's own XLA decode
step.  CPU runs use interpret mode (selected automatically)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu import models
from bluefog_tpu.models.llama import (_amax_quantize, _cached_attention)
from bluefog_tpu.models import llama_generate
from bluefog_tpu.parallel.pallas_decode import (decode_attention,
                                                decode_attention_int8)


def _rand_cache(b, n_kv, s, d, seed=0):
    rng = np.random.RandomState(seed)
    k = jnp.asarray(rng.randn(b, n_kv, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, n_kv, s, d), jnp.float32)
    return k, v


@pytest.mark.parametrize("idx", [0, 5, 127])
@pytest.mark.parametrize("rep", [1, 4])
def test_decode_attention_matches_xla(idx, rep):
    b, n_kv, s, d = 2, 3, 128, 16
    n_q = n_kv * rep
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, 1, n_q, d), jnp.float32)
    k, v = _rand_cache(b, n_kv, s, d)
    # zero the unwritten tail like a real cache (the kernel must mask it)
    mask = (np.arange(s) <= idx)[None, None, :, None]
    k = k * mask
    v = v * mask
    ref = _cached_attention(q, k, v, jnp.int32(idx))
    out = decode_attention(q, k, v, jnp.int32(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_decode_attention_int8_matches_dequant_reference():
    """The int8 kernel == dequantize-the-cache + float attention (its
    scales commute exactly; probabilities are never re-quantized)."""
    b, n_kv, rep, s, d = 2, 2, 4, 256, 32
    idx = 200
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, 1, n_kv * rep, d), jnp.float32)
    k, v = _rand_cache(b, n_kv, s, d, seed=3)
    mask = (np.arange(s) <= idx)[None, None, :, None]
    k = k * mask
    v = v * mask
    kq, ks = _amax_quantize(k)
    vq, vs = _amax_quantize(v)
    ks, vs = ks[..., 0], vs[..., 0]
    k_deq = kq.astype(jnp.float32) * ks[..., None]
    v_deq = vq.astype(jnp.float32) * vs[..., None]
    ref = _cached_attention(q, k_deq, v_deq, jnp.int32(idx))
    out = decode_attention_int8(q, kq, ks, vq, vs, jnp.int32(idx))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_vmap_over_rows_folds_into_the_kernel_batch(quantized):
    """The serving engine maps the decode step over its cache slots,
    every slot at its OWN position: the kernel's vmap rule folds the
    mapped axis into its batch grid axis (one launch, per-row
    positions) and must equal a Python loop of single-slot calls."""
    slots, n_kv, rep, s, d = 3, 2, 2, 64, 16
    positions = jnp.asarray([0, 17, 63], jnp.int32)
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(slots, 1, 1, n_kv * rep, d), jnp.float32)
    k, v = _rand_cache(slots, n_kv, s, d, seed=5)
    cache = (k[:, None], v[:, None])          # [slots, B=1, KV, S, D]
    if quantized:
        (kq, ks), (vq, vs) = _amax_quantize(cache[0]), _amax_quantize(
            cache[1])
        operands = (kq, ks[..., 0], vq, vs[..., 0])
        kernel = decode_attention_int8
    else:
        operands, kernel = cache, decode_attention

    def one(q, idx, *ops):
        return kernel(q, *ops, idx)

    mapped = jax.jit(jax.vmap(one))(q, positions, *operands)
    jaxpr = str(jax.make_jaxpr(jax.vmap(one))(q, positions, *operands))
    assert jaxpr.count("pallas_call") == 1
    for i in range(slots):
        alone = one(q[i], positions[i], *[op[i] for op in operands])
        np.testing.assert_array_equal(np.asarray(mapped[i]),
                                      np.asarray(alone))


def test_decode_attention_blocked_softmax_is_stable():
    """Online softmax across S blocks == one-shot softmax (block_s
    smaller than S exercises the flash recurrence)."""
    b, n_kv, rep, s, d = 1, 2, 2, 512, 16
    idx = 511
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(b, 1, n_kv * rep, d) * 4.0, jnp.float32)
    k, v = _rand_cache(b, n_kv, s, d, seed=5)
    ref = _cached_attention(q, k, v, jnp.int32(idx))
    out = decode_attention(q, k, v, jnp.int32(idx), block_s=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_quant,weight_quant", [
    ("none", "none"), ("int8", "int8")])
def test_generate_pallas_decode_token_parity(kv_quant, weight_quant):
    """llama_generate with decode_attn='pallas' emits the same tokens as
    the XLA path: for the full-precision cache both compute identical
    f32 attention; for kv int8 + weight-only int8 the XLA path dequants
    the cache into float attention — the exact math the kernel fuses."""
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32)
    model = models.Llama(cfg)
    rng = np.random.RandomState(6)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 7)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 4), jnp.int32))
    if weight_quant != "none":
        from bluefog_tpu.models import quantize_llama_params
        variables = jax.jit(quantize_llama_params)(variables)
    kw = dict(kv_quant=kv_quant, weight_quant=weight_quant)
    # pin the reference to the XLA lowering: the default decode_attn=
    # "auto" resolves to pallas for short full-precision caches, which
    # would make this parity check compare pallas against itself
    ref = llama_generate(variables, cfg, prompt, 12, decode_attn="xla",
                         **kw)
    out = llama_generate(variables, cfg, prompt, 12, decode_attn="pallas",
                         **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_attn_validation():
    with pytest.raises(ValueError):
        models.LlamaConfig.tiny(decode_attn="pallas")  # decode-only knob
    with pytest.raises(ValueError):
        models.LlamaConfig.tiny(decode=True, decode_attn="mosaic")
