"""Splash attention backend (parallel/splash.py).

Runs the real library kernel in pallas interpret mode on CPU (the
conftest pins JAX_PLATFORMS=cpu), so these exercise the exact program
that runs on the chip.  Numerical references are plain-XLA attention.
The library kernel is x64-incompatible (int32 program ids mixed with
Python ints), so every test scopes ``jax.enable_x64(False)`` — the
wrapper refuses to run otherwise, with the same advice.  Perf evidence
for the backend lives in benchmarks/splash_ab.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import llama as models
from bluefog_tpu.parallel.splash import splash_attention


def _ref_attention(q, k, v):
    b, t, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / (d ** 0.5)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _qkv(b=2, t=256, h=4, kv=2, d=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(ks[0], (b, t, h, d), dtype),
            jax.random.normal(ks[1], (b, t, kv, d), dtype),
            jax.random.normal(ks[2], (b, t, kv, d), dtype))


def test_splash_forward_matches_reference():
    with jax.enable_x64(False):
        q, k, v = _qkv()
        out = splash_attention(q, k, v, causal=True, block_q=128,
                               block_kv=128)
        ref = _ref_attention(q, k, v)
    # splash downcasts its VMEM scratch to bf16 — bf16-class tolerance
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_splash_gradients_match_reference():
    with jax.enable_x64(False):
        q, k, v = _qkv(t=256)

        def loss_splash(q, k, v):
            o = splash_attention(q, k, v, causal=True, block_q=128,
                                 block_kv=128)
            return (o.astype(jnp.float32) ** 2).sum()

        def loss_ref(q, k, v):
            return (_ref_attention(q, k, v).astype(jnp.float32) ** 2).sum()

        gs = jax.grad(loss_splash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gs, gr, "q k v".split()):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-2,
            err_msg=f"d{name} mismatch")


def test_splash_non_causal_refused():
    q, k, v = _qkv(t=128)
    with pytest.raises(NotImplementedError, match="causal"):
        splash_attention(q, k, v, causal=False)


def test_splash_x64_refused_with_advice():
    q, k, v = _qkv(t=128)
    assert jax.config.read("jax_enable_x64")  # conftest default
    with pytest.raises(NotImplementedError, match="enable_x64"):
        splash_attention(q, k, v, causal=True)


def test_llama_splash_matches_xla_loss():
    """Model-level: attn_impl='splash' computes the same loss/grads as
    the plain XLA path on the tiny config."""
    with jax.enable_x64(False):
        cfg_x = models.LlamaConfig.tiny(dtype=jnp.float32)
        cfg_s = models.LlamaConfig.tiny(dtype=jnp.float32,
                                        attn_impl="splash")
        model_x = models.Llama(cfg_x)
        model_s = models.Llama(cfg_s)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 128),
                                    0, 256)
        params = model_x.init(jax.random.PRNGKey(1), tokens)

        import optax

        def loss(m, p):
            logits = m.apply(p, tokens)
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]))

        lx, gx = jax.value_and_grad(lambda p: loss(model_x, p))(params)
        ls, gs = jax.value_and_grad(lambda p: loss(model_s, p))(params)
    assert abs(float(lx) - float(ls)) < 2e-3
    flat_x = jax.tree_util.tree_leaves(gx)
    flat_s = jax.tree_util.tree_leaves(gs)
    for a, b in zip(flat_x, flat_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-2, rtol=3e-2)


def test_splash_config_guards():
    with pytest.raises(ValueError, match="splash"):
        models.LlamaConfig.tiny(attn_impl="splash", attn_mode="ring",
                                sp_axis="sp")
    with pytest.raises(ValueError, match="attn_impl"):
        models.LlamaConfig.tiny(attn_impl="bogus")
