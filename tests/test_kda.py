"""``models/kda.py``: the chunked form, the single step taken T times
and a plain token loop in float64 are one recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import kda


def token_loop(q, k, v, g, beta, state):
    """The recurrence as written, float64, one sequence: q, k, g [T, H,
    D], v [T, H, Dv], beta [T, H], state [H, D, Dv]."""
    s = np.array(state, np.float64)
    out = []
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            decayed = np.exp(g[t, h])[:, None] * s[h]
            kk = k[t, h][:, None]
            s[h] = decayed - beta[t, h] * kk @ (kk.T @ decayed) \
                + beta[t, h] * kk @ v[t, h][None, :]
        out.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.stack(out), s


def draw(seed, t, h=3, d=8, low=-5.0, live=None):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(t, h, d))) * d ** -0.5
    k = unit(rng.normal(size=(t, h, d)))
    v = rng.normal(size=(t, h, d))
    g = low * rng.uniform(0.0, 1.0, size=(t, h, d))
    beta = rng.uniform(0.0, 1.0, size=(t, h))
    if live is not None:
        g = np.where(live[:, None, None], g, 0.0)
        beta = np.where(live[:, None], beta, 0.0)
    state = rng.normal(size=(h, d, d))
    return q, k, v, g, beta, state


def as_f32(*xs):
    return [jnp.asarray(x, jnp.float32)[None] for x in xs]


@pytest.mark.parametrize("t", [1, 2, 15, 16, 17, 33, 64])
def test_chunked_steps_and_the_token_loop_agree(t):
    """Several lengths, with a block boundary inside from 17 on.  The
    tolerance is float32's own: every product is HIGHEST and the state
    float32, so the three differ by rounding over at most 64 steps."""
    q, k, v, g, beta, state = draw(t, t)
    want_o, want_s = token_loop(q, k, v, g, beta, state)
    o, s = jax.jit(kda.delta_chunked)(*as_f32(q, k, v, g, beta, state))
    np.testing.assert_allclose(o[0], want_o, atol=2e-5)
    np.testing.assert_allclose(s[0], want_s, atol=2e-5)
    s1, outs, step = as_f32(state)[0], [], jax.jit(kda.delta_step)
    for i in range(t):
        o1, s1 = step(*as_f32(q[i], k[i], v[i], g[i], beta[i]), s1)
        outs.append(o1[0])
    np.testing.assert_allclose(np.stack(outs), want_o, atol=2e-5)
    np.testing.assert_allclose(s1[0], want_s, atol=2e-5)


def test_twenty_steps_through_the_kernel_are_the_chunked_form():
    """The serving layout's single-token step (``parallel/pallas_kda``,
    interpreted, at the kernel's width of 128: the first from zero state
    by the index-0 rule, over a leaf that holds something else) twenty
    times, against ``delta_chunked`` over the same twenty tokens."""
    from bluefog_tpu.parallel import pallas_kda

    q, k, v, g, beta, stale = draw(11, 20, d=128)
    want_o, want_s = kda.delta_chunked(
        *as_f32(q, k, v, g, beta), jnp.zeros((1,) + stale.shape))
    s1, outs = as_f32(stale)[0], []
    for i in range(20):
        o1, s1 = pallas_kda.delta_step(
            *as_f32(q[i], k[i], v[i], g[i], beta[i]), s1, fresh=i == 0)
        outs.append(o1[0])
    np.testing.assert_allclose(np.stack(outs), want_o[0], atol=2e-5)
    np.testing.assert_allclose(s1[0], want_s[0], atol=2e-5)


def test_a_decay_at_the_lower_bound_keeps_the_block_finite():
    """Every channel at g = -4.999 for a whole block: exp(-G) reaches
    e^80 inside the block, under float32's e^88."""
    t = 2 * kda.KDA_BLOCK
    q, k, v, g, beta, state = draw(5, t)
    g = np.full_like(g, -4.999)
    want_o, want_s = token_loop(q, k, v, g, beta, state)
    o, s = kda.delta_chunked(*as_f32(q, k, v, g, beta, state))
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[0], want_o, atol=2e-5)
    np.testing.assert_allclose(s[0], want_s, atol=2e-5)


def test_no_decay_and_correlated_keys():
    """g = 0 (the state never fades) and every key the same direction:
    the triangular system is as far from the identity as it gets."""
    q, k, v, g, beta, state = draw(6, 32)
    g = np.zeros_like(g)
    k = np.broadcast_to(k[:1], k.shape) + 0.01 * k
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    want_o, want_s = token_loop(q, k, v, g, beta, state)
    o, s = kda.delta_chunked(*as_f32(q, k, v, g, beta, state))
    np.testing.assert_allclose(o[0], want_o, atol=1e-4)
    np.testing.assert_allclose(s[0], want_s, atol=1e-4)


def test_a_token_that_is_not_live_changes_nothing():
    """A padded tail (g 0, beta 0) leaves the state where the live
    tokens left it, in both forms."""
    live = np.arange(24) < 13
    q, k, v, g, beta, state = draw(7, 24, live=live)
    _, want_s = token_loop(q[:13], k[:13], v[:13], g[:13], beta[:13], state)
    _, s = kda.delta_chunked(*as_f32(q, k, v, g, beta, state))
    np.testing.assert_allclose(s[0], want_s, atol=2e-5)
    before = as_f32(state)[0]
    _, after = kda.delta_step(*as_f32(q[20], k[20], v[20], g[20], beta[20]),
                              before)
    np.testing.assert_array_equal(after, before)


def test_the_convolution_keeps_the_inputs_behind_the_live_tokens():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 6, 5)), jnp.float32)
    hist = jnp.asarray(rng.normal(size=(2, 3, 5)), jnp.float32)
    filt = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    y, kept = kda.causal_conv(x, hist, filt, jnp.asarray([6, 2]))
    behind = np.concatenate([hist, x], 1)
    for t in range(6):
        np.testing.assert_allclose(
            y[:, t], (behind[:, t:t + 4] * np.asarray(filt)).sum(1),
            rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(kept[0], behind[0, 6:9])
    np.testing.assert_array_equal(kept[1], behind[1, 2:5])
    # nothing live: the history stays
    _, kept = kda.causal_conv(x, hist, filt, jnp.asarray([0, 0]))
    np.testing.assert_array_equal(kept, hist)
