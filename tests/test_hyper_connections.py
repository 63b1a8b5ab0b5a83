"""The residual path of several streams (``models/hyper_connections.py``)
at a small size on the CPU: ``hc_pre`` / ``hc_post`` against a twenty-line
transcription of the equations, the mixing matrix's sums after 20
Sinkhorn turns, the clamp, the dtypes, the two scopes, and that the
turns are unrolled."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models import hyper_connections as hc

N, C, TOKENS = 4, 32, 24
MIXING = dict(n=N, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)


def draw(seed=0, alpha=(1.0, 1.0, 1.0), phi_std=0.05):
    """Streams of two sequences and one sublayer's parameters; ``phi``
    at 0.05 gives ``xh phi`` a deviation of 0.05 sqrt(128) = 0.57."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (2, TOKENS // 2, N * C), jnp.float32)
    p = {"phi": phi_std * jax.random.normal(k[1], (N * C, 2 * N + N * N)),
         "alpha": jnp.asarray(alpha, jnp.float32),
         "b_pre": 0.3 * jax.random.normal(k[2], (N,)),
         "b_post": 0.3 * jax.random.normal(k[3], (N,)),
         "b_res": 0.3 * jax.random.normal(k[4], (N, N))}
    y = jax.random.normal(k[5], (2, TOKENS // 2, C), jnp.float32)
    return x, jax.tree.map(lambda a: a.astype(jnp.float32), p), y


def transcription(x, p, y, iters=20, eps=1e-6, lo=-30.0, hi=30.0,
                  norm_eps=1e-6):
    """The equations of ISSUE 32 as they stand, float64 numpy: ``(y_in,
    H_post, H_res, X')`` of streams ``x [..., n, C]``."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    vec = x.reshape(x.shape[:-2] + (-1,))
    xh = vec / np.sqrt(np.mean(vec ** 2, -1, keepdims=True) + norm_eps)
    proj = xh @ p["phi"]
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))
    h_pre = sig(p["alpha"][0] * proj[..., :N] + p["b_pre"])
    h_post = 2.0 * sig(p["alpha"][1] * proj[..., N:2 * N] + p["b_post"])
    r = proj[..., 2 * N:].reshape(proj.shape[:-1] + (N, N))
    m = np.exp(np.clip(p["alpha"][2] * r + p["b_res"], lo, hi))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    y_in = (h_pre[..., None] * x).sum(-2)
    out = np.einsum("...ij,...jc->...ic", m, x) \
        + h_post[..., None] * y[..., None, :]
    return y_in, h_post, m, out


def streams(x):
    return np.asarray(x).reshape(x.shape[:-1] + (N, C))


def test_pre_and_post_are_the_equations():
    x, p, y = draw()
    y_in, h_post, h_res = hc.hc_pre(x, p, **MIXING)
    out = hc.hc_post(x, y, h_post, h_res, n=N)
    want_in, want_post, want_res, want_out = transcription(streams(x), p, y)
    assert y_in.shape == y.shape and out.shape == x.shape
    assert h_post.shape == (2, TOKENS // 2, N)
    assert h_res.shape == (2, TOKENS // 2, N * N)
    # float32 against float64: roundings of a few dozen operations
    close = lambda got, want: np.abs(np.asarray(got) - want).max() \
        < 2e-5 * np.abs(want).max()
    assert close(y_in, want_in) and close(h_post, want_post)
    assert close(h_res.reshape(want_res.shape), want_res)
    assert close(streams(out), want_out)
    # the coefficients differ from token to token: the draw is no constant
    assert np.asarray(h_res).std(axis=(0, 1)).min() > 1e-2


def test_the_mixing_matrix_after_twenty_turns():
    """Columns sum to 1 (the last division is by the column sums); rows
    as nearly as 20 turns bring them: the tolerance is this draw's own
    row error after 20 turns of the transcription, with room, and far
    under the error after 2 turns."""
    x, p, y = draw(seed=1, alpha=(1.0, 1.0, 3.0), phi_std=0.2)
    _, _, h_res = hc.hc_pre(x, p, **MIXING)
    m = np.asarray(h_res).reshape(-1, N, N)
    assert (m > 0).all()
    assert np.abs(m.sum(1) - 1.0).max() < 1e-5
    rows = lambda mat: np.abs(mat.sum(2) - 1.0).max()
    after_20 = rows(transcription(streams(x), p, y)[2].reshape(-1, N, N))
    after_2 = rows(transcription(streams(x), p, y, iters=2)[2]
                   .reshape(-1, N, N))
    assert rows(m) < 2 * after_20 + 1e-5 < 0.1 * after_2
    # and program and transcription agree on the 20th turn, not the limit
    _, _, two = hc.hc_pre(x, p, **dict(MIXING, iters=2))
    assert np.abs(np.asarray(two).reshape(-1, N, N) - m).max() > 1e-3


def test_the_clamp_holds_at_thirty():
    """With ``alpha_res`` 200 the logits reach far past 30: clamped they
    give a finite matrix equal to the transcription's; unclamped the
    exponential overflows float32."""
    x, p, y = draw(seed=2, alpha=(1.0, 1.0, 200.0), phi_std=0.2)
    proj = transcription(streams(x), p, y)  # float64 survives either way
    _, _, h_res = hc.hc_pre(x, p, **MIXING)
    assert np.isfinite(np.asarray(h_res)).all()
    want = proj[2].reshape(h_res.shape)
    assert np.abs(np.asarray(h_res) - want).max() < 1e-4
    _, _, loose = hc.hc_pre(x, p, **dict(MIXING, clamp=(-1e9, 1e9)))
    assert not np.isfinite(np.asarray(loose)).all()
    # the bound is +-30, not another: at 20 the matrix differs
    _, _, other = hc.hc_pre(x, p, **dict(MIXING, clamp=(-20.0, 20.0)))
    assert np.abs(np.asarray(other) - np.asarray(h_res)).max() > 1e-6


def test_streams_keep_their_dtype_and_coefficients_are_float32():
    x, p, y = draw(seed=3)
    xb, yb = x.astype(jnp.bfloat16), y.astype(jnp.bfloat16)
    y_in, h_post, h_res = hc.hc_pre(xb, p, **MIXING)
    out = hc.hc_post(xb, yb, h_post, h_res, n=N)
    assert y_in.dtype == out.dtype == jnp.bfloat16
    assert h_post.dtype == h_res.dtype == jnp.float32
    # the coefficients come from the bf16 streams in float32: against the
    # transcription over the same rounded streams they agree as float32
    want = transcription(streams(xb.astype(jnp.float32)), p,
                         yb.astype(jnp.float32))
    assert np.abs(np.asarray(h_res).reshape(want[2].shape)
                  - want[2]).max() < 2e-5
    assert np.abs(streams(out.astype(jnp.float32)) - want[3]).max() \
        < 2 ** -7 * np.abs(want[3]).max()


def test_entry_and_exit():
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 3, C), jnp.bfloat16)
    x = hc.expand(h, N)
    assert x.shape == (2, 3, N * C) and x.dtype == h.dtype
    assert (streams(x.astype(jnp.float32))
            == np.asarray(h, np.float32)[..., None, :]).all()
    total = hc.collapse(x, N)
    assert total.dtype == h.dtype
    assert np.allclose(np.asarray(total, np.float32),
                       N * np.asarray(h, np.float32), rtol=2 ** -7)


def test_the_turns_are_unrolled_under_the_two_scopes():
    x, p, y = draw(seed=5)

    def both(x, p, y):
        y_in, h_post, h_res = hc.hc_pre(x, p, **MIXING)
        return hc.hc_post(x, y + y_in, h_post, h_res, n=N)

    lowered = jax.jit(both).lower(x, p, y)
    text = lowered.as_text()
    assert "stablehlo.while" not in text and "stablehlo.reduce" in text
    # 20 turns x (rows, then columns): two divisions a turn, and no
    # reduction but the mean square's
    assert text.count("stablehlo.divide") >= 2 * 20
    assert text.count("stablehlo.reduce") == 1
    text = lowered.as_text(debug_info=True)
    assert hc.SCOPE_HC_PRE in text and hc.SCOPE_HC_POST in text


def test_mapped_sequences_give_what_the_unmapped_call_gives():
    """Under ``vmap`` (the engine's decode step maps over slots) the
    turns give what the unmapped call gives, still with no loop."""
    x, p, y = draw(seed=6)
    pre = lambda x: hc.hc_pre(x, p, **MIXING)
    want = pre(x)
    got = jax.vmap(pre)(x)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    text = jax.jit(jax.vmap(pre)).lower(x).as_text()
    assert "stablehlo.while" not in text


@pytest.mark.parametrize("n", [1, 2, 4])
def test_sinkhorn_alone(n):
    m = jnp.exp(jax.random.normal(jax.random.PRNGKey(n), (5, n * n)))
    out = np.asarray(hc.sinkhorn(m, n, 20, 1e-6)).reshape(5, n, n)
    assert np.abs(out.sum(1) - 1.0).max() < 1e-5
    assert np.abs(out.sum(2) - 1.0).max() < 1e-3
