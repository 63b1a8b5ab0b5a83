"""A residual path of SEVERAL streams, mixed a token at a time by
manifold-constrained hyper-connections (arXiv:2512.24880 over
arXiv:2409.19606), for a model under ``models/`` whose config asks for
it (``models/mla_moe.py`` with ``hc_mult`` > 1; imported lazily with it).

Per token the residual is ``X`` in ``R^{n x C}``.  Around each sublayer
``F`` (which keeps its own pre-norm), with that sublayer's own float32
parameters ``phi [nC, 2n + n^2]``, ``alpha [3]`` (pre, post, res),
``b_pre [n]``, ``b_post [n]``, ``b_res [n, n]``::

    xh        = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)   no learned scale
    [p, q, R] = xh phi                                     R row-major
    H_pre     = sigmoid(alpha_pre p + b_pre)
    H_post    = 2 sigmoid(alpha_post q + b_post)
    M         = exp(clip(alpha_res R + b_res, lo, hi))
    iters times:  M = M / (rowsum(M) + eps);  M = M / (colsum(M) + eps)
    y         = F(norm(sum_i H_pre[i] X[i]))
    X'[i]     = sum_j M[i, j] X[j] + H_post[i] y

so the columns of ``H_res = M`` sum to 1 and its rows as nearly as
``iters`` turns bring them.  ``hc_pre`` gives ``F``'s input and the
coefficients, ``hc_post`` writes the streams back.

The streams travel FLAT, ``vec(X)`` as ``[..., n * C]`` in the model's
dtype with stream ``i`` in columns ``i C .. (i + 1) C``: the projection
wants that vector, a stream is a lane-aligned slice of it, and no array
has a minor dimension of ``n``.  Statistics and coefficients are
float32; ``xh phi = (vec(X) phi) / rms`` so projection and mean square
read ``X`` as it stands.  The Sinkhorn turns are unrolled, every entry
of the matrix an array of its own over the tokens (no slice, no
reduction, no loop): a compiler can fuse the chain.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["Mixing", "hc_pre", "hc_post", "sinkhorn", "expand", "collapse"]

SCOPE_HC_PRE = "bf.hc.pre"
SCOPE_HC_POST = "bf.hc.post"


class Mixing(nn.Module):
    """One sublayer's mixing parameters, float32 (the config gives
    ``hc_mult``, ``dim``, ``initializer_range``)."""
    cfg: Any

    @nn.compact
    def __call__(self) -> dict:
        cfg = self.cfg
        n = cfg.hc_mult
        zeros, f32 = nn.initializers.zeros, jnp.float32
        return {
            "phi": self.param(
                "phi", nn.initializers.normal(cfg.initializer_range),
                (n * cfg.dim, 2 * n + n * n), f32),
            "alpha": self.param("alpha", nn.initializers.ones, (3,), f32),
            "b_pre": self.param("b_pre", zeros, (n,), f32),
            "b_post": self.param("b_post", zeros, (n,), f32),
            "b_res": self.param("b_res", zeros, (n, n), f32),
        }


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps"))
def _turns(entries, n: int, iters: int, eps: float):
    """The turns on ``entries [n * n, ...]`` (row-major), each entry an
    array of its own from the first turn to the last: a turn is
    additions and divisions of same-shaped arrays, no slice and no
    reduction between them, which a compiler fuses several turns at a
    time (a described v5e: 41 fusions a sublayer, the tokens on the
    minor axis by its own choice of layout, where turns over one
    ``[n, n, N]`` array sliced for every sum made 115).  Jitted, so the
    sublayers of a program trace the unrolled turns once."""
    e = [[entries[i * n + j] for j in range(n)] for i in range(n)]
    for _ in range(iters):
        for i in range(n):
            rows = functools.reduce(lambda a, b: a + b, e[i]) + eps
            e[i] = [x / rows for x in e[i]]
        for j in range(n):
            cols = functools.reduce(
                lambda a, b: a + b, [e[i][j] for i in range(n)]) + eps
            for i in range(n):
                e[i][j] = e[i][j] / cols
    return jnp.stack([x for row in e for x in row])


def sinkhorn(m, n: int, iters: int, eps: float):
    """``iters`` turns of rows-then-columns normalisation of the
    positive ``n x n`` matrices ``m [..., n * n]`` (row-major), each sum
    plus ``eps`` before it divides; float32 ``[..., n * n]``."""
    return jnp.moveaxis(_turns(jnp.moveaxis(m, -1, 0), n, iters, eps), 0, -1)


def _stream(x, i: int, n: int):
    c = x.shape[-1] // n
    return x[..., i * c:(i + 1) * c]


def hc_pre(x, p: dict, *, n: int, iters: int, eps: float, clamp,
           norm_eps: float):
    """What a sublayer reads of the streams ``x [..., n * C]`` and how it
    will write them back: ``(y_in [..., C]`` in ``x``'s dtype, ``H_post
    [..., n]``, ``H_res [..., n * n]`` row-major``)``, both float32."""
    with jax.named_scope(SCOPE_HC_PRE):
        xf = x.astype(jnp.float32)
        mean_sq = jnp.mean(xf * xf, axis=-1, keepdims=True)
        proj = jnp.dot(xf, p["phi"], precision=lax.Precision.HIGHEST) \
            * lax.rsqrt(mean_sq + norm_eps)
        alpha = p["alpha"]
        h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + p["b_pre"])
        h_post = 2.0 * jax.nn.sigmoid(
            alpha[1] * proj[..., n:2 * n] + p["b_post"])
        m = jnp.exp(jnp.clip(
            alpha[2] * proj[..., 2 * n:] + p["b_res"].reshape(-1),
            clamp[0], clamp[1]))
        h_res = sinkhorn(m, n, iters, eps)
        y_in = h_pre[..., :1] * _stream(xf, 0, n)
        for i in range(1, n):
            y_in = y_in + h_pre[..., i:i + 1] * _stream(xf, i, n)
        return y_in.astype(x.dtype), h_post, h_res


def hc_post(x, y, h_post, h_res, *, n: int):
    """The streams after a sublayer that gave ``y [..., C]``:
    ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, in ``x``'s
    dtype."""
    with jax.named_scope(SCOPE_HC_POST):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        streams = [_stream(xf, j, n) for j in range(n)]
        out = []
        for i in range(n):
            mixed = h_post[..., i:i + 1] * yf
            for j in range(n):
                mixed = mixed + h_res[..., i * n + j:i * n + j + 1] \
                    * streams[j]
            out.append(mixed)
        return jnp.concatenate(out, axis=-1).astype(x.dtype)


def expand(h, n: int):
    """The entry: every stream is the embedding."""
    with jax.named_scope(SCOPE_HC_PRE):
        return jnp.concatenate([h] * n, axis=-1)


def collapse(x, n: int):
    """The exit: the streams' sum, in ``x``'s dtype."""
    with jax.named_scope(SCOPE_HC_POST):
        xf = x.astype(jnp.float32)
        total = _stream(xf, 0, n)
        for i in range(1, n):
            total = total + _stream(xf, i, n)
        return total.astype(x.dtype)
