"""Kimi Delta Attention (arXiv:2510.26692, section 3): a RECURRENT mixer
whose memory of a sequence is one matrix a head, whatever the length.
``models/mla_moe.py`` builds it for the layers its config calls
``"kda"``; imported lazily with it.

One layer on the normed input ``a`` (``H`` heads, ``D`` = ``kda_head_dim``
for keys and values alike, position ``t``)::

    q~, k~, v~ = a W_q, a W_k, a W_v                   each [H x D]
    q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                  depthwise causal convolution over positions, one
                  filter of ``kda_conv_kernel`` taps a channel
    q = q / |q| * D^-1/2;  k = k / |k|                 L2 norm a head
    g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (a W_f + dt_bias))
                  [H x D], in (kda_lower_bound, 0): the safe gate
    alpha_t = exp(g_t)                                 decay, a CHANNEL
    beta_t = sigmoid(a W_beta)                         [H]
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t                                    S in R^(D x D), S_0 = 0
    out = [sigmoid(a W_g) * rmsnorm_head(o_t)] W_o

What a sequence leaves behind is ``S`` (float32: 2 MiB a layer at 32 x
128 x 128) and the last ``kda_conv_kernel - 1`` inputs of the
convolution: two ``state_*`` leaves of the cache (``serving/protocol.py``)
beside the layer's ``cache_index``.  Their rule: **a state leaf is what
the model left after the LIVE tokens behind ``cache_index``, and a call
that starts at index 0 starts from zero state whatever the leaf holds.**
A token that is not live (``live`` False: a chunk's padded tail, a slot
that does not decode) leaves both as they were: alpha 1, beta 0, no
shift of the convolution's inputs.  Within one call the live tokens come
first (the protocol's two cases give nothing else).

The recurrence is computed in one of two forms, equal in exact
arithmetic, and the call's length says which (as ``LatentAttention``
chooses):

* STEP, a single token (the engine's decode program, one token a slot
  under ``vmap``): with ``u = beta (v - S^T (alpha k))`` the new state is
  ``alpha S + k u^T`` and the read-out ``S^T (alpha q) + u (k . q)``:
  both products with the OLD state in one pass over it, and one pass to
  write the new one.  Two walks over memory compute it, and the layout
  says which (``steps_in_kernel``).  ``delta_step`` is the plain form:
  the training layout, a CPU run, ``decode_attn="xla"`` and the tests'
  reference; mapped over the engine's slots it reads EVERY slot's state
  twice and writes it once.  Where the serving layout's cache reads are
  kernels (``decode_attn="pallas"``, what ``"auto"`` resolves to on a
  TPU) it is ``parallel/pallas_kda.py:delta_step``: one kernel over the
  rows that are live, a row's state read once and written once where the
  leaf lies, a row that is not live untouched, the index-0 rule a flag a
  row in place of a select over the leaf.
* CHUNKED, a call of several tokens (a prefill chunk, the training
  layout): blocks of ``KDA_BLOCK`` positions.  With ``G`` the running sum
  of ``g`` from the block's start, ``A[t, j] = sum_c k_t[c] k_j[c]
  exp(G_t[c] - G_j[c])`` (``j < t``) and ``B`` likewise with ``q_t``
  (``j <= t``), the block's ``u`` solve the triangular system ``(I +
  Diag(beta) A) U = Diag(beta) (V - (e^G K) S_0)`` at once; then ``O =
  (e^G Q) S_0 + B U`` and ``S' = e^(G_last) S_0 + (e^(G_last - G) K)^T
  U``.  Everything that does not read ``S_0`` is computed for all blocks
  together; only three products a block wait for the state the block
  before left.  The safe gate is what lets ``A`` and ``B`` be products
  of two factors in float32: inside a block ``|G| < 5 x 16 = 80`` and
  float32 holds ``e^88``.

The state and every product that touches it are float32 at
``Precision.HIGHEST`` (a TPU would otherwise round the state to
bfloat16 at every read).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.models.experts import _dense
from bluefog_tpu.models.llama import RMSNorm

__all__ = ["KimiDeltaAttention", "delta_step", "delta_chunked", "causal_conv",
           "steps_in_kernel", "KDA_BLOCK"]

SCOPE_ATTN_KDA = "bf.attn.kda"
SCOPE_KDA_STATE = "bf.attn.kda_state"
SCOPE_KDA_CONV = "bf.attn.kda_conv"
# positions of one block of the chunked form: -kda_lower_bound x
# KDA_BLOCK must stay under float32's e^88
KDA_BLOCK = 16
HIGHEST = lax.Precision.HIGHEST


def causal_conv(x, history, filters, n_live):
    """Depthwise causal convolution of ``x [B, T, C]`` behind ``history
    [B, K - 1, C]`` (the inputs before the call, oldest first) with
    ``filters [K, C]`` (the last tap meets the current input): ``(y [B,
    T, C] float32, history')``, the inputs behind the call's ``n_live
    [B]`` live tokens, which come first."""
    taps = filters.shape[0]
    t = x.shape[1]
    behind = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    y = sum(behind[:, j:j + t].astype(jnp.float32) * filters[j]
            for j in range(taps))
    kept = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
        row, n, taps - 1, axis=0))(behind, n_live)
    return y, kept.astype(history.dtype)


def steps_in_kernel(cfg) -> bool:
    """Whether ``cfg``'s single-token step is the kernel over the live
    rows (``parallel/pallas_kda.py``): the serving layout whose cache
    reads are kernels too, at a width the kernel tiles."""
    if not (cfg.decode and cfg.decode_attn == "pallas"):
        return False
    from bluefog_tpu.parallel import pallas_kda

    return pallas_kda.steppable(cfg.kda_head_dim)


def delta_step(q, k, v, g, beta, state):
    """One token a sequence.  q, k, g ``[B, H, D]``, v ``[B, H, Dv]``,
    beta ``[B, H]``, all float32; state ``[B, H, D, Dv]``.  Returns
    ``(o [B, H, Dv], state')``."""
    alpha = jnp.exp(g)
    # products of a vector with the state as a multiply and a sum over
    # rows: exact float32, and two sums over one read of the state
    read = lambda x: jnp.sum(state * (alpha * x)[..., None], axis=-2)
    u = beta[..., None] * (v - read(k))
    new = alpha[..., None] * state + k[..., None] * u[..., None, :]
    o = read(q) + u * jnp.sum(k * q, -1, keepdims=True)
    return o, new


def _unit_lower_inverse(n):
    """``(I + n)^-1`` of a strictly lower triangular ``n [..., R, R]`` by
    forward substitution, row by row (``R`` is a block: unrolled)."""
    rows = n.shape[-1]
    inv = jnp.broadcast_to(jnp.eye(rows, dtype=n.dtype), n.shape)
    for i in range(1, rows):
        row = -jnp.einsum("...j,...jk->...k", n[..., i, :i],
                          inv[..., :i, :], precision=HIGHEST)
        inv = inv.at[..., i, :].add(row)
    return inv


def delta_chunked(q, k, v, g, beta, state):
    """``T`` tokens a sequence, block by block.  q, k, g ``[B, T, H,
    D]``, v ``[B, T, H, Dv]``, beta ``[B, T, H]``, all float32 (a token
    that is not live comes with ``g`` 0 and ``beta`` 0); state ``[B, H,
    D, Dv]``.  Returns ``(o [B, T, H, Dv], state')``."""
    b, t, h, d = q.shape
    r = KDA_BLOCK
    pad = -t % r
    if pad:
        # a padded position is not live: it decays nothing, writes nothing
        widen = lambda x: jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)]
                                  * (x.ndim - 2))
        q, k, v, g, beta = (widen(x) for x in (q, k, v, g, beta))
    n = (t + pad) // r
    # [n, B, H, r, .]: the scan runs over blocks
    blocks = lambda x: jnp.moveaxis(
        x.reshape((b, n, r) + x.shape[2:]), (1, 2), (0, 3))
    q, k, v, g = (blocks(x) for x in (q, k, v, g))
    beta = jnp.moveaxis(beta.reshape(b, n, r, h), (1, 2), (0, 3))
    run = jnp.cumsum(g, axis=-2)                  # G, from the block's start
    last = run[..., -1:, :]
    k_in, q_in = k * jnp.exp(run), q * jnp.exp(run)
    k_out = k * jnp.exp(-run)
    k_end = k * jnp.exp(last - run)
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision=HIGHEST)
    row, col = jnp.arange(r)[:, None], jnp.arange(r)[None, :]
    a = jnp.where(row > col, mm("nbhtc,nbhjc->nbhtj", k_in, k_out), 0.0)
    bm = jnp.where(row >= col, mm("nbhtc,nbhjc->nbhtj", q_in, k_out), 0.0)
    solve = _unit_lower_inverse(beta[..., None] * a) * beta[..., None, :]
    w = mm("nbhtj,nbhjc->nbhtc", solve, k_in)
    uv = mm("nbhtj,nbhjv->nbhtv", solve, v)

    def block(s, xs):
        w, uv, q_in, bm, k_end, decay = xs
        u = uv - mm("bhtc,bhcv->bhtv", w, s)
        o = mm("bhtc,bhcv->bhtv", q_in, s) + mm("bhtj,bhjv->bhtv", bm, u)
        s = decay[..., None] * s + mm("bhtc,bhtv->bhcv", k_end, u)
        return s, o

    state, o = lax.scan(block, state,
                        (w, uv, q_in, bm, k_end, jnp.exp(last[..., 0, :])))
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * r, h, -1)
    return o[:, :t], state


class KimiDeltaAttention(nn.Module):
    """The mixer of a ``"kda"`` layer.  The config gives ``dim``,
    ``n_heads``, ``kda_head_dim``, ``kda_conv_kernel``,
    ``kda_lower_bound``, ``norm_eps``, ``initializer_range``, ``dtype``,
    ``decode``."""
    cfg: Any

    @nn.compact
    def __call__(self, x, live=None):
        cfg = self.cfg
        b, t, _ = x.shape
        h, d, taps = cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
        init = nn.initializers.normal(cfg.initializer_range)
        if live is None:
            live = jnp.ones((b, t), bool)
        stepped = t == 1 and steps_in_kernel(cfg)
        with jax.named_scope(SCOPE_ATTN_KDA):
            qkv = jnp.concatenate(
                [_dense(cfg, h * d, name)(x) for name in ("wq", "wk", "wv")],
                axis=-1)
            filters = jnp.concatenate(
                [self.param(f"conv_{c}", init, (taps, h * d), jnp.float32)
                 for c in "qkv"], axis=-1)
            a_log = self.param("A_log", nn.initializers.zeros, (h,),
                               jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (h * d,),
                                 jnp.float32)
            gate_in = _dense(cfg, h * d, "wf")(x).astype(jnp.float32)
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None]
                * (gate_in + dt_bias).reshape(b, t, h, d))
            beta = jax.nn.sigmoid(
                _dense(cfg, h, "wbeta")(x).astype(jnp.float32))
            g = jnp.where(live[..., None, None], g, 0.0)
            beta = jnp.where(live[..., None], beta, 0.0)
            state = jnp.zeros((b, h, d, d), jnp.float32)
            history = jnp.zeros((b, taps - 1, 3 * h * d), cfg.dtype)
            if cfg.decode:
                ci = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((), jnp.int32))
                ss = self.variable("cache", "state_s", jnp.zeros,
                                   state.shape, jnp.float32)
                sc = self.variable("cache", "state_conv", jnp.zeros,
                                   history.shape, cfg.dtype)
                # a call at index 0 starts from nothing, whatever the
                # slot's last request left in the leaves (the kernel
                # takes the flag: no select over the leaf)
                fresh = ci.value == 0
                state = ss.value if stepped else jnp.where(fresh, 0.0,
                                                           ss.value)
                history = jnp.where(fresh, 0, sc.value).astype(cfg.dtype)
            with jax.named_scope(SCOPE_KDA_CONV):
                mixed, history = causal_conv(
                    qkv, history, filters, live.sum(-1, dtype=jnp.int32))
                q, k, v = (y.reshape(b, t, h, d) for y in jnp.split(
                    nn.silu(mixed), 3, axis=-1))
                unit = lambda y: y * lax.rsqrt(
                    jnp.sum(y * y, -1, keepdims=True) + 1e-6)
                q, k = unit(q) * d ** -0.5, unit(k)
            with jax.named_scope(SCOPE_KDA_STATE):
                if t == 1:
                    token = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                    if stepped:
                        from bluefog_tpu.parallel import pallas_kda

                        o, state = pallas_kda.delta_step(
                            *token, state, live=live[:, 0], fresh=fresh)
                    else:
                        o, state = delta_step(*token, state)
                    o = o[:, None]
                else:
                    o, state = delta_chunked(q, k, v, g, beta, state)
            if cfg.decode:
                ss.value, sc.value, ci.value = state, history, ci.value + t
            o = RMSNorm(cfg.norm_eps, name="o_norm")(o.astype(cfg.dtype))
            gate = jax.nn.sigmoid(
                _dense(cfg, h * d, "wg")(x).astype(jnp.float32))
            out = (gate.reshape(b, t, h, d) * o.astype(jnp.float32)).astype(
                cfg.dtype).reshape(b, t, h * d)
            return _dense(cfg, cfg.dim, "wo")(out)
