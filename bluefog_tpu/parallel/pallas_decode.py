"""Pallas TPU fused decode-attention step (GQA, int8-cache aware).

Round-5 closure of the verdict's decode-floor item: the round-4 per-layer
bisection attributed ~50 us/layer at 200M/B=32 to "batched-tiny-dot MXU
latency + small-op overheads" — a diagnosis, not a refutation.  This
kernel is the experiment: ONE ``pallas_call`` per layer replaces the
XLA chain (quantize -> two einsums -> softmax -> scale folds) that the
cached-attention step otherwise lowers to, with

* GQA batched dots: each grid row owns one (batch, kv-head) pair; its
  ``rep`` query heads attend as a single [rep, S] score block, so the
  cache streams at its native kv-head count (never widened);
* in-kernel int8 cache dequant: the cache blocks convert to f32 INSIDE
  the kernel, and both per-vector scales commute to the cheap side —
  the key scale multiplies the [rep, block_s] score columns (not the
  [block_s, D] key block), the value scale folds into the
  probabilities;
* probabilities kept in float (never re-quantized): the w8a8 path's
  per-step probability re-quantization was VPU work linear in cache
  length and cost it the long-context crown
  (benchmarks/decode_200m_v5e1_r04.json long_context note); here the
  value contraction runs f32 x f32 against the converted block, so the
  long-context behavior matches the weight-only mode by construction;
* online softmax over S blocks (the flash recurrence, pallas_attention
  ``_kernel``), so the score matrix never exceeds [rep, block_s] and
  the same kernel serves 128-long and 128k-long caches.

The stream is bounded by what is live (PR 27): a row's position rides in
scalar prefetch, the K/V index maps name only the blocks at or before
it, and a row that does not decode (``live`` false: a free slot of a
serving pool, one still prefilling) names the block already held, so
the pipeline copies nothing for it.  The XLA lowering
(``models.llama._cached_attention``) reads every reserved position
behind its mask.  Readings on a v5e at 32 rows x 2048 positions, 8 KV
heads of 128 in bf16, 16 calls a step (PERF.md section 6, PR 27): 9 rows
live at 150-1290 and 23 dead, 1.57 ms against XLA's 5.87; every row at
2047, 6.51 against 5.87; a (row, block) that computes costs about 3 us
whatever the block's size and whatever dtype the tiles enter the MXU
in, a grid step that is skipped 0.35 us, hence blocks of 512.
``generate.decode_config`` takes the kernel for a full-precision cache
on that evidence; the int8 variant shares the body and was not measured
again.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "decode_attention_int8",
           "streamed_positions", "tileable"]

_NEG_INF = -1e30
# Cache positions one grid step streams, or the largest divisor of the
# cache length under it.  Measured at 256 / 512 / 1024 (the module
# docstring's shapes): 2.23 / 1.57 / 1.71 ms a step at the serve cell's
# positions, 12.1 / 6.5 / 5.8 with every row full.
_BLOCK_S = 512


def _fit_block(t: int, want: int) -> int:
    want = min(want, t)
    for b in range(want, 0, -1):
        if t % b == 0:
            return b
    return 1


def tileable(s_len: int) -> bool:
    """Whether a cache of ``s_len`` positions splits into blocks of at
    least 8 rows (a prime length past ``_BLOCK_S`` does not: a 1-row
    block would run one grid step a position)."""
    return s_len < 8 or _fit_block(s_len, _BLOCK_S) >= 8


# rows of the kernel's scalar-prefetch operand
_POS, _SRC, _FIRST, _LAST = range(4)


def _stream_plan(idx, live, block_s: int):
    """``[4, B]`` int32, what each row streams: its position (``-1``
    for a row that does not decode: no block of it is live), and the
    K/V blocks its grid steps name: step ``sj`` names block
    ``clip(sj, first, last)`` of row ``src``.  A live row walks its own
    blocks up to its position's and stays on that one; a dead row names
    the block the row before it ended on, through all its steps.  The
    pipeline copies nothing for a block it already holds, so only the
    blocks at or before a live row's position are fetched (and block 0
    of row 0 where that row is dead: something has to be named)."""
    rows = jnp.arange(idx.shape[0], dtype=jnp.int32)
    src = jax.lax.cummax(jnp.where(live, rows, 0))
    last = jnp.where(live, idx // block_s, 0)[src]
    return jnp.stack([jnp.where(live, idx, -1), src,
                      jnp.where(live, 0, last), last])


def _named_block(bk, sj, plan):
    """The K/V index map: the block grid step ``(bk, sj)`` names."""
    return plan[_SRC, bk], 0, jnp.clip(sj, plan[_FIRST, bk],
                                       plan[_LAST, bk]), 0


def streamed_positions(positions, s_len: int, *, fused: bool = True,
                       block_s: Optional[int] = None) -> int:
    """Cache positions one call fetches for rows whose queries sit at
    ``positions`` (``-1``: a row that does not decode; the K and the V
    row of a position count once): the distinct blocks ``_stream_plan``
    names, counted on the host.  ``fused=False`` is the XLA lowering
    (``models.llama._cached_attention``), which reads every row's
    ``s_len`` positions behind its mask."""
    if not fused:
        return len(positions) * s_len
    block_s = _fit_block(s_len, block_s or _BLOCK_S)
    blocks = sum(min(int(p), s_len - 1) // block_s + 1
                 for p in positions if p >= 0)
    if len(positions) and positions[0] < 0:
        blocks += 1
    return blocks * block_s


def _decode_kernel(plan_ref, q_ref, k_ref, v_ref, *refs, scale: float,
                   n_kv: int):
    """Grid = (B, S blocks); ``plan_ref`` is ``_stream_plan`` (scalar
    prefetch: the K/V index maps of ``_decode_impl`` read it too) and
    holds one cache position per batch element.  One batch element's
    [KV * rep, D] query tile is resident; its KV heads process as a
    STATIC in-kernel loop (one program per batch element instead of per
    (batch, kv) pair — per-program overhead amortizes over the kv
    heads, measured ~2x end-to-end at B=32/KV=4 vs the (B*KV,) grid).
    K/V stream as
    [KV, block_s, D] tiles (int8 when quantized — converted in-kernel,
    scales applied on the score/probability side where they are
    O(rep * block_s), not O(block_s * D)).  ``refs``: the two scale
    blocks of a quantized cache, then the output and the scratch."""
    quantized = len(refs) == 6
    if quantized:
        ks_ref, vs_ref = refs[:2]
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    sj = pl.program_id(1)
    n_s = pl.num_programs(1)
    idx = plan_ref[_POS, pl.program_id(0)]
    heads = q_ref.shape[1]
    rep = heads // n_kv
    block_s = k_ref.shape[2]

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    # a block wholly past the row's position was not fetched (the index
    # map named a block already held): no arithmetic either
    @pl.when(sj * block_s <= idx)
    def _():
        # Per-kv-head dots in a STATIC loop.  (A block-diagonal packing
        # that fuses the kv heads into two big dots — [heads, KV*D] @
        # [KV*D, bs] and [heads, KV*bs] @ [KV*bs, D] — was built and
        # measured on the chip: EQUAL at B=32/S=384, 2.3x SLOWER at
        # S=2304, because its in-kernel K transposes and [heads, KV*bs]
        # operand builds scale with S while the tiny-dot latency they
        # save does not.  The loop keeps every operand in its native
        # layout: tpu.matmul absorbs the [rep, D] x [block_s, D]^T
        # contraction without an explicit transpose.)
        for kv in range(n_kv):
            sl = slice(kv * rep, (kv + 1) * rep)
            q = q_ref[0, sl].astype(jnp.float32)          # [rep, D]
            k_blk = k_ref[0, kv].astype(jnp.float32)      # [block_s, D]
            v_blk = v_ref[0, kv].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                # key scale is constant along the contracted head_dim:
                # apply to the score columns ([0, kv] basic indexing
                # keeps the loads 2D — fancier indexing lowers to >2D
                # gathers Mosaic refuses; scales carry a trailing
                # singleton so their blocks stay TPU-tileable)
                s = s * ks_ref[0, kv][:, 0][None, :]
            # the single decode query sits at global position idx: keys
            # at j <= idx are valid (j == idx was just written), the
            # cache tail beyond is unwritten zeros and must be masked
            pos = sj * block_s + jax.lax.broadcasted_iota(
                jnp.int32, (rep, block_s), 1)
            s = jnp.where(pos <= idx, s, _NEG_INF)

            m, l, acc = m_ref[sl], l_ref[sl], acc_ref[sl]
            blk_m = jnp.max(s, axis=-1)
            new_m = jnp.maximum(m, blk_m)
            p = jnp.exp(s - new_m[:, None])
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
            corr = jnp.exp(m - new_m)
            m_ref[sl] = new_m
            l_ref[sl] = l * corr + jnp.sum(p, axis=-1)
            if quantized:
                # value scale varies along the contracted position
                # axis: fold into the probabilities (kept float — NEVER
                # re-quantized, the round-4 w8a8 long-context
                # regression); the softmax denominator above uses the
                # UNSCALED p, so this only rescales the values
                p = p * vs_ref[0, kv][:, 0][None, :]
            acc_ref[sl] = acc * corr[:, None] + jax.lax.dot_general(
                p, v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(sj == n_s - 1)
    def _():
        safe_l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / safe_l[:, None]).astype(o_ref.dtype)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def _decode_impl(q, k_all, v_all, ks_all, vs_all, idx, live, *, block_s,
                 interpret):
    """q: [B, 1, n_q, D]; k_all/v_all: KV-HEAD-MAJOR [B, KV, S, D]
    (int8 when quantized); ks_all/vs_all: [B, KV, S] f32 scales or None;
    idx: the current position, and live: whether the row decodes (the
    output of one that does not is zeros), each one scalar for every
    row or [B] per row.  Returns [B, 1, n_q, D] in q's dtype.  Jitted,
    so that the layers of a model (same shapes) share one trace and one
    Mosaic lowering."""
    b, t, n_q, d = q.shape
    assert t == 1, "the fused decode kernel serves single-token steps"
    n_kv, s_len = k_all.shape[1], k_all.shape[2]
    quantized = ks_all is not None
    block = _fit_block(s_len, block_s or _BLOCK_S)
    if block < 8 and s_len >= 8:
        # no viable tiling (e.g. a prime cache length > the wanted
        # block): a 1-position block would run one grid step per cache
        # position — refuse loudly instead of being silently 100x slow
        raise ValueError(
            f"cache length {s_len} has no block divisor in [8, "
            f"{min(_BLOCK_S, s_len)}]; pad max_len to a multiple of 8 or "
            "use decode_attn='xla'")

    q3 = q.reshape(b, n_q, d)  # kv-major head order matches the cache
    per_row = lambda x, dtype: jnp.broadcast_to(
        jnp.asarray(x, dtype).reshape(-1), (b,))
    plan = _stream_plan(jnp.clip(per_row(idx, jnp.int32), 0, s_len - 1),
                        per_row(live, bool), block)

    def row(bk, sj, plan_ref):
        return bk, 0, 0

    kv_spec = pl.BlockSpec((1, n_kv, block, d), _named_block)
    in_specs = [pl.BlockSpec((1, n_q, d), row), kv_spec, kv_spec]
    args = [plan, q3, k_all, v_all]
    if quantized:
        # trailing singleton keeps the scale block TPU-tileable (last
        # dim equals the array dim; second-to-last is the 8-aligned
        # block)
        scale_spec = pl.BlockSpec((1, n_kv, block, 1), _named_block)
        in_specs += [scale_spec, scale_spec]
        args += [ks_all[..., None], vs_all[..., None]]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / d ** 0.5, n_kv=n_kv),
        name="decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s_len // block),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_q, d), row),
            scratch_shapes=[
                pltpu.VMEM((n_q,), jnp.float32),
                pltpu.VMEM((n_q,), jnp.float32),
                pltpu.VMEM((n_q, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_q, d), q.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(b, 1, n_q, d)


@functools.lru_cache(maxsize=None)
def _row_batched(block_s: Optional[int], interpret: bool):
    """``_decode_impl`` as ``call(idx, live, q, k_all, v_all[, ks_all,
    vs_all])`` with its own ``vmap`` rule: a mapped axis of independent
    rows FOLDS into the kernel's batch grid axis, each row keeping its
    own position.  The serving engine maps the decode step over its
    cache slots; Pallas's generic batching would instead batch the SMEM
    position operand into a squeezed ``[slots, 1]`` block, which the
    TPU lowering refuses (SMEM blocks must span the whole array)."""

    @jax.custom_batching.custom_vmap
    def call(idx, live, q, k_all, v_all, *scales):
        ks_all, vs_all = scales or (None, None)
        return _decode_impl(q, k_all, v_all, ks_all, vs_all, idx, live,
                            block_s=block_s, interpret=interpret)

    @call.def_vmap
    def _fold(axis_size, in_batched, idx, live, *arrays):
        idx_batched, live_batched, *arrays_batched = in_batched
        b = arrays[0].shape[1 if arrays_batched[0] else 0]

        def fold(x, batched):
            if not batched:
                x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
            return x.reshape((axis_size * b,) + x.shape[2:])

        def rows(x, batched, dtype):
            x = jnp.asarray(x, dtype)
            if not batched:
                x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
            return jnp.broadcast_to(x.reshape(axis_size, -1),
                                    (axis_size, b)).reshape(-1)

        out = call(rows(idx, idx_batched, jnp.int32),
                   rows(live, live_batched, bool),
                   *map(fold, arrays, arrays_batched))
        return out.reshape((axis_size, b) + out.shape[1:]), True

    return call


def decode_attention(q, k_all, v_all, idx, *, live=None,
                     block_s: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Fused GQA decode-attention step over a full-precision cache.

    q: [B, 1, n_q, D]; k_all/v_all: [B, KV, S, D] (cache layout/dtype);
    idx: current position, a scalar or [B] per row; live: False (a
    scalar or [B]) for a row whose output nobody reads: none of its
    cache is fetched and it returns zeros.  Drop-in for the
    decode-step case of ``models.llama._cached_attention`` (reference
    has no counterpart — decode itself is a new capability,
    docs/parity.md)."""
    return _row_batched(block_s, _auto_interpret(interpret))(
        idx, jnp.asarray(True if live is None else live), q, k_all, v_all)


def decode_attention_int8(q, kq_all, ks_all, vq_all, vs_all, idx, *,
                          live=None, block_s: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Fused GQA decode-attention step over the int8 K/V cache with
    in-kernel dequant and float probabilities.

    kq_all/vq_all: int8 [B, KV, S, D]; ks_all/vs_all: f32 [B, KV, S]
    per-vector scales (the ``kv_quant='int8'`` cache layout,
    models/llama.py).  Replaces the decode-step case of both
    ``_cached_attention_int8`` (whose probability re-quantization cost
    it the long-context crown) and the dequant-then-attend path."""
    return _row_batched(block_s, _auto_interpret(interpret))(
        idx, jnp.asarray(True if live is None else live), q, kq_all,
        vq_all, ks_all, vs_all)
