"""Pallas TPU flash-attention kernel.

The reference's only custom kernel is a CUDA buffer-scale
(reference bluefog/common/cuda/cuda_kernels.cu); SURVEY.md §7.9 calls for
Pallas kernels where the TPU build needs custom compute.  Attention is the
hot op of the Llama stress config, so this is the first one: a blockwise
online-softmax (flash) kernel that keeps the score matrix in VMEM, streams
K/V blocks, and optionally returns the log-sum-exp residual so callers can
merge partial attentions — exactly what ring attention needs per ring step.

Design:
* grid = (batch*heads, query blocks); per instance the q block lives in
  VMEM, K/V stream as [T_k, D] slices; scores/accumulator in f32.
* GQA without widening: the K/V BlockSpec index map folds query head h to
  kv head h // (H/H_kv) — no repeated K/V in HBM or VMEM.
* global position offsets arrive as SMEM scalars, so the same compiled
  kernel serves every ring step (offsets are traced values).
* backward = two blockwise Pallas passes (dQ over K blocks; dK/dV over Q
  blocks) using the saved (out, lse) residuals and the standard
  delta = rowsum(dO * O) trick — no T x T matrix ever materializes, so
  long-context training stays VMEM/HBM bounded by single tiles.

Interpret mode (CPU tests) is selected automatically off the backend.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_with_lse"]

_NEG_INF = -1e30


def _apply_causal_mask(s, q_off, kv_off, qi, kj):
    """Mask scores s [block_q, block_k] with the GLOBAL causal rule
    q_pos >= kv_pos, where positions include the ring-step offsets held in
    SMEM.  Single source of truth for forward, dQ and dK/dV kernels."""
    block_q, block_k = s.shape
    q_pos = (q_off + qi * block_q +
             jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
    kv_pos = (kv_off + kj * block_k +
              jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    return jnp.where(q_pos >= kv_pos, s, _NEG_INF)


def _kv_index_map(h: int, h_kv: int):
    """BlockSpec index map folding query-head grid rows onto KV heads:
    row bh = batch*H + head  ->  kv row batch*H_kv + head // (H/H_kv)."""
    group = h // h_kv

    def kv_index(bh, qi, kj):
        return (bh // h * h_kv + (bh % h) // group, kj, 0)

    return kv_index


def _block_live(q_off, kv_off, qi, kj, block_q, block_k):
    """False iff the (qi, kj) score block is ENTIRELY above the causal
    diagonal (every kv_pos > every q_pos) — its probabilities are all
    zero, so the dots and softmax update can be skipped outright.  The
    skipped fraction is (n_k - 1)/(2 n_k) of the grid: 25% at seq 2048
    with 1024-wide k blocks, approaching half as sequences grow (the
    round-5 roofline measured the unskipped kernel at 9-10% MFU while
    every matmul sat at 94-97% — attention IS the MFU wall, and the
    above-diagonal blocks were pure masked work)."""
    q_max = q_off + qi * block_q + block_q - 1
    kv_min = kv_off + kj * block_k
    return kv_min <= q_max


def _static_offs(q_offset, kv_offset):
    """(q_offset, kv_offset) when both are compile-time ints (the
    full-sequence path), else None (ring steps trace them) — the ONE
    place the staticness rule lives."""
    if isinstance(q_offset, int) and isinstance(kv_offset, int):
        return (q_offset, kv_offset)
    return None


def _clamp_dead_kv(kv_index, q_offset, kv_offset, block_q, block_k,
                   causal: bool):
    """Wrap a K/V BlockSpec index map so DEAD (qi, kj) blocks re-request
    the row's LAST LIVE kj — Pallas elides the HBM->VMEM copy when the
    block index repeats, so skipped blocks stop paying their DMA too.
    Only possible when the ring offsets are STATIC python ints (the
    full-sequence training path; ring attention's traced offsets keep
    the plain map — its blocks are live or about to rotate anyway)."""
    if not causal or _static_offs(q_offset, kv_offset) is None:
        return kv_index

    def clamped(bh, qi, kj):
        last_live = (q_offset + (qi + 1) * block_q - 1
                     - kv_offset) // block_k
        kj_eff = jnp.minimum(kj, jnp.maximum(last_live, 0))
        return kv_index(bh, qi, kj_eff)

    return clamped


def _kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            m_ref, l_ref, acc_ref, *, causal: bool, scale: float,
            offs=None):
    """Grid = (batch*heads, q blocks, k blocks).  Only one (block_q, D) Q
    tile and one (block_k, D) K/V tile are resident in VMEM per instance —
    long sequences never stage whole K/V on chip.  The online-softmax state
    (m, l, acc) lives in VMEM scratch, which persists across the innermost
    (k-block) grid dimension.  Causal mode skips fully-masked k blocks
    (``_block_live``)."""
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    qi = pl.program_id(1)
    # STATIC ring offsets (the full-sequence path) fold the mask/skip
    # arithmetic into compile-time constants — no SMEM scalar reads in
    # the hot loop; traced offsets (ring steps) read the SMEM refs.
    q_off = offs[0] if offs is not None else q_off_ref[0]
    kv_off = offs[1] if offs is not None else kv_off_ref[0]
    # NATIVE-dtype dot operands with f32 accumulation: numerically
    # IDENTICAL for the score matmul (the MXU multiplies the same bf16
    # mantissas either way); the P·V dot rounds the f32 probabilities
    # to the value dtype (f32 inputs stay exact; bf16 inputs get the
    # standard FlashAttention mixed-precision PV dot).  Measured
    # end-to-end NEUTRAL (docs/performance.md round 5: Mosaic already
    # absorbed the old operand upcasts) — kept as the cleaner form, not
    # as a perf lever; the kernel's cost sits in the softmax's
    # cross-lane reductions, also measured there.
    q = q_ref[0]                          # [block_q, D]
    block_q, d = q.shape
    block_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _():
        # m/l live as [block_q, 128] LANE-REPLICATED tiles, not 1-D
        # vectors: the row-reduction results (max/sum with keepdims)
        # stay in the score tile's sublane layout and broadcasts read a
        # full lane tile (1-D stats measured ~1.4x slower fwd than the
        # jax reference kernel, which replicates its stats the same
        # way; [bq, 1] columns recovered most of it, [bq, 128] the
        # rest — 4.02 -> 3.19 -> 2.86 ms at the 1B shapes)
        m_ref[:] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    live = _block_live(q_off, kv_off, qi, kj,
                       block_q, block_k) if causal else True

    @pl.when(live)
    def _():
        k_blk = k_ref[0]                  # [block_k, D]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            s = _apply_causal_mask(s, q_off, kv_off, qi, kj)
        m, l = m_ref[:, :1], l_ref[:, :1]               # [bq, 1] views
        acc = acc_ref[:]
        blk_m = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_m)
        p = jnp.exp(s - new_m)
        if causal:
            # fully-masked rows have s == new_m == _NEG_INF, where the
            # subtraction would give exp(0) = 1; zero them explicitly
            p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - new_m)
        lanes = m_ref.shape[1]
        m_ref[:] = jnp.broadcast_to(new_m, (block_q, lanes))
        l_ref[:] = jnp.broadcast_to(
            l * corr + jnp.sum(p, axis=-1, keepdims=True),
            (block_q, lanes))
        acc_ref[:] = acc * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_k - 1)
    def _():
        l_final = l_ref[:, :1]
        safe_l = jnp.maximum(l_final, 1e-30)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # lse = m + log(l); fully-masked rows stay at ~_NEG_INF
        lse_ref[0] = jnp.where(l_final > 0,
                               m_ref[:, :1] + jnp.log(safe_l), _NEG_INF)


def _fit_block(t: int, want: int) -> int:
    """Largest divisor of ``t`` that is <= ``want`` — block sizes must tile
    the sequence exactly (no tail handling in the kernel)."""
    want = min(want, t)
    for b in range(want, 0, -1):
        if t % b == 0:
            return b
    return 1


def _flash_fwd_impl(q, k, v, q_offset, kv_offset, *, causal, scale,
                    block_q, block_k, interpret):
    b, t_q, h, d = q.shape
    h_kv = k.shape[2]
    t_k = k.shape[1]
    block_q = _fit_block(t_q, block_q)
    block_k = _fit_block(t_k, block_k)

    # [B, T, H, D] -> [B*H, T, D] (kv keeps its narrow head count)
    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, t_q, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * h_kv, t_k, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * h_kv, t_k, d)
    q_off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))
    kv_off = jnp.reshape(jnp.asarray(kv_offset, jnp.int32), (1,))

    kv_index = _clamp_dead_kv(_kv_index_map(h, h_kv), q_offset, kv_offset,
                              block_q, block_k, causal)
    offs = _static_offs(q_offset, kv_offset)
    grid = (b * h, t_q // block_q, t_k // block_k)
    out, lse = pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale, offs=offs),
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            # trailing singleton keeps the block TPU-tileable (last dim
            # equals the array dim; second-to-last is the 8-aligned block_q)
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),    # running numer acc
        ],
        interpret=interpret,
    )(q_off, kv_off, qt, kt, vt)
    out = jnp.moveaxis(out.reshape(b, h, t_q, d), 1, 2)
    lse = lse.reshape(b, h, t_q)
    return out, lse


def _recompute_p(q, k, lse, q_off, kv_off, qi, kj, scale, causal):
    """Recompute the normalized probability block P = exp(S - lse) with the
    global causal mask; fully-masked entries (S == _NEG_INF) go to 0 even
    when the whole row is masked (lse == _NEG_INF would give exp(0)).
    ``lse`` is a [block_q, 1] column (sublane-aligned with the score
    tile — see the forward kernel's scratch note)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _apply_causal_mask(s, q_off, kv_off, qi, kj)
    p = jnp.exp(s - lse)
    return jnp.where(s <= _NEG_INF / 2, 0.0, p)


def _bwd_dq_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, acc_ref, *, causal, scale,
                   offs=None):
    """Grid (bh, qi, kj): accumulate dQ_i = sum_j dS_ij K_j * scale."""
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    qi = pl.program_id(1)
    q_off = offs[0] if offs is not None else q_off_ref[0]
    kv_off = offs[1] if offs is not None else kv_off_ref[0]
    # native-dtype dot operands, f32 accumulation (see _kernel's note)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]          # [block_q, 1] columns, sublane-aligned
    delta = delta_ref[0]
    block_q, d = q.shape
    block_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros((block_q, d), jnp.float32)

    live = _block_live(q_off, kv_off, qi, kj,
                       block_q, block_k) if causal else True

    @pl.when(live)
    def _():
        k = k_ref[0]
        v = v_ref[0]
        p = _recompute_p(q, k, lse, q_off, kv_off, qi, kj,
                         scale, causal)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(kj == n_k - 1)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, kv_off_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    causal, scale, group, offs=None):
    """Grid (b*h_kv, kj, qi*group): accumulate dK_j / dV_j over every query
    block and every query head in this KV head's group."""
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    qi = t // group
    q_off = offs[0] if offs is not None else q_off_ref[0]
    kv_off = offs[1] if offs is not None else kv_off_ref[0]
    # native-dtype dot operands, f32 accumulation (see _kernel's note)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]          # [block_q, 1] columns, sublane-aligned
    delta = delta_ref[0]
    block_q, d = q.shape
    block_k = k_ref.shape[1]

    @pl.when(t == 0)
    def _():
        dk_acc[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_acc[:] = jnp.zeros((block_k, d), jnp.float32)

    kj = pl.program_id(1)
    live = _block_live(q_off, kv_off, qi, kj,
                       block_q, block_k) if causal else True

    @pl.when(live)
    def _():
        k = k_ref[0]
        v = v_ref[0]
        p = _recompute_p(q, k, lse, q_off, kv_off, qi, kj,
                         scale, causal)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(t == n_t - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, out, lse, do, q_offset, kv_offset, *, causal,
                    scale, block_q, block_k, interpret):
    b, t_q, h, d = q.shape
    h_kv, t_k = k.shape[2], k.shape[1]
    group = h // h_kv
    block_q = _fit_block(t_q, block_q)
    block_k = _fit_block(t_k, block_k)
    # Both bwd kernels materialize TWO f32 [block_q, block_k] score-sized
    # intermediates (p and dp) — cap their product at 1M elements (8 MB)
    # so large k tiles (which the forward can afford with its single
    # score buffer) don't blow the 16 MB scoped-VMEM budget here; the
    # q tile shrinks instead, which bwd tolerates (its accumulators are
    # keyed on k blocks).
    while block_q * block_k > (1 << 20) and block_q > 8:
        block_q = _fit_block(t_q, block_q // 2)

    qt = jnp.moveaxis(q, 2, 1).reshape(b * h, t_q, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * h_kv, t_k, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * h_kv, t_k, d)
    dot = jnp.moveaxis(do, 2, 1).reshape(b * h, t_q, d)
    lse3 = lse.reshape(b * h, t_q, 1)
    # delta = rowsum(dO * O), the softmax-jacobian diagonal term
    delta3 = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                     axis=-1)  # [B, T, H]
    delta3 = jnp.moveaxis(delta3, 2, 1).reshape(b * h, t_q, 1)
    q_off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (1,))
    kv_off = jnp.reshape(jnp.asarray(kv_offset, jnp.int32), (1,))

    kv_index = _clamp_dead_kv(_kv_index_map(h, h_kv), q_offset, kv_offset,
                              block_q, block_k, causal)
    offs = _static_offs(q_offset, kv_offset)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          offs=offs),
        name="flash_bwd_dq",
        grid=(b * h, t_q // block_q, t_k // block_k),
        in_specs=[smem, smem, q_spec,
                  pl.BlockSpec((1, block_k, d), kv_index),
                  pl.BlockSpec((1, block_k, d), kv_index),
                  q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q_off, kv_off, qt, kt, vt, dot, lse3, delta3)

    # dK/dV: grid row is a KV head; the innermost dim sweeps (q block,
    # group member) pairs so GQA head sums accumulate in scratch instead of
    # materializing widened dK/dV.
    def q_row(bkv, kj, t):
        qi = t // group
        if causal and offs is not None:
            # dead (low-qi) steps re-request the kj row's FIRST LIVE q
            # block so their elided DMAs match the skipped compute
            # (same trick as _clamp_dead_kv; with equal static spans the
            # first live qi always exists)
            first_live = (kv_offset + kj * block_k - q_offset
                          + block_q - 1) // block_q
            qi = jnp.maximum(qi, first_live)
        return ((bkv // h_kv) * h + (bkv % h_kv) * group + t % group,
                qi, 0)

    kv_self = pl.BlockSpec((1, block_k, d), lambda bkv, kj, t: (bkv, kj, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          group=group, offs=offs),
        name="flash_bwd_dkv",
        grid=(b * h_kv, t_k // block_k, (t_q // block_q) * group),
        in_specs=[smem, smem,
                  pl.BlockSpec((1, block_q, d), q_row),
                  kv_self, kv_self,
                  pl.BlockSpec((1, block_q, d), q_row),
                  pl.BlockSpec((1, block_q, 1), q_row),
                  pl.BlockSpec((1, block_q, 1), q_row)],
        out_specs=[kv_self, kv_self],
        out_shape=[jax.ShapeDtypeStruct((b * h_kv, t_k, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, t_k, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(q_off, kv_off, qt, kt, vt, dot, lse3, delta3)

    dq = jnp.moveaxis(dq.reshape(b, h, t_q, d), 1, 2)
    dk = jnp.moveaxis(dk.reshape(b, h_kv, t_k, d), 1, 2)
    dv = jnp.moveaxis(dv.reshape(b, h_kv, t_k, d), 1, 2)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_offset, kv_offset, causal, scale, block_q, block_k,
           interpret):
    out, _ = _flash_fwd_impl(q, k, v, q_offset, kv_offset, causal=causal,
                             scale=scale, block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return out


def _flash_fwd(q, k, v, q_offset, kv_offset, causal, scale, block_q, block_k,
               interpret):
    out, lse = _flash_fwd_impl(q, k, v, q_offset, kv_offset, causal=causal,
                               scale=scale, block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return out, (q, k, v, out, lse, q_offset, kv_offset)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse, q_offset, kv_offset = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, out, lse, g, q_offset, kv_offset, causal=causal,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    # 1024 tiles measured +18%/+13% end-to-end on v5e at head_dim 64
    # (round 3, docs/performance.md); _fit_block clamps to t's divisors
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention.  q: [B, T_q, H, D]; k/v: [B, T_k, H_kv, D] (GQA
    served by index mapping, never materialized).  Differentiable
    (recompute-based backward).  Mixed-dtype q/k/v are normalized to
    q's dtype (the kernels feed operands to the MXU natively)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    return _flash(q, k, v, q_offset, kv_offset, causal, scale, block_q,
                  block_k, _auto_interpret(interpret))


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    # 1024 tiles measured +18%/+13% end-to-end on v5e at head_dim 64
    # (round 3, docs/performance.md); _fit_block clamps to t's divisors
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Forward-only variant returning (out, lse) with
    lse[b, h, t] = logsumexp of that row's masked scores — the residual
    needed to merge partial attentions across ring steps."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    return _flash_fwd_impl(q, k, v, q_offset, kv_offset, causal=causal,
                           scale=scale, block_q=block_q, block_k=block_k,
                           interpret=_auto_interpret(interpret))
