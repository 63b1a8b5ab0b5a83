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

The LATENT variant (PR 33, ``latent_decode_attention``) serves the
absorbed single-token step of ``models/mla_moe.py``: a position's cached
row ``[c ; k_r]`` is ONE "KV head" that every query head scores whole
and whose first ``dc`` values are the head's values, so one cache
operand feeds both contractions and is fetched once (the einsums read
the pool twice).  It shares the plan, the index map, the block fit, the
host's count and the ``vmap`` fold with the dense kernel and has a body
of its own.  The leaf is read AS THE CHIP LAYS IT OUT: a width that is
no multiple of the 128 lanes (320 and 576 at the published widths) lies
position-minor, so the kernel streams ``[dc + dr, block_s]`` tiles of
the transposed view and no copy of the pool is made on the way in (a
row-major kernel compiled too, behind a transposed copy of every leaf
in and out: 0.39 GiB of temporaries a layer at 32 x 16,384 x 320);
``latent_tileable`` says where that holds.  Readings on a v5e, a
step's five calls inside one program, ms (PERF.md section 6, PR 33):
32 rows x 16,384 x 320 in bf16 with 5 rows live at 700-9,900, blocks of
512 / 1,024 / 2,048 / 4,096: 0.65 / 0.41 / 0.32 / 0.35 against the
einsums' 4.58; every row full 4.32 / 2.84 / 2.29 / 2.28 against 4.58
(the dense kernel lost that row to XLA; this one reads half the bytes);
64 x 4,096 x 576 with 23 rows live at 40-1,400: 0.56 / 0.55 / 0.75 /
1.15 against 4.12, full 2.68 / 2.15 / 2.09 / 2.08.  A grid step that is
skipped costs 0.07 us, a call 16 us before its first step, a block that
computes 0.85 / 1.11 / 1.79 us at 512 / 1,024 / 2,048 rows of 640 bytes
and 1.05 / 1.68 / 3.26 at as many of 1,152 (700-730 GB/s from 1.2 MB a
block up): hence ``latent_block``, a quarter of the cache between 512
and 2,048 rows.  Probabilities rounded to the cache's dtype before the
value contraction, as the einsums round them, bought no time; they stay
float32.

The STACKED form (PR 40, ``decode_attention(leaf=, fresh=)``) serves a
loop that is ROLLED over its layers (``models/looped.py``: 48 layers
run four times over shared weights, a cache for every pass).  Such a
loop carries ONE pair of leaves ``[B, N, KV, S, D]`` for its ``N``
(pass, layer) caches; a slice of it handed to a custom call would be a
copy (two ``[slots, 16, 768, 128]`` slices a layer application, 9.4 GB
a step).  So the call takes the whole pair, the leaf rides in scalar
prefetch beside the position and the index map names its blocks inside
the stack (the stack's axis is a squeezed block dimension: the body is
the dense kernel's).  With ``fresh`` the call also WRITES: the step's
own key and value rows come in beside the query, stand in for the
cache's row while the block that holds the position is scored, and
leave in the ``_WRITE_ROWS`` = 16 rows around it through an output that
aliases the cache (``_decode_write_kernel``).  Readings on a v5e, the
whole decode program of Ouro-2.6B at 8 slots x 768 positions, 16 KV
heads of 128 under 16 query heads, 192 calls a step, rows at 100-700
(PERF.md section 6, PR 40): rows written by XLA first (a
``dynamic_update_slice`` under the engine's map over slots: a scatter,
a (slot, leaf) at a time) and the kernel after, 57.7 ms a step, of
which the scatter 12.8 and the kernel 15.0; the einsums in the
kernel's place 55.9; the kernel writing, 45.5 (blocks of 384: the
largest divisor of 768 under ``_BLOCK_S``), 44.8 at 128, 43.7 at 768;
four of eight rows live 38.3.  A call is 76 us: 16 before its first
step and about 5.6 a block that computes, because each of 16 heads has
ONE query row and its two products are a row against a block (at 8
heads under 32, a block computed in 3).

The DENSE writing form (PR 41, ``decode_attention(fresh=)`` without
``leaf``) is the stacked one over a plain pair ``[B, KV, S, D]`` taken as
a stack of one leaf: a unit axis, a bitcast in the compiled program and
no copy of a leaf.  ``models/llama.py`` hands it the single-token step's
rows wherever the cache's blocks are whole tiles (``writable``).
Readings on a v5e, the whole decode program of the Mistral-7B serving
cut traced in its cells (16 layers, 32 slots x 2048 positions, 8 KV
heads of 128 under 32 query heads in bf16, 16 calls a step; PERF.md
section 6, PR 41): rows written by XLA first, 14.76 ms a step with 6.0
slots live, of which 32 loops of 0.137 ms (4.38: the scatter, a loop
over the slots for K and one for V a layer) and the kernel 0.77 (48 us a
call); the kernel writing, 11.41 ms with 4.7 slots live, no loop, the
kernel 0.99 (62 us a call: alone, the writing form costs 14 us a call
before any row is live and 0-0.45 us a block).  With 20-30 slots live
16.91 -> 12.97 ms, the kernel 2.99 -> 2.82 (187 -> 176 us, fewer rows).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "decode_attention_int8",
           "latent_block", "latent_decode_attention", "latent_tileable",
           "streamed_positions", "tileable", "writable"]

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


def writable(s_len: int) -> bool:
    """Whether a call that also writes (``decode_attention(fresh=)``)
    serves a cache of ``s_len`` positions: the tile it puts back lies
    inside one block, so a block is whole ``_WRITE_ROWS`` tiles."""
    return _fit_block(s_len, _BLOCK_S) % _WRITE_ROWS == 0


# rows of the kernel's scalar-prefetch operand (the fifth only where the
# cache operands are stacks of leaves: the leaf a row reads; the sixth
# only where the call also writes: the tile of rows it writes back)
_POS, _SRC, _FIRST, _LAST, _LEAF, _TILE = range(6)
# Cache rows of the tile a writing call puts back around a row's new
# position: the sublanes of one bfloat16 tile (two float32 ones).
_WRITE_ROWS = 16


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


def _named_leaf_block(bk, sj, plan):
    """``_named_block`` in leaf ``plan[_LEAF]`` of a stack ``[B, N, KV,
    S, D]``."""
    src, head, block, last = _named_block(bk, sj, plan)
    return src, plan[_LEAF, bk], head, block, last


def _written_tile(bk, sj, plan):
    """The output index map of a writing call: the tile around the new
    position of the row whose blocks grid step ``(bk, sj)`` names (a
    row that does not decode names the tile the row before it wrote,
    and the pipeline writes nothing back for a tile it already
    holds)."""
    return plan[_SRC, bk], plan[_LEAF, bk], 0, plan[_TILE, bk], 0


def _row_plan(idx, live, b: int, s_len: int, want: int, leaf=None,
              writes: bool = False):
    """``(block, plan)`` of a call over ``b`` rows of ``s_len``
    positions: the block that fits under ``want`` and ``_stream_plan``
    over ``idx`` and ``live``, each one scalar for every row or ``[b]``
    per row; with ``leaf`` (the same), a fifth row that names it, and
    with ``writes`` a sixth, the ``_WRITE_ROWS`` tile of the position of
    the row a step names."""
    block = _fit_block(s_len, want)
    if block < 8 and s_len >= 8:
        # no viable tiling (e.g. a prime cache length > the wanted
        # block): a 1-position block would run one grid step per cache
        # position — refuse loudly instead of being silently 100x slow
        raise ValueError(
            f"cache length {s_len} has no block divisor in [8, "
            f"{min(want, s_len)}]; pad max_len to a multiple of 8 or "
            "use decode_attn='xla'")
    per_row = lambda x, dtype: jnp.broadcast_to(
        jnp.asarray(x, dtype).reshape(-1), (b,))
    idx = jnp.clip(per_row(idx, jnp.int32), 0, s_len - 1)
    live = per_row(live, bool)
    plan = _stream_plan(idx, live, block)
    if leaf is not None:
        plan = jnp.concatenate([plan, per_row(leaf, jnp.int32)[None]])
    if writes:
        if block % _WRITE_ROWS:
            raise ValueError(
                f"a cache of {s_len} positions in blocks of {block} is no "
                f"whole number of {_WRITE_ROWS}-row tiles: the kernel "
                "cannot write the step's rows (fresh=); write them first")
        tile = (jnp.where(live, idx, 0) // _WRITE_ROWS)[plan[_SRC]]
        plan = jnp.concatenate([plan, tile[None]])
    return block, plan


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


def _fold_block(s, v_blk, sl, m_ref, l_ref, acc_ref, v_scale=None):
    """One block's masked scores ``s [rep, block_s]`` and values ``v_blk
    [block_s, D]`` folded into the running softmax of the head rows
    ``sl`` (the flash recurrence).  ``v_scale``: a quantized block's
    value scales; the softmax denominator uses the UNSCALED
    probabilities, so they only rescale the values."""
    m, l, acc = m_ref[sl], l_ref[sl], acc_ref[sl]
    blk_m = jnp.max(s, axis=-1)
    new_m = jnp.maximum(m, blk_m)
    p = jnp.exp(s - new_m[:, None])
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    corr = jnp.exp(m - new_m)
    m_ref[sl] = new_m
    l_ref[sl] = l * corr + jnp.sum(p, axis=-1)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[sl] = acc * corr[:, None] + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _softmax_start(sj, m_ref, l_ref, acc_ref):
    """A row's first grid step: the running softmax starts empty."""
    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)


def _softmax_end(sj, o_ref, l_ref, acc_ref):
    """A row's last grid step: the sum over its probabilities."""
    @pl.when(sj == pl.num_programs(1) - 1)
    def _():
        safe_l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / safe_l[:, None]).astype(o_ref.dtype)


def _decode_write_kernel(plan_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                         o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref, *,
                         scale: float, n_kv: int):
    """``_decode_kernel`` over a cache that does NOT hold the step's own
    key and value rows yet: they come in beside the query (``kn_ref``,
    ``vn_ref``: [1, KV, D]), stand in for the cache's row at the
    position while the block that holds it is scored, and leave in the
    ``_WRITE_ROWS`` tile around it, which is put back through an output
    that IS the cache (``ko_ref``, ``vo_ref``: [1, KV, _WRITE_ROWS, D],
    aliased; ``_written_tile``).  The write costs one small tile a row
    where XLA's scatter of a step's rows into a mapped pool cost more
    than the attention (PERF.md section 6, PR 40)."""
    sj, bk = pl.program_id(1), pl.program_id(0)
    idx = plan_ref[_POS, bk]
    rep = q_ref.shape[1] // n_kv
    block_s = k_ref.shape[2]
    _softmax_start(sj, m_ref, l_ref, acc_ref)

    @pl.when(sj * block_s <= idx)
    def _():
        at = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, 1), 0)
        pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (rep, block_s), 1)
        for kv in range(n_kv):
            sl = slice(kv * rep, (kv + 1) * rep)
            q = q_ref[0, sl].astype(jnp.float32)
            k_blk = jnp.where(at == idx,
                              kn_ref[0, kv:kv + 1].astype(jnp.float32),
                              k_ref[0, kv].astype(jnp.float32))
            v_blk = jnp.where(at == idx,
                              vn_ref[0, kv:kv + 1].astype(jnp.float32),
                              v_ref[0, kv].astype(jnp.float32))
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            _fold_block(jnp.where(pos <= idx, s, _NEG_INF), v_blk, sl,
                        m_ref, l_ref, acc_ref)

    # the block that holds the position: its tile, the new row in place
    @pl.when((sj * block_s <= idx) & (idx < (sj + 1) * block_s))
    def _():
        start = pl.multiple_of(
            (idx - sj * block_s) // _WRITE_ROWS * _WRITE_ROWS, _WRITE_ROWS)
        new = (sj * block_s + start + jax.lax.broadcasted_iota(
            jnp.int32, (_WRITE_ROWS, 1), 0)) == idx
        for kv in range(n_kv):
            ko_ref[0, kv] = jnp.where(
                new, kn_ref[0, kv:kv + 1],
                k_ref[0, kv, pl.ds(start, _WRITE_ROWS), :])
            vo_ref[0, kv] = jnp.where(
                new, vn_ref[0, kv:kv + 1],
                v_ref[0, kv, pl.ds(start, _WRITE_ROWS), :])

    # a first row that does not decode names its own first tile, which
    # no row before it wrote: put back what is there
    @pl.when((bk == 0) & (idx < 0) & (sj == 0))
    def _():
        ko_ref[0] = k_ref[0, :, :_WRITE_ROWS, :]
        vo_ref[0] = v_ref[0, :, :_WRITE_ROWS, :]

    _softmax_end(sj, o_ref, l_ref, acc_ref)


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
    idx = plan_ref[_POS, pl.program_id(0)]
    heads = q_ref.shape[1]
    rep = heads // n_kv
    block_s = k_ref.shape[2]
    _softmax_start(sj, m_ref, l_ref, acc_ref)

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

            # value scale varies along the contracted position axis:
            # folded into the probabilities (kept float — NEVER
            # re-quantized, the round-4 w8a8 long-context regression)
            _fold_block(s, v_blk, sl, m_ref, l_ref, acc_ref,
                        vs_ref[0, kv][:, 0][None, :] if quantized else None)

    _softmax_end(sj, o_ref, l_ref, acc_ref)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def _decode_impl(q, k_all, v_all, ks_all, vs_all, idx, live, leaf=None,
                 fresh=None, *, block_s, interpret):
    """q: [B, 1, n_q, D]; k_all/v_all: KV-HEAD-MAJOR [B, KV, S, D]
    (int8 when quantized); ks_all/vs_all: [B, KV, S] f32 scales or None;
    idx: the current position, and live: whether the row decodes (the
    output of one that does not is zeros), each one scalar for every
    row or [B] per row.  With ``leaf`` (the same; a rolled loop's
    counter), k_all/v_all are STACKS [B, N, KV, S, D] of which the call
    reads leaf ``leaf`` through its index map: no slice of the stack is
    made.  With ``fresh = (k, v)`` ([B, KV, D] each: the step's own key
    and value rows, which the stacks do not hold yet) the call attends
    over them too and WRITES them at ``(leaf, idx)``
    (``_decode_write_kernel``): ``(out, k_all', v_all')``, the stacks
    updated in place.  Returns [B, 1, n_q, D] in q's dtype.  Jitted, so
    that the layers of a model (same shapes) share one trace and one
    Mosaic lowering."""
    b, t, n_q, d = q.shape
    assert t == 1, "the fused decode kernel serves single-token steps"
    n_kv, s_len = k_all.shape[-3], k_all.shape[-2]
    quantized = ks_all is not None
    stacked, writes = leaf is not None, fresh is not None
    q3 = q.reshape(b, n_q, d)  # kv-major head order matches the cache
    block, plan = _row_plan(idx, live, b, s_len, block_s or _BLOCK_S, leaf,
                            writes=writes)

    def row(bk, sj, plan_ref):
        return bk, 0, 0

    if stacked:
        # the stack's axis is squeezed: the body sees [1, KV, block, D]
        kv_spec = pl.BlockSpec((1, None, n_kv, block, d), _named_leaf_block)
    else:
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

    kernel = _decode_kernel
    out_specs = pl.BlockSpec((1, n_q, d), row)
    out_shape = jax.ShapeDtypeStruct((b, n_q, d), q.dtype)
    aliases = {}
    if writes:
        # the rows ride beside the query; the stacks are outputs too
        kernel = _decode_write_kernel
        in_specs[1:1] = [pl.BlockSpec((1, n_kv, d), row)] * 2
        args[2:2] = [x.astype(k_all.dtype) for x in fresh]
        tile_spec = pl.BlockSpec((1, None, n_kv, _WRITE_ROWS, d),
                                 _written_tile)
        out_specs = [out_specs, tile_spec, tile_spec]
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (k_all, v_all)]
        aliases = {4: 1, 5: 2}    # operands, the plan counted: k_all, v_all
    out = pl.pallas_call(
        functools.partial(kernel, scale=1.0 / d ** 0.5, n_kv=n_kv),
        name="decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s_len // block),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((n_q,), jnp.float32),
                pltpu.VMEM((n_q,), jnp.float32),
                pltpu.VMEM((n_q, d), jnp.float32),
            ]),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*args)
    if writes:
        return out[0].reshape(b, 1, n_q, d), out[1], out[2]
    return out.reshape(b, 1, n_q, d)


def _row_batched(impl, scalars=(jnp.int32, bool)):
    """``impl(idx, live, *arrays)`` with its own ``vmap`` rule: a mapped
    axis of independent rows FOLDS into the kernel's batch grid axis,
    each row keeping its own position.  The serving engine maps the
    decode step over its cache slots; Pallas's generic batching would
    instead batch the SMEM position operand into a squeezed ``[slots,
    1]`` block, which the TPU lowering refuses (SMEM blocks must span
    the whole array).  ``scalars``: the dtypes of the leading operands
    that hold one number a row (``idx``, ``live``, and whatever a
    variant adds)."""
    call = jax.custom_batching.custom_vmap(impl)

    @call.def_vmap
    def _fold(axis_size, in_batched, *operands):
        n = len(scalars)
        arrays, arrays_batched = operands[n:], in_batched[n:]
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

        out = call(*map(rows, operands[:n], in_batched[:n], scalars),
                   *map(fold, arrays, arrays_batched))
        unfold = lambda x: x.reshape((axis_size, b) + x.shape[1:])
        return jax.tree.map(unfold, out), jax.tree.map(lambda x: True, out)

    return call


@functools.lru_cache(maxsize=None)
def _dense_call(block_s: Optional[int], interpret: bool):
    """``_decode_impl`` as ``call(idx, live, q, k_all, v_all[, ks_all,
    vs_all])`` under ``_row_batched``."""

    def call(idx, live, q, k_all, v_all, *scales):
        ks_all, vs_all = scales or (None, None)
        return _decode_impl(q, k_all, v_all, ks_all, vs_all, idx, live,
                            block_s=block_s, interpret=interpret)

    return _row_batched(call)


@functools.lru_cache(maxsize=None)
def _stacked_call(block_s: Optional[int], interpret: bool):
    """``_decode_impl`` over stacks of leaves, as ``call(idx, live,
    leaf, q, k_all, v_all[, k, v])`` under ``_row_batched``."""

    def call(idx, live, leaf, q, k_all, v_all, *fresh):
        return _decode_impl(q, k_all, v_all, None, None, idx, live, leaf,
                            fresh or None, block_s=block_s,
                            interpret=interpret)

    return _row_batched(call, (jnp.int32, bool, jnp.int32))


def decode_attention(q, k_all, v_all, idx, *, live=None, leaf=None,
                     fresh=None, block_s: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Fused GQA decode-attention step over a full-precision cache.

    q: [B, 1, n_q, D]; k_all/v_all: [B, KV, S, D] (cache layout/dtype);
    idx: current position, a scalar or [B] per row; live: False (a
    scalar or [B]) for a row whose output nobody reads: none of its
    cache is fetched and it returns zeros.  Drop-in for the
    decode-step case of ``models.llama._cached_attention`` (reference
    has no counterpart — decode itself is a new capability,
    docs/parity.md).

    ``leaf`` (a scalar, traced or not): k_all/v_all are stacks of ``N``
    such caches, [B, N, KV, S, D], and the step attends over leaf
    ``leaf`` alone.  The leaf rides in scalar prefetch beside the
    position and the index map names its blocks inside the stack: a
    loop that is rolled over its layers (``models/looped.py``) hands the
    kernel the whole stack it carries and no copy of a slice.

    ``fresh = (k, v)`` ([B, KV, D] each): the cache does not hold the
    step's own key and value rows yet; the call attends over them as
    the rows at ``idx``, writes them there (a row that is not ``live``
    writes nothing) and returns ``(out, k_all', v_all')``: the cache
    write of a decode step inside the kernel that reads the cache, for
    a step whose rows XLA would scatter into a mapped pool one slot (and
    leaf) at a time.  Without ``leaf`` the pair is a stack of one leaf.
    A cache whose blocks are no whole tiles (``writable``) raises."""
    live = jnp.asarray(True if live is None else live)
    interpret = _auto_interpret(interpret)
    if fresh is not None and leaf is None:
        # a unit axis: a bitcast on the way in and on the way out
        out, k_all, v_all = _stacked_call(block_s, interpret)(
            idx, live, jnp.zeros((), jnp.int32), q, k_all[:, None],
            v_all[:, None], *fresh)
        return out, k_all[:, 0], v_all[:, 0]
    if leaf is not None:
        return _stacked_call(block_s, interpret)(
            idx, live, jnp.asarray(leaf, jnp.int32), q, k_all, v_all,
            *(fresh or ()))
    return _dense_call(block_s, interpret)(idx, live, q, k_all, v_all)


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
    return _dense_call(block_s, _auto_interpret(interpret))(
        idx, jnp.asarray(True if live is None else live), q, kq_all,
        vq_all, ks_all, vs_all)


# ------------------------------------------------------------------ #
# the latent variant: one cached row serves every head, and both
# contractions (models/mla_moe.py, the absorbed single-token step)
# ------------------------------------------------------------------ #
# Cache positions one grid step of the latent kernel streams: a quarter
# of the cache, between the dense kernel's block and four of them (the
# module docstring's readings: 2,048 of 16,384 and 1,024 of 4,096).
_LATENT_BLOCKS = (_BLOCK_S, 4 * _BLOCK_S)


def latent_block(s_len: int) -> int:
    """The latent kernel's block for a cache of ``s_len`` positions (or
    the largest divisor of the length under it): the kernel and the
    host's count of what it streams both ask here."""
    least, most = _LATENT_BLOCKS
    return _fit_block(s_len, min(most, max(least, s_len // 4)))


def latent_tileable(s_len: int, width: int) -> bool:
    """Whether the latent kernel reads a leaf of ``s_len`` rows of
    ``width`` values AS IT LIES on a TPU.  The chip lays a leaf whose
    width is no multiple of its 128 lanes position-minor (no published
    latent is one: 320, 576), and the kernel streams it that way, in
    blocks of whole lanes; a leaf of whole lanes lies row-major, and the
    kernel's view of it would cost a transposed copy of the pool a call
    (sandbox, described v5e: 0.16 GiB of temporaries at 32 x 4,096 x
    640)."""
    return width % 128 != 0 and latent_block(s_len) % 128 == 0


def _latent_kernel(plan_ref, q_ref, c_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   dc: int):
    """Grid = (B, S blocks), the plan and the recurrence of
    ``_decode_kernel``.  One row's ``[H, dc + dr]`` query tile is
    resident; the latent streams POSITION-MINOR, as ``[dc + dr,
    block_s]`` tiles that every head scores whole and whose first ``dc``
    rows are the values.  The tiles enter the MXU as the cache holds
    them (the einsums of ``mla_moe.absorbed_step`` do the same), sums
    and probabilities are float32."""
    sj = pl.program_id(1)
    idx = plan_ref[_POS, pl.program_id(0)]
    block_s = c_ref.shape[3]
    _softmax_start(sj, m_ref, l_ref, acc_ref)

    @pl.when(sj * block_s <= idx)
    def _():
        q = q_ref[0]                                  # [H, dc + dr]
        c_t = c_ref[0, 0]                             # [dc + dr, block_s]
        # the column groups as two dots: dc + dr (320, 576 at the
        # published widths) is no multiple of the 128 lanes, dc is
        s = jnp.dot(q[:, :dc], c_t[:dc],
                    preferred_element_type=jnp.float32)
        s += jnp.dot(q[:, dc:], c_t[dc:],
                     preferred_element_type=jnp.float32)
        pos = sj * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= idx, s, _NEG_INF)

        m, l = m_ref[:], l_ref[:]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - new_m[:, None])
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - new_m)
        m_ref[:] = new_m
        l_ref[:] = l * corr + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
            p, c_t[:dc].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    _softmax_end(sj, o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("dc", "block_s", "interpret"))
def _latent_impl(idx, live, qcat, latent_t, *, dc, block_s, interpret):
    """qcat: [B, 1, H, dc + dr]; latent_t: [B, 1, dc + dr, S] (one "KV
    head" for all ``H``, position-minor); idx, live: as
    ``_decode_impl``.  Returns float32 [B, 1, H, dc].  Jitted, so that
    the layers of a model share one trace and one Mosaic lowering."""
    b, t, h, width = qcat.shape
    assert t == 1, "the fused decode kernel serves single-token steps"
    s_len = latent_t.shape[3]
    block, plan = _row_plan(idx, live, b, s_len,
                            block_s or latent_block(s_len))

    def row(bk, sj, plan_ref):
        return bk, 0, 0

    def named_columns(bk, sj, plan_ref):
        src, head, blk, _ = _named_block(bk, sj, plan_ref)
        return src, head, 0, blk

    out = pl.pallas_call(
        functools.partial(_latent_kernel, dc=dc),
        name="latent_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s_len // block),
            in_specs=[pl.BlockSpec((1, h, width), row),
                      pl.BlockSpec((1, 1, width, block), named_columns)],
            out_specs=pl.BlockSpec((1, h, dc), row),
            scratch_shapes=[
                pltpu.VMEM((h,), jnp.float32),
                pltpu.VMEM((h,), jnp.float32),
                pltpu.VMEM((h, dc), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, dc), jnp.float32),
        interpret=interpret,
    )(plan, qcat.reshape(b, h, width), latent_t)
    return out.reshape(b, 1, h, dc)


@functools.lru_cache(maxsize=None)
def _latent_call(dc: int, block_s: Optional[int], interpret: bool):
    return _row_batched(functools.partial(
        _latent_impl, dc=dc, block_s=block_s, interpret=interpret))


def latent_decode_attention(qcat, latent, idx, *, dc: int, live=None,
                            block_s: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """The absorbed decode step of latent attention over the cache as
    it lies, up to the value up-projection.

    qcat: [B, 1, H, dc + dr], a head's ``[q_n W_uk^T ; q_r]`` with every
    scale already on it; latent: [B, S, dc + dr], the position's ``[c ;
    k_r]`` that all heads share; idx, live: as ``decode_attention``.
    Returns ``u_h = sum_j p_j c_j``, float32 [B, 1, H, dc]: what
    ``models.mla_moe.absorbed_step`` hands to ``W_uv``."""
    # a TPU lays a leaf whose width is no multiple of 128 lanes (none
    # published is) position-minor: the transposed view is the leaf as
    # it lies, and no copy of the pool is made on the way in
    return _latent_call(dc, block_s, _auto_interpret(interpret))(
        idx, jnp.asarray(True if live is None else live), qcat,
        jnp.swapaxes(latent, 1, 2)[:, None])
