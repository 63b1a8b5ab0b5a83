"""Pallas TPU single-token step of the delta rule (``models/kda.py``) over
the rows that are LIVE.

``models/kda.py:delta_step`` is written for one sequence.  The serving
engine maps the decode step over its slots, so under XLA every recurrent
layer reads the ``state_s`` leaf of EVERY slot twice (``S^T (alpha k)``,
then ``alpha S + k u^T``, which waits for ``u``) and writes it once,
whoever decodes: 64 slots x 6 layers x 2 MiB x 3 = 2.4 GB a step in the
Ling3 cell, 3.6 ms of a 12.0 ms decode program, the same at 10 decoding
slots as at 19 (PERF.md section 6, PR 38).  What the step has to move is
the state of the rows that decode, once in and once out.

This kernel is ``delta_step``'s arithmetic with that walk over memory.
A row is one slot of one layer: ``state [H, D, Dv]`` float32 and the
token's ``q, k, g [H, D]``, ``v [H, Dv]``, ``beta [H]``.  The grid is
(rows, blocks of ``_BLOCK_H`` heads); a grid step of a live row

1. holds its block of the state in VMEM, fetched ONCE;
2. computes from that one copy both products with the OLD state, ``S^T
   (alpha k)`` and ``S^T (alpha q)``, then ``u = beta (v - S^T (alpha
   k))``, the read-out ``o = S^T (alpha q) + u (k . q)`` and the new
   state ``alpha S + k u^T``: float32 multiplies and sums over rows on
   the vector unit, as ``delta_step`` computes them (the state never
   enters the matrix unit: nothing rounds it to bfloat16; on the chip
   the new state is ``delta_step``'s bit for bit and the read-out its
   last bits);
3. writes the block of the new state ONCE, through an output that IS
   the leaf (``input_output_aliases``): the donated pool is advanced
   where it lies.

A row that is not live (a slot that does not decode, a free slot) costs
nothing: a scalar-prefetch plan names the live rows FIRST and points
every grid step after them at the block the last of them ended on, so
the pipeline fetches nothing and writes nothing back for it (the
``_stream_plan`` / ``_written_tile`` shape of
``parallel/pallas_decode.py``).  Its bytes in the leaf come back bit for
bit, the protocol's rule for a token that is not live, and its ``o`` is
zeros.  A row whose ``cache_index`` is 0 (``fresh``) starts from zero
state whatever the leaf holds: the flag rides in the plan, the kernel
takes ``S`` as zeros and its grid steps name the block already held, so
the stale state is not even read.

The engine's ``vmap`` over slots folds into the row axis through
``pallas_decode._row_batched``.

Readings on a v5e (PERF.md section 6, PR 43).  Alone, six calls over six
leaves of ``[64, 1, 32, 128, 128]`` in one program, ms: ``delta_step``
under ``vmap`` 3.55 whoever is live; the kernel with 0 / 10 / 19 / 35 /
64 slots live 0.35 / 0.48 / 0.82 / 1.45 / 2.56 at 32 heads a step, 0.36
/ 0.55 / 0.88 / 1.48 / 2.56 at 16, 0.34 / 0.71 / 1.10 / 1.79 / 3.03 at
8 (of the 0.35 with nothing live most is the program around the calls).
With the rows left in place (a plan that names a dead row's blocks after
the live row before it) 19 live slots read 1.07 at 16 heads and 1.18 at
32: every live row behind a dead one waits for its own first fetch,
6.7-7.8 us a row where the live rows first take 6.2-6.5.  A read-out block of its own for
every row, live or not, was a DMA a row: it is written for live rows
alone and masked outside the kernel.  In the Ling3 cell's decode program
(13.2 slots decode a step, six calls): 0.091 ms a call, 597 GB/s of the
state's bytes in and out, 73% of the HBM peak, where ``delta_step`` took
3.63 ms a step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bluefog_tpu.parallel import pallas_decode

__all__ = ["delta_step", "steppable"]

# heads of one grid step: 32 x 128 x 128 float32 is 2 MiB, in and out
# double-buffered 8 MiB of VMEM (the module docstring's readings)
_BLOCK_H = 32
# rows of the scalar-prefetch plan, one column a step of the grid's row
# axis
_MOVES, _FRESH, _ROW, _HELD = range(4)
# planes of the packed token operand: q, k, v, g and beta (a head's
# number along the lanes)
_Q, _K, _V, _G, _BETA = range(5)


def steppable(head_dim: int) -> bool:
    """Whether the kernel serves a state of ``head_dim`` x ``head_dim``
    a head: its tiles are whole lanes (every published width is 128).
    A model of another width keeps ``models.kda.delta_step``."""
    return head_dim % 128 == 0


def _step_plan(live, fresh):
    """``[4, B]`` int32, what each step of the grid's row axis moves.
    The LIVE rows come first, in order, so that the pipeline fetches a
    live row's first block while the one before it computes (with the
    rows in place, every live row behind one that is not waited for its
    own fetch: 6.7-7.8 us a row against 6.2).  Step ``i`` holds: whether it
    moves a state at all (``i`` under the count of live rows), whether
    that row starts from zero state, the row whose blocks it names in
    the token operand and both outputs (the ``i``-th live row; past the
    last live row, still that one: nothing new to write back) and in
    the state's INPUT (the same row if the step reads its state, moves
    and is not fresh, else the row of the reading step before it:
    nothing new to fetch).  Where no step moves or reads, row 0:
    something has to be named."""
    rows = jnp.arange(live.shape[0], dtype=jnp.int32)
    rank = jnp.cumsum(live, dtype=jnp.int32) - 1
    # [step, row]: row is the step-th live one (a compare and a sum of B
    # x B: no sort, no scatter)
    hit = live[None, :] & (rank[None, :] == rows[:, None])
    pick = lambda x: jnp.sum(jnp.where(hit, x[None, :], 0), axis=1)
    moves = rows <= rank[-1]
    starts = pick(fresh.astype(jnp.int32))
    row = jax.lax.cummax(pick(rows))
    held = jax.lax.cummax(jnp.where(moves & (starts == 0), row, 0))
    return jnp.stack([moves.astype(jnp.int32), starts, row, held])


def _kda_kernel(plan_ref, x_ref, s_ref, o_ref, so_ref):
    """Grid = (rows, head blocks).  ``x_ref [1, 5, hb, D]``: the token's
    packed q, k, v, g, beta of the block's heads; ``s_ref`` / ``so_ref
    [1, hb, D, Dv]``: the block of the state, in and (aliased) out;
    ``o_ref [1, H, Dv]``: the row's read-out, resident over its blocks
    (no step writes that of a row that is not live: ``_step_impl`` puts
    zeros there)."""
    bk, hj = pl.program_id(0), pl.program_id(1)
    moves = plan_ref[_MOVES, bk] == 1
    fresh = plan_ref[_FRESH, bk] == 1
    hb = s_ref.shape[1]

    @pl.when(moves)
    def _():
        x = x_ref[0]
        q, k, v, beta = x[_Q], x[_K], x[_V], x[_BETA]     # [hb, D]
        alpha = jnp.exp(x[_G])
        kq = jnp.sum(k * q, axis=-1, keepdims=True)       # [hb, 1]
        # a head's vector along the state's ROWS: [D, hb]
        alpha_c, k_c = alpha.T, k.T
        ak_c, aq_c = (alpha * k).T, (alpha * q).T
        outs = []
        for i in range(hb):
            col = lambda y: y[:, i:i + 1]
            s = jnp.where(fresh, 0.0, s_ref[0, i])        # [D, Dv]
            read = lambda y: jnp.sum(s * col(y), axis=0, keepdims=True)
            u = beta[i:i + 1] * (v[i:i + 1] - read(ak_c))  # [1, Dv]
            outs.append(read(aq_c) + u * kq[i:i + 1])
            so_ref[0, i] = col(alpha_c) * s + col(k_c) * u
        start = pl.multiple_of(hj * hb, hb)
        o_ref[0, pl.ds(start, hb), :] = jnp.concatenate(outs, axis=0)

    # no row is live: every step names the last block of row 0, which
    # no step wrote: put back what is there
    @pl.when(jnp.logical_not(moves) & (bk == 0) & (hj == 0))
    def _():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def _step_impl(live, fresh, q, k, v, g, beta, state, *, block_h, interpret):
    """q, k, g ``[B, H, D]``, v ``[B, H, Dv]`` (``Dv`` = ``D``), beta
    ``[B, H]``, state ``[B, H, D, Dv]``, all float32; live, fresh: one
    scalar for every row or ``[B]`` per row.  Returns ``(o [B, H, Dv],
    state')``, the state updated in place.  Jitted, so that the layers
    of a model (same shapes) share one trace and one Mosaic lowering."""
    b, h, d, dv = state.shape
    assert d == dv, "the packed token operand holds keys and values alike"
    # the most heads under ``_BLOCK_H`` that split ``h`` into blocks of
    # whole sublanes (or all of them)
    hb = block_h or next(n for n in range(min(h, _BLOCK_H), 0, -1)
                         if h % n == 0 and (n % 8 == 0 or n == h))
    assert h % hb == 0, (h, hb)
    last = h // hb - 1
    per_row = lambda x: jnp.broadcast_to(
        jnp.asarray(x, bool).reshape(-1), (b,))
    live = per_row(live)
    plan = _step_plan(live, per_row(fresh))
    x = jnp.stack([q, k, v, g, jnp.broadcast_to(beta[..., None], q.shape)],
                  axis=1).astype(jnp.float32)

    def block(walks, hj):
        # a step that moves (reads) its own state walks its blocks; any
        # other stays on the last block of the row it names, where that
        # row ended
        return jnp.where(walks, hj, last)

    def token(bk, hj, plan):
        return plan[_ROW, bk], 0, block(plan[_MOVES, bk] == 1, hj), 0

    def held(bk, hj, plan):
        reads = (plan[_MOVES, bk] == 1) & (plan[_FRESH, bk] == 0)
        return plan[_HELD, bk], block(reads, hj), 0, 0

    def written(bk, hj, plan):
        return plan[_ROW, bk], block(plan[_MOVES, bk] == 1, hj), 0, 0

    o, state = pl.pallas_call(
        _kda_kernel,
        name="kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb),
            in_specs=[pl.BlockSpec((1, 5, hb, d), token),
                      pl.BlockSpec((1, hb, d, dv), held)],
            out_specs=[pl.BlockSpec((1, h, dv), lambda bk, hj, plan:
                                    (plan[_ROW, bk], 0, 0)),
                       pl.BlockSpec((1, hb, d, dv), written)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={2: 1},    # operands, the plan counted: state
        interpret=interpret,
    )(plan, x, state)
    # a row that is not live wrote no read-out (a block of its own a row
    # is a DMA a row, whoever is live): zeros, in whatever reads ``o``
    # next
    return jnp.where(live[:, None, None], o, 0.0), state


@functools.lru_cache(maxsize=None)
def _step_call(block_h: Optional[int], interpret: bool):
    return pallas_decode._row_batched(functools.partial(
        _step_impl, block_h=block_h, interpret=interpret), (bool, bool))


def delta_step(q, k, v, g, beta, state, *, live=None, fresh=None,
               block_h: Optional[int] = None,
               interpret: Optional[bool] = None):
    """``models.kda.delta_step`` over the rows that are live, the state
    advanced where it lies.

    q, k, g ``[B, H, D]``, v ``[B, H, D]``, beta ``[B, H]``, float32;
    state ``[B, H, D, D]`` float32, the cache leaf as it stands.  live:
    False (a scalar or ``[B]``) for a row whose token is not live: its
    state is neither read nor written and its ``o`` is zeros.  fresh:
    True (the same) for a row that starts from zero state whatever the
    leaf holds (``cache_index`` 0).  Returns ``(o [B, H, D], state')``.
    Under ``jax.vmap`` (the engine's map over slots) the mapped axis
    folds into the rows."""
    as_flag = lambda x, default: jnp.asarray(default if x is None else x)
    # asked of the module at each call: what steers the other kernels of
    # a compile for a described chip (perfbench/rehearse.py) steers this
    return _step_call(block_h, pallas_decode._auto_interpret(interpret))(
        as_flag(live, True), as_flag(fresh, False), q, k, v, g, beta, state)
