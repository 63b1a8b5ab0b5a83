"""Pallas fused 1x1-conv backward — the ResNet bandwidth kernel.

Round-2 verdict item 1 ("hand-scheduled conv-backward kernel").  The
whole-step audit (benchmarks/profile_resnet_convs.py + XLA cost
analysis) shows batch-128 ResNet-50 on v5e is **HBM-bandwidth-bound**:
the forward runs at the bandwidth roofline and the backward's wall is
the 1x1 convolutions — pure matmuls whose XLA backward materializes
transposed operands and reads the upstream cotangent twice (once for
the input gradient, once for the weight gradient).

This kernel computes BOTH gradients in ONE pass over the data:

    dx[n, ci] = dy[n, co] @ w[ci, co]^T        (MXU, per tile)
    dw[ci, co] += x[n, ci]^T @ dy[n, co]       (MXU, accumulated in VMEM)

Each N-tile of ``x`` and ``dy`` is loaded from HBM exactly once; ``dw``
lives in a float32 VMEM accumulator across the whole grid (constant
output index map) and is written back once.  Ideal traffic is
``|x| + |dy| + |dx| + |dw|`` — the information-theoretic floor.
The transposed contractions are expressed as ``dot_general`` dimension
numbers, so no transposed copy of any N-sized tensor is ever
materialized.

The forward path stays with XLA (a 1x1 conv IS a matmul and already
runs at the roofline); only the backward is hand-scheduled, wired in
through ``jax.custom_vjp``.  Strided 1x1 convs (ResNet's projection
shortcuts) are handled by slicing the input at stride positions in the
forward and scattering ``dx`` back through the same positions — the
kernel itself always sees the dense stride-1 problem.

Reference counterpart: the CUDA ScaleBuffer kernel era of hand-written
device code (reference bluefog/cuda/cuda_kernels.cu) — here the hot op
is the conv backward, not the weighted combine (which XLA already
fuses, docs/performance.md).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["conv1x1", "conv1x1_backward"]


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _tile_n(n: int, ci: int, co: int) -> int:
    """Largest divisor of n fitting the ~16 MB scoped-VMEM budget:
    resident blocks (w bf16 + dw f32 output + f32 accumulator scratch =
    10*ci*co bytes) plus DOUBLE-buffered streaming x/dy/dx blocks.
    Prefers sublane-aligned (multiple-of-8) divisors."""
    # Mosaic pads the lane (last) dim to 128: budget with PADDED widths.
    # Resident: w^T [co, ci] (bf16) + dw out [ci, co] (f32) + acc
    # scratch [ci, co] (f32); streaming: x/dx [tn, ci] + dy [tn, co],
    # double-buffered.
    ci_p = -(-ci // 128) * 128
    co_p = -(-co // 128) * 128
    budget = 11 * 1024 * 1024 - (2 * co * ci_p + 8 * ci * co_p)
    row_bytes = 2 * 2 * (2 * ci_p + co_p)  # bf16 x + dx + dy, dbl-buffered
    target = max(min(budget // max(row_bytes, 1), n), 1)
    best = 1
    for t in range(min(target, n), 0, -1):
        if n % t == 0:
            if t % 8 == 0:
                return t  # first (largest) aligned divisor wins
            best = max(best, t)
    return best


def _bwd_kernel(x_ref, dy_ref, wt_ref, dx_ref, dw_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dy = dy_ref[:]
    # dx = dy @ w^T (w passed pre-transposed [co, ci]: the canonical
    # contract-dim1-with-dim0 MXU matmul) -> [TN, ci]
    dx = lax.dot_general(dy, wt_ref[:],
                         dimension_numbers=(((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dw += x^T @ dy: contract N (dim 0 of both) -> [ci, co], f32 VMEM
    # scratch accumulator (NOT an output-block revisit, which would
    # serialize the dx output pipeline)
    acc_ref[:] += lax.dot_general(x_ref[:], dy,
                                  dimension_numbers=(((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dw_ref[:] = acc_ref[:]


def conv1x1_backward(x2d: jax.Array, dy2d: jax.Array, w: jax.Array,
                     interpret: Optional[bool] = None):
    """Fused (dx, dw) for ``y = x2d @ w``.

    x2d [N, ci], dy2d [N, co], w [ci, co]; returns dx2d [N, ci] in
    x2d's dtype and dw [ci, co] in float32 (accumulated in f32 on the
    MXU regardless of input dtype).
    """
    n, ci = x2d.shape
    co = dy2d.shape[1]
    tn = _tile_n(n, ci, co)
    if tn < 64:
        # Resident w/dw/accumulator blocks leave no VMEM for streaming
        # (huge ci*co, e.g. the 1024->2048 projection): XLA's backward
        # is the better program there
        dx = lax.dot_general(dy2d, w,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dw = lax.dot_general(x2d, dy2d,
                             dimension_numbers=(((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return dx.astype(x2d.dtype), dw
    grid = (n // tn,)
    dx, dw = pl.pallas_call(
        _bwd_kernel,
        name="conv1x1_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, ci), lambda i: (i, 0)),
            pl.BlockSpec((tn, co), lambda i: (i, 0)),
            pl.BlockSpec((co, ci), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, ci), lambda i: (i, 0)),
            pl.BlockSpec((ci, co), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ci), x2d.dtype),
            jax.ShapeDtypeStruct((ci, co), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ci, co), jnp.float32)],
        interpret=_auto_interpret(interpret),
    )(x2d, dy2d, w.T)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv1x1(x: jax.Array, w: jax.Array, stride: int = 1,
            interpret: Optional[bool] = None) -> jax.Array:
    """1x1 convolution ``y[b,i,j,co] = sum_ci x[b,si,sj,ci] w[ci,co]``
    with the Pallas fused backward.

    ``x`` NHWC, ``w`` [ci, co] (squeeze the [1,1,ci,co] conv kernel).
    Forward is a plain XLA matmul (already bandwidth-optimal); backward
    is one fused Pallas pass producing dx and dw together.
    """
    return _fwd_impl(x, w, stride)


def _fwd_impl(x, w, stride):
    if stride > 1:
        x = x[:, ::stride, ::stride, :]
    b, h, wd, ci = x.shape
    y = lax.dot_general(x.reshape(-1, ci), w,
                        dimension_numbers=(((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return y.reshape(b, h, wd, -1).astype(x.dtype)


def _conv1x1_fwd(x, w, stride, interpret):
    return _fwd_impl(x, w, stride), (x, w)


def _conv1x1_bwd(stride, interpret, res, dy):
    x, w = res
    xs = x[:, ::stride, ::stride, :] if stride > 1 else x
    b, h, wd, ci = xs.shape
    dy2d = dy.reshape(-1, dy.shape[-1]).astype(xs.dtype)
    dx2d, dw = conv1x1_backward(xs.reshape(-1, ci), dy2d,
                                w.astype(xs.dtype), interpret=interpret)
    dxs = dx2d.reshape(b, h, wd, ci)
    if stride > 1:
        dx = jnp.zeros(x.shape, dxs.dtype).at[:, ::stride, ::stride, :].set(
            dxs)
    else:
        dx = dxs
    return dx, dw.astype(w.dtype)


conv1x1.defvjp(_conv1x1_fwd, _conv1x1_bwd)
