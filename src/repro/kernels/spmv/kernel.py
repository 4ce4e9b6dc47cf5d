"""Predicated block-ELL SpMV — the paper's SVE-predication showcase on TPU.

SVE handles ragged sparse rows with predicate registers; the TPU analogue is
per-tile masking: rows are grouped into (8, 128)-aligned tiles and each
lane's contribution is gated by ``lane < row_nnz`` (a predicate computed from
``broadcasted_iota``), so a row occupies only ceil(nnz/128) lanes-issues
instead of the fixed-width max over all rows.  The kernel also implements
the paper's synthetic repeat-K loop (Sec. 3.2) as a ``fori_loop`` with a
loop-carried accumulator (their `#pragma unroll(1)` + no-DCE trick — the
carried dependency stops XLA from folding the K FMAs).

The x gather runs in the wrapper, as an XLA gather: Mosaic has no 1-D
gather from VMEM, so the kernel receives the (nb, rb, width) gathered
operand beside the values and keeps the lane predicate and the FMA loop.

Grid: one program per row-block.  Row lengths and results travel as
(nb, rb, 1) columns so every block is (8, 128)-tileable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spmv_kernel(values_ref, xg_ref, nnz_ref, y_ref, *, repeat: int):
    vals = values_ref[0]  # (rb, width)
    rb, width = vals.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rb, width), 1)
    pred = lane < nnz_ref[0]  # (rb, 1) lengths: predicate register analogue
    contrib = jnp.where(pred, vals * xg_ref[0], 0.0)
    inv = jnp.asarray(1.0 / repeat, vals.dtype)

    def body(_, acc):
        # loop-carried FMA: repeat x the arithmetic intensity, same result
        return acc + contrib.sum(axis=-1, keepdims=True) * inv

    acc0 = jnp.zeros((rb, 1), vals.dtype)
    y_ref[0] = jax.lax.fori_loop(0, repeat, body, acc0)


def spmv_blockell(values, col_idx, row_nnz, x, *, repeat: int = 1,
                  interpret: bool = True):
    """y = A @ x for block-ELL A.  values/col_idx: (nb, rb, width);
    row_nnz: (nb, rb); x: (n_cols,).  Returns (nb*rb,)."""
    nb, rb, width = values.shape
    kernel = functools.partial(_spmv_kernel, repeat=repeat)
    y = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, rb, width), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rb, width), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rb, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rb, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, rb, 1), values.dtype),
        interpret=interpret,
    )(values, x[col_idx], row_nnz[..., None])
    return y.reshape(nb * rb)


def spmv_fixed_width(values, col_idx, row_nnz, x, *, interpret: bool = True):
    """The fixed-width-SIMD strawman: no predication — every row is padded
    to the full tile width and all lanes issue (the paper's ASIMD 1.0x
    case).  Numerically identical (padding values are zero); the cost model
    differs (see kernels.spmv.ops.issue_counts)."""
    nb, rb, width = values.shape

    def kernel(values_ref, xg_ref, y_ref):
        y_ref[0] = (values_ref[0] * xg_ref[0]).sum(axis=-1, keepdims=True)

    y = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, rb, width), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, rb, width), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rb, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, rb, 1), values.dtype),
        interpret=interpret,
    )(values, x[col_idx])
    return y.reshape(nb * rb)
