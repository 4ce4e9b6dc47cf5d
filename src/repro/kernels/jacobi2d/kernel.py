"""Jacobi 2D 5-point stencil — the paper's memory-bound PDE sweep.

TPU adaptation: the grid is tiled over row-blocks; each program reads its
(br, W) tile plus two sublane-aligned halo blocks — the ``hb`` rows just
above and just below the tile, through their own BlockSpecs on the same
input — and writes one (br, W) output tile.  Neighbours are formed with
sublane/lane rotations (``pltpu.roll``) and a row select for the halo row,
so every load is tile-aligned and the body is pure VPU work (no gather).
Roofline: AI = 4 flops / 12 bytes per point (fp32), firmly memory-bound
(paper Fig. 7 / Table 3: Class 2 at 1 thread).

Boundary semantics: Dirichlet — the outermost ring passes through unchanged,
interior points get the 4-neighbour average.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _jacobi_kernel(u_ref, up_ref, dn_ref, out_ref, *, br: int, H: int, W: int):
    r0 = pl.program_id(0) * br  # first output row of this tile
    mid = u_ref[...]
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (br, W), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (br, W), 1)
    local = row - r0

    # north neighbours: the tile shifted down one row, its first row taken
    # from the last row of the halo block above (at the top edge the halo
    # block is clamped, but row 0 is a boundary row and masked anyway)
    north = jnp.where(local == 0, up_ref[up_ref.shape[0] - 1:, :],
                      pltpu.roll(mid, 1, 0))
    # south neighbours: shifted up one row, the last row from the halo below
    south = jnp.where(local == br - 1, dn_ref[:1, :],
                      pltpu.roll(mid, br - 1, 0))
    # the wrapped columns land on boundary columns, which are masked
    west = pltpu.roll(mid, 1, 1)
    east = pltpu.roll(mid, W - 1, 1)
    avg = 0.25 * (north + south + west + east)

    interior = (row > 0) & (row < H - 1) & (col > 0) & (col < W - 1)
    out_ref[...] = jnp.where(interior, avg.astype(out_ref.dtype), mid)


def jacobi_step(u: jax.Array, *, block_rows: int = 128, interpret: bool = True):
    """One Jacobi sweep over u (H, W)."""
    H, W = u.shape
    br = min(block_rows, H)
    assert H % br == 0, (H, br)
    # halo blocks: one sublane tile (8 rows fp32, 16 bf16), or less when
    # the row block itself is smaller
    hb = math.gcd(br, 32 // u.dtype.itemsize)
    per = br // hb  # halo blocks per row block
    last = H // hb - 1
    return pl.pallas_call(
        lambda u_ref, up_ref, dn_ref, o_ref: _jacobi_kernel(
            u_ref, up_ref, dn_ref, o_ref, br=br, H=H, W=W),
        grid=(H // br,),
        in_specs=[
            pl.BlockSpec((br, W), lambda i: (i, 0)),
            pl.BlockSpec((hb, W), lambda i: (jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((hb, W), lambda i: (jnp.minimum((i + 1) * per, last), 0)),
        ],
        out_specs=pl.BlockSpec((br, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), u.dtype),
        interpret=interpret,
    )(u, u, u)
