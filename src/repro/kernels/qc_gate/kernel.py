"""State-vector RX-gate kernel — the paper's quantum-circuit simulator core.

An RX(theta) on qubit q of an n-qubit state mixes amplitude pairs whose
indices differ in bit q:

    |a'> = cos(t/2)|a> - i sin(t/2)|b>,   |b'> = cos(t/2)|b> - i sin(t/2)|a>

TPU adaptation: complex64 is not a vector-unit-native type, so the state is
stored as separate (re, im) fp32 planes (structure-of-arrays — the same
trick SVE ports of QC simulators use to keep lanes dense), reshaped to
(outer, 2, inner) with inner = 2**q so the pair partner is a fixed stride.
The kernel tiles the OUTER axis with BlockSpecs; each program applies the
rotation to a (bo, 2, inner) tile in VMEM.  AI ~ 6 flops / 16 bytes per
amplitude — memory-bound for large n (paper Fig. 5: speedup collapses once
the socket's bandwidth saturates at ~8 threads).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_TILE_AMPS = 1 << 16


def _rx_kernel(re_ref, im_ref, ore_ref, oim_ref, *, cos: float, sin: float):
    re = re_ref[...]  # (bo, 2, inner)
    im = im_ref[...]
    re0, re1 = re[:, 0], re[:, 1]
    im0, im1 = im[:, 0], im[:, 1]
    # (cos - i sin X) rotation: a' = c*a - i s*b ; b' = c*b - i s*a
    ore0 = cos * re0 + sin * im1
    oim0 = cos * im0 - sin * re1
    ore1 = cos * re1 + sin * im0
    oim1 = cos * im1 - sin * re0
    ore_ref[...] = jnp.stack([ore0, ore1], axis=1)
    oim_ref[...] = jnp.stack([oim0, oim1], axis=1)


def rx_gate(
    re: jax.Array,
    im: jax.Array,
    qubit: int,
    theta: float,
    *,
    block_outer: Optional[int] = None,
    interpret: bool = True,
):
    """Apply RX(theta) on ``qubit`` to the state (re, im), both (2**n,).

    ``block_outer`` defaults to tiles of about ``_TILE_AMPS`` amplitudes
    per plane, counting the inner axis at no less than one 128-lane row:
    256 outer rows up to qubit 6, fewer above, so the double-buffered
    in/out tiles stay inside the default scoped VMEM at any qubit."""
    import math

    n_amp = re.shape[0]
    inner = 1 << qubit
    outer = n_amp // (2 * inner)
    assert outer * 2 * inner == n_amp, (n_amp, qubit)
    c, s = math.cos(theta / 2), math.sin(theta / 2)
    re3 = re.reshape(outer, 2, inner)
    im3 = im.reshape(outer, 2, inner)
    bo = min(block_outer or max(1, _TILE_AMPS // (2 * max(inner, 128))), outer)
    assert outer % bo == 0
    kernel = functools.partial(_rx_kernel, cos=c, sin=s)
    ore, oim = pl.pallas_call(
        kernel,
        grid=(outer // bo,),
        in_specs=[
            pl.BlockSpec((bo, 2, inner), lambda i: (i, 0, 0)),
            pl.BlockSpec((bo, 2, inner), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bo, 2, inner), lambda i: (i, 0, 0)),
            pl.BlockSpec((bo, 2, inner), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((outer, 2, inner), re.dtype),
            jax.ShapeDtypeStruct((outer, 2, inner), im.dtype),
        ],
        interpret=interpret,
    )(re3, im3)
    return ore.reshape(n_amp), oim.reshape(n_amp)
