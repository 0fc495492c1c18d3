"""Lane-dense stencil windows shared by the DIA Pallas kernels.

Mosaic (the TPU Pallas compiler) tiles a 32-bit array in (8, 128)
vregs and refuses what interpret mode accepts: dynamic loads that start
off a tile boundary, ``dynamic_slice`` on in-register values, and scalar
stores into VMEM.  The DIA kernels therefore share one layout:

* a length-n vector is viewed as ``(n / 128, 128)`` rows of lanes, with
  a leading batch dimension (RHS index or band index), so every operand
  is ``(lead, rows, 128)`` and every block is whole tiles;
* a grid step owns ``rows`` rows and reads ``hb`` more rows on each
  side through two extra BlockSpecs on the SAME array (aligned tiles,
  no copy of the vector), or from a small ``(lead, 2*hb, 128)`` edge
  operand at the ends of the sweep — zeros on one device, the
  neighbours' halo rows on a shard;
* a stencil offset is a static element shift of that window, built from
  ``pltpu.roll`` on lanes and sublanes plus one lane select
  (:func:`shift`); rows near the window's ends wrap and are garbage, so
  ``hb`` covers the chain's reach and only the centre rows are kept;
* per-tile reduction partials are (1, 128) lane vectors written into
  one (8, 128) row of an output block (:func:`partials_tile`); the
  caller finishes the lane sum outside the kernel.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
RED_ROWS = 8  # one (8, 128) tile carries up to 8 reduction partials


def sublanes(*dtypes) -> int:
    """Rows of the native tile of the narrowest dtype (8 at 32-bit)."""
    return max(8 * max(1, 4 // jnp.dtype(d).itemsize) for d in dtypes)


def halo_rows(halo: int, depth: int, sub: int) -> int:
    """Window rows per side for ``depth`` chained stencils of reach ``halo``.

    One stencil application moves data by at most ``ceil(halo / 128)``
    rows; the count is rounded up to whole tiles (``sub`` rows).
    """
    need = depth * -(-halo // LANE)
    return max(sub, -(-need // sub) * sub)


def legal_block(block: int, hb: int) -> int:
    """Smallest multiple of ``hb`` tile rows (in elements) >= ``block``."""
    unit = hb * LANE
    return max(unit, -(-block // unit) * unit)


def shift(x: jnp.ndarray, off: int) -> jnp.ndarray:
    """``y.flat[i] = x.flat[i + off]`` on a ``(W, 128)`` window.

    ``off`` is static.  The result wraps around the window's ends: rows
    within ``ceil(|off| / 128)`` of an end hold other rows' values.
    """
    if off == 0:
        return x
    W = x.shape[0]
    q, rem = divmod(off, LANE)
    # int32 amounts: Mosaic's rotate takes no 64-bit shift under x64
    roll = lambda v, s, ax: pltpu.roll(v, np.int32(s), ax)
    a = roll(x, (-rem) % LANE, 1) if rem else x         # a[r,c] = x[r,(c+rem)%L]
    rows = lambda v, s: roll(v, (-s) % W, 0) if s % W else v
    same = rows(a, q)                                    # x[r+q, (c+rem)%L]
    if not rem:
        return same
    nxt = rows(a, q + 1)                                 # x[r+q+1, (c+rem)%L]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < LANE - rem, same, nxt)


def window_specs(lead: int, rows: int, hb: int, n_rows: int,
                 batched: bool) -> list:
    """BlockSpecs (centre, left halo, right halo, edges) of one operand.

    The operand is ``(lead_total, n_rows, 128)``; with ``batched`` the
    leading block follows the grid's RHS index ``j``, otherwise block 0
    (``lead`` = all bands) is shared.  Grids are ``(k, n_rows / rows)``.
    """
    per = rows // hb
    last = n_rows // hb - 1
    lj = (lambda j: j) if batched else (lambda j: 0)
    return [
        pl.BlockSpec((lead, rows, LANE), lambda j, i: (lj(j), i, 0)),
        pl.BlockSpec((lead, hb, LANE),
                     lambda j, i: (lj(j), jnp.maximum(i * per - 1, 0), 0)),
        pl.BlockSpec((lead, hb, LANE),
                     lambda j, i: (lj(j), jnp.minimum((i + 1) * per, last), 0)),
        pl.BlockSpec((lead, 2 * hb, LANE), lambda j, i: (lj(j), 0, 0)),
    ]


def window(refs, k: int, hb: int, acc) -> jnp.ndarray:
    """The ``(rows + 2*hb, 128)`` window of leading index ``k``.

    ``refs`` are the four refs of :func:`window_specs`; the first and
    last grid steps take their outer rows from the edge operand.
    """
    c_ref, l_ref, r_ref, e_ref = refs
    i = pl.program_id(1)
    edge = e_ref[k].astype(acc)
    left = jnp.where(i == 0, edge[:hb], l_ref[k].astype(acc))
    right = jnp.where(i == pl.num_programs(1) - 1, edge[hb:],
                      r_ref[k].astype(acc))
    return jnp.concatenate([left, c_ref[k].astype(acc), right], axis=0)


def partials_tile(sums: Sequence[jnp.ndarray], acc) -> jnp.ndarray:
    """Stack (1, 128) lane partials into one (RED_ROWS, 128) tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, (RED_ROWS, LANE), 0)
    tile = jnp.zeros((RED_ROWS, LANE), acc)
    for m, s in enumerate(sums):
        tile = jnp.where(row == m, s, tile)
    return tile


def as_rows(v: jnp.ndarray) -> jnp.ndarray:
    """``(..., n)`` -> ``(lead, n / 128, 128)``; 1-D input gets lead 1.

    A ``(lead, rows, 128)`` operand is already in row layout.
    """
    if v.ndim == 3:
        return v
    lead = v.shape[0] if v.ndim == 2 else 1
    return v.reshape(lead, v.shape[-1] // LANE, LANE)


def edges(left: jnp.ndarray, right: jnp.ndarray, hb: int) -> jnp.ndarray:
    """Edge operand ``(lead, 2*hb, 128)`` from the rows just outside.

    ``left`` (lead, a) are the ``a`` rows before row 0, ``right``
    (lead, b) the ``b`` rows after the last; the rest is zero.
    """
    width = hb * LANE
    lead = left.shape[0]
    lo = jnp.zeros((lead, width - left.shape[-1]), left.dtype)
    hi = jnp.zeros((lead, width - right.shape[-1]), right.dtype)
    ext = jnp.concatenate([lo, left, right.astype(left.dtype), hi], axis=-1)
    return ext.reshape(lead, 2 * hb, LANE)


def zero_edges(lead: int, hb: int, dtype) -> jnp.ndarray:
    """Edge operand of a sweep whose outside rows are all zero."""
    return jnp.zeros((lead, 2 * hb, LANE), dtype)
