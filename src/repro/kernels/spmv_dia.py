"""Pallas TPU kernel: banded (DIA) SpMV — the paper's compute hot-spot.

TPU adaptation of the stencil SpMV (DESIGN.md §Hardware-adaptation): the
vector and the bands are viewed lane-dense as ``(rows, 128)`` and tiled
in whole (8, 128) tiles; each grid step reads its tile of x together
with ``hb`` halo rows on either side (kernels/stencil.py) and applies
every band as a static rolled shift of that window.  Nothing is
resident, so the VMEM footprint is a few tiles whatever n is.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import stencil
from repro.kernels.stencil import LANE

DEFAULT_BLOCK = 8 * LANE  # one (8, 128) VPU tile per grid step


def _spmv_kernel(x_c, x_l, x_r, x_e, bands_ref, y_ref, *,
                 offsets: Sequence[int], hb: int):
    acc = y_ref.dtype
    xw = stencil.window((x_c, x_l, x_r, x_e), 0, hb, acc)
    rows = y_ref.shape[1]
    y = jnp.zeros((rows, LANE), acc)
    for k, off in enumerate(offsets):  # static unroll over bands
        y = y + bands_ref[k].astype(acc) * stencil.shift(xw, off)[hb:hb + rows]
    y_ref[0] = y


def halo_rows(offsets: Sequence[int], *dtypes) -> int:
    """Window rows per side of the SpMV for this operator and dtypes."""
    halo = max(abs(o) for o in offsets)
    return stencil.halo_rows(halo, 1, stencil.sublanes(*dtypes))


def spmv_dia(offsets: Sequence[int], bands: jnp.ndarray, x_ext: jnp.ndarray,
             halo: int, *, block: int = DEFAULT_BLOCK,
             interpret: bool = False) -> jnp.ndarray:
    """y[i] = sum_k bands[k,i] * x_ext[i + halo + offsets[k]].

    bands (n_bands, n); x_ext (n + 2*halo,).  ``block`` is rounded up to
    whole halo windows (``halo_rows(...) * 128`` rows); when n is not a
    multiple of it the rows are zero-padded, with the right halo kept
    next to row n-1.
    """
    nb, n = bands.shape
    assert x_ext.shape[0] == n + 2 * halo, (x_ext.shape, n, halo)
    hb = halo_rows(offsets, bands.dtype, x_ext.dtype)
    block = stencil.legal_block(min(block, n), hb)
    dt = x_ext.dtype
    left = x_ext[None, :halo]
    if n % block:
        # [x | right halo | zeros]: the pad rows carry zero bands
        n_pad = -(-(n + halo) // block) * block
        x = jnp.pad(x_ext[halo:], (0, n_pad - n - halo))
        bands = jnp.pad(bands, ((0, 0), (0, n_pad - n)))
        right = x_ext[None, :0]
    else:
        n_pad = n
        x = x_ext[halo:halo + n]
        right = x_ext[None, n + halo:]
    rows, n_rows = block // LANE, n_pad // LANE
    kernel = functools.partial(_spmv_kernel, offsets=tuple(offsets), hb=hb)
    y = pl.pallas_call(
        kernel,
        grid=(1, n_pad // block),
        in_specs=stencil.window_specs(1, rows, hb, n_rows, batched=False)
        + [pl.BlockSpec((nb, rows, LANE), lambda j, i: (0, i, 0))],
        out_specs=pl.BlockSpec((1, rows, LANE), lambda j, i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows, LANE), dt),
        interpret=interpret,
    )(*[stencil.as_rows(x)] * 3, stencil.edges(left, right, hb),
      stencil.as_rows(bands))
    return y.reshape(n_pad)[:n]
