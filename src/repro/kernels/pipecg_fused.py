"""Pallas TPU kernel: the fully-fused PIPECG iteration body.

The pipelined rearrangement costs extra AXPYs (8 vector updates/iteration vs
3 for CG) — PIPECG is MORE memory-bound than CG.  On GPUs the fix is fewer
kernel launches (paper §5, ref [19]); the TPU-idiomatic equivalent is fewer
HBM passes: this kernel reads the 10 state vectors tile-by-tile ONCE,
applies all eight updates, AND accumulates the three reductions of the next
iteration (gamma', delta', ||r'||^2) — so a whole PIPECG iteration becomes
one HBM sweep + one psum.

Naive:  8 AXPYs x (2 reads + 1 write) + 3 dots x 2 reads ~= 30 n words.
Fused:  10 reads + 8 writes                             ~= 18 n words (1.7x),
and the reduction partials ride along for free.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import stencil
from repro.kernels.stencil import LANE

DEFAULT_BLOCK = 1024
NVEC = 10  # x, r, u, w, m, n, z, q, s, p


def _pipecg_kernel(ab_ref, x_ref, r_ref, u_ref, w_ref, m_ref, n_ref,
                   z_ref, q_ref, s_ref, p_ref,
                   xo, ro, uo, wo, zo, qo, so, po, red_o):
    i = pl.program_id(0)
    alpha = ab_ref[0]
    beta = ab_ref[1]

    z2 = n_ref[...] + beta * z_ref[...]
    q2 = m_ref[...] + beta * q_ref[...]
    s2 = w_ref[...] + beta * s_ref[...]
    p2 = u_ref[...] + beta * p_ref[...]
    x2 = x_ref[...] + alpha * p2
    r2 = r_ref[...] - alpha * s2
    u2 = u_ref[...] - alpha * q2
    w2 = w_ref[...] - alpha * z2

    xo[...] = x2
    ro[...] = r2
    uo[...] = u2
    wo[...] = w2
    zo[...] = z2
    qo[...] = q2
    so[...] = s2
    po[...] = p2

    @pl.when(i == 0)
    def _init():
        red_o[...] = jnp.zeros_like(red_o)

    # (1, 128) lane partials per dot; the caller sums the lanes
    lanes = lambda v: jnp.sum(v, axis=0, keepdims=True)
    red_o[...] += stencil.partials_tile(
        [lanes(r2 * u2), lanes(w2 * u2), lanes(r2 * r2)], red_o.dtype)


def pipecg_fused(x, r, u, w, m, n_, z, q, s, p, alpha, beta, *,
                 block: int = DEFAULT_BLOCK, interpret: bool = False
                 ) -> Tuple[jnp.ndarray, ...]:
    """Fused PIPECG updates + dots: 8 AXPYs and 3 dots in one HBM pass.

    Returns (x', r', u', w', z', q', s', p', red) with ``red`` (3,) =
    (<r',u'>, <w',u'>, <r',r'>); the M-apply and SpMV sweeps stay with
    the caller (the update-kernel fallback path of the FusedEngine).
    Vectors are tiled lane-dense as (rows, 128); n must be a multiple of
    ``block`` and ``block`` of 8 * 128 (the ops.py wrapper pads).
    """
    n = x.shape[0]
    assert n % block == 0 and block % (8 * LANE) == 0, (n, block)
    rows = block // LANE
    dt = x.dtype
    ab = jnp.stack([jnp.asarray(alpha, dt), jnp.asarray(beta, dt)])

    vec_spec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    outs = pl.pallas_call(
        _pipecg_kernel,
        grid=(n // block,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [vec_spec] * NVEC,
        out_specs=[vec_spec] * 8
        + [pl.BlockSpec((stencil.RED_ROWS, LANE), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n // LANE, LANE), dt)] * 8
        + [jax.ShapeDtypeStruct((stencil.RED_ROWS, LANE), dt)],
        interpret=interpret,
    )(ab, *(v.reshape(n // LANE, LANE) for v in (x, r, u, w, m, n_, z, q,
                                                  s, p)))
    return tuple(o.reshape(n) for o in outs[:8]) + (
        jnp.sum(outs[8][:3], axis=-1),)
