"""BSR (blocked-ELL) SpMV + single-sweep PIPECG iteration as Pallas kernels.

The ``BsrMatrix`` layout (core/krylov/operator.py) stores every block row
as exactly ``max_deg`` (block-column index, dense bs x bs block) pairs,
padded with self-pointing zero blocks.  The uniform degree makes every
gather shape static, which is what Pallas needs: a tile of block rows
reads its index tile, gathers the x-blocks it names from the
VMEM-resident vector, and contracts with one batched block GEMV
(``rdij,rdj->ri``) — no scatter, no per-row control flow.

``pipecg_bsr_fused`` is the BSR rendering of the DIA single-sweep
mega-kernel (kernels/pipecg_spmv_fused.py): a WHOLE preconditioned
PIPECG iteration — p' = u + beta p, s' = A p', q' = diag^-1 s',
u' = u - alpha q', w' = A u', the x/r updates and the 6 fused reduction
partials (5 Gram entries + the ABFT checksum residual 1^T(Au') - c^T u')
— in one sweep over the tiled vectors.  Where the DIA kernel widens its
tile by 2*halo rows to reach the stencil's neighborhood, the BSR kernel
keeps u/p/indices/blocks fully VMEM-resident and follows the TWO-level
index chain instead: w' = A u' needs u' at the tile's block columns, and
u' there needs s' = A p' at those columns, a nested gather
``indices[indices[tile]]`` with static (brows, deg, deg) shape.  The
resident-operand footprint is the same assumption the DIA sweep makes
for its bands; the reduction row layout (k, 6) and the ``@pl.when(i==0)``
init are shared with the DIA kernel so the distributed/ABFT consumers
see an identical contract.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BROWS = 256
NRED = 6  # <r,u>, <w,u>, <r,r>, <r,w>, <w,w>, ABFT 1^T(Au') - c^T u'


def _spmv_kernel(idx_ref, blocks_ref, xb_ref, yo, *, brows: int):
    i = pl.program_id(0)
    base = i * brows
    idx = idx_ref[pl.ds(base, brows), :]
    blk = blocks_ref[pl.ds(base, brows), :, :, :]
    xb = xb_ref[...]                      # resident (nbr, bs)
    g = jnp.take(xb, idx, axis=0)         # (brows, deg, bs)
    yo[...] = jnp.einsum("rdij,rdj->ri", blk, g).astype(yo.dtype)


def spmv_bsr(indices: jnp.ndarray, blocks: jnp.ndarray, x: jnp.ndarray, *,
             brows: int = DEFAULT_BROWS, interpret: bool = False
             ) -> jnp.ndarray:
    """``y = A x`` for a blocked-ELL operator, one tiled Pallas sweep.

    ``indices`` (nbr, deg) int32, ``blocks`` (nbr, deg, bs, bs), ``x``
    (n,) with ``n = nbr * bs``; ``nbr`` must be a multiple of ``brows``
    (the ops.py wrapper pads with self-pointing zero-block rows).
    """
    nbr, deg = indices.shape
    bs = blocks.shape[-1]
    assert nbr % brows == 0, (nbr, brows)
    xb = x.reshape(nbr, bs)
    kern = functools.partial(_spmv_kernel, brows=brows)
    resident = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    y = pl.pallas_call(
        kern,
        grid=(nbr // brows,),
        in_specs=[resident(indices.shape), resident(blocks.shape),
                  resident(xb.shape)],
        out_specs=pl.BlockSpec((brows, bs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbr, bs), x.dtype),
        interpret=interpret,
    )(indices, blocks, xb)
    return y.reshape(x.shape)


def _fused_kernel(ab_ref, idx_ref, blocks_ref, invd_ref, csum_ref, u_ref,
                  p_ref, x_ref, r_ref, xo, ro, uo, po, red_o, *,
                  brows: int):
    j = pl.program_id(0)          # RHS index (batch)
    i = pl.program_id(1)          # block-row tile index
    base = i * brows
    acc = red_o.dtype
    alpha = ab_ref[0, 0]
    beta = ab_ref[0, 1]

    idx_all = idx_ref[...]                           # (nbr, deg)
    blk_all = blocks_ref[...].astype(acc)            # (nbr, deg, bs, bs)
    invd_all = invd_ref[...].astype(acc)             # (nbr, bs)
    # the RHS block is already selected by the BlockSpec index map; load
    # leading index 0 within the block (j only names the grid position)
    del j
    u_all = u_ref[0].astype(acc)                     # (nbr, bs)
    p_all = p_ref[0].astype(acc)
    # stage 1 everywhere: p' = u + beta p (vector-sized, VMEM-resident)
    pp_all = u_all + beta * p_all

    take_rows = lambda a: jax.lax.dynamic_slice_in_dim(a, base, brows, 0)
    idx_t = take_rows(idx_all)                       # (brows, deg)
    blk_t = take_rows(blk_all)                       # (brows, deg, bs, bs)

    # stage 2 at the tile rows: s' = A p', q' = diag^-1 s'
    pp1 = jnp.take(pp_all, idx_t, axis=0)            # (brows, deg, bs)
    s_t = jnp.einsum("rdij,rdj->ri", blk_t, pp1)     # (brows, bs)
    # stage 2/3 at the tile's block COLUMNS (level-2 index chain): w' = A u'
    # needs u' at columns c = idx_t[r, d], and u'(c) needs s'(c) there
    idx2 = jnp.take(idx_all, idx_t, axis=0)          # (brows, deg, deg)
    pp2 = jnp.take(pp_all, idx2, axis=0)             # (brows, deg, deg, bs)
    blk2 = jnp.take(blk_all, idx_t, axis=0)          # (brows, deg, deg, bs, bs)
    s_cols = jnp.einsum("rdeij,rdej->rdi", blk2, pp2)
    invd_cols = jnp.take(invd_all, idx_t, axis=0)
    u_cols = jnp.take(u_all, idx_t, axis=0)
    u2_cols = u_cols - alpha * invd_cols * s_cols    # u' at the columns

    # stage 4: w' = A u' on the tile rows
    w2 = jnp.einsum("rdij,rdj->ri", blk_t, u2_cols)  # (brows, bs)

    # tile-level updates
    pp_t = take_rows(pp_all)
    u2 = take_rows(u_all) - alpha * take_rows(invd_all) * s_t
    x2 = x_ref[0].astype(acc) + alpha * pp_t
    r2 = r_ref[0].astype(acc) - alpha * s_t

    xo[0] = x2.astype(xo.dtype)
    ro[0] = r2.astype(ro.dtype)
    uo[0] = u2.astype(uo.dtype)
    po[0] = pp_t.astype(po.dtype)

    @pl.when(i == 0)
    def _init():
        red_o[...] = jnp.zeros_like(red_o)

    red_o[0, 0] += jnp.sum(r2 * u2)
    red_o[0, 1] += jnp.sum(w2 * u2)
    red_o[0, 2] += jnp.sum(r2 * r2)
    red_o[0, 3] += jnp.sum(r2 * w2)
    red_o[0, 4] += jnp.sum(w2 * w2)
    c_t = csum_ref[pl.ds(base, brows), :].astype(acc)
    red_o[0, 5] += jnp.sum(w2) - jnp.sum(c_t * u2)


def pipecg_bsr_fused(indices: jnp.ndarray, blocks: jnp.ndarray,
                     inv_diag: jnp.ndarray, csum: jnp.ndarray,
                     x, r, u, p, alpha, beta, *,
                     brows: int = DEFAULT_BROWS, interpret: bool = False
                     ) -> Tuple[jnp.ndarray, ...]:
    """One full preconditioned PIPECG iteration on a blocked-ELL operator.

    Vectors are (k, n) — k right-hand sides over the leading grid
    dimension — with ``n = nbr * bs``; ``alpha`` / ``beta`` are (k,).
    ``inv_diag`` / ``csum`` are (n,) (``csum`` = the ABFT column sums
    c = A^T 1, computed by the caller BEFORE any storage demotion).
    ``nbr`` must be a multiple of ``brows`` (the ops.py wrapper pads).

    Returns (x', r', u', p', red) with red (k, 6) laid out exactly like
    the DIA sweep's reduction row (see kernels/pipecg_spmv_fused.py).
    """
    k_rhs, n = x.shape
    nbr, deg = indices.shape
    bs = blocks.shape[-1]
    assert n == nbr * bs, (n, nbr, bs)
    assert nbr % brows == 0, (nbr, brows)
    dt = x.dtype
    blk = lambda v: v.reshape(v.shape[:-1] + (nbr, bs))
    ab = jnp.stack([jnp.asarray(alpha, dt), jnp.asarray(beta, dt)],
                   axis=-1).reshape(k_rhs, 2)
    kern = functools.partial(_fused_kernel, brows=brows)
    resident = lambda shape: pl.BlockSpec(shape,
                                          lambda j, i: (0,) * len(shape))
    vec_spec = pl.BlockSpec((1, brows, bs), lambda j, i: (j, i, 0))
    xb, rb, ub, pb = blk(x), blk(r), blk(u), blk(p)
    outs = pl.pallas_call(
        kern,
        grid=(k_rhs, nbr // brows),
        in_specs=[
            pl.BlockSpec((1, 2), lambda j, i: (j, 0)),        # alpha/beta
            resident(indices.shape),
            resident(blocks.shape),
            resident((nbr, bs)),                              # diag^-1
            resident((nbr, bs)),                              # c = A^T 1
            pl.BlockSpec((1, nbr, bs), lambda j, i: (j, 0, 0)),  # u
            pl.BlockSpec((1, nbr, bs), lambda j, i: (j, 0, 0)),  # p
            vec_spec,                                         # x
            vec_spec,                                         # r
        ],
        out_specs=[vec_spec] * 4 + [pl.BlockSpec((1, NRED),
                                                 lambda j, i: (j, 0))],
        out_shape=[jax.ShapeDtypeStruct((k_rhs, nbr, bs), dt),
                   jax.ShapeDtypeStruct((k_rhs, nbr, bs), r.dtype),
                   jax.ShapeDtypeStruct((k_rhs, nbr, bs), u.dtype),
                   jax.ShapeDtypeStruct((k_rhs, nbr, bs), p.dtype),
                   jax.ShapeDtypeStruct((k_rhs, NRED), dt)],
        interpret=interpret,
    )(ab, indices, blocks, inv_diag.reshape(nbr, bs),
      csum.reshape(nbr, bs), ub, pb, xb, rb)
    x2, r2, u2, p2, red = outs
    flat = lambda v: v.reshape(k_rhs, n)
    return flat(x2), flat(r2), flat(u2), flat(p2), red
