"""Pallas TPU kernel: a WHOLE pipelined BiCGStab iteration in one sweep.

``core/krylov/bicgstab.py::pipebicgstab`` carries the state
``(x, r, w, t, pa, a, c)`` plus the fixed shadow residual ``r_hat`` and
derives every scalar (alpha, beta, omega) from ONE (6, 6) Gram reduction
per iteration.  Given those three scalars, the whole vector body —

    p  = r + beta pa          s  = w + beta a        z  = t + beta c
    v  = A z                                          (SpMV 1)
    q  = r - alpha s          y  = w - alpha z
    x' = x + alpha p + omega q
    r' = q - omega y          w' = y - omega (t - alpha v)
    t' = A w'                                         (SpMV 2)
    pa' = p - omega s         a' = s - omega z        c' = z - omega v
    gram = C C^T,  C = [r', w', t', a', c', r_hat]

— is a single HBM pass: the chain ``z -> v -> w' -> t'`` is re-derived
in-register per tile with the halo-recompute trick of the PIPECG sweep
(``t``/``c`` reach +-2h, ``w`` +-h), so only the tile rows round-trip HBM.
The Jacobi preconditioner costs NOTHING here: right preconditioning folds
``diag^-1`` into the DIA bands once per solve (loop-invariant), so the
kernel never sees it.  Per iteration the sweep moves

    reads:  x, r, pa, a, r_hat (tiled) + w, t, c (resident, +-2h)
            + bands (resident, +-h) + c = A^T 1 (resident)
    writes: x', r', w', t', pa', a', c'
    ==  (16 + n_bands) n words  ==  19n for tridiagonal operators
    (the +1n over PR 5's 18n is the ABFT column-sum vector; the checksum
    residual itself rides a 7th row of the Gram payload for free)

vs ~(28 + 2 n_bands) n = 34n for the unfused classical chain (2 SpMVs +
4 AXPY updates + 5 dots as separate ops).

Mixed precision: like the PIPECG sweep, the carried chains (r, w, t,
pa, a, c, r_hat) and the resident operator may arrive in a narrower
storage dtype (PrecisionPolicy).  Loads up-cast to x's dtype, all
arithmetic and the Gram partials run there, and only the chain stores
down-cast — at bf16 the sweep is (2 + (14 + n_bands) * 0.5) n = 10.5n
fp32-equivalent words (vs 19n), gated by the
``pipebicgstab_fused_bf16`` row of BENCH_kernels.json.

``pipebicgstab_halo`` is the sharded rendering: the caller passes the 2h
left/right rows of w/t/c received from its ring neighbors
(``lax.ppermute`` inside shard_map) and an operator pre-extended by h
(exchanged once per solve).  The emitted (6, 6) Gram is then a PARTIAL
sum the distributed driver finishes with a deferred psum — the same
split-phase structure as ``pipecg_spmv_halo``, with pad rows masked out
of the Gram partials.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.checksum import dia_column_checksum

DEFAULT_BLOCK = 1024
NBASIS = 6  # Gram basis [r', w', t', a', c', r_hat]
NGRAM = NBASIS + 1  # + ABFT checksum row: gram[6, 0] = 1^T(Aw') - c^T w'


def _kernel(sc_ref, bands_ref, csum_ref, w_ref, t_ref, c_ref, x_ref,
            r_ref, pa_ref, a_ref, rh_ref, xo, ro, wo, to, pao, ao, co,
            gram_o, *, offsets: Sequence[int], halo: int, block: int,
            n_valid: int = None):
    """One tile of the fused p-BiCGStab sweep (see module docstring)."""
    i = pl.program_id(0)
    base = i * block
    h = halo
    # accumulation dtype: loads up-cast here, arithmetic + Gram partials
    # run at it; only the chain stores down-cast to the storage dtype
    acc = gram_o.dtype
    alpha = sc_ref[0]
    beta = sc_ref[1]
    omega = sc_ref[2]

    # resident operands are extended by 2h per side: index 0 == row -2h
    w2 = w_ref[pl.ds(base, block + 4 * h)].astype(acc)
    t2 = t_ref[pl.ds(base, block + 4 * h)].astype(acc)
    c2 = c_ref[pl.ds(base, block + 4 * h)].astype(acc)
    z2 = t2 + beta * c2                      # z on rows [base-2h, ..+2h)

    # v = A z on rows [base-h, base+block+h); bands_ref index 0 == row -h
    v1 = jnp.zeros((block + 2 * h,), acc)
    for k, off in enumerate(offsets):        # static unroll over bands
        bk = bands_ref[k, pl.ds(base, block + 2 * h)].astype(acc)
        v1 = v1 + bk * jax.lax.dynamic_slice_in_dim(
            z2, h + off, block + 2 * h)

    w1 = jax.lax.dynamic_slice_in_dim(w2, h, block + 2 * h)
    t1 = jax.lax.dynamic_slice_in_dim(t2, h, block + 2 * h)
    z1 = jax.lax.dynamic_slice_in_dim(z2, h, block + 2 * h)
    y1 = w1 - alpha * z1                     # y on +-h
    wn1 = y1 - omega * (t1 - alpha * v1)     # w' on +-h

    # t' = A w' on the tile rows
    tn = jnp.zeros((block,), acc)
    for k, off in enumerate(offsets):
        bk = bands_ref[k, pl.ds(base + h, block)].astype(acc)
        tn = tn + bk * jax.lax.dynamic_slice_in_dim(wn1, h + off, block)

    # tile-level updates
    z_t = jax.lax.dynamic_slice_in_dim(z2, 2 * h, block)
    v_t = jax.lax.dynamic_slice_in_dim(v1, h, block)
    w_t = jax.lax.dynamic_slice_in_dim(w2, 2 * h, block)
    y_t = jax.lax.dynamic_slice_in_dim(y1, h, block)
    wn_t = jax.lax.dynamic_slice_in_dim(wn1, h, block)
    r_t = r_ref[:].astype(acc)
    rh_t = rh_ref[:].astype(acc)
    p_t = r_t + beta * pa_ref[:].astype(acc)
    s_t = w_t + beta * a_ref[:].astype(acc)
    q_t = r_t - alpha * s_t
    xn = x_ref[:].astype(acc) + alpha * p_t + omega * q_t
    rn = q_t - omega * y_t
    pan = p_t - omega * s_t
    an = s_t - omega * z_t
    cn = z_t - omega * v_t

    xo[:] = xn.astype(xo.dtype)
    ro[:] = rn.astype(ro.dtype)
    wo[:] = wn_t.astype(wo.dtype)
    to[:] = tn.astype(to.dtype)
    pao[:] = pan.astype(pao.dtype)
    ao[:] = an.astype(ao.dtype)
    co[:] = cn.astype(co.dtype)

    @pl.when(i == 0)
    def _init():
        gram_o[...] = jnp.zeros_like(gram_o)

    # next iteration's fused Gram partials; rows >= n_valid are pad rows
    # whose values may carry halo (neighbor) data — mask them out
    C = jnp.stack([rn, wn_t, tn, an, cn, rh_t])  # (6, block)
    if n_valid is not None:
        rows = base + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
        C = jnp.where(rows < n_valid, C, 0)
    gram_o[:NBASIS, :] += C @ C.T
    # ABFT checksum partial for the in-kernel SpMV t' = A w': the signed
    # residual 1^T(Aw') - c^T w' rides a 7th Gram row through the same
    # (single) psum; |.| is taken after the reduction (C rows are already
    # pad-masked, so tn/wn here are C[2]/C[1]).
    c_tile = csum_ref[pl.ds(base, block)].astype(acc)
    gram_o[NBASIS, 0] += jnp.sum(C[2]) - jnp.sum(c_tile * C[1])


def _sweep(offsets, bands_e, csum, w_e, t_e, c_e, x, r, pa, a, rh,
           scalars, *, halo: int, block: int, n_valid: int = None,
           interpret: bool = False) -> Tuple[jnp.ndarray, ...]:
    """The shared pallas_call: one grid sweep over pre-extended operands.

    ``bands_e`` is extended by ``halo`` rows each side and ``w_e`` /
    ``t_e`` / ``c_e`` by ``2*halo`` — with zeros (single-device path) or
    neighbor rows (sharded path).  ``csum`` (n,) holds the local slice of
    the ABFT column sums c = A^T 1 of the (Jacobi-folded) operator.
    ``scalars`` is the (3,) array ``[alpha, beta, omega]``; ``n_valid``
    (static) masks pad rows out of the Gram partials.
    """
    n = x.shape[0]
    assert n % block == 0, (n, block)
    assert block >= 2 * halo, (block, halo)
    # x and the Gram payload stay at the solve (accumulation) dtype; the
    # carried chains keep whatever storage dtype the caller passes
    dt = x.dtype

    kern = functools.partial(_kernel, offsets=tuple(offsets), halo=halo,
                             block=block, n_valid=n_valid)
    vec_spec = pl.BlockSpec((block,), lambda i: (i,))
    resident = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    outs = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            resident((3,)),                  # alpha / beta / omega
            resident(bands_e.shape),         # bands (+h)
            resident(csum.shape),            # c = A^T 1
            resident(w_e.shape),             # w (+2h)
            resident(t_e.shape),             # t (+2h)
            resident(c_e.shape),             # c (+2h)
            vec_spec,                        # x
            vec_spec,                        # r
            vec_spec,                        # pa
            vec_spec,                        # a
            vec_spec,                        # r_hat
        ],
        out_specs=[vec_spec] * 7 + [resident((NGRAM, NBASIS))],
        out_shape=[jax.ShapeDtypeStruct((n,), dt),
                   jax.ShapeDtypeStruct((n,), r.dtype),
                   jax.ShapeDtypeStruct((n,), w_e.dtype),
                   jax.ShapeDtypeStruct((n,), t_e.dtype),
                   jax.ShapeDtypeStruct((n,), pa.dtype),
                   jax.ShapeDtypeStruct((n,), a.dtype),
                   jax.ShapeDtypeStruct((n,), c_e.dtype),
                   jax.ShapeDtypeStruct((NGRAM, NBASIS), dt)],
        interpret=interpret,
    )(scalars, bands_e, csum, w_e, t_e, c_e, x, r, pa, a, rh)
    return tuple(outs)


def _scalars(alpha, beta, omega, dt) -> jnp.ndarray:
    """Stack the three runtime scalars into the kernel's (3,) operand."""
    return jnp.stack([jnp.asarray(alpha, dt), jnp.asarray(beta, dt),
                      jnp.asarray(omega, dt)])


def pipebicgstab_fused(offsets: Sequence[int], bands: jnp.ndarray,
                       x, r, w, t, pa, a, c, r_hat, alpha, beta, omega, *,
                       block: int = DEFAULT_BLOCK, interpret: bool = False
                       ) -> Tuple[jnp.ndarray, ...]:
    """One full pipelined BiCGStab iteration, single HBM sweep.

    All vectors are (n,) with scalar ``alpha`` / ``beta`` / ``omega``;
    ``bands`` is (n_bands, n) with the (Jacobi-folded) operator.  n must
    be a multiple of ``block`` (the ops.py wrapper pads).  Returns
    ``(x', r', w', t', pa', a', c', gram)`` with ``gram`` (7, 6): rows
    0..5 the Gram matrix of ``[r', w', t', a', c', r_hat]`` — the next
    iteration's fused-reduction payload — and ``gram[6, 0]`` the ABFT
    checksum residual 1^T(Aw') - c^T w' of the in-kernel SpMV.
    """
    halo = max(abs(o) for o in offsets)
    bands_e = jnp.pad(bands, ((0, 0), (halo, halo)))
    csum = dia_column_checksum(offsets, bands)
    w_e = jnp.pad(w, (2 * halo, 2 * halo))
    t_e = jnp.pad(t, (2 * halo, 2 * halo))
    c_e = jnp.pad(c, (2 * halo, 2 * halo))
    return _sweep(offsets, bands_e, csum, w_e, t_e, c_e, x, r, pa, a,
                  r_hat, _scalars(alpha, beta, omega, x.dtype), halo=halo,
                  block=block, interpret=interpret)


def pipebicgstab_halo(offsets: Sequence[int], bands_ext: jnp.ndarray,
                      x, r, w, t, pa, a, c, r_hat,
                      w_lr: Tuple[jnp.ndarray, jnp.ndarray],
                      t_lr: Tuple[jnp.ndarray, jnp.ndarray],
                      c_lr: Tuple[jnp.ndarray, jnp.ndarray],
                      alpha, beta, omega, *,
                      block: int = DEFAULT_BLOCK, interpret: bool = False
                      ) -> Tuple[jnp.ndarray, ...]:
    """Sharded single-sweep p-BiCGStab iteration with neighbor halos.

    Same sweep as :func:`pipebicgstab_fused`, but the extension rows are
    real neighbor data: ``w_lr`` / ``t_lr`` / ``c_lr`` are ``(left,
    right)`` halo rows of width ``2*halo`` per side (this iteration's
    ``lax.ppermute`` payload; chain-boundary shards pass zeros) and
    ``bands_ext`` (n_bands, n + 2*halo) is the operator pre-extended by
    ``halo`` per side, exchanged once per solve.  Pads the row dimension
    to ``block`` internally; pad rows are masked out of the Gram
    partials.  The returned ``gram`` holds this shard's PARTIAL sums —
    the caller must finish them with a ``psum`` over the mesh axis.  The
    checksum row gram[6] tiles exactly: its column sums come from
    ``bands_ext`` (halo=h), the local slice of the GLOBAL c = A^T 1, so
    the psum'd entry is the exact global 1^T(Aw') - c^T w'.
    """
    n = x.shape[0]
    halo = max(abs(o) for o in offsets)
    pad = (-n) % block
    w_l, w_r = w_lr
    t_l, t_r = t_lr
    c_l, c_r = c_lr
    assert w_l.shape == (2 * halo,), (w_l.shape, halo)
    # extension layout: [left halo | local rows | right halo | zero pad] —
    # the pad must come AFTER the right halo so row n-1's stencil still
    # reads the neighbor rows (cf. pipecg_spmv_halo); pads match each
    # carried array's storage dtype so a bf16 policy stays bf16
    ext = lambda l_, v, r_: jnp.concatenate(
        [l_.astype(v.dtype), v, r_.astype(v.dtype),
         jnp.zeros((pad,), v.dtype)])
    w_e = ext(w_l, w, w_r)
    t_e = ext(t_l, t, t_r)
    c_e = ext(c_l, c, c_r)
    bands_p = jnp.pad(bands_ext, ((0, 0), (0, pad)))
    csum = jnp.pad(dia_column_checksum(offsets, bands_ext, halo=halo),
                   (0, pad))
    vecs = [jnp.pad(v, (0, pad)) for v in (x, r, pa, a, r_hat)]
    outs = _sweep(offsets, bands_p, csum, w_e, t_e, c_e, *vecs,
                  _scalars(alpha, beta, omega, x.dtype), halo=halo,
                  block=block, n_valid=(n if pad else None),
                  interpret=interpret)
    if pad:
        outs = tuple(o[:n] for o in outs[:7]) + (outs[7],)
    return outs
