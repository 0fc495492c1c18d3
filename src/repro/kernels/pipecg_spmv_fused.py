"""Pallas TPU kernel: a WHOLE preconditioned PIPECG iteration in one sweep.

``pipecg_fused`` collapses the eight AXPYs + three dots into one HBM pass,
but the iteration still needs two more sweeps: the Jacobi apply
``m = diag(A)^-1 w`` and the DIA SpMV ``n = A m``.  This kernel removes
those too, by exploiting the exact-arithmetic identities of the
Ghysels-Vanroose recurrences

    s_i = A p_i,    q_i = M s_i,    z_i = A q_i,    w_i = A u_i,

so the only state that must round-trip HBM is (x, r, u, p).  Everything
else is re-derived inside the tile sweep:

    p' = u + beta p                                   (tile +-2h)
    s' = A p'                                         (tile +-h)
    q' = diag^-1 s'                                   (tile +-h)
    x' = x + alpha p'      r' = r - alpha s'
    u' = u - alpha q'                                 (tile +-h)
    w' = A u'                                         (tile)
    partials: <r',u'>, <w',u'>, <r',r'>, <r',w'>, <w',w'>,
              1^T w' - c^T u'   (ABFT checksum of the in-kernel SpMV)

The halo recompute duplicates O(halo) flops per tile — free on a
memory-bound kernel.  Every operand is tiled lane-dense as (rows, 128)
(kernels/stencil.py): ``u``, ``p``, the bands and ``diag^-1`` are read
as a window of the tile plus ``hb`` rows on each side, so per iteration
the kernel moves

    reads:  x, r, c = A^T 1 (tile) + u, p, diag^-1, bands (window)
    writes: x', r', u', p'
    ==  (10 + n_bands) n words  ==  13n for the tridiagonal ex23 operator
    plus the window overlap, (4 + n_bands) * 2 hb * 128 words per tile
    (the +1n over PR 5's 12n is the ABFT column-sum vector; the checksum
    residual itself rides the existing reduction row for free)

vs ~38n for the unfused chain (8 AXPYs x 3 + 3 dots x 2 + M-apply x 3 +
SpMV x 5).  A leading multi-RHS grid dimension batches k right-hand sides
over the same operator.  The rows just outside the vector come from a
small edge operand (zeros here), so no padded copy of u or p is made.

The reduction partials feed BOTH inner-product modes: CG-style (ip='id':
gamma=<r,u>, delta=<w,u>) and CR-style (ip='A': gamma=<r,w>, delta=<w,w>).

Mixed precision (PrecisionPolicy, core/krylov/options.py): the carried
r/u/p and the resident operator (bands, diag^-1, c = A^T 1) may arrive
in a narrower STORAGE dtype (bf16, fp8-e4m3).  Every load is up-cast to
the accumulation dtype (x's dtype — x and the reduction row red never
down-cast), all in-kernel arithmetic runs at that precision, and only
the r'/u'/p' stores down-cast back.  At bf16 storage the sweep above
shrinks to  x(1) + r(.5) reads + x(1) + r/u/p(1.5) writes  +  resident
u/p(1) + bands(1.5) + diag^-1(.5) + c(.5)  ==  7.5n fp32-equivalent
words for the tridiagonal operator (vs 13n) — measured and gated by the
``pipecg_spmv_fused_bf16`` row of BENCH_kernels.json.

``pipecg_spmv_halo`` is the sharded rendering of the same sweep: instead
of zero edge rows, the caller passes the 2h left/right rows of u/p
received from its ring neighbors (``lax.ppermute`` inside shard_map) and
an operator (bands, diag^-1) pre-extended by h with the neighbors' rows —
loop-invariant, exchanged and split (``halo_operator``) once per solve.
The kernel body is identical; only the provenance of the edge rows
differs, so one local iteration (updates + Jacobi + DIA SpMV + partial
dots) still costs one HBM pass per shard, and the emitted reduction row is a PARTIAL sum the distributed
driver finishes with a deferred psum (split-phase, see
core/krylov/distributed.py).  When the local row count is padded to the
block size, halo rows leak real (neighbor) values into the pad region, so
the kernel masks rows >= n_valid out of the reduction partials.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import stencil
from repro.kernels.checksum import dia_column_checksum
from repro.kernels.stencil import LANE

DEFAULT_BLOCK = 1024
NRED = 6  # <r,u>, <w,u>, <r,r>, <r,w>, <w,w>, ABFT 1^T(Au') - c^T u'


def _kernel(ab_ref, *refs, offsets: Sequence[int], hb: int,
            n_valid: int = None):
    (b_c, b_l, b_r, b_e, d_c, d_l, d_r, d_e, u_c, u_l, u_r, u_e,
     p_c, p_l, p_r, p_e, csum_ref, x_ref, r_ref,
     xo, ro, uo, po, red_o) = refs
    j = pl.program_id(0)          # RHS index (batch)
    i = pl.program_id(1)          # tile index
    rows = x_ref.shape[1]
    # accumulation dtype: every load is up-cast here and all arithmetic,
    # reduction partials and x ride at this precision; only the r/u/p
    # stores down-cast back to the carried storage dtype (bf16/fp8 under
    # a PrecisionPolicy, == acc on the default fp32/fp64 path)
    acc = red_o.dtype
    alpha = ab_ref[j, 0].astype(acc)
    beta = ab_ref[j, 1].astype(acc)
    win = lambda refs4, k: stencil.window(refs4, k, hb, acc)
    mid = lambda v: v[hb:hb + rows]   # the tile's own rows of a window

    # every stage runs on the window [tile - hb rows, tile + hb rows);
    # each stencil leaves ceil(h/128) garbage rows at both ends, which
    # the 2-stencil chain keeps inside the hb halo rows
    u_w = win((u_c, u_l, u_r, u_e), 0)
    p2 = u_w + beta * win((p_c, p_l, p_r, p_e), 0)        # p' = u + beta p
    s2 = jnp.zeros_like(p2)
    for k, off in enumerate(offsets):  # static unroll over bands
        s2 = s2 + win((b_c, b_l, b_r, b_e), k) * stencil.shift(p2, off)
    q2 = win((d_c, d_l, d_r, d_e), 0) * s2                # q' = diag^-1 s'
    u2 = u_w - alpha * q2                                 # u' = u - alpha q'
    w2 = jnp.zeros((rows, LANE), acc)                     # w' = A u' (tile)
    for k, off in enumerate(offsets):
        w2 = w2 + b_c[k].astype(acc) * mid(stencil.shift(u2, off))

    p2, s2, u2 = mid(p2), mid(s2), mid(u2)
    x2 = x_ref[0].astype(acc) + alpha * p2
    r2 = r_ref[0].astype(acc) - alpha * s2
    xo[0] = x2.astype(xo.dtype)
    ro[0] = r2.astype(ro.dtype)
    uo[0] = u2.astype(uo.dtype)
    po[0] = p2.astype(po.dtype)

    @pl.when(i == 0)
    def _init():
        red_o[...] = jnp.zeros_like(red_o)

    # next iteration's fused reduction partials; rows >= n_valid are pad
    # rows whose values may carry halo (neighbor) data — mask them out
    if n_valid is not None:
        idx = ((i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0))
               * LANE + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1))
        keep = idx < n_valid
        r2, u2, w2 = (jnp.where(keep, v, 0) for v in (r2, u2, w2))
    # ABFT checksum partial for the in-kernel SpMV w' = A u': the signed
    # residual 1^T(Au') - c^T u' with c = A^T 1 (kernels/checksum.py).
    # Rounding-level when the sweep executed faithfully, O(corruption)
    # otherwise; the consumer takes |.| after finishing the psum.
    c_t = csum_ref[0].astype(acc)
    lanes = lambda v: jnp.sum(v, axis=0, keepdims=True)
    red_o[0] += stencil.partials_tile(
        [lanes(r2 * u2), lanes(w2 * u2), lanes(r2 * r2), lanes(r2 * w2),
         lanes(w2 * w2), lanes(w2 - c_t * u2)], acc)


def _ab(alpha, beta, k_rhs, dt):
    """Stack per-RHS scalars into the kernel's (k, 2) operand."""
    ab = jnp.stack([jnp.asarray(alpha, dt), jnp.asarray(beta, dt)], axis=-1)
    return ab.reshape(k_rhs, 2)


def halo_rows(offsets: Sequence[int], *dtypes) -> int:
    """Window rows per side of the sweep for this operator and dtypes.

    The chain p' -> s' = A p' -> w' = A u' applies the stencil twice.
    """
    halo = max(abs(o) for o in offsets)
    return stencil.halo_rows(halo, 2, stencil.sublanes(*dtypes))


# window-sized values the kernel body holds at its peak, at the
# accumulation dtype: u, p', s', one band's window, the shifted p' with
# the two rolls and the lane select that build it, and their product
WINDOW_TEMPS = 8


def sweep_vmem_bytes(rows: int, hb: int, n_bands: int, acc, storage,
                     operator) -> int:
    """Scoped VMEM of one grid step of :func:`_sweep` at ``rows`` rows.

    Every input and output block is double-buffered.  The windowed
    operands (the bands, diag^-1, u and p) each bring a centre block,
    two ``hb``-row halo blocks and the ``2*hb``-row edge operand; c, x
    and r one tile each; the outputs x', r', u', p' one tile each and
    the reduction row.  On top come ``WINDOW_TEMPS`` values of the
    kernel body over the ``rows + 2*hb`` window.  ``acc`` is x's dtype,
    ``storage`` that of r, u and p, ``operator`` that of the bands,
    diag^-1 and c.  The v5e's Mosaic compile takes every block this
    counts within its default scoped VMEM (tests/test_tpu_compile.py).
    """
    a, s, o = (jnp.dtype(d).itemsize for d in (acc, storage, operator))
    windowed = ((n_bands + 1) * o + 2 * s) * (rows + 4 * hb)
    tiles = (o + a + s) * rows
    outs = (a + 3 * s) * rows + a * stencil.RED_ROWS
    temps = WINDOW_TEMPS * a * (rows + 2 * hb)
    return LANE * (2 * (windowed + tiles + outs) + temps)


def _sweep(offsets, bands, invd, csum, u, p, x, r, ab, edges, *, block: int,
           n_valid: int = None, interpret: bool = False
           ) -> Tuple[jnp.ndarray, ...]:
    """The shared pallas_call: one grid sweep over (k, n) vectors.

    ``bands`` (n_bands, n), ``invd`` / ``csum`` (n,), ``u`` / ``p`` /
    ``x`` / ``r`` (k, n).  ``edges`` = (bands, invd, u, p) edge operands
    (stencil.edges): the rows just outside the vector, zeros on one
    device or the neighbors' rows on a shard.  ``n_valid`` (static)
    masks pad rows out of the reduction partials; None means every row
    is valid.
    """
    k_rhs, n = x.shape
    nb = bands.shape[0]
    hb = edges[2].shape[1] // 2
    assert n % block == 0 and block % (hb * LANE) == 0, (n, block, hb)
    rows, n_rows = block // LANE, n // LANE
    # x and the reduction row stay at the solve (accumulation) dtype;
    # r/u/p keep whatever storage dtype the caller carries them in
    dt = x.dtype

    kern = functools.partial(_kernel, offsets=tuple(offsets), hb=hb,
                             n_valid=n_valid)
    win = functools.partial(stencil.window_specs, rows=rows, hb=hb,
                            n_rows=n_rows)
    tile = lambda lead, batched: pl.BlockSpec(
        (lead, rows, LANE),
        (lambda j, i: (j, i, 0)) if batched else (lambda j, i: (0, i, 0)))
    rowsv = stencil.as_rows
    args = (ab, *[rowsv(bands)] * 3, edges[0], *[rowsv(invd)] * 3, edges[1],
            *[rowsv(u)] * 3, edges[2], *[rowsv(p)] * 3, edges[3],
            rowsv(csum), rowsv(x), rowsv(r))
    outs = pl.pallas_call(
        kern,
        grid=(k_rhs, n // block),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]       # alpha/beta
        + win(nb, batched=False) + win(1, batched=False)       # bands, diag^-1
        + win(1, batched=True) + win(1, batched=True)          # u, p
        + [tile(1, False), tile(1, True), tile(1, True)],      # c, x, r
        out_specs=[tile(1, True)] * 4
        + [pl.BlockSpec((1, stencil.RED_ROWS, LANE), lambda j, i: (j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((k_rhs, n_rows, LANE), dt),
                   jax.ShapeDtypeStruct((k_rhs, n_rows, LANE), r.dtype),
                   jax.ShapeDtypeStruct((k_rhs, n_rows, LANE), u.dtype),
                   jax.ShapeDtypeStruct((k_rhs, n_rows, LANE), p.dtype),
                   jax.ShapeDtypeStruct((k_rhs, stencil.RED_ROWS, LANE), dt)],
        # x' and r' overwrite x and r: each is read tile by tile, so a
        # solve loop keeps them in place instead of copying them back into
        # its carry (u and p are read as windows across tiles: not these)
        input_output_aliases={len(args) - 2: 0, len(args) - 1: 1},
        interpret=interpret,
    )(*args)
    vecs = tuple(o.reshape(k_rhs, n) for o in outs[:4])
    return vecs + (jnp.sum(outs[4][:, :NRED], axis=-1),)


def pipecg_spmv_fused(offsets: Sequence[int], op: "HaloOperator", x, r, u, p,
                      alpha, beta, *, block: int, interpret: bool = False
                      ) -> Tuple[jnp.ndarray, ...]:
    """One full preconditioned PIPECG iteration, single HBM sweep.

    All vectors are (k, n) — k right-hand sides batched over the leading
    grid dimension; ``alpha`` / ``beta`` are (k,).  ``op`` is the
    operator split by :func:`local_operator` for this ``block``, once
    per solve, and shared across the batch.  When the sweep is longer
    than n, the vectors are zero-padded to it; zero rows stay zero and
    add nothing to the partials.

    Returns (x', r', u', p', red) with red (k, 6) =
    (<r',u'>, <w',u'>, <r',r'>, <r',w'>, <w',w'>, chk) per RHS, where
    chk = 1^T(Au') - c^T u' is the ABFT checksum residual of the
    in-kernel SpMV (rounding-level unless the sweep was corrupted).
    """
    k_rhs, n = x.shape
    hb = op.bands_edges.shape[1] // 2
    pad = op.bands.shape[1] * LANE - n
    if pad:
        x, r, u, p = (jnp.pad(v, ((0, 0), (0, pad))) for v in (x, r, u, p))
    edges = (op.bands_edges, op.invd_edges,
             stencil.zero_edges(k_rhs, hb, u.dtype),
             stencil.zero_edges(k_rhs, hb, p.dtype))
    outs = _sweep(offsets, op.bands, op.invd, op.csum, u, p, x, r,
                  _ab(alpha, beta, k_rhs, x.dtype), edges, block=block,
                  interpret=interpret)
    return tuple(o[:, :n] for o in outs[:4]) + (outs[4],)


class HaloOperator(NamedTuple):
    """The loop-invariant operand of :func:`pipecg_spmv_halo`.

    Built once per solve by :func:`halo_operator` (a shard) or
    :func:`local_operator` (one device), so no iteration
    slices, pads, re-tiles or re-sums the operator.  ``bands``, ``invd``
    and ``csum`` are in row layout (stencil.as_rows: (lead, m / 128,
    128), lead n_bands, 1 and 1) and span the sweep's m rows: the local
    n rows, and on a padded sweep (m > n) the right halo rows and zeros
    after them.  ``bands_edges`` / ``invd_edges`` are the edge operands
    (stencil.edges) of the rows just outside the sweep.
    """

    bands: jnp.ndarray
    invd: jnp.ndarray
    csum: jnp.ndarray
    bands_edges: jnp.ndarray
    invd_edges: jnp.ndarray


def halo_operator(offsets: Sequence[int], bands_ext: jnp.ndarray,
                  invd_ext: jnp.ndarray, *, block: int, hb: int
                  ) -> HaloOperator:
    """Split the halo-extended operator for sweeps of ``block`` rows.

    ``bands_ext`` (n_bands, n + 2*halo) / ``invd_ext`` (n + 2*halo,) are
    the local operator rows pre-extended by ``halo`` per side with the
    neighbors' values.  ``block`` must be a legal block for ``hb``
    window rows (stencil.legal_block).  When n is not a multiple of it
    the sweep is padded: [local rows | right halo | zeros], so row n-1's
    stencil reads the neighbor rows at n..n+2h-1 and the right edge
    operand is empty.  The column sums are taken from ``bands_ext``
    (halo=h), i.e. the local slice of the GLOBAL c = A^T 1 including
    neighbor-row contributions, so the psum of the per-shard checksum
    partials reproduces the exact global checksum residual.
    """
    halo = max(abs(o) for o in offsets)
    n = bands_ext.shape[-1] - 2 * halo
    assert block % (hb * LANE) == 0, (block, hb)
    csum = dia_column_checksum(offsets, bands_ext, halo=halo)
    bands_l, bands_r = bands_ext[:, :halo], bands_ext[:, n + halo:]
    invd_l, invd_r = invd_ext[None, :halo], invd_ext[None, n + halo:]
    bands, invd = bands_ext[:, halo:n + halo], invd_ext[None, halo:n + halo]
    if n % block:
        n_pad = -(-(n + 2 * halo) // block) * block
        ext = lambda v, right: jnp.concatenate(
            [v, right, jnp.zeros(v.shape[:-1] + (n_pad - n - right.shape[-1],),
                                 v.dtype)], axis=-1)
        bands, invd = ext(bands, bands_r), ext(invd, invd_r)
        csum = jnp.pad(csum, (0, n_pad - n))
        bands_r, invd_r = bands_r[:, :0], invd_r[:, :0]
    rows = stencil.as_rows
    return HaloOperator(rows(bands), rows(invd), rows(csum),
                        stencil.edges(bands_l, bands_r, hb),
                        stencil.edges(invd_l, invd_r, hb))


def local_operator(offsets: Sequence[int], bands: jnp.ndarray,
                   inv_diag: jnp.ndarray, *, block: int, hb: int
                   ) -> HaloOperator:
    """One device's operator, split for sweeps of ``block`` rows.

    ``bands`` (n_bands, n) / ``inv_diag`` (n,) are the whole matrix, so
    the rows outside it are zero: :func:`halo_operator` of the operator
    extended by zero halos.
    """
    halo = max(abs(o) for o in offsets)
    return halo_operator(offsets, jnp.pad(bands, ((0, 0), (halo, halo))),
                         jnp.pad(inv_diag, (halo, halo)), block=block, hb=hb)


def pipecg_spmv_halo(offsets: Sequence[int], op: HaloOperator, x, r, u, p,
                     u_lr: Tuple[jnp.ndarray, jnp.ndarray],
                     p_lr: Tuple[jnp.ndarray, jnp.ndarray], alpha, beta, *,
                     block: int, interpret: bool = False
                     ) -> Tuple[jnp.ndarray, ...]:
    """Sharded single-sweep PIPECG iteration with neighbor-supplied halos.

    Same sweep as :func:`pipecg_spmv_fused`, but the edge rows are
    real neighbor data instead of zeros:

    * ``u_lr`` / ``p_lr``: ``(left, right)`` halo rows of width ``2*halo``
      per side, shaped (k, 2*halo) — the ``lax.ppermute`` payload of this
      iteration (chain-boundary shards pass zeros, matching the global
      zero extension of the DIA bands).
    * ``op``: the operator split by :func:`halo_operator` for this
      ``block``, once per solve.

    When the operator's sweep is padded past n, u and p get the same
    [rows | right halo | zeros] layout, and pad rows are masked out of
    the reduction partials (they see halo data, not zeros).  The
    returned ``red`` (k, 6) holds this shard's PARTIAL sums — the caller
    must finish them with a ``psum`` over the mesh axis, the checksum
    entry red[:, 5] included.
    """
    k_rhs, n = x.shape
    halo = max(abs(o) for o in offsets)
    u_l, u_r = u_lr
    p_l, p_r = p_lr
    assert u_l.shape == (k_rhs, 2 * halo), (u_l.shape, k_rhs, halo)
    hb = op.bands_edges.shape[1] // 2
    assert hb == halo_rows(offsets, op.bands.dtype, op.invd.dtype, x.dtype,
                           r.dtype, u.dtype, p.dtype), hb
    # pads match each carried array's storage dtype so a bf16 policy
    # stays bf16 end to end
    u_l, u_r, p_l, p_r = (v.astype(w.dtype)
                          for v, w in ((u_l, u), (u_r, u), (p_l, p), (p_r, p)))
    n_sweep = op.bands.shape[1] * LANE
    n_valid = None
    if n_sweep != n:
        n_valid = n
        ext = lambda v, right: jnp.concatenate(
            [v, right, jnp.zeros(v.shape[:-1]
                                 + (n_sweep - n - right.shape[-1],), v.dtype)],
            axis=-1)
        u, p = ext(u, u_r), ext(p, p_r)
        x, r = (jnp.pad(v, ((0, 0), (0, n_sweep - n))) for v in (x, r))
        u_r, p_r = u_r[:, :0], p_r[:, :0]
    edges = (op.bands_edges, op.invd_edges,
             stencil.edges(u_l, u_r, hb), stencil.edges(p_l, p_r, hb))
    outs = _sweep(offsets, op.bands, op.invd, op.csum, u, p, x, r,
                  _ab(alpha, beta, k_rhs, x.dtype), edges, block=block,
                  n_valid=n_valid, interpret=interpret)
    if n_valid is not None:
        outs = tuple(o[:, :n] for o in outs[:4]) + (outs[4],)
    return outs


# ---------------------------------------------------------------------------
# Depth-l ghost-chain sweep (the l-deep pipelined solvers, pipecg_l)
# ---------------------------------------------------------------------------
#
# Depth-l pipelining (core/krylov/pipeline.py) trades the per-iteration
# fused reduction for ONE Gram reduction per l iterations: each block
# builds the theta-scaled ghost basis
#
#     C = [p, Ãp, ..., Ã^l p, r, Ãr, ..., Ã^{l-1} r],   Ã = A / theta,
#
# and the single (2l+1, 2l+1) Gram matrix G = C C^T carries ALL the
# reduction rows the l coefficient-space CG steps consume — one psum in
# flight per depth-l block where the depth-1 solver keeps one per
# iteration.  The kernel below produces the whole chain AND the Gram
# partials in one HBM sweep: each tile loads p and r once with an
# l*halo extension and re-derives every chain link in-register (the same
# halo-recompute trick as the single-sweep iteration kernel, reaching
# l*halo instead of 2*halo), so per block the kernel moves
#
#     reads:  p, r (resident, +l*h)  + bands (resident, +l*h)
#     writes: the 2l+1 chain rows
#   ==  (2l + 3 + n_bands) n words per l iterations
#   ==  (2 + (3 + n_bands)/l) n words per iteration  ->  5n at l=2,
#       3.5n at l=4 for the tridiagonal ex23 operator (vs 12n for the
#       depth-1 single sweep; the block-end reconstruction x/r/p += C^T c
#       adds (2l+7)n per block, so end-to-end ~9.5n (l=2) / ~6.8n (l=4)).
#
# ``ghost_chain_halo`` is the sharded rendering: the caller ppermutes ONE
# l*halo-wide edge strip of p and r per block (depth-l amortizes message
# count as well as reduction count) and passes the operator rows
# pre-extended by l*halo once per solve; pad rows are masked out of the
# Gram partials exactly like the single-sweep kernel's n_valid mask.

def _chain_kernel(th_ref, bands_ref, p_ref, r_ref, chain_o, gram_o, *,
                  offsets: Sequence[int], halo: int, block: int, l: int,
                  n_valid: int = None):
    """One tile of the ghost-chain sweep: all 2l+1 links + Gram partials."""
    i = pl.program_id(0)
    base = i * block
    H = l * halo                  # extension reach consumed by the chain
    # Gram partials fix the accumulation dtype; p/r/bands loads up-cast
    # to it and only the chain store down-casts to the storage dtype
    acc = gram_o.dtype
    th_inv = th_ref[0]            # 1/theta (runtime scalar)

    def links(ref, depth):
        # a_j[q] = (Ã^j v)[base - (H - j*h) + q]; refs are +H extended so
        # index 0 == global row -H and global row g sits at index g + H
        a = ref[pl.ds(base, block + 2 * H)].astype(acc)
        out = [jax.lax.dynamic_slice_in_dim(a, H, block)]
        for j in range(1, depth + 1):
            nxt = jnp.zeros((block + 2 * (H - j * halo),), acc)
            bk_rows = pl.ds(base + j * halo, block + 2 * (H - j * halo))
            for k, off in enumerate(offsets):
                bk = bands_ref[k, bk_rows].astype(acc)
                nxt = nxt + bk * jax.lax.dynamic_slice_in_dim(
                    a, halo + off, block + 2 * (H - j * halo))
            a = nxt * th_inv
            out.append(jax.lax.dynamic_slice_in_dim(a, H - j * halo, block))
        return out

    rows = links(p_ref, l) + links(r_ref, l - 1)   # 2l+1 tile rows
    C = jnp.stack(rows)                            # (2l+1, block)
    chain_o[:, :] = C.astype(chain_o.dtype)

    @pl.when(i == 0)
    def _init():
        gram_o[...] = jnp.zeros_like(gram_o)

    if n_valid is not None:   # mask pad rows out of the Gram partials
        gr = base + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
        C = jnp.where(gr < n_valid, C, 0)
    gram_o[:, :] += C @ C.T


def _chain_sweep(offsets, bands_e, p_e, r_e, theta, *, halo: int, block: int,
                 l: int, n: int, n_valid: int = None,
                 interpret: bool = False, accum_dtype=None):
    """Shared pallas_call for the ghost-chain sweep over +l*halo operands.

    ``accum_dtype`` fixes the Gram (and in-kernel arithmetic) dtype when
    the chain is carried in a narrower storage dtype; it defaults to the
    chain dtype promoted to at least float32.
    """
    assert n % block == 0, (n, block)
    H = l * halo
    assert block >= 2 * H, (block, H)
    m = 2 * l + 1
    dt = p_e.dtype
    acc = (jnp.dtype(accum_dtype) if accum_dtype is not None
           else jnp.promote_types(dt, jnp.float32))
    kern = functools.partial(_chain_kernel, offsets=tuple(offsets), halo=halo,
                             block=block, l=l, n_valid=n_valid)
    resident = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    chain, gram = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            resident((1,)),                 # 1/theta
            resident(bands_e.shape),        # bands (+l*h)
            resident(p_e.shape),            # p (+l*h)
            resident(r_e.shape),            # r (+l*h)
        ],
        out_specs=[pl.BlockSpec((m, block), lambda i: (0, i)),
                   resident((m, m))],
        out_shape=[jax.ShapeDtypeStruct((m, n), dt),
                   jax.ShapeDtypeStruct((m, m), acc)],
        interpret=interpret,
    )(jnp.reshape(1.0 / jnp.asarray(theta, acc), (1,)), bands_e, p_e, r_e)
    return chain, gram


def ghost_chain_fused(offsets: Sequence[int], bands: jnp.ndarray, p, r,
                      theta, l: int, *, block: int = DEFAULT_BLOCK,
                      interpret: bool = False, accum_dtype=None):
    """Depth-l ghost basis + Gram partials in one sweep (zero extensions).

    ``p`` / ``r`` are (n,); returns ``(chain, gram)`` with ``chain``
    (2l+1, n) = [p, Ãp, .., Ã^l p, r, Ãr, .., Ã^{l-1} r] for the
    theta-scaled operator Ã = A/theta, and ``gram`` (2l+1, 2l+1) the full
    Gram matrix C C^T — the block's single fused reduction payload.
    """
    n = p.shape[-1]
    halo = max(abs(o) for o in offsets)
    H = l * halo
    bands_e = jnp.pad(bands, ((0, 0), (H, H)))
    p_e = jnp.pad(p, (H, H))
    r_e = jnp.pad(r, (H, H))
    return _chain_sweep(offsets, bands_e, p_e, r_e, theta, halo=halo,
                        block=block, l=l, n=n, interpret=interpret,
                        accum_dtype=accum_dtype)


def ghost_chain_halo(offsets: Sequence[int], bands_ext: jnp.ndarray, p, r,
                     p_lr: Tuple[jnp.ndarray, jnp.ndarray],
                     r_lr: Tuple[jnp.ndarray, jnp.ndarray], theta, l: int, *,
                     block: int = DEFAULT_BLOCK, interpret: bool = False,
                     accum_dtype=None):
    """Sharded ghost-chain sweep with neighbor-supplied l*halo extensions.

    ``p_lr`` / ``r_lr`` are ``(left, right)`` strips of width ``l*halo``
    (the ONE ppermute payload of the whole depth-l block); ``bands_ext``
    is (n_bands, n + 2*l*halo), pre-extended once per solve.  Pad rows are
    masked out of the Gram partials; the returned ``gram`` holds this
    shard's PARTIAL sums (the caller psums them — one collective per l
    iterations).
    """
    n = p.shape[-1]
    halo = max(abs(o) for o in offsets)
    H = l * halo
    pad = (-n) % block
    p_l, p_r = p_lr
    r_l, r_r = r_lr
    assert p_l.shape == (H,), (p_l.shape, H)
    zpad = jnp.zeros((pad,), p.dtype)
    # pad AFTER the right halo, as in pipecg_spmv_halo
    p_e = jnp.concatenate([p_l, p, p_r, zpad])
    r_e = jnp.concatenate([r_l, r, r_r, zpad])
    bands_p = jnp.pad(bands_ext, ((0, 0), (0, pad)))
    chain, gram = _chain_sweep(offsets, bands_p, p_e, r_e, theta, halo=halo,
                               block=block, l=l, n=n + pad,
                               n_valid=(n if pad else None),
                               interpret=interpret, accum_dtype=accum_dtype)
    if pad:
        chain = chain[:, :n]
    return chain, gram
