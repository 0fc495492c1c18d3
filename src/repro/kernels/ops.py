"""jit'd dispatch wrappers for the Pallas kernels.

On the CPU backend kernels run in ``interpret=True`` mode — the kernel
body executes in Python/XLA for correctness validation; on TPU the same
calls lower to Mosaic.  Any other backend raises: there is no silent
interpreted fallback off the CPU.  Wrappers pad the row dimension to the
block size so callers never worry about alignment.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_dots as _fd
from repro.kernels import pipebicgstab_fused as _pb
from repro.kernels import pipecg_fused as _pf
from repro.kernels import pipecg_spmv_fused as _ps
from repro.kernels import spmv_dia as _sd
from repro.kernels import ref
from repro.kernels import stencil


def _interpret() -> bool:
    """Interpret mode on the CPU backend, Mosaic on a TPU; nothing else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels lower to Mosaic on a TPU and interpret on the "
        f"CPU; backend {backend!r} has neither")


def _rel_words(dtype, ref_dtype) -> float:
    """Traffic of one ``dtype`` element relative to one ``ref_dtype`` one.

    The autotuner ranks blocks by modeled HBM words; under a mixed
    PrecisionPolicy the carried vectors move ``itemsize(storage) /
    itemsize(accum)`` of the bytes the accumulation dtype would.
    """
    return jnp.dtype(dtype).itemsize / jnp.dtype(ref_dtype).itemsize


def _storage_key(dtype, ref_dtype):
    """Autotune-key marker: the storage dtype when it differs from accum."""
    return jnp.dtype(dtype) if jnp.dtype(dtype) != jnp.dtype(ref_dtype) \
        else None


def _pad_to(x, mult, axis=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnums=(0, 3))
def spmv_dia_ext(offsets: Tuple[int, ...], bands, x_ext, halo: int):
    """Banded SpMV on a halo-extended vector (kernel-backed)."""
    from repro.kernels import autotune

    nb, n = bands.shape
    hb = _sd.halo_rows(offsets, bands.dtype, x_ext.dtype)
    ro = _rel_words(bands.dtype, x_ext.dtype)
    block = autotune.best_block(
        "spmv_dia", n, x_ext.dtype,
        # tiled words/row: y write + x and band reads; x and the bands
        # are also read over the 2*hb halo rows of every tile
        words_per_row=2.0 + nb * ro,
        step_words=2 * hb * stencil.LANE * (1 + nb * ro),
        min_block=hb * stencil.LANE,
        dtype_storage=_storage_key(bands.dtype, x_ext.dtype))
    return _sd.spmv_dia(offsets, bands, x_ext, halo, block=block,
                        interpret=_interpret())


def _bsr_pad(indices, blocks, brows):
    """Pad block rows to a multiple of ``brows`` with self-pointing zeros."""
    nbr, deg = indices.shape
    pad = (-nbr) % brows
    if pad == 0:
        return indices, blocks, 0
    idx_pad = jnp.tile(jnp.arange(nbr, nbr + pad,
                                  dtype=indices.dtype)[:, None], (1, deg))
    indices_p = jnp.concatenate([indices, idx_pad], axis=0)
    blocks_p = jnp.pad(blocks, ((0, pad), (0, 0), (0, 0), (0, 0)))
    return indices_p, blocks_p, pad


@functools.partial(jax.jit, static_argnames=("block",))
def spmv_bsr(indices, blocks, x, block: int = None):
    """Blocked-ELL SpMV ``y = A x`` (kernel-backed, padded).

    ``indices`` (nbr, deg) int32 with self-pointing zero-block pad
    entries, ``blocks`` (nbr, deg, bs, bs), ``x`` (n,) with
    ``n = nbr * bs``.  ``block`` is the tile size in BLOCK ROWS; the
    default comes from the autotuner under the format-extended key.
    """
    from repro.kernels import autotune
    from repro.kernels import spmv_bsr as _sb

    nbr, deg = indices.shape
    bs = blocks.shape[-1]
    if block is None:
        ro = _rel_words(blocks.dtype, x.dtype)
        block = autotune.best_block(
            "spmv_bsr", nbr, x.dtype,
            # tiled words per BLOCK row: y write + gathered x reads at bs
            # words each, blocks at deg*bs^2, int32 ELL indices at deg
            words_per_row=2.0 * bs + (deg * bs * bs) * ro + deg * 0.5,
            resident_words=float(nbr * bs),
            min_block=1, fmt="bsr")
    block = max(min(block, nbr), 1)
    indices_p, blocks_p, pad = _bsr_pad(indices, blocks, block)
    if pad:
        xp = jnp.pad(x, (0, pad * bs))
        y = _sb.spmv_bsr(indices_p, blocks_p, xp, brows=block,
                         interpret=_interpret())
        return y[: nbr * bs]
    return _sb.spmv_bsr(indices, blocks, x, brows=block,
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block",))
def pipecg_bsr_fused_step(indices, blocks, inv_diag, x, r, u, p, alpha,
                          beta, block: int = None):
    """Single-sweep PIPECG iteration on a blocked-ELL (BSR) operator.

    The BSR rendering of :func:`pipecg_spmv_fused_step` — same contract:
    (n,) vectors with scalar alpha/beta or batched (k, n) with (k,);
    returns (x', r', u', p', red) with the shared (k, 6) reduction row
    (5 Gram partials + the ABFT checksum residual, computed from column
    sums taken at the operator's dtype before any storage demotion).
    Pads the block-row dimension with self-pointing zero-block rows,
    which contribute exact zeros to every partial — no mask needed.
    """
    from repro.kernels import autotune
    from repro.kernels import spmv_bsr as _sb
    from repro.kernels.checksum import bsr_column_checksum

    squeeze = x.ndim == 1
    if squeeze:
        x, r, u, p = (v[None] for v in (x, r, u, p))
        alpha = jnp.asarray(alpha)[None]
        beta = jnp.asarray(beta)[None]
    k_rhs = x.shape[0]
    nbr, deg = indices.shape
    bs = blocks.shape[-1]
    if block is None:
        rs = _rel_words(u.dtype, x.dtype)
        ro = _rel_words(blocks.dtype, x.dtype)
        block = autotune.best_block(
            "pipecg_spmv", nbr, x.dtype,
            # tiled words per BLOCK row: x,r reads + x,r,u,p writes
            words_per_row=(2.0 + 4.0 * rs) * bs,
            # once-per-sweep residents: u, p, diag^-1, column sums,
            # blocks and the int32 ELL indices
            resident_words=(2 * rs + 2) * nbr * bs
            + (deg * bs * bs * ro + deg * 0.5) * nbr,
            min_block=1, k_rhs=k_rhs,
            dtype_storage=_storage_key(u.dtype, x.dtype), fmt="bsr")
    block = max(min(block, nbr), 1)
    csum = bsr_column_checksum(indices, blocks)
    indices_p, blocks_p, pad = _bsr_pad(indices, blocks, block)
    if pad:
        invd_p = jnp.pad(inv_diag, (0, pad * bs))
        csum_p = jnp.pad(csum, (0, pad * bs))
        vecs = [jnp.pad(v, ((0, 0), (0, pad * bs))) for v in (x, r, u, p)]
        outs = _sb.pipecg_bsr_fused(indices_p, blocks_p, invd_p, csum_p,
                                    *vecs, alpha, beta, brows=block,
                                    interpret=_interpret())
        outs = tuple(o[:, : nbr * bs] for o in outs[:4]) + (outs[4],)
    else:
        outs = _sb.pipecg_bsr_fused(indices, blocks, inv_diag, csum,
                                    x, r, u, p, alpha, beta, brows=block,
                                    interpret=_interpret())
    if squeeze:
        outs = tuple(o[0] for o in outs)
    return outs


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_mha(q, k, v, causal: bool = True):
    """Flash attention fwd; pads S to the block size."""
    from repro.kernels import flash_attn as _fa

    S = q.shape[1]
    blk = min(_fa.BLK_Q, S) if S % min(_fa.BLK_Q, S) == 0 else 1
    if blk == 1:  # awkward sizes: fall back to padding to 128
        blk = _fa.BLK_Q
        qp, n = _pad_to(q, blk, axis=1)
        kp, _ = _pad_to(k, blk, axis=1)
        vp, _ = _pad_to(v, blk, axis=1)
        out = _fa.flash_attention(qp, kp, vp, causal=causal, blk_q=blk,
                                  blk_kv=blk, interpret=_interpret())
        return out[:, :n]
    return _fa.flash_attention(q, k, v, causal=causal, blk_q=blk, blk_kv=blk,
                               interpret=_interpret())


@jax.jit
def fused_dots(V, z):
    """One-pass multi-dot V @ z (kernel-backed, padded to the block)."""
    block = min(_fd.DEFAULT_BLOCK, V.shape[1])
    if V.shape[1] % block:
        Vp, n = _pad_to(V, block, axis=1)
        zp = jnp.pad(z, (0, Vp.shape[1] - n))
        return _fd.fused_dots(Vp, zp, block=block, interpret=_interpret())
    return _fd.fused_dots(V, z, block=block, interpret=_interpret())


# scoped VMEM a Mosaic kernel may use without raising its limit: the
# default of the v5e (and v4; a v6e grants 32 MiB)
SCOPED_VMEM_BYTES = 16 << 20
# the sweep's least block, 64 rows of 128 lanes (or the least multiple
# of the halo rows above it), taken even where the footprint model
# would not fit it
MIN_SWEEP_ROWS = 64


def _sweep_rows(n: int, hb: int, fits) -> list:
    """Candidate block rows of the PIPECG sweep over ``n`` elements.

    Multiples of ``hb`` from ``MIN_SWEEP_ROWS`` (or the whole vector,
    where it is shorter) up to the whole vector, as far as ``fits(rows)``
    holds.  Where one of them divides ``n``, only those: a larger block
    never pads a vector that a smaller one tiles exactly.
    """
    whole = stencil.legal_block(n, hb) // stencil.LANE
    lo = min(-(-MIN_SWEEP_ROWS // hb) * hb, whole)
    rows = [lo]
    for r in range(lo + hb, whole + 1, hb):
        if not fits(r):
            break
        rows.append(r)
    exact = [r for r in rows if n % (r * stencil.LANE) == 0]
    return exact or rows


def _sweep_block(kind, offsets, bands, inv_diag, x, u, **key):
    """Block of the single-sweep PIPECG kernel, from its halo and VMEM.

    Per row the sweep reads x, r, c = A^T 1 and writes x, r, u, p; u, p,
    the bands and diag^-1 are read over each tile plus 2*hb halo rows,
    so a larger tile re-reads fewer halo rows.  The candidates are the
    blocks whose footprint (``sweep_vmem_bytes``) fits the default scoped
    VMEM; the words model then takes the one that moves the least, the
    largest where none pads.  r/u/p count at their storage dtype, the
    operator at its own.
    """
    from repro.kernels import autotune

    hb = _ps.halo_rows(offsets, bands.dtype, inv_diag.dtype, x.dtype, u.dtype)
    nb = bands.shape[0]
    rs = _rel_words(u.dtype, x.dtype)        # carried r/u/p storage
    ro = _rel_words(bands.dtype, x.dtype)    # operator storage
    windowed = 2 * rs + (nb + 1) * ro
    fits = lambda r: _ps.sweep_vmem_bytes(
        r, hb, nb, x.dtype, u.dtype, bands.dtype) <= SCOPED_VMEM_BYTES
    rows = _sweep_rows(x.shape[-1], hb, fits)
    return autotune.best_block(
        kind, x.shape[-1], x.dtype,
        words_per_row=2.0 + 4.0 * rs + ro + windowed,
        step_words=2 * hb * stencil.LANE * windowed,
        min_block=hb * stencil.LANE,
        candidates=[r * stencil.LANE for r in rows],
        dtype_storage=_storage_key(u.dtype, x.dtype), n_bands=nb, **key)


def pipecg_sweep_operator(offsets: Tuple[int, ...], bands, inv_diag, x, u,
                          block: int = None):
    """Once per solve on one device: the sweep's block and split operator.

    ``bands`` (n_bands, n) / ``inv_diag`` (n,) are the whole operator;
    ``x`` ((n,) or (k, n)) and ``u`` give the solve and storage dtypes
    (r and p are carried in u's).  The default block is sized from the
    sweep's halo and VMEM footprint (:func:`_sweep_block`).  Returns
    ``(block, op)`` for :func:`pipecg_sweep_step`.
    """
    if block is None:
        block = _sweep_block("pipecg_spmv", offsets, bands, inv_diag, x, u)
    hb = _ps.halo_rows(offsets, bands.dtype, inv_diag.dtype, x.dtype, u.dtype)
    block = stencil.legal_block(min(block, x.shape[-1]), hb)
    return block, _ps.local_operator(offsets, bands, inv_diag, block=block,
                                     hb=hb)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("block",))
def pipecg_sweep_step(offsets: Tuple[int, ...], op, x, r, u, p, alpha, beta,
                      *, block: int):
    """Single-sweep PIPECG iteration (updates + Jacobi + SpMV + dots).

    Accepts (n,) vectors with scalar alpha/beta, or batched (k, n) vectors
    with (k,) alpha/beta.  ``block`` and ``op`` come from
    :func:`pipecg_sweep_operator`, once per solve.
    """
    squeeze = x.ndim == 1
    if squeeze:
        x, r, u, p = (v[None] for v in (x, r, u, p))
        alpha = jnp.asarray(alpha)[None]
        beta = jnp.asarray(beta)[None]
    outs = _ps.pipecg_spmv_fused(offsets, op, x, r, u, p, alpha, beta,
                                 block=block, interpret=_interpret())
    if squeeze:
        outs = tuple(o[0] for o in outs)
    return outs


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("block",))
def pipecg_spmv_fused_step(offsets: Tuple[int, ...], bands, inv_diag,
                           x, r, u, p, alpha, beta, block: int = None):
    """One single-sweep PIPECG iteration on an operator split for it.

    A solver splits the operator once per solve instead
    (:func:`pipecg_sweep_operator` + :func:`pipecg_sweep_step`).
    """
    blk, op = pipecg_sweep_operator(offsets, bands, inv_diag, x, u,
                                    block=block)
    return pipecg_sweep_step(offsets, op, x, r, u, p, alpha, beta, block=blk)


def pipecg_halo_operator(offsets: Tuple[int, ...], bands_ext, invd_ext, x, u,
                         block: int = None, n_shards: int = 1):
    """Once per solve: the halo sweep's block and its split operator.

    ``bands_ext`` / ``invd_ext`` are the halo-extended local operator;
    ``x`` (k, n_local) and ``u`` give the RHS count and the solve and
    storage dtypes (r and p are carried in u's).  The default block is
    sized from the sweep's halo and VMEM footprint (:func:`_sweep_block`)
    and cached on (backend, n_local, n_shards, k_rhs, band count).  Returns
    ``(block, op)`` for :func:`pipecg_spmv_halo_step`.
    """
    k_rhs, n = x.shape
    halo = max(abs(o) for o in offsets)
    if n < 2 * halo:
        raise ValueError(
            f"local shard of {n} rows is narrower than the 2*halo={2*halo} "
            "stencil reach; use fewer shards or a wider local block")
    if block is None:
        block = _sweep_block("pipecg_spmv_halo", offsets, bands_ext,
                             invd_ext, x, u, n_shards=n_shards, k_rhs=k_rhs)
    hb = _ps.halo_rows(offsets, bands_ext.dtype, invd_ext.dtype, x.dtype,
                       u.dtype)
    block = stencil.legal_block(min(block, n), hb)
    return block, _ps.halo_operator(offsets, bands_ext, invd_ext,
                                    block=block, hb=hb)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("block",))
def pipecg_spmv_halo_step(offsets: Tuple[int, ...], op, x, r, u, p,
                          u_left, u_right, p_left, p_right, alpha, beta, *,
                          block: int):
    """Per-shard single-sweep PIPECG iteration with neighbor halos.

    Vectors are (k, n_local); ``u_left``/``u_right``/``p_left``/``p_right``
    are the (k, 2*halo) ppermute payloads; ``block`` and ``op`` come from
    :func:`pipecg_halo_operator`, once per solve.  Returns (x', r', u',
    p', red) where ``red`` (k, 6) is this shard's PARTIAL reduction row
    including the ABFT checksum entry red[:, 5] (the caller psums it).
    """
    return _ps.pipecg_spmv_halo(offsets, op, x, r, u, p,
                                (u_left, u_right), (p_left, p_right),
                                alpha, beta, block=block,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(0, 5),
                   static_argnames=("block", "accum_dtype"))
def ghost_chain_step(offsets: Tuple[int, ...], bands, p, r, theta, l: int,
                     block: int = None, accum_dtype=None):
    """Depth-l ghost basis + Gram in one sweep (kernel-backed, padded).

    Returns ``(chain, gram)``: the (2l+1, n) theta-scaled basis
    [p, Ãp, .., Ã^l p, r, .., Ã^{l-1} r] and its (2l+1, 2l+1) Gram matrix
    — the single fused-reduction payload of one depth-l block
    (see kernels/pipecg_spmv_fused.py and core/krylov/pipeline.py).
    """
    from repro.kernels import autotune

    n = p.shape[-1]
    halo = max(abs(o) for o in offsets)
    H = l * halo
    acc = accum_dtype if accum_dtype is not None else p.dtype
    if block is None:
        rs = _rel_words(p.dtype, acc)
        ro = _rel_words(bands.dtype, acc)
        block = autotune.best_block(
            "ghost_chain", n, p.dtype,
            # tiled words/row: 2l+1 chain writes (p/r resident)
            words_per_row=float(2 * l + 1) * rs,
            resident_words=(2 * rs + bands.shape[0] * ro) * n,
            min_block=2 * H, k_rhs=l,
            dtype_storage=_storage_key(p.dtype, acc))
    block = max(min(block, n), 2 * H)
    pad = (-n) % block
    if pad:
        bands_p, _ = _pad_to(bands, block, axis=1)
        chain, gram = _ps.ghost_chain_fused(
            offsets, bands_p, jnp.pad(p, (0, pad)), jnp.pad(r, (0, pad)),
            theta, l, block=block, interpret=_interpret(),
            accum_dtype=accum_dtype)
        # zero-padded rows contribute zeros to the Gram: no mask needed
        return chain[:, :n], gram
    return _ps.ghost_chain_fused(offsets, bands, p, r, theta, l, block=block,
                                 interpret=_interpret(),
                                 accum_dtype=accum_dtype)


@functools.partial(jax.jit, static_argnums=(0, 9),
                   static_argnames=("block", "n_shards", "accum_dtype"))
def ghost_chain_halo_step(offsets: Tuple[int, ...], bands_ext, p, r,
                          p_left, p_right, r_left, r_right, theta, l: int,
                          block: int = None, n_shards: int = 1,
                          accum_dtype=None):
    """Per-shard depth-l ghost-chain sweep with neighbor halos.

    ``p_left``/``p_right``/``r_left``/``r_right`` are the (l*halo,)
    ppermute payloads — ONE exchange per depth-l block; ``bands_ext`` the
    once-per-solve l*halo-extended operator.  The returned ``gram`` is
    this shard's PARTIAL (2l+1, 2l+1) Gram (the caller psums it: one
    collective per l iterations).
    """
    from repro.kernels import autotune

    n = p.shape[-1]
    halo = max(abs(o) for o in offsets)
    H = l * halo
    if n < 2 * H:
        raise ValueError(
            f"local shard of {n} rows is narrower than the 2*l*halo={2 * H} "
            "chain reach; use fewer shards or a smaller depth")
    acc = accum_dtype if accum_dtype is not None else p.dtype
    if block is None:
        rs = _rel_words(p.dtype, acc)
        ro = _rel_words(bands_ext.dtype, acc)
        block = autotune.best_block(
            "ghost_chain_halo", n, p.dtype,
            words_per_row=float(2 * l + 1) * rs,
            resident_words=(2 * rs + bands_ext.shape[0] * ro) * n,
            min_block=2 * H, n_shards=n_shards, k_rhs=l,
            dtype_storage=_storage_key(p.dtype, acc))
    block = max(min(block, n), 2 * H)
    return _ps.ghost_chain_halo(offsets, bands_ext, p, r, (p_left, p_right),
                                (r_left, r_right), theta, l, block=block,
                                interpret=_interpret(),
                                accum_dtype=accum_dtype)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("block",))
def pipebicgstab_fused_step(offsets: Tuple[int, ...], bands, x, r, w, t,
                            pa, a, c, r_hat, alpha, beta, omega,
                            block: int = None):
    """Single-sweep pipelined BiCGStab iteration (updates + 2 SpMVs + Gram).

    All vectors (n,) with scalar alpha/beta/omega; ``bands`` carries the
    (Jacobi-folded) operator.  Pads the row dimension to the block size
    (zero-padded rows contribute zeros to the Gram — no mask needed); the
    default block comes from the autotuner under the
    ``"pipebicgstab_spmv"`` key.  Returns (x', r', w', t', pa', a', c',
    gram (7, 6)) — gram rows 0..5 are the Gram matrix, gram[6, 0] the
    ABFT checksum residual of the in-kernel SpMV.
    """
    from repro.kernels import autotune

    n = x.shape[0]
    halo = max(abs(o) for o in offsets)
    if block is None:
        rs = _rel_words(r.dtype, x.dtype)        # carried-chain storage
        ro = _rel_words(bands.dtype, x.dtype)    # resident operator
        block = autotune.best_block(
            "pipebicgstab_spmv", n, x.dtype,
            # tiled words/row: x read/write at accum + r,pa,a,r_hat reads
            # and 6 chain writes at the storage dtype
            words_per_row=2.0 + 10.0 * rs,
            # once-per-sweep: w,t,c (+2h) + bands (+h) + ABFT column sums
            resident_words=(3 * rs + (bands.shape[0] + 1) * ro) * n,
            min_block=2 * halo,
            dtype_storage=_storage_key(r.dtype, x.dtype))
    block = max(min(block, n), 2 * halo)
    pad = (-n) % block
    if pad:
        bands_p, _ = _pad_to(bands, block, axis=1)
        vecs = [jnp.pad(v, (0, pad))
                for v in (x, r, w, t, pa, a, c, r_hat)]
        outs = _pb.pipebicgstab_fused(offsets, bands_p, *vecs,
                                      alpha, beta, omega, block=block,
                                      interpret=_interpret())
        return tuple(o[:n] for o in outs[:7]) + (outs[7],)
    return _pb.pipebicgstab_fused(offsets, bands, x, r, w, t, pa, a, c,
                                  r_hat, alpha, beta, omega, block=block,
                                  interpret=_interpret())


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("block", "n_shards"))
def pipebicgstab_halo_step(offsets: Tuple[int, ...], bands_ext, x, r, w, t,
                           pa, a, c, r_hat, w_left, w_right, t_left,
                           t_right, c_left, c_right, alpha, beta, omega,
                           block: int = None, n_shards: int = 1):
    """Per-shard single-sweep p-BiCGStab iteration with neighbor halos.

    Vectors are (n_local,); ``*_left`` / ``*_right`` are the (2*halo,)
    ppermute payloads of w/t/c; ``bands_ext`` the once-per-solve
    halo-extended operator.  Returns (x', r', w', t', pa', a', c', gram)
    where ``gram`` (7, 6) is this shard's PARTIAL Gram + checksum row
    (the caller psums it).  The default block is autotuned on
    (backend, n_local, n_shards).
    """
    from repro.kernels import autotune

    n = x.shape[0]
    halo = max(abs(o) for o in offsets)
    if n < 2 * halo:
        raise ValueError(
            f"local shard of {n} rows is narrower than the 2*halo={2*halo} "
            "stencil reach; use fewer shards or a wider local block")
    if block is None:
        rs = _rel_words(r.dtype, x.dtype)
        ro = _rel_words(bands_ext.dtype, x.dtype)
        block = autotune.best_block(
            "pipebicgstab_halo", n, x.dtype,
            words_per_row=2.0 + 10.0 * rs,
            resident_words=(3 * rs + (bands_ext.shape[0] + 1) * ro) * n,
            min_block=2 * halo, n_shards=n_shards,
            dtype_storage=_storage_key(r.dtype, x.dtype))
    block = max(min(block, n), 2 * halo)
    return _pb.pipebicgstab_halo(offsets, bands_ext, x, r, w, t, pa, a, c,
                                 r_hat, (w_left, w_right),
                                 (t_left, t_right), (c_left, c_right),
                                 alpha, beta, omega, block=block,
                                 interpret=_interpret())


@jax.jit
def pipecg_fused_step(x, r, u, w, m, n_, z, q, s, p, alpha, beta):
    """Fused PIPECG updates + dots (update-kernel path, padded)."""
    block = _pf.DEFAULT_BLOCK
    if x.shape[0] % block:
        vecs = [x, r, u, w, m, n_, z, q, s, p]
        padded = []
        for v in vecs:
            vp, n = _pad_to(v, block)
            padded.append(vp)
        outs = _pf.pipecg_fused(*padded, alpha, beta, block=block,
                                interpret=_interpret())
        return tuple(o[:n] for o in outs[:8]) + (outs[8],)
    return _pf.pipecg_fused(x, r, u, w, m, n_, z, q, s, p, alpha, beta,
                            block=block, interpret=_interpret())
