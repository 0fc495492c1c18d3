"""Distributed Krylov solves: shard_map + ppermute halos + psum dots.

This is the JAX-native rendering of the paper's computational model:

  local computation   = per-shard DIA SpMV + AXPYs           (green boxes)
  halo exchange       = lax.ppermute with neighbors          (ICI p2p)
  global sync         = lax.psum for every inner product     (dotted lines)

The *pipelined* solvers (pipecg / pipecr / pgmres) are the SAME functions as
the local ones — the rearranged data dependencies mean the psum produced at
the end of iteration i is consumed only after the next SpMV, which is what
lets XLA's latency-hiding scheduler overlap the collective (split-phase
semantics, cf. DESIGN.md §Hardware-adaptation).

``distributed_solve(..., engine="sharded_fused")`` replaces the naive
per-op iteration with the sharded single-sweep engine
(:class:`~repro.core.krylov.engine.ShardedFusedEngine`): each shard runs
one halo-aware Pallas sweep per iteration (kernels/pipecg_spmv_fused.py)
that emits PARTIAL reduction rows, and the finishing ``psum`` is carried
across the scan boundary so its result is consumed only by the next
iteration's scalar recurrence — never by that iteration's halo
``ppermute`` or kernel operands.  In the compiled HLO the all-reduce and
the collective-permutes of a loop body are therefore mutually
independent (asserted by ``launch/hlo_analysis.py::split_phase_overlap``)
— the paper's MPI_Iallreduce/MPI_Wait window, rendered in XLA.

``distributed_solve(..., noise=...)`` splices a host-side NoiseHook
(core/noise/injection.py) into the per-shard SpMV so every Krylov
iteration stalls for a freshly sampled waiting time — the campaign
runner's in-silico rendering of the paper's noisy Piz Daint runs
(DESIGN.md §In-silico-noise-traces).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.krylov.base import (SolveResult, make_psum_dot,
                                    run_until_done)
from repro.core.krylov.operators import DiaMatrix
from repro.core.krylov.options import PrecisionPolicy, as_policy
from repro.core.noise.injection import NoiseHook

AXIS = "shards"


def _resolve_precision(precision) -> PrecisionPolicy:
    """Coerce a precision selector (policy / preset name / None)."""
    return as_policy(precision)


def _noise_tick(noise: NoiseHook, axis_name, dtype):
    """One per-shard host-callback stall; returns the (zero) tick.

    Passes the mesh ``axis_index`` as an operand so the hook draws from
    that shard's deterministic RNG substream (and so fault injectors —
    core/noise/faults.py — know WHICH shard is calling: a kill/stall/
    corrupt fault is keyed to a logical shard id).  Effectful io_callback:
    XLA may not elide, cache or hoist it; the caller adds the tick to a
    live value so the stall stays on the data-dependent critical path.
    """
    from jax.experimental import io_callback

    names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    # linearized (row-major) shard id over a tuple of mesh axes, so a 2D
    # process grid addresses the same per-shard RNG substreams / fault
    # schedule a flattened 1D mesh of equal size would
    idx = jnp.zeros((), jnp.int32)
    for nm in names:
        idx = idx * jax.lax.axis_size(nm) + jax.lax.axis_index(nm)
    tick = io_callback(noise, jax.ShapeDtypeStruct((), jnp.float32), idx,
                       ordered=False)
    return tick.astype(dtype)


def halo_exchange_cols(x: jnp.ndarray, halo: int, axis_name: str = AXIS):
    """(left, right) halos of width ``halo`` along the LAST axis.

    Works for any leading batch shape — vectors (n,), RHS batches (k, n)
    and band stacks (n_bands, n) all exchange their edge columns with the
    ring neighbors; chain-boundary devices receive zeros (matches the
    zero padding of DIA bands at the matrix boundary).
    """
    n_dev = jax.lax.axis_size(axis_name)
    if n_dev == 1 or halo == 0:
        z = jnp.zeros(x.shape[:-1] + (halo,), x.dtype)
        return z, z
    right_send = [(i, i + 1) for i in range(n_dev - 1)]   # i -> i+1
    left_send = [(i + 1, i) for i in range(n_dev - 1)]    # i -> i-1
    left = jax.lax.ppermute(x[..., -halo:], axis_name, right_send)
    right = jax.lax.ppermute(x[..., :halo], axis_name, left_send)
    return left, right


def halo_exchange(x_local: jnp.ndarray, halo: int, axis_name: str = AXIS):
    """1-D vector variant of :func:`halo_exchange_cols` (same semantics)."""
    return halo_exchange_cols(x_local, halo, axis_name)


def halo_exchange_compressed(x: jnp.ndarray, halo: int, axis_name: str,
                             ef_l: jnp.ndarray, ef_r: jnp.ndarray,
                             use_ef: bool):
    """int8-wire variant of :func:`halo_exchange_cols`.

    Each edge strip is quantized at the sender
    (distributed/compression.py::compress_halo) and travels as an int8
    payload plus a scalar fp32 scale — two ppermutes per direction
    instead of one, but ~4x fewer wire bytes vs an fp32 strip (~8x vs
    fp64).  Both payloads derive ONLY from the carried vector ``x``,
    never from the pending split-phase reduction, so the overlap
    invariant of the sharded engines (one all-reduce per body, no
    permute->all-reduce dependence; launch/hlo_analysis.py) is
    preserved — ``split_phase_overlap`` tolerates extra permutes.

    ``ef_l`` / ``ef_r`` are the sender-side error-feedback strips for
    the left/right EDGE of ``x`` (shape ``x.shape[:-1] + (halo,)``);
    with ``use_ef`` the quantization residual of the same boundary rows
    re-enters next iteration (Seide-style) instead of accumulating into
    the attainable-accuracy floor.  Returns
    ``(left, right, new_ef_l, new_ef_r)`` with the received halos cast
    back to ``x.dtype``.
    """
    from repro.distributed import compression as comp

    n_dev = jax.lax.axis_size(axis_name)
    if n_dev == 1 or halo == 0:
        z = jnp.zeros(x.shape[:-1] + (halo,), x.dtype)
        return z, z, jnp.zeros_like(ef_l), jnp.zeros_like(ef_r)
    right_send = [(i, i + 1) for i in range(n_dev - 1)]   # i -> i+1
    left_send = [(i + 1, i) for i in range(n_dev - 1)]    # i -> i-1
    # right EDGE strip travels rightward (arrives as the neighbor's LEFT
    # halo); left edge travels leftward — same routing as the fp32 path
    qr, sr, ef_r_new = comp.compress_halo(
        x[..., -halo:], ef_r if use_ef else None)
    ql, sl, ef_l_new = comp.compress_halo(
        x[..., :halo], ef_l if use_ef else None)
    left = comp.decompress_halo(
        jax.lax.ppermute(qr, axis_name, right_send),
        jax.lax.ppermute(sr, axis_name, right_send), x.dtype)
    right = comp.decompress_halo(
        jax.lax.ppermute(ql, axis_name, left_send),
        jax.lax.ppermute(sl, axis_name, left_send), x.dtype)
    if not use_ef:
        ef_l_new = jnp.zeros_like(ef_l)
        ef_r_new = jnp.zeros_like(ef_r)
    return left, right, ef_l_new, ef_r_new


def dia_matvec_local(offsets, bands_local, x_local, axis_name: str = AXIS,
                     use_kernel: bool = False):
    """Per-shard DIA matvec with halo exchange.

    bands_local: (n_bands, n_local); x_local: (n_local,).
    """
    halo = max(abs(o) for o in offsets)
    left, right = halo_exchange(x_local, halo, axis_name)
    x_ext = jnp.concatenate([left, x_local, right])
    n_local = x_local.shape[0]
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.spmv_dia_ext(offsets, bands_local, x_ext, halo)
    y = jnp.zeros_like(x_local)
    for k, off in enumerate(offsets):
        y = y + bands_local[k] * jax.lax.dynamic_slice_in_dim(
            x_ext, halo + off, n_local)
    return y


# ---------------------------------------------------------------------------
# Sharded fused engine: halo-aware single-sweep kernel + split-phase psum
# ---------------------------------------------------------------------------

def _local_partials(r, u, w, csum):
    """This shard's (k, 6) reduction row
    [<r,u>, <w,u>, <r,r>, <r,w>, <w,w>, 1^T w - c^T u].

    One fused pass per operand via the multi-dot kernel
    (kernels/fused_dots.py) — the same reduction tail the kernel sweep
    accumulates in steady state, including the ABFT checksum partial
    (``csum`` is this shard's slice of the GLOBAL column checksum
    ``c = A^T 1``, so the psum'd entry is exactly ``1^T (A u) - c^T u``
    up to fp reassociation; kernels/checksum.py).
    """
    from repro.kernels import ops as kops

    def one(rj, uj, wj):
        rw = jnp.stack([rj, wj])
        d_u = kops.fused_dots(rw, uj)          # <r,u>, <w,u>
        d_r = kops.fused_dots(rw, rj)          # <r,r>, <w,r> = <r,w>
        d_w = kops.fused_dots(wj[None], wj)    # <w,w>
        chk = (jnp.sum(wj) - jnp.sum(csum * uj))[None]
        return jnp.concatenate([d_u, d_r, d_w, chk])

    return jax.vmap(one)(r, u, w)


def sharded_pipecg_solve(offsets: Tuple[int, ...], bands_local, b_local, *,
                         axis_name: str, ip: str = "id", M=None,
                         maxiter: int = 100, tol: float = 0.0,
                         block: Optional[int] = None, n_shards: int = 1,
                         noise: Optional[NoiseHook] = None,
                         x0=None, carried=None,
                         with_state: bool = False,
                         precision=None):
    """Per-shard PIPECG/PIPECR body of the ShardedFusedEngine.

    Runs INSIDE shard_map.  Each iteration is one halo-aware Pallas sweep
    (kernels/pipecg_spmv_fused.py::pipecg_spmv_halo) plus one scalar psum
    — and the psum is *split-phase*: the kernel of iteration i emits a
    partial (k, 6) reduction row — the five Krylov partials plus the ABFT
    checksum partial ``1^T w' - c^T u'`` (kernels/checksum.py), which
    therefore rides the SAME carried all-reduce at zero extra collectives
    — that is carried unreduced across the loop boundary; iteration i+1
    first issues its halo ppermutes (which depend only on the carried
    vectors), then finishes the reduction with ``psum`` and feeds the
    result to the scalar alpha/beta recurrence gating the kernel launch.
    The psum'd checksum column is returned per iteration as
    ``SolveResult.detect_history`` (detection latency: one iteration).  Inside one loop body the all-reduce and
    the collective-permutes therefore have no data dependence on each
    other, which is what lets XLA overlap them (the HLO assertion lives
    in launch/hlo_analysis.py::split_phase_overlap).

    Because the reduction consumed at iteration i is the one INITIATED at
    iteration i-1, the residual history comes out shifted by one; a final
    psum after the loop supplies the last residual and the history is
    rolled back into the naive solvers' alignment (hist[i] = ||r_{i+1}||).

    The loop stops after the step whose psum'd ``||r||`` meets ``tol``
    (:func:`~repro.core.krylov.base.run_until_done`): ``done`` comes from
    the all-reduced row, so every shard takes the same exit.  ``tol = 0``
    runs all ``maxiter`` steps.  One right-hand side at the default
    precision needs no masked update; several freeze each converged
    column until the last one is done.  History entries from the last
    executed step on hold the final ``res_norm`` and checksum.

    ``M`` may be None (identity) or ``"jacobi"`` — in-kernel
    preconditioning only; opaque callables are rejected.  ``noise`` (a
    NoiseHook) adds an io_callback stall to the partial-reduction row so
    the sampled wait sits on the iteration's critical path.

    **Elastic warm start** (the fault-recovery hooks, distributed/fault.py):
    ``with_state=True`` additionally returns the carried Krylov state as a
    dict ``{x, r, u, p, gamma_prev, alpha_prev, done}`` in the internal
    batched form; a later call — under ANY shard count — resumes exactly
    from it via ``carried=`` (the mesh-dependent partial reduction is
    recomputed from ``(r, u, A u)``, identical up to fp reassociation).
    ``x0=`` instead RESTARTS the recurrence from an iterate with one
    synchronous true-residual evaluation ``r = b - A x0`` — the Cools
    residual-replacement re-glue used after a disruptive recovery.

    ``precision`` (a :class:`~repro.core.krylov.options.PrecisionPolicy`,
    preset name or None): with ``storage='bf16'`` the carried basis
    vectors r/u/p and the operator extension live in bfloat16 — the
    kernel loads them, accumulates at the solve dtype and stores back in
    storage precision (kernels/pipecg_spmv_fused.py) — while ``x``, the
    partial reduction row and the scalar recurrences stay full
    precision.  With ``wire='int8'`` the ppermute halo strips travel as
    int8 payloads with fp32 scales (:func:`halo_exchange_compressed`);
    ``error_feedback`` carries the sender-side quantization residual in
    the scan state.  ``wire_gram='int8'`` additionally squeezes the
    carried reduction row through the int8 grid before the carry
    (compression.compress_gram) — EXCEPT its ABFT checksum column,
    preserved verbatim so the rounding-level detector keeps its floor.
    The Gram wire is off by default and known-unsafe: each reduction is
    consumed once, so its quantization error corrupts alpha/beta
    directly (see options.PrecisionPolicy).
    """
    from repro.kernels import ops as kops

    policy = _resolve_precision(precision)
    halo = max(abs(o) for o in offsets)
    batched = b_local.ndim == 2
    B = b_local if batched else b_local[None]
    k_rhs, n_local = B.shape
    dt = B.dtype
    if n_local < 2 * halo:
        raise ValueError(
            f"sharded_fused engine: local shard of {n_local} rows is "
            f"narrower than the 2*halo={2 * halo} stencil reach")
    if M is None:
        invd = jnp.ones((n_local,), dt)
    elif M == "jacobi":
        invd = (1.0 / bands_local[offsets.index(0)]).astype(dt)
    else:
        raise ValueError(
            "sharded_fused engine preconditions in-kernel: M must be None "
            f"or 'jacobi', got {M!r}")

    # loop-invariant operator extension: one ppermute per solve, hoisted
    # out of the iteration loop by construction
    bl, br = halo_exchange_cols(bands_local, halo, axis_name)
    bands_ext = jnp.concatenate([bl, bands_local, br], axis=-1)
    il, ir = halo_exchange_cols(invd, halo, axis_name)
    invd_ext = jnp.concatenate([il, invd, ir], axis=-1)
    # this shard's slice of the GLOBAL column checksum c = A^T 1: every
    # contributing band value lives in the halo-extended local bands, so
    # no extra exchange is needed (kernels/checksum.py)
    from repro.kernels.checksum import dia_column_checksum
    csum_loc = dia_column_checksum(offsets, bands_ext, halo=halo).astype(dt)
    # storage demotion AFTER the checksum: the detector's reference
    # c = A^T 1 is computed from the full-precision operator
    sdt = policy.storage_dtype
    if sdt is not None:
        bands_ext = bands_ext.astype(sdt)
        invd_ext = invd_ext.astype(sdt)
    wire_halo = policy.wire == "int8"
    wire_gram = policy.wire_gram == "int8"
    use_ef = policy.error_feedback

    def mv(v):  # (k, n_local) halo matvec — init only; the loop uses the kernel
        lv, rv = halo_exchange_cols(v, halo, axis_name)
        v_ext = jnp.concatenate([lv, v, rv], axis=-1)
        y = jnp.zeros_like(v)
        for kb, off in enumerate(offsets):
            y = y + bands_local[kb] * jax.lax.dynamic_slice_in_dim(
                v_ext, halo + off, n_local, axis=-1)
        return y

    one = jnp.ones((k_rhs,), dt)
    if carried is not None and x0 is not None:
        raise ValueError("pass either x0 (residual-replacement restart) or "
                         "carried (exact continuation), not both")
    if carried is not None:
        # exact continuation of a previous segment's Krylov state
        # (possibly saved under a DIFFERENT mesh: every entry is a global
        # (k_rhs, .) host array that the caller's in_specs re-shard).
        # The mesh-dependent partial `red` is NOT carried — it is
        # recomputed from (r, u, w = A u) below, identical up to fp
        # reassociation across shard counts.
        x = carried["x"].astype(dt)
        r = carried["r"].astype(dt)
        u = carried["u"].astype(dt)
        p = carried["p"].astype(dt)
        gamma_prev = carried["gamma_prev"].astype(dt)
        alpha_prev = carried["alpha_prev"].astype(dt)
        done0 = carried["done"]
        first = jnp.asarray(False)
    else:
        if x0 is None:
            x = jnp.zeros_like(B)
            r = B              # r0 = b - A*0
        else:
            x = (x0 if batched else x0[None]).astype(dt)
            # synchronous true residual — the Cools residual-replacement
            # re-glue that puts a recovered solve back on the attainable-
            # accuracy floor (PAPERS.md 1804.02962)
            r = B - mv(x)
        u = invd * r
        p = jnp.zeros_like(B)
        gamma_prev = one
        alpha_prev = one
        done0 = jnp.zeros((k_rhs,), bool)
        first = jnp.asarray(True)
    w = mv(u)
    red0 = _local_partials(r, u, w, csum_loc)
    # carried basis vectors demote to storage precision (x and the
    # reduction row stay at the solve dtype); identity when sdt is None
    if sdt is not None:
        r, u, p = r.astype(sdt), u.astype(sdt), p.astype(sdt)
    # the ABFT checksum column rides the carried psum verbatim — int8
    # would silence the rounding-level detector (compression.py)
    chk_mask = jnp.zeros((k_rhs, 6), bool).at[:, 5].set(True)
    if wire_gram:
        from repro.distributed import compression as comp
        red0, gef0 = comp.compress_gram(red0, None, preserve=chk_mask)
        if not use_ef:
            gef0 = jnp.zeros_like(gef0)
    state0 = dict(x=x, r=r, u=u, p=p, red=red0,
                  gamma_prev=gamma_prev, alpha_prev=alpha_prev,
                  first=first, done=done0,
                  iters=jnp.zeros((k_rhs,), jnp.int32))
    if wire_gram:
        state0["gef"] = gef0
    if wire_halo:
        # sender-side error-feedback strips, one per edge per exchanged
        # vector, carried across the loop
        ef0 = jnp.zeros(r.shape[:-1] + (2 * halo,), r.dtype)
        state0.update(efu_l=ef0, efu_r=ef0, efp_l=ef0, efp_r=ef0)
    bb = jax.lax.psum(jnp.sum(B * B, axis=-1), axis_name)
    tol2 = jnp.asarray(tol, dt) ** 2 * bb
    # the sweep's block and operator split, once per solve: no iteration
    # re-slices, re-pads or re-sums the loop-invariant operator
    blk, hop = kops.pipecg_halo_operator(offsets, bands_ext, invd_ext, x, u,
                                         block=block, n_shards=n_shards)
    freeze = k_rhs > 1 or not policy.is_default

    def step(st):
        # ---- halo exchange for THIS iteration's sweep: depends only on
        # the carried vectors, NOT on the pending reduction ----
        if wire_halo:
            ul, ur, efu_l, efu_r = halo_exchange_compressed(
                st["u"], 2 * halo, axis_name, st["efu_l"], st["efu_r"],
                use_ef)
            pl_, pr, efp_l, efp_r = halo_exchange_compressed(
                st["p"], 2 * halo, axis_name, st["efp_l"], st["efp_r"],
                use_ef)
        else:
            ul, ur = halo_exchange_cols(st["u"], 2 * halo, axis_name)
            pl_, pr = halo_exchange_cols(st["p"], 2 * halo, axis_name)
        # ---- split-phase: finish the reduction initiated LAST iteration;
        # its only consumers are the scalar recurrences below ----
        red = jax.lax.psum(st["red"], axis_name)
        gamma, delta = ((red[:, 0], red[:, 1]) if ip == "id"
                        else (red[:, 3], red[:, 4]))
        rr = red[:, 2]
        chk = red[:, 5]     # ABFT checksum residual, same carried psum
        beta = jnp.where(st["first"], jnp.zeros_like(gamma),
                         gamma / st["gamma_prev"])
        alpha = jnp.where(st["first"], gamma / delta,
                          gamma / (delta - beta * gamma / st["alpha_prev"]))
        x, r, u, p, red_new = kops.pipecg_spmv_halo_step(
            offsets, hop, st["x"], st["r"], st["u"], st["p"],
            ul, ur, pl_, pr, alpha, beta, block=blk)
        if wire_gram:
            # squeeze the partial reduction through the int8 wire grid
            # BEFORE the carry: the psum count and dataflow — the HLO
            # overlap invariant — are untouched (compression.py)
            from repro.distributed import compression as comp
            red_new, gef = comp.compress_gram(
                red_new, st["gef"] if use_ef else None, preserve=chk_mask)
        if noise is not None:
            # the tick rides the partial-reduction row so the stall gates
            # the next psum — and a fault injector's NaN tick poisons it
            red_new = red_new + _noise_tick(noise, axis_name, dt)

        mask = st["done"]
        if not policy.is_default:
            # low-precision breakdown guard: past the storage floor the
            # recurrence scalars can lose positivity / blow up — freeze
            # AT the last good iterate instead of propagating NaN.  The
            # default path is untouched (the ABFT fault campaign relies
            # on a poisoned psum flowing through to the detector).
            bad = ~(jnp.isfinite(gamma) & jnp.isfinite(alpha)
                    & jnp.isfinite(rr))
            mask = mask | bad
        done = mask | (rr <= tol2)

        def frz(nv, ov):  # freeze converged systems (masked update)
            if not freeze:  # the loop exits after the step that sets done
                return nv
            m = (mask.reshape(mask.shape + (1,) * (nv.ndim - mask.ndim))
                 if nv.ndim > mask.ndim else mask)
            return jnp.where(m, ov, nv)

        new = dict(x=frz(x, st["x"]), r=frz(r, st["r"]), u=frz(u, st["u"]),
                   p=frz(p, st["p"]), red=frz(red_new, st["red"]),
                   gamma_prev=frz(gamma, st["gamma_prev"]),
                   alpha_prev=frz(alpha, st["alpha_prev"]),
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        if wire_halo:
            new.update(efu_l=efu_l, efu_r=efu_r, efp_l=efp_l, efp_r=efp_r)
        if wire_gram:
            new["gef"] = gef if use_ef else st["gef"]
        return new, (jnp.sqrt(jnp.maximum(rr, 0.0)), chk)

    st, (hist, chk_hist), steps = run_until_done(step, state0, maxiter)
    red_fin = jax.lax.psum(st["red"], axis_name)
    res = jnp.sqrt(jnp.maximum(red_fin[:, 2], 0.0))
    # roll the shifted history into the naive alignment hist[i] =
    # ||r_{i+1}||; from the last executed step on it holds the final psum
    rolled = (jnp.arange(maxiter) + 1 < steps)[:, None]   # (maxiter, 1)
    hist = jnp.where(rolled, jnp.roll(hist, -1, axis=0), res)  # (maxiter, k)
    chk_hist = jnp.where(rolled, jnp.roll(chk_hist, -1, axis=0),
                         red_fin[:, 5])
    if batched:
        result = SolveResult(x=st["x"], iters=st["iters"], res_norm=res,
                             res_history=hist.T, detect_history=chk_hist.T)
    else:
        result = SolveResult(x=st["x"][0], iters=st["iters"][0],
                             res_norm=res[0], res_history=hist[:, 0],
                             detect_history=chk_hist[:, 0])
    if not with_state:
        return result
    # the internal (k_rhs, .) batched form, always — so a later segment
    # (under ANY mesh) can feed it straight back as ``carried=``
    carried_out = dict(x=st["x"], r=st["r"], u=st["u"], p=st["p"],
                       gamma_prev=st["gamma_prev"],
                       alpha_prev=st["alpha_prev"], done=st["done"])
    return result, carried_out


# ---------------------------------------------------------------------------
# 2D process grid: N/S/E/W halo pairs + ONE Gram psum over BOTH mesh axes
# ---------------------------------------------------------------------------

def _exchange_along(v: jnp.ndarray, w: int, axis_name: str, axis: int):
    """(low, high) halos of width ``w`` along array axis ``axis``.

    The generic-axis sibling of :func:`halo_exchange_cols`: strips travel
    over the ONE mesh axis ``axis_name`` (a chain, not a ring), and
    chain-boundary devices receive zeros — matching the zero band
    coefficients a DIA operator carries at the matrix boundary.
    """
    n_dev = jax.lax.axis_size(axis_name)
    if n_dev == 1 or w == 0:
        shp = list(v.shape)
        shp[axis] = w
        z = jnp.zeros(shp, v.dtype)
        return z, z
    fwd = [(i, i + 1) for i in range(n_dev - 1)]   # i -> i+1
    bwd = [(i + 1, i) for i in range(n_dev - 1)]   # i -> i-1
    ext = v.shape[axis]
    low = jax.lax.ppermute(jax.lax.slice_in_dim(v, ext - w, ext, axis=axis),
                           axis_name, fwd)
    high = jax.lax.ppermute(jax.lax.slice_in_dim(v, 0, w, axis=axis),
                            axis_name, bwd)
    return low, high


def halo_exchange_2d(v: jnp.ndarray, wy: int, wx: int,
                     axis_y: str, axis_x: str) -> jnp.ndarray:
    """Two-phase corner-carrying halo exchange on a 2D process grid.

    ``v`` is ``(..., ly, lx)`` — this shard's tile of a ``(ny, nx)`` grid
    field, sharded ``axis_y`` over rows and ``axis_x`` over columns.
    Phase 1 exchanges N/S row strips of width ``wy``; phase 2 exchanges
    W/E column strips of width ``wx`` of the *row-extended* array, so the
    corner blocks ride through the edge neighbors and no diagonal
    ppermute is needed — 4 messages per field (``HaloSpec.neighbors``),
    the count perfmodel/comm.py charges.  Returns the
    ``(..., ly + 2*wy, lx + 2*wx)`` extension with zeros past the chain
    boundary.
    """
    n, s = _exchange_along(v, wy, axis_y, axis=-2)
    v = jnp.concatenate([n, v, s], axis=-2)
    w_, e = _exchange_along(v, wx, axis_x, axis=-1)
    return jnp.concatenate([w_, v, e], axis=-1)


def _apply2d(doffs, bands_e: jnp.ndarray, v_e: jnp.ndarray,
             hy: int, hx: int) -> jnp.ndarray:
    """Stencil apply ``y = A v`` on a (possibly halo-extended) 2D tile.

    ``doffs`` are the per-band grid displacements ``(dy, dx)``
    (``DiaMatrix.grid_offsets``); ``bands_e`` is ``(nb, oy, ox)`` — the
    band coefficients at the OUTPUT rows — and ``v_e`` is
    ``(..., oy + 2*hy, ox + 2*hx)``, the input extended ``(hy, hx)``
    beyond the output extent.  Every slice is static, so the unrolled
    band loop lowers to ``nb`` fused multiply-adds:
    ``y[i, j] = sum_k bands_e[k, i, j] * v_e[i + hy + dy_k, j + hx + dx_k]``.
    """
    oy, ox = bands_e.shape[-2], bands_e.shape[-1]
    y = jnp.zeros(v_e.shape[:-2] + (oy, ox), v_e.dtype)
    for k, (dy, dx) in enumerate(doffs):
        y = y + bands_e[k] * v_e[..., hy + dy:hy + dy + oy,
                                 hx + dx:hx + dx + ox]
    return y


def _dia2d_column_checksum(doffs, bands_e: jnp.ndarray,
                           hy: int, hx: int) -> jnp.ndarray:
    """This shard's ``(ly, lx)`` slice of the GLOBAL column sums A^T 1.

    Grid rendering of :func:`~repro.kernels.checksum.dia_column_checksum`:
    column ``(i, j)`` is written by row ``(i - dy, j - dx)`` of band
    ``k``, and every contributing row lives inside the ``(hy, hx)``
    halo-extended local bands, so no extra communication is needed.
    """
    ly, lx = bands_e.shape[-2] - 2 * hy, bands_e.shape[-1] - 2 * hx
    c = jnp.zeros((ly, lx), bands_e.dtype)
    for k, (dy, dx) in enumerate(doffs):
        c = c + bands_e[k, hy - dy:hy - dy + ly, hx - dx:hx - dx + lx]
    return c


def _crop2d(v: jnp.ndarray, cy: int, cx: int) -> jnp.ndarray:
    """Drop a ``(cy, cx)``-wide frame from the trailing two axes."""
    return v[..., cy:v.shape[-2] - cy, cx:v.shape[-1] - cx]


def sharded_pipecg_solve_2d(doffs, bands_local, b_local, *,
                            axis_names: Tuple[str, str], ip: str = "id",
                            M=None, maxiter: int = 100, tol: float = 0.0,
                            noise: Optional[NoiseHook] = None
                            ) -> SolveResult:
    """Per-shard PIPECG body on a 2D ``(py, px)`` process grid.

    Runs INSIDE shard_map over BOTH mesh axes.  The 1D body's single
    W/E halo pair becomes the ``HaloSpec`` neighbor set N/S/W/E — the
    two-phase corner-carrying exchange of :func:`halo_exchange_2d` —
    while the split-phase reduction structure is IDENTICAL: the partial
    ``(6,)`` reduction row of iteration i (five Krylov partials + the
    ABFT checksum partial) is carried unreduced across the scan
    boundary, and iteration i+1 issues its u/p halo exchanges first
    (they depend only on the carried vectors), then finishes the
    reduction with ONE ``psum`` over the axis-name TUPLE — a single
    all-reduce spanning the whole grid, so
    ``launch/hlo_analysis.py::split_phase_overlap`` certifies the same
    one-all-reduce-per-body window as the 1D engine.

    The per-iteration sweep uses the recompute trick instead of a
    second exchange: u/p travel once at width ``(2*hy, 2*hx)``, then the
    derived quantities contract the extent ``(2h) -> (h) -> 0`` as
    p' = u + beta p, s' = A p', u' = u - alpha diag^-1 s', w' = A u'.
    Single-RHS (``b_local`` is this shard's ``(ly, lx)`` tile); ``M`` is
    None or ``"jacobi"``.  The residual history is rolled into the naive
    alignment exactly like :func:`sharded_pipecg_solve`, and the psum'd
    checksum column is returned as ``detect_history``.
    """
    if ip != "id":
        raise ValueError(
            "the 2D-grid body implements the pipecg ('id') inner-product "
            f"pairing only; got ip={ip!r}")
    ay, ax = axis_names
    axes = (ay, ax)
    hy = max(abs(dy) for dy, _ in doffs)
    hx = max(abs(dx) for _, dx in doffs)
    if b_local.ndim != 2:
        raise ValueError(
            "sharded_pipecg_solve_2d is single-RHS: b_local must be this "
            f"shard's (ly, lx) tile, got shape {b_local.shape}")
    ly, lx = b_local.shape
    dt = b_local.dtype
    if ly < 2 * hy or lx < 2 * hx:
        raise ValueError(
            f"2D-grid engine: local tile ({ly}, {lx}) is narrower than "
            f"the (2*hy, 2*hx) = ({2 * hy}, {2 * hx}) stencil reach")
    diag_k = doffs.index((0, 0))
    if M is None:
        invd = jnp.ones((ly, lx), dt)
    elif M == "jacobi":
        invd = (1.0 / bands_local[diag_k]).astype(dt)
    else:
        raise ValueError(
            "2D-grid engine preconditions in-kernel: M must be None or "
            f"'jacobi', got {M!r}")

    # loop-invariant operator extension: one 4-message exchange per solve
    bands_h = halo_exchange_2d(bands_local, hy, hx, ay, ax)
    invd_h = halo_exchange_2d(invd, hy, hx, ay, ax)
    csum_loc = _dia2d_column_checksum(doffs, bands_h, hy, hx).astype(dt)

    def mv(v):  # extent-0 matvec — init only; the scan fuses its own
        v_e = halo_exchange_2d(v, hy, hx, ay, ax)
        return _apply2d(doffs, bands_local, v_e, hy, hx)

    def partials(r, u, w):
        return jnp.stack([jnp.sum(r * u), jnp.sum(w * u), jnp.sum(r * r),
                          jnp.sum(r * w), jnp.sum(w * w),
                          jnp.sum(w) - jnp.sum(csum_loc * u)])

    x = jnp.zeros_like(b_local)
    r = b_local
    u = invd * r
    p = jnp.zeros_like(b_local)
    w = mv(u)
    red0 = partials(r, u, w)
    one = jnp.ones((), dt)
    state0 = dict(x=x, r=r, u=u, p=p, red=red0, gamma_prev=one,
                  alpha_prev=one, first=jnp.asarray(True),
                  done=jnp.asarray(False), iters=jnp.zeros((), jnp.int32))
    bb = jax.lax.psum(jnp.sum(b_local * b_local), axes)
    tol2 = jnp.asarray(tol, dt) ** 2 * bb

    def step(st, _):
        # ---- halo exchange first: depends only on the carried vectors,
        # never on the pending reduction ----
        u_e = halo_exchange_2d(st["u"], 2 * hy, 2 * hx, ay, ax)
        p_e = halo_exchange_2d(st["p"], 2 * hy, 2 * hx, ay, ax)
        # ---- split-phase: finish the reduction initiated LAST iteration
        # with one all-reduce over the whole (py, px) grid ----
        red = jax.lax.psum(st["red"], axes)
        gamma, delta, rr, chk = red[0], red[1], red[2], red[5]
        beta = jnp.where(st["first"], jnp.zeros_like(gamma),
                         gamma / st["gamma_prev"])
        alpha = jnp.where(st["first"], gamma / delta,
                          gamma / (delta - beta * gamma / st["alpha_prev"]))
        # recompute trick: extent (2hy, 2hx) -> (hy, hx) -> 0
        pp_e = u_e + beta * p_e
        s_e = _apply2d(doffs, bands_h, pp_e, hy, hx)
        u2_e = _crop2d(u_e, hy, hx) - alpha * invd_h * s_e
        w2 = _apply2d(doffs, bands_local, u2_e, hy, hx)
        pp = _crop2d(pp_e, 2 * hy, 2 * hx)
        s = _crop2d(s_e, hy, hx)
        u2 = _crop2d(u2_e, hy, hx)
        x2 = st["x"] + alpha * pp
        r2 = st["r"] - alpha * s
        red_new = partials(r2, u2, w2)
        if noise is not None:
            red_new = red_new + _noise_tick(noise, axes, dt)
        done = st["done"] | (rr <= tol2)
        frz = lambda nv, ov: jnp.where(st["done"], ov, nv)
        new = dict(x=frz(x2, st["x"]), r=frz(r2, st["r"]),
                   u=frz(u2, st["u"]), p=frz(pp, st["p"]),
                   red=frz(red_new, st["red"]),
                   gamma_prev=frz(gamma, st["gamma_prev"]),
                   alpha_prev=frz(alpha, st["alpha_prev"]),
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        return new, (jnp.sqrt(jnp.maximum(rr, 0.0)), chk)

    st, (hist, chk_hist) = jax.lax.scan(step, state0, None, length=maxiter)
    red_fin = jax.lax.psum(st["red"], axes)
    res = jnp.sqrt(jnp.maximum(red_fin[2], 0.0))
    hist = jnp.concatenate([hist[1:], res[None]])
    chk_hist = jnp.concatenate([chk_hist[1:], red_fin[5][None]])
    return SolveResult(x=st["x"], iters=st["iters"], res_norm=res,
                       res_history=hist, detect_history=chk_hist)


# ---------------------------------------------------------------------------
# Sharded BSR: block-DIA halo body, same split-phase psum carry
# ---------------------------------------------------------------------------

def _bsr_apply(boffs, bblocks_e: jnp.ndarray, v_e: jnp.ndarray,
               hb: int) -> jnp.ndarray:
    """Block-banded apply ``y = A v`` on a halo-extended block-row range.

    ``bblocks_e`` is ``(n_boff, obr, bs, bs)`` — the per-block-row dense
    blocks at the OUTPUT block rows (``BsrMatrix.block_bands``) — and
    ``v_e`` is ``(..., obr + 2*hb, bs)``, the input extended ``hb`` block
    rows beyond the output extent:
    ``y[i] = sum_m bblocks_e[m, i] @ v_e[i + hb + boffs[m]]``.
    """
    obr = bblocks_e.shape[1]
    y = jnp.zeros(v_e.shape[:-2] + (obr, v_e.shape[-1]), v_e.dtype)
    for m, off in enumerate(boffs):
        sl = jax.lax.slice_in_dim(v_e, hb + off, hb + off + obr, axis=-2)
        y = y + jnp.einsum("rij,...rj->...ri", bblocks_e[m], sl)
    return y


def _bsr_column_checksum_local(boffs, bblocks_e: jnp.ndarray,
                               hb: int) -> jnp.ndarray:
    """This shard's ``(lbr, bs)`` slice of the GLOBAL column sums A^T 1.

    Block column ``j`` is written by block row ``j - boffs[m]``, whose
    blocks live inside the ``hb``-extended local block bands — the
    block-DIA rendering of ``kernels/checksum.py``.
    """
    lbr = bblocks_e.shape[1] - 2 * hb
    colsums = jnp.sum(bblocks_e, axis=-2)        # (n_boff, lbr + 2hb, bs)
    c = jnp.zeros((lbr, bblocks_e.shape[-1]), bblocks_e.dtype)
    for m, off in enumerate(boffs):
        c = c + jax.lax.slice_in_dim(colsums[m], hb - off, hb - off + lbr,
                                     axis=0)
    return c


def sharded_pipecg_bsr_solve(boffs, bblocks_local, b_local, *,
                             axis_name: str, ip: str = "id", M=None,
                             maxiter: int = 100, tol: float = 0.0,
                             noise: Optional[NoiseHook] = None
                             ) -> SolveResult:
    """Per-shard PIPECG body for a BSR operator, sharded on block rows.

    Runs INSIDE shard_map.  The driver converts the blocked-ELL layout to
    block-DIA form (``BsrMatrix.block_bands``: static block offsets +
    ``(n_boff, nbr, bs, bs)`` dense blocks) so the body can mirror the
    1D DIA engine in BLOCK coordinates: the halo is ``hb = max|boffs|``
    block rows, u/p travel once per iteration at width ``2*hb`` block
    rows (:func:`_exchange_along` over the vectors' block axis), and the
    recompute trick contracts the extent ``2hb -> hb -> 0`` through
    p' = u + beta p, s' = A p', u' = u - alpha diag^-1 s', w' = A u'.
    The split-phase structure is IDENTICAL to
    :func:`sharded_pipecg_solve`: iteration i's partial ``(6,)``
    reduction row (five Krylov partials + the ABFT checksum partial
    against the locally sliced global column sums) is carried unreduced
    across the scan boundary and finished by iteration i+1's single
    ``psum`` AFTER the halo ppermutes are issued.

    Single-RHS (``b_local`` is this shard's ``(lbr, bs)`` block rows);
    ``M`` is None or ``"jacobi"``.  History alignment and
    ``detect_history`` match the 1D DIA body.
    """
    if ip != "id":
        raise ValueError(
            "the sharded BSR body implements the pipecg ('id') "
            f"inner-product pairing only; got ip={ip!r}")
    hb = max(abs(int(o)) for o in boffs)
    if b_local.ndim != 2:
        raise ValueError(
            "sharded_pipecg_bsr_solve is single-RHS: b_local must be this "
            f"shard's (lbr, bs) block rows, got shape {b_local.shape}")
    lbr, bs = b_local.shape
    dt = b_local.dtype
    if lbr < 2 * hb:
        raise ValueError(
            f"sharded BSR engine: local shard of {lbr} block rows is "
            f"narrower than the 2*hb={2 * hb} block-stencil reach")
    if M is None:
        invd = jnp.ones((lbr, bs), dt)
    elif M == "jacobi":
        diag_m = boffs.index(0)
        d = jnp.einsum("rii->ri", bblocks_local[diag_m])
        invd = (1.0 / d).astype(dt)
    else:
        raise ValueError(
            "sharded BSR engine preconditions in-kernel: M must be None "
            f"or 'jacobi', got {M!r}")

    # loop-invariant operator extension: one exchange per solve
    def ext_rows(v, w):
        lo, hi = _exchange_along(v, w, axis_name, axis=-3 if v.ndim == 4
                                 else -2)
        ax = -3 if v.ndim == 4 else -2
        return jnp.concatenate([lo, v, hi], axis=ax)

    bblocks_h = ext_rows(bblocks_local, hb)      # (n_boff, lbr+2hb, bs, bs)
    invd_h = ext_rows(invd, hb)
    csum_loc = _bsr_column_checksum_local(boffs, bblocks_h, hb).astype(dt)

    def ext_vec(v, w):
        lo, hi = _exchange_along(v, w, axis_name, axis=-2)
        return jnp.concatenate([lo, v, hi], axis=-2)

    def mv(v):  # extent-0 matvec — init only
        return _bsr_apply(boffs, bblocks_local, ext_vec(v, hb), hb)

    def partials(r, u, w):
        return jnp.stack([jnp.sum(r * u), jnp.sum(w * u), jnp.sum(r * r),
                          jnp.sum(r * w), jnp.sum(w * w),
                          jnp.sum(w) - jnp.sum(csum_loc * u)])

    crop = lambda v, c: v[..., c:v.shape[-2] - c, :]
    x = jnp.zeros_like(b_local)
    r = b_local
    u = invd * r
    p = jnp.zeros_like(b_local)
    w = mv(u)
    red0 = partials(r, u, w)
    one = jnp.ones((), dt)
    state0 = dict(x=x, r=r, u=u, p=p, red=red0, gamma_prev=one,
                  alpha_prev=one, first=jnp.asarray(True),
                  done=jnp.asarray(False), iters=jnp.zeros((), jnp.int32))
    bb = jax.lax.psum(jnp.sum(b_local * b_local), axis_name)
    tol2 = jnp.asarray(tol, dt) ** 2 * bb

    def step(st, _):
        # halo exchange first (depends only on carried vectors), then the
        # split-phase psum finishing LAST iteration's reduction
        u_e = ext_vec(st["u"], 2 * hb)
        p_e = ext_vec(st["p"], 2 * hb)
        red = jax.lax.psum(st["red"], axis_name)
        gamma, delta, rr, chk = red[0], red[1], red[2], red[5]
        beta = jnp.where(st["first"], jnp.zeros_like(gamma),
                         gamma / st["gamma_prev"])
        alpha = jnp.where(st["first"], gamma / delta,
                          gamma / (delta - beta * gamma / st["alpha_prev"]))
        pp_e = u_e + beta * p_e                       # extent 2hb
        s_e = _bsr_apply(boffs, bblocks_h, pp_e, hb)  # extent hb
        u2_e = crop(u_e, hb) - alpha * invd_h * s_e   # extent hb
        w2 = _bsr_apply(boffs, bblocks_local, u2_e, hb)
        pp = crop(pp_e, 2 * hb)
        s = crop(s_e, hb)
        u2 = crop(u2_e, hb)
        x2 = st["x"] + alpha * pp
        r2 = st["r"] - alpha * s
        red_new = partials(r2, u2, w2)
        if noise is not None:
            red_new = red_new + _noise_tick(noise, axis_name, dt)
        done = st["done"] | (rr <= tol2)
        frz = lambda nv, ov: jnp.where(st["done"], ov, nv)
        new = dict(x=frz(x2, st["x"]), r=frz(r2, st["r"]),
                   u=frz(u2, st["u"]), p=frz(pp, st["p"]),
                   red=frz(red_new, st["red"]),
                   gamma_prev=frz(gamma, st["gamma_prev"]),
                   alpha_prev=frz(alpha, st["alpha_prev"]),
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        return new, (jnp.sqrt(jnp.maximum(rr, 0.0)), chk)

    st, (hist, chk_hist) = jax.lax.scan(step, state0, None, length=maxiter)
    red_fin = jax.lax.psum(st["red"], axis_name)
    res = jnp.sqrt(jnp.maximum(red_fin[2], 0.0))
    hist = jnp.concatenate([hist[1:], res[None]])
    chk_hist = jnp.concatenate([chk_hist[1:], red_fin[5][None]])
    return SolveResult(x=st["x"], iters=st["iters"], res_norm=res,
                       res_history=hist, detect_history=chk_hist)


# ---------------------------------------------------------------------------
# Sharded pipelined BiCGStab: 3 halo pairs + ONE (7, 6) Gram psum per body
# ---------------------------------------------------------------------------

def sharded_pipebicgstab_solve(offsets: Tuple[int, ...], bands_local,
                               b_local, *, axis_name: str, M=None,
                               maxiter: int = 100, tol: float = 0.0,
                               block: Optional[int] = None,
                               n_shards: int = 1,
                               noise: Optional[NoiseHook] = None,
                               precision=None
                               ) -> SolveResult:
    """Per-shard pipelined BiCGStab body of the ShardedFusedEngine.

    Runs INSIDE shard_map.  Each iteration is one halo-aware Pallas sweep
    (kernels/pipebicgstab_fused.py::pipebicgstab_halo) plus one scalar
    psum of the (7, 6) partial Gram — six basis rows plus the ABFT
    checksum partial ``1^T t' - c^T w'`` riding the same payload
    (kernels/checksum.py; returned per iteration as
    ``SolveResult.detect_history``) — and the psum is *split-phase*: the
    kernel of iteration i emits the partial Gram that is carried
    unreduced across the scan boundary; iteration i+1 first issues its
    halo ppermutes of w/t/c (which depend only on the carried vectors),
    then finishes the reduction and unwinds ALL FOUR classical BiCGStab
    inner products from it (core/krylov/bicgstab.py::pbicgstab_scalars)
    before gating the kernel launch.  Inside one loop body the single
    all-reduce and the collective-permutes are therefore mutually
    independent — four hidden synchronizations per iteration where the
    PIPECG body hides two (launch/hlo_analysis.py::split_phase_overlap
    certifies the window, with exactly one all-reduce per body).

    Single-RHS (``b_local`` (n_local,)).  ``M`` may be None or
    ``"jacobi"`` — right preconditioning folded into the local bands with
    one invd halo exchange per solve; residuals are TRUE residuals of
    ``A x = b`` and ``x`` is unscaled locally at the end.  The residual
    history is rolled into the classical alignment exactly like
    ``sharded_pipecg_solve``.

    ``precision`` works as in :func:`sharded_pipecg_solve`: storage
    demotion covers the six carried chain vectors r/w/t/pa/a/c and the
    operator extension (x, the (7, 6) partial Gram and the scalar
    recurrences stay full precision); ``wire='int8'`` compresses the
    three w/t/c halo pairs with optional sender-side error feedback,
    and ``wire_gram='int8'`` (off by default, known-unsafe) the carried
    Gram payload minus its preserved ABFT checksum entry.
    """
    from repro.core.krylov.bicgstab import pbicgstab_scalars
    from repro.kernels import ops as kops

    policy = _resolve_precision(precision)
    halo = max(abs(o) for o in offsets)
    if b_local.ndim != 1:
        raise ValueError(
            "the sharded pipebicgstab path is single-RHS; batch over "
            "solves instead of RHS columns")
    n_local = b_local.shape[0]
    dt = b_local.dtype
    if n_local < 2 * halo:
        raise ValueError(
            f"sharded_fused engine: local shard of {n_local} rows is "
            f"narrower than the 2*halo={2 * halo} stencil reach")
    if M == "jacobi":
        invd = (1.0 / bands_local[offsets.index(0)]).astype(dt)
        il, ir = halo_exchange_cols(invd, halo, axis_name)
        invd_ext = jnp.concatenate([il, invd, ir])
        # A_hat[i, i+off] = A[i, i+off] * invd[i+off]  (column scaling,
        # consistent across shard boundaries via the exchanged invd rows)
        rows = [bands_local[k] * jax.lax.dynamic_slice_in_dim(
                    invd_ext, halo + off, n_local)
                for k, off in enumerate(offsets)]
        bands_local = jnp.stack(rows)
        unscale = invd
    elif M is None:
        unscale = None
    else:
        raise ValueError(
            "sharded pipebicgstab preconditions by folding Jacobi into "
            f"the bands: M must be None or 'jacobi', got {M!r}")

    # loop-invariant operator extension: one ppermute per solve
    bl, br = halo_exchange_cols(bands_local, halo, axis_name)
    bands_ext = jnp.concatenate([bl, bands_local, br], axis=-1)
    # local slice of the global column checksum c = A_hat^T 1 (computed
    # AFTER the Jacobi fold so the checksum guards the operator the
    # kernel actually applies; kernels/checksum.py)
    from repro.kernels.checksum import dia_column_checksum
    csum_loc = dia_column_checksum(offsets, bands_ext, halo=halo).astype(dt)
    # storage demotion AFTER the checksum (full-precision reference)
    sdt = policy.storage_dtype
    if sdt is not None:
        bands_ext = bands_ext.astype(sdt)
    wire_halo = policy.wire == "int8"
    wire_gram = policy.wire_gram == "int8"
    use_ef = policy.error_feedback

    def mv(v):  # halo matvec — init only; the scan uses the kernel
        lv, rv = halo_exchange_cols(v, halo, axis_name)
        v_ext = jnp.concatenate([lv, v, rv])
        y = jnp.zeros_like(v)
        for kb, off in enumerate(offsets):
            y = y + bands_local[kb] * jax.lax.dynamic_slice_in_dim(
                v_ext, halo + off, n_local)
        return y

    x = jnp.zeros_like(b_local)
    r = b_local                 # r0 = b - A_hat * 0
    r_hat = r
    w = mv(r)
    t = mv(w)
    zero = jnp.zeros_like(b_local)
    V0 = jnp.stack([r, w, t, zero, zero, r_hat])
    G0 = V0 @ V0.T              # this shard's PARTIAL initial Gram
    # 7th row: the ABFT checksum partial 1^T t - c^T w of the init basis,
    # matching the kernel's (7, 6) partial-Gram layout
    chk0 = jnp.sum(t) - jnp.sum(csum_loc * w)
    G0 = jnp.concatenate([G0, jnp.zeros((1, 6), dt).at[0, 0].set(chk0)],
                         axis=0)
    # carried chains demote to storage precision (x and the Gram stay dt)
    if sdt is not None:
        r, w, t = r.astype(sdt), w.astype(sdt), t.astype(sdt)
        r_hat = r_hat.astype(sdt)
        zero = zero.astype(sdt)
    chk_mask = jnp.zeros((7, 6), bool).at[6, 0].set(True)
    if wire_gram:
        from repro.distributed import compression as comp
        G0, gef0 = comp.compress_gram(G0, None, preserve=chk_mask)
        if not use_ef:
            gef0 = jnp.zeros_like(gef0)
    one = jnp.ones((), dt)
    eps = jnp.asarray(1e-300 if dt == jnp.float64 else 1e-30, dt)
    state0 = dict(x=x, r=r, w=w, t=t, pa=zero, a=zero, c=zero, G=G0,
                  rho_prev=one, alpha_prev=one, omega_prev=one,
                  first=jnp.asarray(True),
                  done=jnp.asarray(False), iters=jnp.asarray(0, jnp.int32))
    if wire_gram:
        state0["gef"] = gef0
    if wire_halo:
        ef0 = jnp.zeros((2 * halo,), r.dtype)
        state0.update(efw_l=ef0, efw_r=ef0, eft_l=ef0, eft_r=ef0,
                      efc_l=ef0, efc_r=ef0)
    bb = jax.lax.psum(jnp.sum(b_local * b_local), axis_name)
    tol2 = jnp.asarray(tol, dt) ** 2 * bb

    def step(st, _):
        # ---- halo exchange for THIS iteration's sweep: depends only on
        # the carried vectors, NOT on the pending reduction ----
        if wire_halo:
            wl, wr, efw_l, efw_r = halo_exchange_compressed(
                st["w"], 2 * halo, axis_name, st["efw_l"], st["efw_r"],
                use_ef)
            tl, tr, eft_l, eft_r = halo_exchange_compressed(
                st["t"], 2 * halo, axis_name, st["eft_l"], st["eft_r"],
                use_ef)
            cl, cr, efc_l, efc_r = halo_exchange_compressed(
                st["c"], 2 * halo, axis_name, st["efc_l"], st["efc_r"],
                use_ef)
        else:
            wl, wr = halo_exchange_cols(st["w"], 2 * halo, axis_name)
            tl, tr = halo_exchange_cols(st["t"], 2 * halo, axis_name)
            cl, cr = halo_exchange_cols(st["c"], 2 * halo, axis_name)
        # ---- split-phase: finish the reduction initiated LAST iteration;
        # its only consumers are the scalar recurrences below ----
        G = jax.lax.psum(st["G"], axis_name)
        chk = G[6, 0]   # ABFT checksum residual, same carried psum
        rr2, rho, alpha, beta, omega = pbicgstab_scalars(
            G, st["rho_prev"], st["alpha_prev"], st["omega_prev"],
            st["first"], eps)
        x, r, w, t, pa, a, c, G_new = kops.pipebicgstab_halo_step(
            offsets, bands_ext, st["x"], st["r"], st["w"], st["t"],
            st["pa"], st["a"], st["c"], r_hat, wl, wr, tl, tr, cl, cr,
            alpha, beta, omega, block=block, n_shards=n_shards)
        if wire_gram:
            # int8 wire grid for the carried Gram payload, checksum entry
            # preserved; psum count/dataflow untouched (compression.py)
            from repro.distributed import compression as comp
            G_new, gef = comp.compress_gram(
                G_new, st["gef"] if use_ef else None, preserve=chk_mask)
        if noise is not None:
            # the tick rides the partial Gram so the sampled stall gates
            # the next psum (critical path)
            G_new = G_new + _noise_tick(noise, axis_name, dt)

        done = st["done"] | (rr2 <= tol2)
        if not policy.is_default:
            # low-precision breakdown guard (cf. sharded_pipecg_solve):
            # freeze at the last good iterate instead of carrying NaN
            done = done | ~(jnp.isfinite(rr2) & jnp.isfinite(alpha)
                            & jnp.isfinite(omega))
        # freeze AT the iterate whose residual met the tolerance (the
        # non-monotone-BiCGStab convention of the local pipebicgstab)
        frz = lambda nv, ov: jnp.where(done, ov, nv)
        new = dict(x=frz(x, st["x"]), r=frz(r, st["r"]), w=frz(w, st["w"]),
                   t=frz(t, st["t"]), pa=frz(pa, st["pa"]),
                   a=frz(a, st["a"]), c=frz(c, st["c"]),
                   G=frz(G_new, st["G"]),
                   rho_prev=frz(rho, st["rho_prev"]),
                   alpha_prev=frz(alpha, st["alpha_prev"]),
                   omega_prev=frz(omega, st["omega_prev"]),
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        if wire_halo:
            new.update(efw_l=efw_l, efw_r=efw_r, eft_l=eft_l, eft_r=eft_r,
                       efc_l=efc_l, efc_r=efc_r)
        if wire_gram:
            new["gef"] = gef if use_ef else st["gef"]
        return new, (jnp.sqrt(jnp.maximum(rr2, 0.0)), chk)

    st, (hist, chk_hist) = jax.lax.scan(step, state0, None, length=maxiter)
    G_fin = jax.lax.psum(st["G"], axis_name)
    res = jnp.sqrt(jnp.maximum(G_fin[0, 0], 0.0))
    # roll the shifted history into the classical alignment
    hist = jnp.concatenate([hist[1:], res[None]])
    chk_hist = jnp.concatenate([chk_hist[1:], G_fin[6, 0][None]])
    x_out = st["x"] if unscale is None else st["x"] * unscale
    return SolveResult(x=x_out, iters=st["iters"], res_norm=res,
                       res_history=hist, detect_history=chk_hist)


# ---------------------------------------------------------------------------
# Depth-l sharded solve: one Gram psum + one l*halo ppermute per l iterations
# ---------------------------------------------------------------------------

def sharded_pipecg_depth_solve(offsets: Tuple[int, ...], bands_local,
                               b_local, *, axis_name: str, l: int,
                               M=None, maxiter: int = 100, tol: float = 0.0,
                               block: Optional[int] = None,
                               n_shards: int = 1,
                               noise: Optional[NoiseHook] = None,
                               precision=None
                               ) -> SolveResult:
    """Per-shard depth-l pipelined CG body (ghost-basis blocks).

    Runs INSIDE shard_map.  Each block of ``l`` iterations is ONE
    halo-aware ghost-chain sweep
    (kernels/pipecg_spmv_fused.py::ghost_chain_halo) preceded by ONE
    ``lax.ppermute`` pair of l*halo-wide edge strips of p and r, and
    followed by ONE ``lax.psum`` of the (2l+1, 2l+1) partial Gram — the
    l-deep fused reduction that replaces the depth-1 engine's l
    per-iteration (k, 5) rows.  Depth therefore amortizes BOTH the
    collective count (1/l reductions per iteration) and the message
    count (one big halo strip instead of l small ones); the permutes of
    a block have no data dependence on the block's all-reduce
    (``launch/hlo_analysis.py::split_phase_overlap`` still certifies the
    overlap window, and its ``depth`` mode additionally asserts the
    one-reduction-per-body amortized structure).

    Semantics match ``core/krylov/pipeline.py::pipecg_l`` with
    ``rr=0`` (the sharded path reconstructs r from the chain so the
    block body stays free of post-reduction halo exchanges).  The ABFT
    state deviation ``1^T (b - A x - r)`` is evaluated once per block
    from the column checksum (two local dots, bundled into the Gram psum
    as a variadic operand — still one all-reduce per body) and returned
    as ``SolveResult.detect_history``.  ``M`` may
    be None or ``"jacobi"`` (symmetrized in, locally, with one halo
    exchange of the scaling vector per solve); residual norms are then
    preconditioned norms.

    ``precision`` supports STORAGE demotion only (carried p/r and the
    operator extension in bf16; the chain, Gram and block recurrences
    stay full precision via the kernel's ``accum_dtype``).  The depth
    path's Gram psum is consumed inside the same block body — it never
    rides the wire as a carried payload — so ``wire='int8'`` is
    rejected rather than silently modeling a wire that does not exist.
    """
    from repro.core.krylov.pipeline import _block_cg_steps, _shift_matrix
    from repro.kernels import ops as kops

    policy = _resolve_precision(precision)
    if policy.wire != "fp32" or policy.wire_gram != "fp32":
        raise ValueError(
            "the depth-l sharded path exchanges one l*halo strip and "
            "finishes its Gram psum inside the same block body: int8 "
            "wire compression applies to the depth-1 "
            "pipecg/pipebicgstab bodies only")
    if b_local.ndim != 1:
        raise ValueError(
            "the depth-l sharded path is single-RHS; use l=1 for the "
            "batched pipecg_multi engine")
    halo = max(abs(o) for o in offsets)
    H = l * halo
    n_local = b_local.shape[0]
    dt = b_local.dtype
    if n_local < 2 * H:
        raise ValueError(
            f"sharded depth-l engine: local shard of {n_local} rows is "
            f"narrower than the 2*l*halo={2 * H} chain reach")
    if M == "jacobi":
        ds = 1.0 / jnp.sqrt(bands_local[offsets.index(0)].astype(dt))
        dl, dr = halo_exchange_cols(ds, halo, axis_name)
        ds_ext = jnp.concatenate([dl, ds, dr])
        rows = [bands_local[k] * ds * jax.lax.dynamic_slice_in_dim(
                    ds_ext, halo + off, n_local)
                for k, off in enumerate(offsets)]
        bands_local = jnp.stack(rows)
        b_local = b_local * ds
        unscale = ds
    elif M is None:
        unscale = None
    else:
        raise ValueError(
            "sharded depth-l engine preconditions via the symmetrized "
            f"operator: M must be None or 'jacobi', got {M!r}")
    theta = jax.lax.pmax(jnp.max(jnp.sum(jnp.abs(bands_local), axis=0)),
                         axis_name)

    # loop-invariant operator extension (+l*halo), one exchange per solve
    bl, br = halo_exchange_cols(bands_local, H, axis_name)
    bands_ext = jnp.concatenate([bl, bands_local, br], axis=-1)
    # local slice of the global column checksum (of the possibly
    # symmetrized operator) for the per-block state-deviation detector
    from repro.kernels.checksum import dia_column_checksum
    csum_loc = dia_column_checksum(offsets, bands_ext, halo=H).astype(dt)
    # storage demotion AFTER theta and the checksum (both reference the
    # full-precision operator); the chain kernel accumulates at dt
    sdt = policy.storage_dtype
    if sdt is not None:
        bands_ext = bands_ext.astype(sdt)

    x = jnp.zeros_like(b_local)
    r = b_local if sdt is None else b_local.astype(sdt)
    p = r
    Tm = _shift_matrix(l, dt)
    nblocks = -(-maxiter // l)
    # one pre-scan psum covers both the tolerance scale and the 1^T b leg
    # of the deviation detector (variadic tuple: still a single psum)
    bb, bsum = jax.lax.psum(
        (jnp.sum(b_local * b_local), jnp.sum(b_local)), axis_name)
    tol2 = jnp.asarray(tol, dt) ** 2 * bb

    def body(st, _):
        # ONE halo exchange per block: l*halo-wide strips of p and r,
        # independent of this block's (and any pending) reduction
        pl_, pr_ = halo_exchange_cols(st["p"], H, axis_name)
        rl_, rr_ = halo_exchange_cols(st["r"], H, axis_name)
        C, gram = kops.ghost_chain_halo_step(
            offsets, bands_ext, st["p"], st["r"], pl_, pr_, rl_, rr_,
            theta, l, block=block, n_shards=n_shards,
            accum_dtype=None if sdt is None else dt)
        # the block's single fused reduction: one psum per l iterations —
        # the ABFT state-deviation partial c^T x + 1^T r rides it as an
        # extra ROW of the Gram payload (one all-reduce in HLO; the
        # hlo_analysis depth gate counts exactly one per body), giving
        # delta = 1^T b - c^T x - 1^T r == 1^T (b - A x - r) per block.
        # Riding INSIDE the array (not as a tuple sibling) means a
        # corrupted reduction payload corrupts the detector entry with it
        # — the injector's tick cannot poison the Gram while leaving the
        # detector clean
        devpart = jnp.sum(csum_loc * st["x"]) + jnp.sum(st["r"].astype(dt))
        gram_ext = jnp.concatenate(
            [gram, jnp.zeros((1, gram.shape[-1]), dt).at[0, 0]
             .set(devpart)], axis=0)
        if noise is not None:
            gram_ext = gram_ext + _noise_tick(noise, axis_name, dt)
        Ge = jax.lax.psum(gram_ext, axis_name)
        G, devp = Ge[:-1], Ge[-1, 0]
        delta = bsum - devp
        xc, rc, pc, hist = _block_cg_steps(G, Tm, l, theta, st["done"])
        # chain combinations accumulate at dt (bf16 C promotes against
        # the dt coefficients); the carried r/p re-demote to storage
        x_new = jnp.where(st["done"], st["x"],
                          st["x"] + (C.T @ xc).astype(dt))
        r_new = jnp.where(st["done"], st["r"],
                          (C.T @ rc).astype(st["r"].dtype))
        p_new = jnp.where(st["done"], st["p"],
                          (C.T @ pc).astype(st["p"].dtype))
        rr2 = jnp.maximum(rc @ G @ rc, 0.0)   # already global (G is)
        done = st["done"] | (rr2 <= tol2)
        hist = jnp.where(st["done"], jnp.sqrt(rr2), hist)
        iters = st["iters"] + jnp.where(st["done"], 0, l).astype(jnp.int32)
        return (dict(x=x_new, r=r_new, p=p_new, done=done, iters=iters),
                (hist, delta))

    state0 = dict(x=x, r=r, p=p, done=jnp.asarray(False),
                  iters=jnp.asarray(0, jnp.int32))
    st, (hist, det_blocks) = jax.lax.scan(body, state0, None,
                                          length=nblocks)
    hist = hist.reshape(-1)[:maxiter]
    # per-block deviation, repeated to per-iteration length so every
    # solver's detect_history shares the (maxiter,) shape contract
    det = jnp.repeat(det_blocks, l)[:maxiter]
    r_fin = st["r"].astype(dt)
    res = jnp.sqrt(jnp.maximum(
        jax.lax.psum(jnp.sum(r_fin * r_fin), axis_name), 0.0))
    x_out = st["x"] if unscale is None else st["x"] * unscale
    return SolveResult(x=x_out, iters=jnp.minimum(st["iters"], maxiter),
                       res_norm=res, res_history=hist, detect_history=det)


# pipelined solvers the sharded engine can express, by function name
_SHARDED_IP = {"pipecg": "id", "pipecg_multi": "id", "pipecr": "A",
               "pipecg_l": "id"}
# solvers routed through the dedicated Gram-reduction body instead of the
# (gamma, delta) ip dispatch above
_SHARDED_GRAM = ("pipebicgstab",)


def _pop_basic_kw(solver_kw, path: str):
    """Extract (M, maxiter, tol) and reject options the given sharded
    path does not implement (depth, mixed precision, warm start, ...)."""
    M = solver_kw.pop("M", None)
    maxiter = solver_kw.pop("maxiter", 100)
    tol = solver_kw.pop("tol", 0.0)
    depth = int(solver_kw.pop("l", 1))
    precision = _resolve_precision(solver_kw.pop("precision", None))
    if depth > 1:
        raise ValueError(
            f"the {path} sharded body is depth-1 only (got l={depth}); "
            "depth-l ghost blocks are implemented for the 1D DIA path")
    if not precision.is_default:
        raise ValueError(
            f"the {path} sharded body runs at the solve dtype only; "
            "mixed-precision policies are implemented for the 1D DIA path")
    if solver_kw:
        raise TypeError(
            f"unsupported kwargs for the {path} sharded path: "
            f"{sorted(solver_kw)}")
    return M, maxiter, tol


def _engine_solve_2d(name, ip, A: DiaMatrix, b, mesh: Mesh, eng, *,
                     noise=None, block=None, **solver_kw) -> SolveResult:
    """Drive :func:`sharded_pipecg_solve_2d` over a 2-axis process grid.

    The operator's ``halo_spec`` (N/S/W/E neighbors, ``(hy, hx)`` strip
    widths) is realized by tiling the ``(ny, nx)`` grid over the mesh
    axes: ``b`` and each band reshape to their grid layout and shard
    BOTH trailing axes, so every shard owns an ``(ny/py, nx/px)`` tile.
    """
    ay, ax = mesh.axis_names
    py, px = mesh.devices.shape
    if A.grid_shape is None:
        raise ValueError(
            "a 2-axis mesh needs a DiaMatrix built with grid_shape="
            "(ny, nx) (e.g. operators.laplacian_2d) so its offsets "
            "decompose into (dy, dx) grid displacements")
    if name != "pipecg":
        raise ValueError(
            f"the 2D-grid sharded body implements pipecg only; got {name!r}")
    if b.ndim != 1:
        raise ValueError(
            "the 2D-grid sharded body is single-RHS; got batched b of "
            f"shape {b.shape}")
    if block is not None:
        raise ValueError(
            "block= tunes the 1D halo kernel; the 2D-grid body has no "
            "Pallas tile to override")
    M, maxiter, tol = _pop_basic_kw(solver_kw, "2D-grid")
    ny, nx = A.grid_shape
    if ny % py or nx % px:
        raise ValueError(
            f"grid {A.grid_shape} does not tile evenly over the "
            f"({py}, {px}) process grid")
    doffs = tuple(A.grid_offsets())
    body = eng.body("pipecg", "dia2d")
    bands2 = A.bands.reshape((len(A.offsets), ny, nx))
    b2 = b.reshape(ny, nx)

    def run(bands_local, b_local):
        return body(doffs, bands_local, b_local, axis_names=(ay, ax),
                    ip=ip, M=M, maxiter=maxiter, tol=tol, noise=noise)

    out_specs = SolveResult(x=P(ay, ax), iters=P(), res_norm=P(),
                            res_history=P(), detect_history=P())
    fn = jax.shard_map(run, mesh=mesh, in_specs=(P(None, ay, ax), P(ay, ax)),
                       out_specs=out_specs, check_vma=False)
    res = fn(bands2, b2)
    return res._replace(x=res.x.reshape(b.shape))


def _engine_solve_bsr(name, ip, A, b, mesh: Mesh, eng, *, noise=None,
                      block=None, **solver_kw) -> SolveResult:
    """Drive :func:`sharded_pipecg_bsr_solve` over block rows.

    Converts the blocked-ELL layout to its block-DIA form once on the
    host (``BsrMatrix.block_bands``), reshapes ``b`` to ``(nbr, bs)``
    and shards the block-row axis over the (single) mesh axis — the
    1D W/E decomposition the operator's ``halo_spec`` describes.
    """
    axes = mesh.axis_names
    if len(axes) != 1:
        raise ValueError(
            "the sharded BSR body shards block rows over a single mesh "
            f"axis; got axes {axes!r}")
    axis = axes[0]
    if name != "pipecg":
        raise ValueError(
            f"the sharded BSR body implements pipecg only; got {name!r}")
    if b.ndim != 1:
        raise ValueError(
            "the sharded BSR body is single-RHS; got batched b of shape "
            f"{b.shape}")
    if block is not None:
        raise ValueError(
            "block= tunes the 1D DIA halo kernel; the sharded BSR body "
            "has no Pallas tile to override")
    M, maxiter, tol = _pop_basic_kw(solver_kw, "BSR")
    boffs, bblocks = A.block_bands()
    n_dev = int(mesh.devices.size)
    if A.n_block_rows % n_dev:
        raise ValueError(
            f"{A.n_block_rows} block rows do not shard evenly over "
            f"{n_dev} devices")
    body = eng.body("pipecg", "bsr")
    b2 = b.reshape(A.n_block_rows, A.bs)

    def run(bb_local, b_local):
        return body(boffs, bb_local, b_local, axis_name=axis, ip=ip, M=M,
                    maxiter=maxiter, tol=tol, noise=noise)

    out_specs = SolveResult(x=P(axis, None), iters=P(), res_norm=P(),
                            res_history=P(), detect_history=P())
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(P(None, axis, None, None), P(axis, None)),
                       out_specs=out_specs, check_vma=False)
    res = fn(bblocks, b2)
    return res._replace(x=res.x.reshape(b.shape))


def _distributed_engine_solve(solver, A, b, mesh: Mesh, eng, *,
                              noise=None, block=None, **solver_kw
                              ) -> SolveResult:
    """shard_map entry for the ShardedFusedEngine path.

    Routes on the operator's declared format and the mesh rank through
    the engine's dispatch table (``ShardedFusedEngine.body``):
    ``DiaMatrix`` on a 1-axis mesh runs the historical halo-kernel
    bodies; ``DiaMatrix`` with a ``grid_shape`` on a 2-axis mesh runs
    :func:`sharded_pipecg_solve_2d` (the tile decomposition its
    ``halo_spec`` describes); ``BsrMatrix`` runs
    :func:`sharded_pipecg_bsr_solve` over block rows.
    """
    from repro.core.krylov.operator import BsrMatrix

    axes = mesh.axis_names
    name = getattr(solver, "__name__", str(solver))
    ip = _SHARDED_IP.get(name)
    if ip is None and name not in _SHARDED_GRAM:
        raise ValueError(
            "engine='sharded_fused' supports pipecg / pipecg_multi / "
            f"pipecr / pipecg_l / pipebicgstab; got solver {name!r}")
    if isinstance(A, BsrMatrix):
        return _engine_solve_bsr(name, ip, A, b, mesh, eng, noise=noise,
                                 block=block, **solver_kw)
    if not isinstance(A, DiaMatrix):
        raise ValueError(
            "engine='sharded_fused' needs a DiaMatrix or BsrMatrix "
            f"operator; got {type(A).__name__}")
    if len(axes) == 2:
        return _engine_solve_2d(name, ip, A, b, mesh, eng, noise=noise,
                                block=block, **solver_kw)
    if len(axes) != 1:
        raise ValueError(
            "engine='sharded_fused' needs a 1-axis (flattened) or 2-axis "
            f"(process-grid) mesh; got axes {axes!r}")
    axis = axes[0]
    M = solver_kw.pop("M", None)
    maxiter = solver_kw.pop("maxiter", 100)
    tol = solver_kw.pop("tol", 0.0)
    depth = int(solver_kw.pop("l", 1))
    precision = _resolve_precision(solver_kw.pop("precision", None))
    x0 = solver_kw.pop("x0", None)
    carried = solver_kw.pop("carried", None)
    with_state = bool(solver_kw.pop("with_state", False))
    if solver_kw:
        raise TypeError(
            f"unsupported kwargs for the sharded_fused path: {sorted(solver_kw)}")
    if depth > 1 and name != "pipecg_l":
        raise ValueError(
            f"pipeline depth l={depth} needs solver pipecg_l, got {name!r}")
    warm = x0 is not None or carried is not None or with_state
    if warm and (name in _SHARDED_GRAM or depth > 1):
        raise ValueError(
            "x0= / carried= / with_state= (elastic warm start) are "
            "implemented for the depth-1 pipecg/pipecr bodies only; the "
            f"{name!r} (l={depth}) path cannot resume mid-recurrence")
    n_shards = int(mesh.devices.size)
    batched = b.ndim == 2
    spec_v = P(None, axis) if batched else P(axis)

    # elastic warm-start operands ride into shard_map with their own
    # specs: vectors shard the point axis, recurrence scalars replicate
    in_specs = [P(None, axis), spec_v]
    extra = []
    if x0 is not None:
        in_specs.append(spec_v)
        extra.append(jnp.asarray(x0))
    if carried is not None:
        carried = {k: jnp.asarray(v) for k, v in carried.items()}
        in_specs.append({k: (P(None, axis) if v.ndim == 2 else P())
                         for k, v in carried.items()})
        extra.append(carried)

    def run(bands_local, b_local, *rest):
        it = iter(rest)
        x0_l = next(it) if x0 is not None else None
        carried_l = next(it) if carried is not None else None
        if name in _SHARDED_GRAM:
            return eng.solve_bicgstab(A.offsets, bands_local, b_local,
                                      axis_name=axis, M=M, maxiter=maxiter,
                                      tol=tol, block=block,
                                      n_shards=n_shards, noise=noise,
                                      precision=precision)
        if depth > 1:
            return eng.solve_depth(A.offsets, bands_local, b_local,
                                   axis_name=axis, l=depth, M=M,
                                   maxiter=maxiter, tol=tol, block=block,
                                   n_shards=n_shards, noise=noise,
                                   precision=precision)
        return eng.solve(A.offsets, bands_local, b_local, axis_name=axis,
                         ip=ip, M=M, maxiter=maxiter, tol=tol, block=block,
                         n_shards=n_shards, noise=noise,
                         x0=x0_l, carried=carried_l, with_state=with_state,
                         precision=precision)

    res_specs = SolveResult(x=spec_v, iters=P(), res_norm=P(),
                            res_history=P(), detect_history=P())
    if with_state:
        out_specs = (res_specs,
                     dict(x=P(None, axis), r=P(None, axis),
                          u=P(None, axis), p=P(None, axis),
                          gamma_prev=P(), alpha_prev=P(), done=P()))
    else:
        out_specs = res_specs
    fn = jax.shard_map(run, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    return fn(A.bands, b, *extra)


def distributed_solve(solver: Callable, A: DiaMatrix, b: jnp.ndarray,
                      mesh: Mesh, *, use_kernel: bool = False,
                      noise: Optional[NoiseHook] = None,
                      engine=None, block: Optional[int] = None,
                      options=None, **solver_kw) -> SolveResult:
    """Run ``solver`` (cg / pipecg / cr / pipecr / gmres / pgmres) with the
    vector sharded over every device of ``mesh`` (flattened).

    ``noise`` (a ``NoiseHook`` or None): when given, each per-shard SpMV is
    followed by a host callback that sleeps a sampled waiting time; the
    callback's zero result is added to the SpMV output so the stall sits on
    the data-dependent critical path (cannot be hoisted or elided).

    ``engine``: None keeps the historical per-op iteration (any solver);
    ``"sharded_fused"`` (or a ShardedFusedEngine instance) runs pipecg /
    pipecg_multi / pipecr as one halo-aware Pallas sweep per shard per
    iteration with a split-phase psum (see sharded_pipecg_solve), and
    pipecg_l with ``l >= 2`` as depth-l ghost-basis blocks — one Gram
    psum and one l*halo-wide ppermute strip per l iterations
    (see sharded_pipecg_depth_solve).
    ``block`` overrides the sharded kernel's autotuned tile size.

    ``options`` (a :class:`~repro.core.krylov.options.SolverOptions`)
    bundles the solve configuration — engine, maxiter/tol, M, pipeline
    depth, noise hook and the mixed-precision
    :class:`~repro.core.krylov.options.PrecisionPolicy` — as one typed
    value; it cannot be mixed with the loose equivalents
    (``engine=`` / ``noise=`` / ``maxiter=`` / ...), which remain
    supported for existing callers.  ``precision=`` (policy or preset
    name) may also be passed directly; non-default policies need
    ``engine='sharded_fused'``.
    """
    from repro.core.krylov.engine import ShardedFusedEngine, get_engine
    from repro.core.krylov.options import SolverOptions

    if options is not None:
        if not isinstance(options, SolverOptions):
            raise TypeError(
                "options= must be a SolverOptions; got "
                f"{type(options).__name__}")
        clashes = [kw for kw in ("maxiter", "tol", "M", "l", "precision")
                   if kw in solver_kw]
        if engine is not None or noise is not None or clashes:
            loose = [kw for kw, v in
                     (("engine", engine), ("noise", noise)) if v is not None]
            raise TypeError(
                "pass the solve configuration either as options= or as "
                "loose kwargs, not both (options= given alongside "
                f"{sorted(loose + clashes)})")
        engine = options.engine
        noise = options.noise
        solver_kw.update(maxiter=options.maxiter, tol=options.tol)
        if options.M is not None:
            solver_kw["M"] = options.M
        if options.depth != 1:
            solver_kw["l"] = options.depth
        if not options.precision.is_default:
            solver_kw["precision"] = options.precision
        if options.rr or options.rr_tau:
            # the sharded bodies re-glue via x0= (fault.py); per-iteration
            # residual replacement is a local-solver feature
            raise ValueError(
                "rr= / rr_tau= (residual replacement) are local-solver "
                "options; the sharded bodies re-glue via x0= restarts "
                "(distributed/fault.py)")

    eng = get_engine(engine)
    if isinstance(eng, ShardedFusedEngine):
        return _distributed_engine_solve(solver, A, b, mesh, eng,
                                         noise=noise, block=block,
                                         **solver_kw)
    if eng is not None:
        raise ValueError(
            "distributed_solve supports engine=None (historical inline "
            "path) or 'sharded_fused'; single-device engines compute "
            f"local reductions and cannot shard (got {eng.name!r})")
    if getattr(solver, "__name__", "") == "pipecg_l":
        raise ValueError(
            "pipecg_l's ghost-basis blocks need the depth-aware sharded "
            "path: use distributed_solve(pipecg_l, A, b, mesh, "
            "engine='sharded_fused', l=...); the historical inline path "
            "(engine=None) cannot express its fused Gram reduction")
    if block is not None:
        raise ValueError(
            "block= only applies to the engine='sharded_fused' kernel "
            "path; the historical inline path has no tile-size override")
    for kw in ("x0", "carried", "with_state"):
        if kw in solver_kw:
            raise ValueError(
                f"{kw}= (elastic warm start) needs engine='sharded_fused'; "
                "the historical inline path cannot resume carried state")
    if not _resolve_precision(solver_kw.pop("precision", None)).is_default:
        raise ValueError(
            "mixed-precision policies (storage demotion / int8 wire) are "
            "implemented by the sharded kernel bodies: use "
            "engine='sharded_fused'; the historical inline path runs at "
            "the solve dtype only")

    axes = mesh.axis_names
    spec_v = P(axes)       # vectors sharded over all axes (flattened)
    spec_b = P(None, axes)  # bands: (n_bands, N) sharded on N

    dot = make_psum_dot(axes if len(axes) > 1 else axes[0])
    offsets = A.offsets

    def run(bands_local, b_local):
        axis = axes if len(axes) > 1 else axes[0]
        mv0 = functools.partial(dia_matvec_local, offsets, bands_local,
                                axis_name=axis,
                                use_kernel=use_kernel)
        extra_kw = {}
        if getattr(solver, "__name__", "") == "pipebicgstab":
            # keep the one-reduction-per-iteration structure even on the
            # historical inline path: finish the locally computed (6, 6)
            # Gram with a single psum instead of 21 per-entry dots
            extra_kw["gram_reduce"] = (
                lambda G, _ax=axis: jax.lax.psum(G, _ax))
        if noise is None:
            mv = mv0
        else:
            def mv(v):
                y = mv0(v)
                # the (zero) tick is added to y so the sleep stays on the
                # critical path (io_callback: never elided or hoisted)
                return y + _noise_tick(noise, axis, y.dtype)
        return solver(mv, b_local, dot=dot, **extra_kw, **solver_kw)

    out_specs = SolveResult(x=spec_v, iters=P(), res_norm=P(), res_history=P())
    fn = jax.shard_map(run, mesh=mesh, in_specs=(spec_b, spec_v),
                       out_specs=out_specs, check_vma=False)
    return fn(A.bands, b)
