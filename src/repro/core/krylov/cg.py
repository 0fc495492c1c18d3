"""CG / CR and their pipelined variants (PIPECG / PIPECR).

Classical CG has TWO global synchronization points per iteration, each of
which gates the very next vector update (the reduction result is consumed
immediately).  PIPECG (Ghysels & Vanroose, Parallel Computing 40(7), 2014)
rearranges the recurrences so the single fused reduction (gamma, delta) of
iteration i is consumed only AFTER the SpMV + preconditioner application of
the same iteration: in MPI terms the reduction becomes a split-phase
collective (MPI_Iallreduce / MPI_Wait); in XLA terms the all-reduce has no
data dependence on the SpMV so the async scheduler overlaps them.

CR is CG in the A-inner product: gamma = <r, w>, delta = <w, w> with
w = A u; both classical and pipelined variants share an implementation with
an ``ip`` ("id" | "A") switch.  Arithmetic equivalence of the pipelined
rearrangements is validated in tests/test_krylov_equivalence.py.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.krylov import abft
from repro.core.krylov.base import (SolveResult, as_matvec, local_dot,
                                    run_until_done)
from repro.core.krylov.engine import get_engine
from repro.core.krylov.options import (UNSET, as_policy, check_supported,
                                       resolve_options)


def _ip_dots(ip: str, r, u, w, dot):
    """(gamma, delta) for the CG family.  ip='id' -> CG; ip='A' -> CR."""
    if ip == "id":
        return dot(r, u), dot(w, u)
    return dot(r, w), dot(w, w)


# ---------------------------------------------------------------------------
# Classical CG / CR (synchronizing)
# ---------------------------------------------------------------------------

def cg(A, b, x0=None, *, maxiter=UNSET, tol=UNSET, M=UNSET, dot=local_dot,
       ip: str = "id", engine=UNSET, options=None) -> SolveResult:
    """Preconditioned CG (ip='id') or CR (ip='A').

    The loop stops after the step at which ``||r|| <= tol ||b||``
    (:func:`~repro.core.krylov.base.run_until_done`); ``tol = 0`` runs
    all ``maxiter`` steps, as the paper's fixed-count timings do.
    ``res_history`` keeps ``maxiter`` entries: those after the last
    executed step repeat its entry (``SolveResult``).

    ``options=SolverOptions(...)`` is the typed spelling of the solver
    knobs (core/krylov/options.py); the loose ``maxiter=/tol=/M=/engine=``
    kwargs keep working through the deprecation shim and resolve to the
    identical code path.  ``engine`` ("naive" / "fused" / Engine / None)
    selects the iteration engine for the SpMV and preconditioner
    applications; None keeps the historical inline path (required for the
    shard_map distributed mode, which passes a psum ``dot`` and a matvec
    closure).
    """
    opts = resolve_options(options, maxiter=maxiter, tol=tol, M=M,
                           engine=engine)
    check_supported(opts, "cg", supported=("engine",))
    maxiter, tol, M, engine = opts.maxiter, opts.tol, opts.M, opts.engine
    eng = get_engine(engine)
    if eng is not None:
        if dot is not local_dot:
            raise ValueError(
                "engine= computes local reductions and cannot honor a custom "
                "dot (e.g. the distributed psum dot); use engine=None there")
        from repro.core.krylov.engine import _resolve_M
        mv = lambda v: eng.spmv(A, v)
        M = _resolve_M(A, M)
    else:
        mv = as_matvec(A)
    M = M if M is not None else (lambda z: z)
    x = jnp.zeros_like(b) if x0 is None else x0

    r = b - mv(x)
    u = M(r)
    w = mv(u)
    gamma, delta = _ip_dots(ip, r, u, w, dot)
    p, s = u, w
    # alpha from the classical formula: gamma / <p, A p>  (s = A p)
    state0 = dict(x=x, r=r, u=u, w=w, p=p, s=s, gamma=gamma,
                  done=jnp.asarray(False), iters=jnp.asarray(0, jnp.int32))
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * dot(b, b)

    def step(st):
        pAp = _ip_dots(ip, st["p"], st["p"], st["s"], dot)[1]  # <s,p> or <s,s>
        alpha = st["gamma"] / pAp
        x = st["x"] + alpha * st["p"]
        r = st["r"] - alpha * st["s"]
        u = M(r)
        w = mv(u)
        gamma_new, _ = _ip_dots(ip, r, u, w, dot)
        beta = gamma_new / st["gamma"]
        p = u + beta * st["p"]
        s = w + beta * st["s"]
        rr = dot(r, r)
        done = st["done"] | (rr <= tol2)
        new = dict(x=x, r=r, u=u, w=w, p=p, s=s, gamma=gamma_new, done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        return new, jnp.sqrt(jnp.maximum(rr, 0.0))

    st, hist, _ = run_until_done(step, state0, maxiter)
    res = jnp.sqrt(jnp.maximum(dot(st["r"], st["r"]), 0.0))
    return SolveResult(x=st["x"], iters=st["iters"], res_norm=res,
                       res_history=hist)


def cr(A, b, x0=None, **kw) -> SolveResult:
    """Conjugate Residuals: CG in the A-inner product (ip='A')."""
    kw.pop("ip", None)
    return cg(A, b, x0, ip="A", **kw)


# ---------------------------------------------------------------------------
# Pipelined CG / CR (split-phase reduction)
# ---------------------------------------------------------------------------

def pipecg(A, b, x0=None, *, maxiter=UNSET, tol=UNSET, M=UNSET,
           dot=local_dot, ip: str = "id", engine=UNSET, rr_tau=UNSET,
           precision=UNSET, options=None) -> SolveResult:
    """Ghysels-Vanroose pipelined CG (Alg. 4 there; PIPECR via ip='A').

    Per iteration: ONE fused reduction (gamma, delta, ||r||^2) whose result
    is consumed only after the SpMV ``n = A m`` and preconditioner ``m = M w``
    — the overlap window.  Extra state (z, q, s, p) vs classical CG is the
    pipelining cost the paper describes (more AXPYs + storage).

    ``options=SolverOptions(...)`` is the typed spelling of the solver
    knobs (core/krylov/options.py); the loose kwargs keep working through
    the deprecation shim and resolve to the identical code path.

    The loop stops after the step at which ``||r|| <= tol ||b||`` (all
    ``maxiter`` steps when ``tol = 0``); ``res_history`` keeps
    ``maxiter`` entries, those after the last executed step repeating
    its entry (``SolveResult``).  Several right-hand sides
    (``pipecg_multi``) freeze each converged column until the last one
    is done.

    ``engine`` ("naive" / "fused" / Engine / None) routes the whole
    iteration through an iteration engine (see core/krylov/engine.py);
    ``engine="fused"`` with a DIA operator and identity/Jacobi M runs each
    iteration as ONE Pallas HBM sweep.  ``engine=None`` keeps the
    historical inline path (used by the distributed shard_map mode).

    ``rr_tau > 0`` enables ADAPTIVE residual replacement (engine paths
    only): a Cools-style deviation recursion (core/krylov/abft.py)
    estimates the gap ``||b - A x - r||`` from the carried reduction and
    re-glues ``r = b - A x`` exactly when the estimate crosses
    ``rr_tau * machine_eps``-scaled ``||r||`` — no fixed period needed.

    ``precision`` (a PrecisionPolicy / preset name) demotes the carried
    basis vectors and the resident operator to the policy's storage
    dtype on the single-sweep fused path; reductions, scalar recurrences
    and ``x`` stay at accum precision.  Wire compression is a
    distributed_solve feature (there are no ppermute payloads locally).
    """
    opts = resolve_options(options, maxiter=maxiter, tol=tol, M=M,
                           engine=engine, rr_tau=rr_tau, precision=precision)
    check_supported(opts, "pipecg",
                    supported=("engine", "rr_tau", "precision"))
    maxiter, tol, M = opts.maxiter, opts.tol, opts.M
    engine, rr_tau = opts.engine, opts.rr_tau
    if engine is not None:
        if dot is not local_dot:
            raise ValueError(
                "engine= computes local reductions and cannot honor a custom "
                "dot (e.g. the distributed psum dot); use engine=None there")
        return _pipecg_engine(A, b, x0, maxiter=maxiter, tol=tol, M=M,
                              ip=ip, engine=engine, rr_tau=rr_tau,
                              precision=opts.precision)
    if rr_tau:
        raise ValueError(
            "rr_tau= (adaptive residual replacement) needs the deviation "
            "recursion carried by an engine path; pass engine='naive' or "
            "'fused' (the inline engine=None path has no detector channel)")
    if not opts.precision.is_default:
        raise ValueError(
            "mixed-precision policies need an engine path (the storage "
            "demotion rides the DIA kernel sweeps): pass engine='fused', "
            "or use distributed_solve(..., engine='sharded_fused') for "
            "the wire-compressed policies")
    mv = as_matvec(A)
    M = M if M is not None else (lambda z: z)
    x = jnp.zeros_like(b) if x0 is None else x0

    r = b - mv(x)
    u = M(r)
    w = mv(u)
    gamma, delta = _ip_dots(ip, r, u, w, dot)
    m = M(w)
    n = mv(m)
    zero = jnp.zeros_like(b)
    state0 = dict(x=x, r=r, u=u, w=w, m=m, n=n,
                  z=zero, q=zero, s=zero, p=zero,
                  gamma=gamma, delta=delta,
                  gamma_prev=jnp.ones_like(gamma), alpha_prev=jnp.ones_like(gamma),
                  first=jnp.asarray(True),
                  done=jnp.asarray(False), iters=jnp.asarray(0, jnp.int32))
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * dot(b, b)

    def step(st):
        gamma, delta = st["gamma"], st["delta"]
        beta = jnp.where(st["first"], 0.0, gamma / st["gamma_prev"])
        alpha = jnp.where(
            st["first"], gamma / delta,
            gamma / (delta - beta * gamma / st["alpha_prev"]))

        z = st["n"] + beta * st["z"]
        q = st["m"] + beta * st["q"]
        s = st["w"] + beta * st["s"]
        p = st["u"] + beta * st["p"]
        x = st["x"] + alpha * p
        r = st["r"] - alpha * s
        u = st["u"] - alpha * q
        w = st["w"] - alpha * z

        # ---- split-phase reduction: initiated here ... ----
        gamma_new, delta_new = _ip_dots(ip, r, u, w, dot)
        rr = dot(r, r)
        # ---- ... overlapped with M-apply + SpMV ... -------
        m = M(w)
        n = mv(m)
        # ---- ... consumed only at the NEXT iteration. -----

        done = st["done"] | (rr <= tol2)
        new = dict(x=x, r=r, u=u, w=w, m=m, n=n, z=z, q=q, s=s, p=p,
                   gamma=gamma_new, delta=delta_new,
                   gamma_prev=gamma, alpha_prev=alpha,
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        return new, jnp.sqrt(jnp.maximum(rr, 0.0))

    st, hist, _ = run_until_done(step, state0, maxiter)
    res = jnp.sqrt(jnp.maximum(dot(st["r"], st["r"]), 0.0))
    return SolveResult(x=st["x"], iters=st["iters"], res_norm=res,
                       res_history=hist)


def pipecr(A, b, x0=None, **kw) -> SolveResult:
    """Pipelined CR: the PIPECG rearrangement in the A-inner product."""
    kw.pop("ip", None)
    return pipecg(A, b, x0, ip="A", **kw)


# ---------------------------------------------------------------------------
# Engine-driven PIPECG (single- and multi-RHS)
# ---------------------------------------------------------------------------

def _pipecg_scalars(st, ip_unused=None):
    """(alpha, beta) from the carried fused-reduction results."""
    gamma, delta = st["gamma"], st["delta"]
    beta = jnp.where(st["first"], jnp.zeros_like(gamma),
                     gamma / st["gamma_prev"])
    alpha = jnp.where(st["first"], gamma / delta,
                      gamma / (delta - beta * gamma / st["alpha_prev"]))
    return alpha, beta


def _pipecg_engine(A, b, x0=None, *, maxiter=100, tol=0.0, M=None,
                   ip: str = "id", engine="naive", rr_tau: float = 0.0,
                   precision=None) -> SolveResult:
    """PIPECG with the vector work delegated to an iteration engine.

    Same scalar recurrences and stopping rule as the inline ``pipecg``;
    only WHO performs the AXPYs/dots/SpMV differs.  One right-hand side
    at the default precision needs no masked update: the loop exits
    after the step that sets ``done``.  Batched states freeze each
    converged column (``jnp.where``) until every column is done, and a
    storage-demoting policy freezes at the last good iterate on a
    breakdown.

    The engine's ``aux`` side-channel (checksum residual + ``<w, w>``) is
    recorded per iteration as ``SolveResult.detect_history`` and — when
    ``rr_tau > 0`` — drives adaptive residual replacement: a
    ``lax.cond``-guarded re-glue ``r = b - A x`` (plus operator images
    for 10-vector states) that costs its SpMVs only on iterations where
    the deviation estimate actually trips (cf. the fixed-period ``rr=``
    of ``pipecg_l``).

    A storage-demoting ``precision`` policy keeps TWO operators: the
    exact ``A`` for init and re-glue (full-precision residual recompute,
    then cast back), and ``A_iter`` with bands in the storage dtype for
    the per-iteration sweep — so the carried r/u/p and the streamed
    bands ride at storage width while every reduction and ``x`` stay at
    accum width (the kernel derives its accumulator from ``x.dtype``).
    """
    from repro.core.krylov.engine import _rdot
    policy = as_policy(precision)
    eng = get_engine(engine)
    A_iter = A
    if not policy.is_default:
        from repro.core.krylov.operators import DiaMatrix
        if policy.wire != "fp32" or policy.wire_gram != "fp32":
            raise ValueError(
                "int8 wire compression applies to ppermute/psum payloads "
                "and needs distributed_solve(..., engine='sharded_fused'); "
                "local engine paths have no wire")
        if not isinstance(A, DiaMatrix):
            raise ValueError(
                "precision storage demotion rides the DIA band stream; "
                "wrap the operator as a DiaMatrix (matrix-free operators "
                "have no resident operand to demote)")
        sdt = policy.storage_dtype
        if sdt is not None:
            A_iter = DiaMatrix(offsets=A.offsets, bands=A.bands.astype(sdt))
    else:
        sdt = None
    vecs, gamma, delta = eng.pipecg_init(A, b, x0, M, ip)
    if sdt is not None:
        if "w" in vecs:
            raise ValueError(
                "precision storage demotion needs the single-sweep fused "
                "path: engine='fused' with a DIA operator and M=None or "
                "'jacobi' (the 10-vector fallback state is accum-only)")
        # x stays at accum width; the carried basis vectors ride at
        # storage width from here on
        vecs = dict(vecs, r=vecs["r"].astype(sdt), u=vecs["u"].astype(sdt),
                    p=vecs["p"].astype(sdt))
    A_iter = eng.pipecg_operator(A_iter, M, vecs)
    one = jnp.ones_like(gamma)
    state0 = dict(vecs=vecs, gamma=gamma, delta=delta,
                  gamma_prev=one, alpha_prev=one,
                  dev=jnp.zeros_like(gamma),
                  first=jnp.asarray(True),
                  done=jnp.zeros(gamma.shape, bool),
                  iters=jnp.zeros(gamma.shape, jnp.int32))
    bb = jnp.sum(b * b, axis=-1)
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * bb
    eps = abft.machine_eps(b.dtype)
    freeze = gamma.ndim > 0 or not policy.is_default

    def _reglue(vecs_in):
        """Recompute r = b - A x, u = M r (+ images for 10-vector state).

        Always runs against the EXACT operator at accum precision — that
        is the whole point of the re-glue — then casts the replacement
        vectors back to the carried storage dtype (identity when the
        policy is default).
        """
        r2 = b - eng.spmv(A, vecs_in["x"])
        u2 = eng.precond(A, M, r2)
        w2 = eng.spmv(A, u2)
        rep = dict(vecs_in, r=r2.astype(vecs_in["r"].dtype),
                   u=u2.astype(vecs_in["u"].dtype))
        if "w" in vecs_in:   # 10-vector states carry operator images too
            m2 = eng.precond(A, M, w2)
            s2 = eng.spmv(A, vecs_in["p"])
            q2 = eng.precond(A, M, s2)
            rep.update(w=w2, m=m2, n=eng.spmv(A, m2),
                       s=s2, q=q2, z=eng.spmv(A, q2))
        g2 = _rdot(r2, u2) if ip == "id" else _rdot(r2, w2)
        d2 = _rdot(w2, u2) if ip == "id" else _rdot(w2, w2)
        return rep, g2, d2, _rdot(r2, r2)

    def step(st):
        alpha, beta = _pipecg_scalars(st)
        vecs, gamma_new, delta_new, rr, aux = eng.pipecg_iter(
            A_iter, M, ip, st["vecs"], alpha, beta)
        dev = st["dev"]
        if rr_tau > 0.0:
            dev = abft.deviation_update(dev, alpha, rr, aux["ww"], eps=eps)
            trip = abft.deviation_trip(dev, rr, rr_tau) & ~st["done"]

            def _sel(t, nv, ov):
                tm = (t.reshape(t.shape + (1,) * (nv.ndim - t.ndim))
                      if nv.ndim > t.ndim else t)
                return jnp.where(tm, nv, ov)

            def _replace(op):
                vs, g, d, rr_in, dv = op
                rep, g2, d2, rr2 = _reglue(vs)
                return (jax.tree.map(lambda nv, ov: _sel(trip, nv, ov),
                                     rep, vs),
                        _sel(trip, g2, g), _sel(trip, d2, d),
                        _sel(trip, rr2, rr_in),
                        jnp.where(trip, jnp.zeros_like(dv), dv))

            # pay the re-glue SpMVs only when some system actually trips
            vecs, gamma_new, delta_new, rr, dev = jax.lax.cond(
                jnp.any(trip), _replace, lambda op: op,
                (vecs, gamma_new, delta_new, rr, dev))
        # gamma = <r, M r> (or <r, A M r>) is exactly zero only when the
        # recurrence has nothing left to reduce: stop there (the happy
        # breakdown of CG) instead of forming 0/0 in the next beta
        done = st["done"] | (rr <= tol2) | (gamma_new == 0)
        mask = st["done"]
        if not policy.is_default:
            # breakdown guard: a demoted recurrence that decays past its
            # attainable floor loses gamma positivity and blows up; freeze
            # at the last good iterate instead of propagating inf/nan.
            # Gated off the default path so exact-arithmetic semantics
            # (incl. the ABFT fault-injection NaN poisoning) are untouched.
            bad = ~(jnp.isfinite(alpha) & jnp.isfinite(gamma_new)
                    & jnp.isfinite(delta_new) & jnp.isfinite(rr))
            mask = mask | bad
            done = done | bad

        def frz(nv, ov):  # freeze converged systems (masked update)
            if not freeze:  # the loop exits after the step that sets done
                return nv
            m = (mask.reshape(mask.shape + (1,) * (nv.ndim - mask.ndim))
                 if nv.ndim > mask.ndim else mask)
            return jnp.where(m, ov, nv)

        new = dict(vecs=jax.tree.map(frz, vecs, st["vecs"]),
                   gamma=frz(gamma_new, st["gamma"]),
                   delta=frz(delta_new, st["delta"]),
                   gamma_prev=frz(st["gamma"], st["gamma_prev"]),
                   alpha_prev=frz(alpha, st["alpha_prev"]),
                   dev=frz(dev, st["dev"]),
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        return new, (jnp.sqrt(jnp.maximum(rr, 0.0)), aux["chk"])

    st, (hist, chk_hist), _ = run_until_done(step, state0, maxiter)
    r = st["vecs"]["r"].astype(b.dtype)  # accum-width norm (no-op at fp32)
    res = jnp.sqrt(jnp.maximum(jnp.sum(r * r, axis=-1), 0.0))
    if hist.ndim == 2:  # batched: (maxiter, k) -> (k, maxiter)
        hist = hist.T
        chk_hist = chk_hist.T
    return SolveResult(x=st["vecs"]["x"], iters=st["iters"], res_norm=res,
                       res_history=hist, detect_history=chk_hist)


def pipecg_multi(A, B, X0=None, *, maxiter=100, tol=0.0, M=None,
                 ip: str = "id", engine="fused",
                 rr_tau: float = 0.0, precision=None) -> SolveResult:
    """Batched PIPECG: solve A x_j = b_j for every row of ``B`` (k, n).

    With ``engine="fused"`` and a DIA operator the k systems share one
    kernel sweep per iteration — the band and diag^-1 reads are amortized
    over the batch (the kernel's leading grid dimension).  Each RHS keeps
    its own alpha/beta trajectory.  Other engines fall back to ``vmap``
    over the single-RHS iteration.

    Returns a SolveResult with x (k, n), res_norm (k,), iters (k,),
    res_history (k, maxiter).
    """
    eng = get_engine(engine)
    from repro.core.krylov.engine import FusedEngine, _jacobi_inv_diag

    k, n = B.shape
    native_batch = (isinstance(eng, FusedEngine)
                    and _jacobi_inv_diag(A, M, n, B.dtype) is not None)
    if native_batch:
        # FusedEngine's single-sweep path is batch-shaped already
        return _pipecg_engine(A, B, X0, maxiter=maxiter, tol=tol, M=M,
                              ip=ip, engine=eng, rr_tau=rr_tau,
                              precision=precision)
    solve = lambda b, x0: _pipecg_engine(
        A, b, x0, maxiter=maxiter, tol=tol, M=M, ip=ip, engine=eng,
        rr_tau=rr_tau, precision=precision)
    X0 = jnp.zeros_like(B) if X0 is None else X0
    return jax.vmap(solve)(B, X0)
