"""BiCGStab for non-symmetric systems (the paper's ref [9] family).

Classical BiCGStab has FOUR synchronization points per iteration (rho,
<r_hat, v>, <t, s>, <t, t>) — even more reduction-latency exposure than CG,
which is why pipelined variants of it exist.  The paper's sum-of-max ->
max-of-sum argument (Eqs. 6/7) therefore predicts a pipelining ceiling
ABOVE the CG family's folk-theorem 2x: fusing four exposed reductions into
one overlapped reduction bounds the latency-dominated speedup at 4x
(``core/perfmodel/sync.py`` renders the general s-sync model).

``pipebicgstab`` is the communication-hiding rendering (Cools & Vanroose's
pipelined BiCGStab recurrences, with the two reduction phases fused into a
single (6, 6) Gram reduction per iteration):

* auxiliary chains ``w = A r``, ``t = A w``, ``s = A p``, ``z = A s``,
  ``v = A z`` are carried by recurrence so one iteration needs exactly the
  classical TWO SpMVs (``v = A z`` and ``t' = A w'``);
* all four classical inner products are *derived after the fact* from the
  Gram matrix of the carried basis ``[r, w, t, a, c, r_hat]`` (with
  ``a = s - omega z``, ``c = z - omega v`` the pre-combined direction
  updates): ``omega``'s numerator/denominator expand as polynomials in
  ``alpha``/``beta`` over Gram entries, so the ONE reduction initiated at
  the end of iteration i is consumed only by iteration i+1's scalar
  recurrence — the split-phase window of DESIGN.md, now hiding four
  synchronizations instead of CG's two;
* preconditioning is RIGHT preconditioning by operator substitution
  (``A_hat = A M``): the recurrence runs on ``A_hat`` unchanged, residuals
  are TRUE residuals of ``A x = b``, and the solution maps back as
  ``x = M y``.  ``M = "jacobi"`` folds into the DIA bands (zero extra
  traffic in the fused kernel); an opaque callable must be linear;
* ``rr=`` (an iteration period, per Cools' residual-replacement analysis)
  recomputes ``r = b - A_hat x`` — and its operator images w, t —
  synchronously every ``rr`` iterations to bound true-residual drift.

The loop is a fixed-trip-count ``lax.scan`` with masked freezes (the
CG / PIPECG loops stop at convergence instead, base.run_until_done); the
residual history is emitted from the CARRIED Gram (the
frozen state's own residual), so the tail after convergence is constant
and equals ``res_norm``.  One fused HBM sweep per iteration for DIA
operators via ``engine="fused"`` (kernels/pipebicgstab_fused.py); the
sharded split-phase path is ``core/krylov/distributed.py::
sharded_pipebicgstab_solve``.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.krylov import abft
from repro.core.krylov.base import SolveResult, as_matvec, local_dot
from repro.core.krylov.engine import get_engine
from repro.core.krylov.operators import DiaMatrix
from repro.core.krylov.options import UNSET, check_supported, resolve_options

# Gram-basis index convention shared with the kernel and the sharded path:
# V = [r, w, t, a, c, r_hat]
GRAM_R, GRAM_W, GRAM_T, GRAM_A, GRAM_C, GRAM_RHAT = range(6)


def bicgstab(A, b, x0=None, *, maxiter=UNSET, tol=UNSET, M=UNSET,
             dot=local_dot, engine=UNSET, options=None) -> SolveResult:
    """Preconditioned BiCGStab (fixed-trip-count scan, masked freeze).

    ``options=SolverOptions(...)`` is the typed spelling of the solver
    knobs (core/krylov/options.py); the loose kwargs keep working
    through the deprecation shim.  ``engine`` ("naive" / "fused" /
    Engine / None) routes the SpMV and preconditioner applications
    through an iteration engine, mirroring ``cg``; ``engine=None`` keeps
    the historical inline path (required for the distributed shard_map
    mode, which passes a psum ``dot`` and a matvec closure).
    """
    opts = resolve_options(options, maxiter=maxiter, tol=tol, M=M,
                           engine=engine)
    check_supported(opts, "bicgstab", supported=("engine",))
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
    r_hat = r
    rho = dot(r_hat, r)
    p = r
    state0 = dict(x=x, r=r, p=p, rho=rho, rr=dot(r, r),
                  done=jnp.asarray(False), iters=jnp.asarray(0, jnp.int32))
    tol2 = jnp.asarray(tol, b.dtype) ** 2 * dot(b, b)
    eps = jnp.asarray(1e-300 if b.dtype == jnp.float64 else 1e-30, b.dtype)

    def step(st, _):
        # preconditioner applied ONCE per vector and reused (the x update
        # below consumes the same M p / M s the SpMVs do)
        Mp = M(st["p"])
        v = mv(Mp)
        alpha = st["rho"] / (dot(r_hat, v) + eps)          # sync 1
        s = st["r"] - alpha * v
        Ms = M(s)
        t = mv(Ms)
        omega = dot(t, s) / (dot(t, t) + eps)              # sync 2+3 (fused)
        x = st["x"] + alpha * Mp + omega * Ms
        r = s - omega * t
        rho_new = dot(r_hat, r)                            # sync 4
        beta = (rho_new / (st["rho"] + eps)) * (alpha / (omega + eps))
        p = r + beta * (st["p"] - omega * v)
        rr = dot(r, r)
        done = st["done"] | (rr <= tol2)
        new = dict(x=x, r=r, p=p, rho=rho_new, rr=rr, done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        new = jax.tree.map(lambda n, o: jnp.where(st["done"], o, n), new, st)
        # once frozen, emit the FROZEN iterate's residual (the carried
        # ``rr`` scalar — no extra reduction) — not the residual of the
        # freshly computed (discarded) state above — so the history tail
        # is constant and equals res_norm
        rr_emit = jnp.where(st["done"], st["rr"], rr)
        return new, jnp.sqrt(jnp.maximum(rr_emit, 0.0))

    st, hist = jax.lax.scan(step, state0, None, length=maxiter)
    # res from the carried scalar: bit-identical to the frozen tail
    res = jnp.sqrt(jnp.maximum(st["rr"], 0.0))
    return SolveResult(x=st["x"], iters=st["iters"], res_norm=res,
                       res_history=hist)


# ---------------------------------------------------------------------------
# Pipelined BiCGStab: one fused (6, 6) Gram reduction per iteration
# ---------------------------------------------------------------------------

def pbicgstab_scalars(G, rho_prev, alpha_prev, omega_prev, first, eps):
    """(rr, rho, alpha, beta, omega) from the fused Gram reduction.

    ``G`` is the (6, 6) Gram matrix of ``[r, w, t, a, c, r_hat]`` carried
    from the previous iteration (the split-phase payload).  All four
    classical BiCGStab inner products unwind from it:

    * ``rho = <r, r_hat>`` and ``<s, r_hat> = <w, r_hat> + beta <a, r_hat>``
      give ``alpha`` (s = w + beta a by the direction recurrence);
    * ``omega = <q, y> / <y, y>`` with ``q = r - alpha s``,
      ``y = w - alpha z`` and ``z = t + beta c`` expands as a polynomial in
      ``alpha``/``beta`` over Gram entries — exact in exact arithmetic.

    Shared by the local solver, the fused kernel driver and the sharded
    split-phase path (the latter psums the partial Gram first).
    """
    R, W, T, As, C, H = (GRAM_R, GRAM_W, GRAM_T, GRAM_A, GRAM_C, GRAM_RHAT)
    rr = G[R, R]
    rho = G[R, H]
    beta = jnp.where(first, jnp.zeros_like(rho),
                     (alpha_prev / (omega_prev + eps)) * (rho / (rho_prev + eps)))
    s_rhat = G[W, H] + beta * G[As, H]
    alpha = rho / (s_rhat + eps)
    qy = (G[R, W] - alpha * (G[R, T] + G[W, W])
          - alpha * beta * (G[R, C] + G[W, As])
          + alpha ** 2 * (G[W, T] + beta * (G[W, C] + G[T, As])
                          + beta ** 2 * G[As, C]))
    yy = (G[W, W] - 2.0 * alpha * (G[W, T] + beta * G[W, C])
          + alpha ** 2 * (G[T, T] + 2.0 * beta * G[T, C]
                          + beta ** 2 * G[C, C]))
    omega = qy / (yy + eps)
    return rr, rho, alpha, beta, omega


def _gram6(vs: Tuple, dot) -> jnp.ndarray:
    """(6, 6) Gram matrix of the basis tuple ``vs`` through ``dot``.

    For the plain local dot this is ONE fused matmul (the single-reduction
    payload); a custom ``dot`` (e.g. the distributed psum dot of the
    historical inline path) is applied per unique entry.
    """
    if dot is local_dot:
        V = jnp.stack(vs)
        return V @ V.T
    G = jnp.zeros((6, 6), vs[0].dtype)
    for i in range(6):
        for j in range(i, 6):
            d = dot(vs[i], vs[j])
            G = G.at[i, j].set(d)
            if i != j:
                G = G.at[j, i].set(d)
    return G


def _right_preconditioned(A, M, b, x0):
    """(A_hat, mv_hat, unscale, y0) for right preconditioning A M y = b.

    ``M`` may be None, ``"jacobi"`` (DIA operators only; folded into the
    bands so the fused kernel preconditions for free) or a LINEAR callable
    (composed into the matvec; ``x0`` is rejected there because mapping it
    into y-space needs M^-1).  Residuals of the A_hat system ARE the true
    residuals of ``A x = b``; the solution maps back as ``x = M y``.
    """
    if M is None:
        A_hat = A
        return A_hat, as_matvec(A), None, x0
    if M == "jacobi":
        if not isinstance(A, DiaMatrix):
            raise ValueError(
                "pipebicgstab M='jacobi' needs a DiaMatrix operator to "
                "derive the diagonal; pass a callable M otherwise")
        invd = 1.0 / A.diagonal()
        n = A.n
        bands = []
        for k, off in enumerate(A.offsets):
            # A_hat[i, i+off] = A[i, i+off] * invd[i+off]  (column scaling)
            invd_off = jax.lax.dynamic_slice_in_dim(
                jnp.pad(invd, (A.halo, A.halo)), A.halo + off, n)
            bands.append(A.bands[k] * invd_off)
        A_hat = DiaMatrix(offsets=A.offsets, bands=jnp.stack(bands))
        y0 = None if x0 is None else x0 / invd
        return A_hat, A_hat.matvec, (lambda y: invd * y), y0
    if callable(M):
        if x0 is not None:
            raise ValueError(
                "pipebicgstab with a callable M is right-preconditioned "
                "(x = M y): an x0 cannot be mapped into y-space without "
                "M^-1; start from x0=None or use M='jacobi'")
        mv = as_matvec(A)
        return A, (lambda v: mv(M(v))), M, None
    raise ValueError(
        f"pipebicgstab M must be None, 'jacobi' or a linear callable, "
        f"got {M!r}")


def pipebicgstab(A, b, x0=None, *, maxiter=UNSET, tol=UNSET, M=UNSET,
                 dot=local_dot, engine=UNSET, rr=UNSET, rr_tau=UNSET,
                 gram_reduce: Optional[Callable] = None,
                 options=None) -> SolveResult:
    """Pipelined BiCGStab: one fused Gram reduction per iteration.

    Same solver surface as ``bicgstab`` (including the typed
    ``options=SolverOptions(...)`` spelling) plus:

    rr:
        Residual-replacement period in iterations (0 = off): every ``rr``
        iterations ``r`` (and its operator images w, t) is recomputed
        synchronously from ``b - A_hat x`` — Cools' stabilization of the
        pipelined recurrences' true-residual drift.  Locally the extra
        work runs under ``lax.cond`` (paid only on replacement
        iterations); on the inline DISTRIBUTED path (custom ``dot`` /
        ``gram_reduce``) a collective inside a cond branch is fragile
        under shard_map, so there the replacement falls back to a
        both-branches select — every iteration then pays 3 extra SpMVs
        and a second reduction.  Combining ``rr`` with the distributed
        inline path therefore trades the single-reduction structure for
        stability; the sharded_fused engine does not take ``rr`` at all.
    rr_tau:
        ADAPTIVE residual replacement (0 = off): a Cools-style deviation
        recursion (core/krylov/abft.py) built from Gram entries the
        carried reduction already holds (``<r, r>``, ``<w, w>``) and the
        step's ``alpha`` estimates the true-vs-recurrence residual gap
        and triggers the same ``_replace`` branch exactly when the
        estimate crosses ``rr_tau * ||r||``-scaled roundoff — no period
        tuning.  Composes with ``rr`` (replacement fires on either
        trigger).  Local ``lax.cond`` path only (the trigger is
        data-dependent, so the both-branches distributed fallback would
        pay the SpMVs every iteration): custom ``dot`` / ``gram_reduce``
        raise.
    engine:
        ``None`` / ``"naive"`` keep the inline jnp recurrence (None also
        honors a custom ``dot``, e.g. the distributed psum dot);
        ``"fused"`` runs the WHOLE iteration (updates + in-band Jacobi +
        both SpMVs + the Gram partials) as one Pallas HBM sweep for DIA
        operators; ``"sharded_fused"`` must go through
        ``distributed_solve`` (its reductions are per-shard partials).
    gram_reduce:
        Optional collective that finishes a locally computed partial
        (6, 6) Gram (e.g. ``lambda G: lax.psum(G, axis)``).  The
        historical inline distributed path passes it so the iteration
        keeps its SINGLE reduction even there (with ``rr=0``; see the
        ``rr`` note) — without it a custom ``dot`` would be applied per
        Gram entry (21 collectives).

    Iteration counts lag ``bicgstab`` by one: convergence is detected
    from the carried reduction, one scan body after the iterate froze.
    """
    opts = resolve_options(options, maxiter=maxiter, tol=tol, M=M,
                           engine=engine, rr=rr, rr_tau=rr_tau)
    check_supported(opts, "pipebicgstab",
                    supported=("engine", "rr", "rr_tau"))
    maxiter, tol, M = opts.maxiter, opts.tol, opts.M
    engine, rr, rr_tau = opts.engine, opts.rr, opts.rr_tau
    eng = get_engine(engine)
    from repro.core.krylov.engine import FusedEngine, ShardedFusedEngine
    if isinstance(eng, ShardedFusedEngine):
        raise ValueError(
            "engine='sharded_fused' computes per-shard partial reductions "
            "and must run inside a mesh: use distributed_solve(pipebicgstab"
            ", A, b, mesh, engine='sharded_fused') instead")
    if eng is not None and dot is not local_dot:
        raise ValueError(
            "engine= computes local reductions and cannot honor a custom "
            "dot (e.g. the distributed psum dot); use engine=None there")

    A_hat, mv, unscale, y0 = _right_preconditioned(A, M, b, x0)
    use_kernel = (isinstance(eng, FusedEngine) and isinstance(A_hat, DiaMatrix)
                  and M in (None, "jacobi"))
    if eng is not None and not use_kernel:
        base = (lambda v, _e=eng, _A=A_hat: _e.spmv(_A, v))
        # a callable M is NOT folded into A_hat: keep the right-
        # preconditioned composition and route only the operator
        # application through the engine
        mv = ((lambda v, _b=base, _M=M: _b(_M(v))) if callable(M)
              else base)

    if gram_reduce is None:
        gram = lambda vs: _gram6(vs, dot)
    else:
        # one stacked local matmul + ONE finishing collective
        gram = lambda vs: gram_reduce(jnp.stack(vs) @ jnp.stack(vs).T)

    adaptive = float(rr_tau) > 0.0
    if adaptive and not (dot is local_dot and gram_reduce is None):
        raise ValueError(
            "rr_tau= (adaptive residual replacement) triggers on a "
            "data-dependent lax.cond and needs the local reduction path; "
            "the distributed inline path (custom dot / gram_reduce) would "
            "pay the replacement SpMVs every iteration — use rr= there")

    y = jnp.zeros_like(b) if y0 is None else y0
    r0 = b - mv(y)
    r_hat = r0
    w0 = mv(r0)
    t0 = mv(w0)
    zero = jnp.zeros_like(b)
    dt = b.dtype
    eps = jnp.asarray(1e-300 if dt == jnp.float64 else 1e-30, dt)
    one = jnp.ones((), dt)
    if use_kernel:
        # the fused kernel emits a 7th Gram row whose [0] entry is the
        # ABFT checksum residual 1^T t' - c^T w' (kernels/checksum.py);
        # match its (7, 6) shape for the carried G, seeding row 6 with
        # the init basis' own checksum so iteration 0 is covered too
        from repro.kernels.checksum import dia_column_checksum
        csum = dia_column_checksum(A_hat.offsets, A_hat.bands).astype(dt)
        base_gram = gram

        def gram(vs):
            chk = jnp.sum(vs[2]) - jnp.sum(csum * vs[1])  # 1^T t - c^T w
            row = jnp.zeros((1, 6), dt).at[0, 0].set(chk)
            return jnp.concatenate([base_gram(vs), row], axis=0)
    G0 = gram((r0, w0, t0, zero, zero, r_hat))
    state0 = dict(x=y, r=r0, w=w0, t=t0, pa=zero, a=zero, c=zero, G=G0,
                  rho_prev=one, alpha_prev=one, omega_prev=one,
                  dev=jnp.zeros((), dt),
                  first=jnp.asarray(True),
                  done=jnp.asarray(False), iters=jnp.asarray(0, jnp.int32))
    tol2 = jnp.asarray(tol, dt) ** 2 * dot(b, b)
    rr_period = int(rr)
    eps_u = abft.machine_eps(dt)

    def step(st, k):
        # ---- consume the reduction initiated LAST iteration: its only
        # consumers are these scalar recurrences (split-phase window) ----
        rr2, rho, alpha, beta, omega = pbicgstab_scalars(
            st["G"], st["rho_prev"], st["alpha_prev"], st["omega_prev"],
            st["first"], eps)
        if use_kernel:
            from repro.kernels import ops as kops
            x, r, w, t, pa, a, c, G = kops.pipebicgstab_fused_step(
                A_hat.offsets, A_hat.bands, st["x"], st["r"], st["w"],
                st["t"], st["pa"], st["a"], st["c"], r_hat,
                alpha, beta, omega)
        else:
            p = st["r"] + beta * st["pa"]
            s = st["w"] + beta * st["a"]
            z = st["t"] + beta * st["c"]
            v = mv(z)                                  # SpMV 1
            q = st["r"] - alpha * s
            yv = st["w"] - alpha * z
            x = st["x"] + alpha * p + omega * q
            r = q - omega * yv
            w = yv - omega * (st["t"] - alpha * v)
            t = mv(w)                                  # SpMV 2
            pa = p - omega * s
            a = s - omega * z
            c = z - omega * v
            # ---- initiate the NEXT iteration's fused reduction ----
            G = gram((r, w, t, a, c, r_hat))
        dev = st["dev"]
        if adaptive:
            # deviation recursion over carried Gram entries (no new dots)
            dev = abft.deviation_update(dev, alpha, rr2,
                                        st["G"][GRAM_W, GRAM_W], eps=eps_u)
        if rr_period or adaptive:
            do_rr = jnp.asarray(False)
            if rr_period:
                do_rr = (k + 1) % rr_period == 0
            if adaptive:
                do_rr = do_rr | abft.deviation_trip(dev, rr2, rr_tau)

            def _replace(op):
                # the 3 extra SpMVs + Gram run ONLY on replacement
                # iterations (lax.cond, not a both-branches select)
                x_, a_, c_ = op[0], op[4], op[5]
                r2 = b - mv(x_)
                w2 = mv(r2)
                t2 = mv(w2)
                return r2, w2, t2, gram((r2, w2, t2, a_, c_, r_hat))

            def _keep(op):
                return op[1], op[2], op[3], op[6]

            if dot is local_dot and gram_reduce is None:
                r, w, t, G = jax.lax.cond(do_rr, _replace, _keep,
                                          (x, r, w, t, a, c, G))
            else:
                # custom (e.g. psum) dot or collective gram_reduce: a
                # collective inside a cond branch is fragile under
                # shard_map — fall back to the both-branches select
                r2, w2, t2, G2 = _replace((x, r, w, t, a, c, G))
                r = jnp.where(do_rr, r2, r)
                w = jnp.where(do_rr, w2, w)
                t = jnp.where(do_rr, t2, t)
                G = jnp.where(do_rr, G2, G)
            dev = jnp.where(do_rr, jnp.zeros_like(dev), dev)
        done = st["done"] | (rr2 <= tol2)
        # freeze AT the iterate whose (carried) residual met the
        # tolerance: BiCGStab is non-monotone, so committing one more
        # step could push res_norm back above tol
        frz = lambda nv, ov: jnp.where(done, ov, nv)
        new = dict(x=frz(x, st["x"]), r=frz(r, st["r"]), w=frz(w, st["w"]),
                   t=frz(t, st["t"]), pa=frz(pa, st["pa"]),
                   a=frz(a, st["a"]), c=frz(c, st["c"]), G=frz(G, st["G"]),
                   rho_prev=frz(rho, st["rho_prev"]),
                   alpha_prev=frz(alpha, st["alpha_prev"]),
                   omega_prev=frz(omega, st["omega_prev"]),
                   dev=frz(dev, st["dev"]),
                   first=jnp.asarray(False), done=done,
                   iters=st["iters"] + (~done).astype(jnp.int32))
        # rr2 comes from the CARRIED Gram — once frozen it is the frozen
        # iterate's own residual, so the emitted tail is constant
        out = jnp.sqrt(jnp.maximum(rr2, 0.0))
        if use_kernel:
            # checksum row of the SAME carried Gram (consumed this body)
            return new, (out, st["G"][6, 0])
        return new, out

    st, ys = jax.lax.scan(step, state0, jnp.arange(maxiter))
    hist, chk_hist = ys if use_kernel else (ys, None)
    # final residual from the CARRIED Gram (bit-identical to the frozen
    # history tail; a recomputed dot would differ in the low bits)
    res = jnp.sqrt(jnp.maximum(st["G"][GRAM_R, GRAM_R], 0.0))
    # the emitted history is ||r_i|| at body i: roll one slot so
    # hist[i] = ||r_{i+1}||, the classical solvers' alignment
    hist = jnp.concatenate([hist[1:], res[None]])
    if chk_hist is not None:
        chk_hist = jnp.concatenate([chk_hist[1:], st["G"][6, 0][None]])
    x_out = st["x"] if unscale is None else unscale(st["x"])
    return SolveResult(x=x_out, iters=st["iters"], res_norm=res,
                       res_history=hist, detect_history=chk_hist)
