"""Chip smoke test: the fused PIPECG solve path on a TPU, end to end.

Default (one chip), fp32 throughout, at n = 2^20 rows:

1. kernels: one DIA SpMV and one single-sweep PIPECG iteration, compiled
   for the chip, against a float64 NumPy reference on random operands;
2. solve: ``pipecg(engine="fused", M="jacobi")`` and classical ``cg`` on
   the 1-D Laplacian (PETSc ex23), each checked by the host float64
   normwise backward error of its answer;
3. serve: a ``SolverServer`` (8 slots, fused engine) answering synthetic
   requests against the same operator; every answer is checked on the
   host: a converged one within its own tolerance, and each one within
   its tolerance or at the fp32 floor of the backward error.

``--chips 4`` runs only the sharded path and its reference:
``distributed_solve(pipecg, ..., engine="sharded_fused")`` over a 4-chip
mesh at 2^20 rows per chip, checked against the host float64 residual,
with each chip holding a quarter of ``x`` and one all-reduce per loop
body in the compiled program.

Informational lines (iterations, residuals, compile and solve times,
the device) come first; the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises, so the script exits non-zero without that line,
as it does when JAX finds no TPU.

    python chip_smoke.py [--chips 4]

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, otherwise
``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 1                 # every right-hand side and random operand
N = 2 ** 20              # rows per chip
MODES = 64               # laplacian_mode_rhs: CG needs about this many steps
MAXITER = 1000
TOL = 1e-6               # relative recursive-residual stopping tolerance
EPS32 = float(np.finfo(np.float32).eps)
# Acceptance of an fp32 answer x: the normwise backward error
#   eta = ||b - A x|| / (||A|| ||x|| + ||b||),   float64 on the host.
# CG in floating point attains eta of a small multiple of the unit
# roundoff (Greenbaum 1997); the pipelined recurrences add a residual gap
# driven by the same local rounding errors (Cools et al. 2018), and the
# single sweep re-derives s = A p and w = A u every iteration, which
# keeps that gap at the classical level.  fp32 CPU runs of both solvers
# on these right-hand sides gave eta = 1 to 7 eps; a wrong kernel gives
# eta of order one.  A relative-residual tolerance would not do: for the
# 1-D Laplacian at 2^20 rows, ||A^-1|| ~ 1e11, so the attainable
# ||b - A x|| / ||b|| depends on which modes the right-hand side holds.
ETA_TOL = 32 * EPS32
# one kernel application against float64: a few fp32 roundings per term
KERNEL_RTOL = 64 * EPS32


def require(cond, what: str) -> None:
    """Fail the smoke (non-zero exit) unless ``cond`` holds."""
    if not cond:
        raise RuntimeError(f"chip smoke check failed: {what}")


def info(**kw) -> None:
    """One informational line (not the result line)."""
    print("smoke", json.dumps(kw, default=float), flush=True)


def true_residual(A, b, x):
    """Host float64 ``(||b - A x|| / ||b||, backward error)`` of ``x``."""
    from repro.core.krylov.operators import dia_gather_matvec

    b64 = np.asarray(b, np.float64)
    x64 = np.asarray(x, np.float64)
    require(np.all(np.isfinite(x64)), "answer is finite")
    r = np.linalg.norm(b64 - dia_gather_matvec(
        A.offsets, np.asarray(A.bands, np.float64), x64, np))
    bn = np.linalg.norm(b64)
    return float(r / bn), float(r / (A.inf_norm() * np.linalg.norm(x64)
                                      + bn))


def compile_with_kernel(fn, *args):
    """jit + compile ``fn`` for ``args``; require a Pallas kernel in it."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    text = compiled.as_text()
    require("tpu_custom_call" in text, "compiled program holds the Pallas "
            "kernel (no silent switch to a jnp engine)")
    return compiled, text, secs


def timed(compiled, *args):
    """Run ``compiled`` to completion; returns (outputs, wall seconds)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def phase_kernels(n: int, rng: np.random.Generator) -> None:
    """SpMV and one fused PIPECG sweep against float64 NumPy."""
    from repro.core.krylov.operators import dia_gather_matvec
    from repro.kernels import ops

    # |offset| 130 spans two 128-lane rows: exercises the sublane shifts
    offsets = (-130, -1, 0, 1, 130)
    h = max(abs(o) for o in offsets)
    bands = rng.standard_normal((len(offsets), n)).astype(np.float32)
    for k, off in enumerate(offsets):   # DIA: no entries outside the matrix
        if off < 0:
            bands[k, :-off] = 0.0
        elif off > 0:
            bands[k, n - off:] = 0.0
    bands[offsets.index(0)] = 4.0 + np.abs(bands[offsets.index(0)])
    x_ext = rng.standard_normal(n + 2 * h).astype(np.float32)

    spmv, _, secs = compile_with_kernel(
        lambda bd, xe: ops.spmv_dia_ext(offsets, bd, xe, h), bands, x_ext)
    y, run = timed(spmv, bands, x_ext)
    b64 = bands.astype(np.float64)
    x64 = x_ext.astype(np.float64)
    terms = np.stack([b64[k] * x64[h + off:h + off + n]
                      for k, off in enumerate(offsets)])
    err = np.max(np.abs(np.asarray(y, np.float64) - terms.sum(0)))
    scale = np.max(np.abs(terms).sum(0))
    info(phase="kernels", kernel="spmv_dia", n=n, compile_s=secs, run_s=run,
         rel_err=err / scale)
    require(err <= KERNEL_RTOL * scale, "spmv_dia matches float64")

    mv = lambda v: dia_gather_matvec(offsets, b64, v, np)
    c = np.zeros(n)                      # ABFT column sums c = A^T 1
    for k, off in enumerate(offsets):
        if off >= 0:
            c[off:] += b64[k, :n - off]
        else:
            c[:n + off] += b64[k, -off:]
    invd = (1.0 / bands[offsets.index(0)]).astype(np.float32)
    x, r, u, p = (rng.standard_normal(n).astype(np.float32) for _ in range(4))
    alpha, beta = np.float32(0.37), np.float32(-0.21)
    step, _, secs = compile_with_kernel(
        lambda *a: ops.pipecg_spmv_fused_step(offsets, *a),
        bands, invd, x, r, u, p, alpha, beta)
    outs, run = timed(step, bands, invd, x, r, u, p, alpha, beta)
    f = lambda v: v.astype(np.float64)
    a, be, iv = float(alpha), float(beta), f(invd)
    p2 = f(u) + be * f(p)
    s2 = mv(p2)
    u2 = f(u) - a * iv * s2
    w2 = mv(u2)
    x2 = f(x) + a * p2
    r2 = f(r) - a * s2
    want = [x2, r2, u2, p2]
    errs = [float(np.max(np.abs(np.asarray(g, np.float64) - w))
                  / np.max(np.abs(w))) for g, w in zip(outs[:4], want)]
    prods = [r2 * u2, w2 * u2, r2 * r2, r2 * w2, w2 * w2, w2 - c * u2]
    red = np.asarray(outs[4], np.float64)
    red_errs = [abs(red[i] - t.sum()) / np.abs(t).sum()
                for i, t in enumerate(prods)]
    info(phase="kernels", kernel="pipecg_spmv_fused", n=n, compile_s=secs,
         run_s=run, vec_rel_err=max(errs), red_rel_err=max(red_errs))
    require(max(errs) <= KERNEL_RTOL, "fused sweep vectors match float64")
    # reductions over 2^20 terms: fp32 partial sums, far from cancellation
    require(max(red_errs) <= 1e-4, "fused sweep reduction row matches")


def phase_solve(n: int) -> None:
    """pipecg (fused single sweep) and cg on the 1-D Laplacian."""
    import jax.numpy as jnp
    from repro.core.krylov import cg, pipecg, tridiagonal_laplacian
    from repro.core.krylov.operators import DiaMatrix
    from repro.core.krylov.options import SolverOptions
    from repro.serve.load import laplacian_mode_rhs

    A = tridiagonal_laplacian(n, dtype=jnp.float32)
    b = laplacian_mode_rhs(n, MODES, np.random.default_rng(SEED))
    b = jnp.asarray(b, jnp.float32)
    opts = SolverOptions(engine="fused", M="jacobi", maxiter=MAXITER, tol=TOL)
    for name, solver in (("pipecg", pipecg), ("cg", cg)):
        run = lambda bands, rhs: solver(DiaMatrix(A.offsets, bands), rhs,
                                        options=opts)
        compiled, _, secs = compile_with_kernel(run, A.bands, b)
        res, wall = timed(compiled, A.bands, b)
        _, eta = true_residual(A, b, res.x)
        iters = int(res.iters)
        info(phase="solve", solver=name, engine="fused", n=n, iters=iters,
             res_norm=float(res.res_norm), backward_error=eta,
             backward_error_eps=eta / EPS32, compile_s=secs, solve_s=wall,
             loop_steps=min(iters + 1, MAXITER))
        require(iters < MAXITER, f"{name} reached tol={TOL}")
        require(eta <= ETA_TOL, f"{name} backward error <= {ETA_TOL:.3g}")


def phase_serve(n: int) -> None:
    """SolverServer with the multi-RHS fused sweep (k = 8 slots)."""
    import jax.numpy as jnp
    from repro.core.krylov import tridiagonal_laplacian
    from repro.core.krylov.options import SolverOptions
    from repro.serve.load import synthetic_requests
    from repro.serve.server import SolverServer

    A = tridiagonal_laplacian(n, dtype=jnp.float32)
    reqs = synthetic_requests(A, 12, tol=1e-4, maxiter=400,
                              modes=(MODES // 2, MODES), M="jacobi",
                              seed=SEED)
    server = SolverServer(k_slots=8, options=SolverOptions(engine="fused"))
    t0 = time.perf_counter()
    server.warmup(reqs[0])
    warm = time.perf_counter() - t0
    batcher = next(iter(server.batchers.values()))
    step = batcher.compiled.step.lower(
        batcher.bands, batcher.state, jnp.asarray(batcher.tol2)).compile()
    require("tpu_custom_call" in step.as_text(),
            "the server's batched step holds the Pallas kernel")
    server.submit_all(reqs)
    stats = server.run()
    recs = {r.rid: r for r in server.records}
    require(sorted(recs) == [r.rid for r in reqs], "every request answered")
    info(phase="serve", n=n, k_slots=8, requests=len(reqs),
         server_converged=stats.n_converged, restarts=stats.restarts,
         warmup_s=warm, wall_s=stats.wall_s, latency=stats.latency.as_dict())
    failed = []
    for req in reqs:
        rec = recs[req.rid]
        require(rec.x is not None, f"request {req.rid} returned an answer")
        rel, eta = true_residual(A, req.b, rec.x)
        # the request's relative tolerance, as a backward error of x
        bn = np.linalg.norm(np.asarray(req.b, np.float64))
        eta_tol = 1.01 * req.tol * bn / (
            A.inf_norm() * np.linalg.norm(np.asarray(rec.x, np.float64)) + bn)
        info(phase="serve", rid=req.rid, converged=rec.converged,
             iters=rec.iters, restarts=rec.restarts, rel_residual=rel,
             backward_error_eps=eta / EPS32, tol_eps=eta_tol / EPS32)
        # the server's contract: converged means the host float64
        # residual met the request's tolerance; any other answer must be
        # as good as fp32 allows
        if rec.converged and rel > 1.01 * req.tol:
            failed.append(f"request {req.rid} converged, rel {rel:.3g} "
                          f"> tol {req.tol}")
        if eta > max(ETA_TOL, eta_tol):
            failed.append(f"request {req.rid} backward error "
                          f"{eta / EPS32:.3g} eps > max(32, "
                          f"{eta_tol / EPS32:.3g}) eps")
    require(not failed, "; ".join(failed))


def phase_sharded(n_per_chip: int, chips: int) -> None:
    """distributed_solve(pipecg, engine="sharded_fused") over ``chips``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.krylov import pipecg, tridiagonal_laplacian
    from repro.core.krylov.distributed import distributed_solve
    from repro.core.krylov.operators import DiaMatrix
    from repro.core.krylov.options import SolverOptions
    from repro.launch.hlo_analysis import split_phase_overlap
    from repro.serve.load import laplacian_mode_rhs

    devs = jax.devices()[:chips]
    require(len(devs) == chips, f"{chips} devices present")
    mesh = Mesh(np.array(devs), ("shards",))
    n = chips * n_per_chip
    A = tridiagonal_laplacian(n, dtype=jnp.float32)
    b = laplacian_mode_rhs(n, MODES, np.random.default_rng(SEED))
    bands = jax.device_put(A.bands, NamedSharding(mesh, P(None, "shards")))
    bd = jax.device_put(jnp.asarray(b, jnp.float32),
                        NamedSharding(mesh, P("shards")))
    opts = SolverOptions(engine="sharded_fused", M="jacobi",
                         maxiter=MAXITER, tol=TOL)
    run = lambda bands_, rhs: distributed_solve(
        pipecg, DiaMatrix(A.offsets, bands_), rhs, mesh, options=opts)
    compiled, text, secs = compile_with_kernel(run, bands, bd)
    overlap = split_phase_overlap(text)
    per_body = [v["all_reduce"] for v in overlap["bodies"].values()]
    require(overlap["overlap_ok"] and per_body == [1],
            f"one all-reduce per loop body, independent of the halo "
            f"permutes (got {overlap})")
    res, wall = timed(compiled, bands, bd)
    shards = res.x.addressable_shards
    require(sorted(s.device.id for s in shards) == sorted(d.id for d in devs)
            and all(s.data.shape == (n_per_chip,) for s in shards),
            "each chip holds its quarter of x")
    _, eta = true_residual(A, bd, res.x)
    iters = int(res.iters)
    info(phase="sharded", chips=chips, n=n, n_per_chip=n_per_chip,
         iters=iters, res_norm=float(res.res_norm), backward_error=eta,
         backward_error_eps=eta / EPS32, compile_s=secs, solve_s=wall,
         overlap=overlap["bodies"])
    require(iters < MAXITER, f"sharded pipecg reached tol={TOL}")
    require(eta <= ETA_TOL, f"sharded backward error <= {ETA_TOL:.3g}")


def main(argv=None) -> int:
    """Run the one-chip phases (or the 4-chip phase); 0 when all pass."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path over a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    info(device=device)
    require(dev.platform == "tpu", "JAX runs on a TPU")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    if args.chips == 1:
        phase_kernels(N, np.random.default_rng(SEED))
        phase_solve(N)
        phase_serve(N)
    else:
        phase_sharded(N, args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
