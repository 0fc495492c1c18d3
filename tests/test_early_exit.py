"""The CG / PIPECG loops stop at convergence (``base.run_until_done``).

A converged solve gives the same ``x``, ``iters`` and ``res_norm``, bit
for bit, whatever ``maxiter`` lets it run past convergence; ``tol = 0``
still runs every step; a batch freezes each converged column until the
last one is done.
"""
import os
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krylov import cg, pipecg, pipecg_multi, tridiagonal_laplacian
from repro.core.krylov.options import SolverOptions

from conftest import run_subprocess_with_retry

N = 256
TOL = 1e-8
PATHS = {
    "fused": (pipecg, dict(engine="fused", M="jacobi")),
    "naive": (pipecg, dict(engine="naive", M="jacobi")),
    "inline": (pipecg, dict(engine=None)),
    "cg": (cg, dict(engine=None)),
}


def _modes_rhs(n, modes):
    """A right-hand side of a few sine modes: CG converges in about as
    many steps as there are modes."""
    k = np.arange(1, n + 1)
    return jnp.asarray(sum((j + 1) * np.sin(np.pi * m * k / (n + 1))
                           for j, m in enumerate(modes)))


def _solve(path, b, maxiter, tol):
    solver, kw = PATHS[path]
    A = tridiagonal_laplacian(b.shape[-1])
    return solver(A, b, options=SolverOptions(maxiter=maxiter, tol=tol, **kw))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_converged_solve_is_the_same_at_any_maxiter(path):
    b = _modes_rhs(N, (3, 40, 77, 150, 201))
    full = _solve(path, b, 256, TOL)
    k = int(full.iters)
    assert 0 < k < 255
    # iters + 1 steps is the least maxiter at which the solve converges
    least = _solve(path, b, k + 1, TOL)
    short = _solve(path, b, k, TOL)
    assert int(short.iters) == k            # cut one step short: not done
    for f in ("x", "iters", "res_norm"):
        assert np.array_equal(np.asarray(getattr(full, f)),
                              np.asarray(getattr(least, f))), f
    assert float(full.res_norm) <= TOL * float(jnp.linalg.norm(b))
    # the history of the steps run, then its last entry repeated
    h, h_least = np.asarray(full.res_history), np.asarray(least.res_history)
    assert h.shape == (256,)
    np.testing.assert_array_equal(h[:k + 1], h_least)
    np.testing.assert_array_equal(h[k + 1:], h[k])


@pytest.mark.parametrize("path", sorted(PATHS))
def test_tol_zero_runs_every_step(path):
    b = _modes_rhs(N, (3, 40, 77, 150, 201))
    maxiter = 12
    out = _solve(path, b, maxiter, 0.0)
    assert int(out.iters) == maxiter
    h = np.asarray(out.res_history)
    assert h.shape == (maxiter,)
    assert np.all(np.isfinite(h))
    assert np.all(np.diff(h) != 0)          # no repeated tail: every step ran


@pytest.mark.parametrize("engine", ["fused", "naive"])
def test_batch_freezes_each_converged_column(engine):
    """``pipecg_multi``: the fused engine's batched loop (one ``done`` per
    column) and the naive engine's ``vmap`` of the one-system loop both
    leave a converged column untouched while the others run on."""
    A = tridiagonal_laplacian(N)
    B = jnp.stack([_modes_rhs(N, (5, 90)),
                   jnp.asarray(np.random.default_rng(0).standard_normal(N))])
    solve = lambda maxiter: pipecg_multi(A, B, maxiter=maxiter, tol=TOL,
                                         M="jacobi", engine=engine)
    full = solve(400)
    iters = np.asarray(full.iters)
    easy, hard = int(iters[0]), int(iters[1])
    assert easy + 1 < hard < 400
    # the easy column, stopped by maxiter right after it converged, is the
    # same as when it sat frozen through the hard column's steps
    cut = solve(easy + 1)
    assert int(cut.iters[0]) == easy and int(cut.iters[1]) == easy + 1
    np.testing.assert_array_equal(np.asarray(full.x[0]), np.asarray(cut.x[0]))
    np.testing.assert_array_equal(np.asarray(full.res_norm[0]),
                                  np.asarray(cut.res_norm[0]))
    h = np.asarray(full.res_history)
    assert h.shape == (2, 400)
    np.testing.assert_array_equal(h[0, easy + 1:], h[0, easy + 1])
    for j in range(2):   # each column converges as it would alone
        one = pipecg(A, B[j], options=SolverOptions(
            maxiter=400, tol=TOL, engine=engine, M="jacobi"))
        assert int(one.iters) == int(iters[j])
        np.testing.assert_allclose(np.asarray(full.x[j]), np.asarray(one.x),
                                   rtol=1e-10, atol=1e-12)


def test_sharded_fused_stops_at_convergence():
    """The 1-D sharded body on 4 virtual CPU devices: a converged solve is
    the same at any maxiter, its history ends on the final residual, and
    ``tol = 0`` runs every step."""
    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.krylov import pipecg, tridiagonal_laplacian
        from repro.core.krylov.distributed import distributed_solve
        from repro.core.krylov.options import SolverOptions

        n, tol = 256, 1e-8
        A = tridiagonal_laplacian(n)
        k = np.arange(1, n + 1)
        b = jnp.asarray(sum((j + 1) * np.sin(np.pi * m * k / (n + 1))
                            for j, m in enumerate((3, 40, 77, 150, 201))))
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("shards",))
        solve = lambda maxiter, tol: jax.jit(lambda bb: distributed_solve(
            pipecg, A, bb, mesh, options=SolverOptions(
                engine="sharded_fused", M="jacobi", maxiter=maxiter,
                tol=tol)))(b)
        full = solve(256, tol)
        it = int(full.iters)
        assert 0 < it < 255, it
        least = solve(it + 1, tol)
        assert int(solve(it, tol).iters) == it
        for f in ("x", "iters", "res_norm"):
            assert np.array_equal(np.asarray(getattr(full, f)),
                                  np.asarray(getattr(least, f))), f
        h = np.asarray(full.res_history)
        assert h.shape == (256,)
        assert np.array_equal(h[:it + 1], np.asarray(least.res_history))
        assert np.all(h[it:] == float(full.res_norm)), h[it - 1:it + 3]
        loc = pipecg(A, b, options=SolverOptions(
            engine="fused", M="jacobi", maxiter=256, tol=tol))
        np.testing.assert_allclose(np.asarray(full.x), np.asarray(loc.x),
                                   rtol=1e-9, atol=1e-12)
        zero = solve(12, 0.0)
        hz = np.asarray(zero.res_history)
        assert int(zero.iters) == 12 and hz.shape == (12,)
        assert np.all(np.diff(hz) != 0)
        print("SHARDED_EARLY_EXIT_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = run_subprocess_with_retry(script, env=env)
    assert "SHARDED_EARLY_EXIT_OK" in proc.stdout, proc.stdout + proc.stderr
