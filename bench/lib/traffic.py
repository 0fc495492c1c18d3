"""Seeded input generators: right-hand-side pools.

Copied into the benchmark (not imported from the program) so that a
change to the program cannot move what it is measured on.

* ``rhs_pool`` makes a ``(pool, n)`` block of unit-norm right-hand sides
  in one jitted call on the device.  ``"modes"`` spans random sine modes
  of the 1-D Dirichlet Laplacian, as ``serve/load.laplacian_mode_rhs``
  does: CG on such a right-hand side converges in about as many
  iterations as it has modes.  The sines are built from exact integer
  phases on the host as two small tables per mode (angle addition over
  ``row = q * R + r``), and the device forms ``b`` with two matrix
  products, so making a pool costs seconds at millions of rows instead
  of the O(modes * n) host loop.  ``"modes2d"`` does the same for the
  5-point Laplacian on the configuration's ``nx`` by ``ny`` grid, whose
  modes are products of a sine in x and one in y: one table per
  direction and one product on the device.  A traffic mix with a
  ``pool_seed`` makes the same pool for every ``--seed``.
* ``max_condition`` bounds the work of every entry: modes are drawn only
  among those whose eigenvalue is at least the operator's largest over
  ``max_condition``, so that no seed draws a mode low enough to keep a
  solve from converging within the mix's ``maxiter``.  Without it every
  mode may be drawn.
"""
from __future__ import annotations


import numpy as np

TILE = 2048    # R: columns of the (n / R, R) row grid of a mode pool


def sub_seed(seed: int, *path: int) -> int:
    """A 31-bit seed derived from any whole ``seed`` and a tag path."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), *path])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def laplacian_1d_eigs(n: int) -> np.ndarray:
    """Eigenvalues ``2 - 2 cos(pi k / (n + 1))`` of tridiag(-1, 2, -1),
    ``k = 1..n``."""
    return 2.0 - 2.0 * np.cos(np.pi * np.arange(1, n + 1) / (n + 1))


def eligible(eigs: np.ndarray, max_condition) -> np.ndarray:
    """Indices of the modes whose eigenvalue is within ``max_condition``
    of the largest (every mode when it is None)."""
    if max_condition is None:
        return np.arange(eigs.size)
    return np.flatnonzero(eigs >= eigs.max() / float(max_condition))


def mode_tables(n: int, pool: int, modes, rng: np.random.Generator,
                max_condition=None):
    """Host tables of a pool of ``pool`` mode right-hand sides.

    Returns ``(c, sq, cq, cr, sr)`` float32 arrays: ``c`` is
    ``(pool, J)`` (zero past each entry's own mode count), ``sq``/``cq``
    are ``(pool, J, n / R)`` = sin/cos(theta q R) and ``cr``/``sr`` are
    ``(pool, J, R)`` = cos/sin(theta (r + 1)), with ``theta = pi k /
    (n + 1)`` for mode index ``k`` in ``1..n``.
    """
    lo, hi = int(modes[0]), int(modes[1])
    if n % TILE:
        raise ValueError(f"n={n} is not a multiple of {TILE}")
    nq = n // TILE
    period = 2 * (n + 1)               # sin(pi t / (n + 1)) has period 2(n+1)
    k_ok = eligible(laplacian_1d_eigs(n), max_condition) + 1
    c = np.zeros((pool, hi))
    ks = np.ones((pool, hi), np.int64)
    for i in range(pool):
        m = int(rng.integers(lo, hi + 1))
        ks[i, :m] = rng.choice(k_ok, size=m, replace=False)
        c[i, :m] = rng.standard_normal(m)
    q = np.arange(nq, dtype=np.int64) * TILE
    r = np.arange(1, TILE + 1, dtype=np.int64)
    ang_q = np.pi * ((ks[..., None] * q) % period) / (n + 1)
    ang_r = np.pi * ((ks[..., None] * r) % period) / (n + 1)
    f = lambda a: a.astype(np.float32)
    return (f(c), f(np.sin(ang_q)), f(np.cos(ang_q)), f(np.cos(ang_r)),
            f(np.sin(ang_r)))


def sines(k: np.ndarray, m: int) -> np.ndarray:
    """``sin(pi k t / (m + 1))`` for ``t = 1..m`` from exact integer
    phases, float32, one row per entry of ``k``."""
    t = np.arange(1, m + 1, dtype=np.int64)
    ang = np.pi * ((k[..., None] * t) % (2 * (m + 1))) / (m + 1)
    return np.sin(ang).astype(np.float32)


def mode_tables_2d(nx: int, ny: int, pool: int, modes,
                   rng: np.random.Generator, max_condition=None):
    """Host tables of a pool of 2-D mode right-hand sides.

    Returns ``(c, sx, sy)``: ``c`` is ``(pool, J)`` (zero past each
    entry's own mode count), ``sx`` ``(pool, J, nx)`` and ``sy``
    ``(pool, J, ny)`` the sines of each mode's distinct ``(kx, ky)``,
    among those that ``max_condition`` admits.
    """
    lo, hi = int(modes[0]), int(modes[1])
    eigs = (laplacian_1d_eigs(ny)[:, None] + laplacian_1d_eigs(nx)).ravel()
    idx_ok = eligible(eigs, max_condition)
    c = np.zeros((pool, hi))
    kx = np.ones((pool, hi), np.int64)
    ky = np.ones((pool, hi), np.int64)
    for i in range(pool):
        m = int(rng.integers(lo, hi + 1))
        idx = rng.choice(idx_ok, size=m, replace=False)
        kx[i, :m], ky[i, :m] = idx % nx + 1, idx // nx + 1
        c[i, :m] = rng.standard_normal(m)
    return c.astype(np.float32), sines(kx, nx), sines(ky, ny)


def _rows(b):
    """The rows of ``b``, each scaled to unit norm, as a tuple."""
    import jax.numpy as jnp

    b = b / jnp.linalg.norm(b, axis=1, keepdims=True)
    return tuple(b[i] for i in range(b.shape[0]))


def rhs_pool(traffic: dict, cfg: dict, seed: int, sharding=None) -> tuple:
    """``traffic["pool"]`` float32 unit-norm right-hand sides of the
    configuration's ``n`` rows, made on the device in one jitted call
    (each placed by ``sharding`` when given)."""
    import jax
    import jax.numpy as jnp

    n, pool = int(cfg["n"]), int(traffic["pool"])
    kind = traffic["rhs"]
    out = sharding
    seed = traffic.get("pool_seed", seed)
    if kind == "modes":
        tables = mode_tables(n, pool, traffic["modes"],
                             np.random.default_rng(sub_seed(seed, 1)),
                             traffic.get("max_condition"))

        def make(c, sq, cq, cr, sr):
            hi = jax.lax.Precision.HIGHEST
            b = (jnp.einsum("pj,pjq,pjr->pqr", c, sq, cr, precision=hi)
                 + jnp.einsum("pj,pjq,pjr->pqr", c, cq, sr, precision=hi))
            return _rows(b.reshape(pool, n))

        return jax.jit(make, out_shardings=out)(*tables)
    if kind == "modes2d":
        nx, ny = int(cfg["nx"]), int(cfg["ny"])
        if nx * ny != n:
            raise ValueError(f"grid {nx} x {ny} is not {n} rows")
        tables = mode_tables_2d(nx, ny, pool, traffic["modes"],
                                np.random.default_rng(sub_seed(seed, 1)),
                                traffic.get("max_condition"))

        def make(c, sx, sy):
            b = jnp.einsum("pj,pjy,pjx->pyx", c, sy, sx,
                           precision=jax.lax.Precision.HIGHEST)
            return _rows(b.reshape(pool, n))

        return jax.jit(make, out_shardings=out)(*tables)
    raise ValueError(f"unknown rhs kind {kind!r}")

