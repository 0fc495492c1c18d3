"""PETSc KSP ex2's operator: the 5-point Laplacian on an nx by ny grid.

Row ``i = y * nx + x`` (x fastest) holds 4 on the diagonal and -1 for
each of its grid neighbours inside the grid (Dirichlet boundary), at
offsets ``-nx, -1, +1, +nx``.  DIA layout, ``A[i, i + offsets[k]] =
bands[k, i]``, entries outside the matrix zero (the layout of the
program's ``laplacian_2d``).  No randomness: ``seed`` is unused.
"""
from __future__ import annotations


def build(cfg: dict, seed: int, sharding=None):
    """``(offsets, bands)`` for the ``cfg["nx"]`` by ``cfg["ny"]`` grid,
    float32 on the device."""
    import jax
    import jax.numpy as jnp

    nx, ny = int(cfg["nx"]), int(cfg["ny"])
    n = nx * ny

    def make():
        i = jnp.arange(n)
        x = i % nx

        def band(inside):
            return jnp.where(inside, -1.0, 0.0).astype(jnp.float32)

        return jnp.stack([band(i >= nx), band(x != 0),
                          jnp.full((n,), 4.0, jnp.float32),
                          band(x != nx - 1), band(i < n - nx)])

    return (-nx, -1, 0, 1, nx), jax.jit(make, out_shardings=sharding)()
