"""ex23's operator: the 1-D Dirichlet Laplacian tridiag(-1, 2, -1).

DIA layout, ``A[i, i + offsets[k]] = bands[k, i]``, entries that fall
outside the matrix are zero (the same layout the program's
``tridiagonal_laplacian`` uses).  No randomness: ``seed`` is unused.
"""
from __future__ import annotations

OFFSETS = (-1, 0, 1)


def build(cfg: dict, seed: int, sharding=None):
    """``(offsets, bands)`` for ``cfg["n"]`` rows, float32 on the device."""
    import jax
    import jax.numpy as jnp

    n = int(cfg["n"])

    def make():
        i = jnp.arange(n)
        lo = jnp.where(i == 0, 0.0, -1.0).astype(jnp.float32)
        hi = jnp.where(i == n - 1, 0.0, -1.0).astype(jnp.float32)
        return jnp.stack([lo, jnp.full((n,), 2.0, jnp.float32), hi])

    return OFFSETS, jax.jit(make, out_shardings=sharding)()
