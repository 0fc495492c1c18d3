"""Common solver machinery: result container, dot contexts.

A ``DotContext`` abstracts the global reduction: the local (single-device)
context is a plain ``jnp.vdot``; the distributed context adds ``psum`` over a
mesh axis (inside shard_map).  This is exactly the paper's model split —
"local computation" vs "global synchronization".
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class SolveResult(NamedTuple):
    """Solver output: solution, iteration count, residual norm + history.

    ``detect_history`` (optional) carries the per-iteration ABFT detector
    values of the solve — the in-kernel SpMV checksum residual for the
    fused/sharded engines, the psum'd state deviation for the depth-l
    path (core/krylov/abft.py).  ``None`` (the default, an empty pytree
    subtree) for solver paths that carry no detector, so existing
    4-field constructions and shard_map out_specs stay valid.

    History tail: both histories always hold ``maxiter`` entries per
    system.  The CG and depth-1 PIPECG loops (``cg``, ``pipecg`` on every
    engine, ``pipecg_multi`` and the 1-D ``sharded_fused`` body) stop
    once every system has converged (:func:`run_until_done`), so the
    entries after the last executed step repeat that step's entry — for
    the sharded body, whose history is shifted back by one, that is the
    final ``res_norm`` and checksum.  With several systems in one loop
    (the fused engine's ``pipecg_multi``, a batched sharded solve), a
    column that converged before the last one keeps reporting its frozen
    state until the loop exits; other engines ``vmap`` the one-system
    loop, so each column's tail starts after its own last step.  The
    other solvers run all ``maxiter`` steps.
    """

    x: jnp.ndarray
    iters: jnp.ndarray            # number of iterations performed
    res_norm: jnp.ndarray         # final ||b - A x||_2
    res_history: jnp.ndarray      # per-iteration residual norms (maxiter,)
    detect_history: Optional[jnp.ndarray] = None  # ABFT detector values


def run_until_done(step: Callable, state0: dict, maxiter: int):
    """Run ``step`` until every entry of ``state["done"]`` holds, or for
    ``maxiter`` steps: ``lax.scan(step, state0, None, length=maxiter)``
    with an early exit.

    ``step(state) -> (state, out)``.  Each step's ``out`` (a pytree of
    arrays) is written into a ``(maxiter, ...)`` buffer; entries after
    the last executed step repeat that step's entry.  Returns
    ``(state, outs, steps)``, ``steps`` the number of steps executed.

    ``tol = 0`` never sets ``done`` short of an exact breakdown, so such
    a solve runs all ``maxiter`` steps.  Under ``vmap`` the loop runs
    until every batch member is done and leaves finished members'
    carries untouched (the while loop's batching rule).
    """
    shapes = jax.eval_shape(lambda st: step(st)[1], state0)
    bufs0 = jax.tree.map(
        lambda s: jnp.zeros((maxiter,) + s.shape, s.dtype), shapes)

    def cond(carry):
        k, st, _ = carry
        return (k < maxiter) & ~jnp.all(st["done"])

    def body(carry):
        k, st, bufs = carry
        st, out = step(st)
        bufs = jax.tree.map(
            lambda buf, o: jax.lax.dynamic_update_index_in_dim(buf, o, k, 0),
            bufs, out)
        return k + 1, st, bufs

    steps, st, bufs = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), state0, bufs0))
    ran = jnp.arange(maxiter) < steps

    def fill(buf):
        last = jax.lax.dynamic_index_in_dim(buf, jnp.maximum(steps - 1, 0), 0)
        return jnp.where(ran.reshape((-1,) + (1,) * (buf.ndim - 1)), buf, last)

    return st, (jax.tree.map(fill, bufs) if maxiter else bufs), steps


def local_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Single-device inner product (the paper's "local computation")."""
    return jnp.sum(a * b)


def make_psum_dot(axis_name: str) -> Callable:
    """Distributed inner product: local dot + psum over ``axis_name``."""
    def pdot(a, b):
        return jax.lax.psum(jnp.sum(a * b), axis_name)
    return pdot


def as_matvec(A) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Normalize an operator (callable or ``.matvec`` object) to a callable."""
    if callable(A):
        return A
    return A.matvec
