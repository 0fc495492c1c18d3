"""Pluggable iteration engines: WHO executes a solver iteration's vector work.

The solvers in this package describe Krylov recurrences; an *engine*
decides how the memory-bound inner loop hits the hardware:

* ``NaiveEngine`` — plain jnp ops, one XLA op per AXPY/dot (~30n words per
  PIPECG iteration of vector traffic, plus M-apply + SpMV sweeps).
* ``FusedEngine`` — Pallas-backed.  For a DIA operator with identity or
  Jacobi preconditioning, a whole PIPECG iteration (8 updates + M-apply +
  SpMV + the fused reduction) is ONE kernel sweep
  (kernels/pipecg_spmv_fused.py, ~(9 + n_bands) n words); otherwise it
  falls back to the update-only fusion kernel (kernels/pipecg_fused.py)
  with explicit operator / preconditioner applications.  GMRES-family
  orthogonalization coefficients go through the one-pass multi-dot kernel
  (kernels/fused_dots.py).
* ``ShardedFusedEngine`` — the distributed counterpart: selected via
  ``distributed_solve(..., engine="sharded_fused")``, it runs the same
  single-sweep kernel per shard inside shard_map with ppermute'd halo
  operands and finishes the kernel's partial reductions with a
  split-phase psum (core/krylov/distributed.py::sharded_pipecg_solve).
  With ``pipecg_l`` and ``l >= 2`` it switches to depth-l ghost-basis
  blocks — one Gram psum and one l*halo ppermute per l iterations
  (sharded_pipecg_depth_solve; DESIGN.md §Depth-l-data-flow).

Engines are selected per solve via ``engine="naive" | "fused"`` (or an
Engine instance) on ``cg`` / ``pipecg`` / ``pipecr`` / ``gmres`` /
``pgmres``; ``engine=None`` keeps the historical inline-jnp code paths
untouched (the distributed shard_map solvers rely on those).

The registry is open: third-party engines register with
``@register_engine``.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.krylov.operator import BsrMatrix
from repro.core.krylov.operators import DiaMatrix

ENGINES: Dict[str, "Engine"] = {}

# operator formats whose fused single-sweep kernels exist (the in-kernel
# Jacobi/identity preconditioning path of FusedEngine)
_SWEEP_FORMATS = ("dia", "bsr")


def register_engine(cls):
    """Class decorator: instantiate + register under ``cls.name``."""
    ENGINES[cls.name] = cls()
    return cls


def get_engine(engine: Union[str, "Engine", None]) -> Optional["Engine"]:
    """Resolve an engine selector (name / instance / None) to an Engine."""
    if engine is None or isinstance(engine, Engine):
        return engine
    try:
        return ENGINES[engine]
    except KeyError:
        raise ValueError(
            f"unknown engine {engine!r}; registered: {sorted(ENGINES)}"
        ) from None


def _jacobi_inv_diag(A, M, n, dtype):
    """inv_diag for the single-sweep path, or None if M is not expressible.

    M may be None (identity), the string "jacobi", or a callable; callables
    are opaque, so only the first two qualify for in-kernel preconditioning.
    Dispatches on the operator protocol's ``format`` tag: any format with
    a fused single-sweep kernel (DIA, BSR) qualifies.
    """
    if getattr(A, "format", None) not in _SWEEP_FORMATS:
        return None
    if M is None:
        return jnp.ones((n,), dtype)
    if M == "jacobi":
        return (1.0 / A.diagonal()).astype(dtype)
    return None


def _resolve_M(A, M) -> Callable:
    if M is None:
        return lambda z: z
    if M == "jacobi":
        inv_d = 1.0 / A.diagonal()
        return lambda z: inv_d * z
    return M


class Engine:
    """Iteration-engine interface.

    ``pipecg_init`` returns an opaque vector-state pytree plus the first
    (gamma, delta); ``pipecg_iter`` advances it by one iteration and
    returns ``(vecs, gamma, delta, rr, aux)`` where ``aux`` is a dict of
    detector side-channels riding the same reduction: ``chk`` (the ABFT
    checksum residual ``1^T w - c^T u``, see core/krylov/abft.py) and
    ``ww`` (``<w, w>``, feeding the deviation recursion).  ``dots`` is the
    GMRES-family multi-dot; ``spmv`` / ``precond`` the standalone operator
    applications.
    """

    name = "abstract"

    def spmv(self, A, x):
        """Operator application; batched (k, n) inputs are vmapped."""
        if x.ndim == 2:
            return jax.vmap(lambda v: self._spmv(A, v))(x)
        return self._spmv(A, v=x)

    def _spmv(self, A, v):
        raise NotImplementedError

    def precond(self, A, M, r):
        return _resolve_M(A, M)(r)

    def dots(self, V, z):
        raise NotImplementedError

    def pipecg_init(self, A, b, x0, M, ip: str):
        raise NotImplementedError

    def pipecg_iter(self, A, M, ip: str, vecs, alpha, beta):
        raise NotImplementedError

    def pipecg_operator(self, A, M, vecs):
        """The operator ``pipecg_iter`` takes, made once before the loop.

        ``vecs`` is the state from ``pipecg_init``.  The default is ``A``.
        """
        return A


class _DiaSweep(NamedTuple):
    """A DIA operator split once per solve for the single-sweep kernel."""

    offsets: Tuple[int, ...]
    block: int
    op: object        # kernels/pipecg_spmv_fused.py::HaloOperator


def _ip_pick(ip: str, ru, wu, rw, ww):
    """(gamma, delta) from the five fused partials."""
    return (ru, wu) if ip == "id" else (rw, ww)


def _rdot(a, b):
    """Row-wise dot: scalar for (n,) operands, (k,) for batched (k, n)."""
    return jnp.sum(a * b, axis=-1)


def _abft_chk(A, u, w):
    """Signed ABFT checksum residual ``1^T w - c^T u`` (``c = A^T 1``).

    Exactly ``1^T (A u - w)`` for any ``SparseOperator`` exposing
    ``column_checksum`` (DIA, BSR) — rounding-level when the carried ``w``
    faithfully tracks ``A u``, O(corruption) otherwise.  For opaque
    operators (no structure to checksum) it returns zeros, so downstream
    detectors see a never-tripping channel rather than a missing one.
    ``A`` is a trace constant under jit, so the column checksum is
    hoisted out of the solver scan.
    """
    if hasattr(A, "column_checksum"):
        c = A.column_checksum().astype(w.dtype)
        # single reduction over (w - c*u): same checksum to rounding, and
        # a standalone plain sum(w) would join XLA's multi-output reduce
        # fusion over w and shift the existing dots' bits (pinned at
        # rtol=1e-12 against the inline path by the equivalence tests)
        return jnp.sum(w - c * u, axis=-1)
    return jnp.zeros(w.shape[:-1], w.dtype)


@register_engine
class NaiveEngine(Engine):
    """Reference engine: every AXPY / dot / SpMV is a separate jnp op."""

    name = "naive"

    def _spmv(self, A, v):
        return A.matvec(v) if hasattr(A, "matvec") else A(v)

    def dots(self, V, z):
        return V @ z

    def pipecg_init(self, A, b, x0, M, ip):
        Mf = _resolve_M(A, M)
        x = jnp.zeros_like(b) if x0 is None else x0
        r = b - self.spmv(A, x)
        u = Mf(r)
        w = self.spmv(A, u)
        gamma = _rdot(r, u) if ip == "id" else _rdot(r, w)
        delta = _rdot(w, u) if ip == "id" else _rdot(w, w)
        m = Mf(w)
        n_ = self.spmv(A, m)
        zero = jnp.zeros_like(b)
        vecs = dict(x=x, r=r, u=u, w=w, m=m, n=n_,
                    z=zero, q=zero, s=zero, p=zero)
        return vecs, gamma, delta

    def pipecg_iter(self, A, M, ip, st, alpha, beta):
        Mf = _resolve_M(A, M)
        alpha = jnp.asarray(alpha)[..., None] if jnp.ndim(alpha) else alpha
        beta = jnp.asarray(beta)[..., None] if jnp.ndim(beta) else beta
        z = st["n"] + beta * st["z"]
        q = st["m"] + beta * st["q"]
        s = st["w"] + beta * st["s"]
        p = st["u"] + beta * st["p"]
        x = st["x"] + alpha * p
        r = st["r"] - alpha * s
        u = st["u"] - alpha * q
        w = st["w"] - alpha * z
        gamma = _rdot(r, u) if ip == "id" else _rdot(r, w)
        delta = _rdot(w, u) if ip == "id" else _rdot(w, w)
        rr = _rdot(r, r)
        m = Mf(w)
        n_ = self.spmv(A, m)
        aux = dict(chk=_abft_chk(A, u, w), ww=_rdot(w, w))
        return (dict(x=x, r=r, u=u, w=w, m=m, n=n_, z=z, q=q, s=s, p=p),
                gamma, delta, rr, aux)


@register_engine
class FusedEngine(Engine):
    """Pallas-backed engine: minimal HBM sweeps per iteration."""

    name = "fused"

    def _spmv(self, A, v):
        if isinstance(A, DiaMatrix):
            from repro.kernels import ops as kops
            h = A.halo
            return kops.spmv_dia_ext(A.offsets, A.bands, jnp.pad(v, (h, h)), h)
        if isinstance(A, BsrMatrix):
            from repro.kernels import ops as kops
            return kops.spmv_bsr(A.indices, A.blocks, v)
        return A.matvec(v) if hasattr(A, "matvec") else A(v)

    def dots(self, V, z):
        from repro.kernels import ops as kops
        return kops.fused_dots(V, z)

    def pipecg_init(self, A, b, x0, M, ip):
        inv_d = _jacobi_inv_diag(A, M, b.shape[-1], b.dtype)
        Mf = _resolve_M(A, M)
        x = jnp.zeros_like(b) if x0 is None else x0
        r = b - self.spmv(A, x)
        u = Mf(r)
        w = self.spmv(A, u)
        gamma = _rdot(r, u) if ip == "id" else _rdot(r, w)
        delta = _rdot(w, u) if ip == "id" else _rdot(w, w)
        if inv_d is not None:
            # single-sweep path: only (x, r, u, p) round-trip HBM per
            # iteration (a DIA operator and its diag^-1 are split once
            # per solve by pipecg_operator)
            return dict(x=x, r=r, u=u, p=jnp.zeros_like(b)), gamma, delta
        # fallback: update-kernel path carries the full 10-vector state
        m = Mf(w)
        n_ = self.spmv(A, m)
        zero = jnp.zeros_like(b)
        vecs = dict(x=x, r=r, u=u, w=w, m=m, n=n_,
                    z=zero, q=zero, s=zero, p=zero)
        return vecs, gamma, delta

    def pipecg_operator(self, A, M, vecs):
        """Split a DIA operator for the single sweep, once per solve.

        The split (row layout, diag^-1, column checksum) is
        loop-invariant; made inside the loop it would be re-done every
        iteration.  diag^-1 takes the OPERATOR's dtype, not x's: under a
        storage-demoting PrecisionPolicy the operator rides in bf16/fp8
        while x stays at accum precision.
        """
        if "w" in vecs or getattr(A, "format", None) != "dia":
            return A
        from repro.kernels import ops as kops

        inv_d = _jacobi_inv_diag(A, M, vecs["x"].shape[-1], A.dtype)
        block, op = kops.pipecg_sweep_operator(A.offsets, A.bands, inv_d,
                                               vecs["x"], vecs["u"])
        return _DiaSweep(A.offsets, block, op)

    def pipecg_iter(self, A, M, ip, st, alpha, beta):
        from repro.kernels import ops as kops

        if "w" not in st:  # single-sweep mega-kernel state
            # Format branch: DIA -> stencil sweep, BSR -> blocked-ELL
            # gather sweep (kernels/spmv_bsr.py)
            if getattr(A, "format", None) == "dia":  # the caller did not
                A = self.pipecg_operator(A, M, st)   # split it: split here
            if isinstance(A, _DiaSweep):
                x, r, u, p, red = kops.pipecg_sweep_step(
                    A.offsets, A.op, st["x"], st["r"], st["u"], st["p"],
                    alpha, beta, block=A.block)
            else:
                inv_d = _jacobi_inv_diag(A, M, st["x"].shape[-1], A.dtype)
                x, r, u, p, red = kops.pipecg_bsr_fused_step(
                    A.indices, A.blocks, inv_d,
                    st["x"], st["r"], st["u"], st["p"], alpha, beta)
            gamma, delta = _ip_pick(ip, red[..., 0], red[..., 1],
                                    red[..., 3], red[..., 4])
            # checksum residual 1^T w' - c^T u' rode the same sweep (col 5)
            aux = dict(chk=red[..., 5], ww=red[..., 4])
            return dict(x=x, r=r, u=u, p=p), gamma, delta, red[..., 2], aux

        # two-sweep fallback: fused updates+dots, then M-apply + SpMV
        Mf = _resolve_M(A, M)
        (x, r, u, w, z, q, s, p, red) = kops.pipecg_fused_step(
            st["x"], st["r"], st["u"], st["w"], st["m"], st["n"],
            st["z"], st["q"], st["s"], st["p"], alpha, beta)
        if ip == "id":
            gamma, delta = red[0], red[1]
        else:
            gamma, delta = _rdot(r, w), _rdot(w, w)
        m = Mf(w)
        n_ = self.spmv(A, m)
        aux = dict(chk=_abft_chk(A, u, w), ww=_rdot(w, w))
        return (dict(x=x, r=r, u=u, w=w, m=m, n=n_, z=z, q=q, s=s, p=p),
                gamma, delta, red[2], aux)


@register_engine
class ShardedFusedEngine(Engine):
    """Distributed single-sweep engine (halo-aware kernel + split-phase psum).

    Unlike the single-device engines, this one does not plug into the
    local solver scan — its reductions are PARTIAL per shard and need the
    mesh to finish them, so it runs only under
    ``distributed_solve(..., engine="sharded_fused")``, which calls
    :meth:`solve` inside shard_map.  Requesting it on a local solver
    raises with a pointer to the right entry point.
    """

    name = "sharded_fused"

    def _reject(self):
        raise ValueError(
            "engine='sharded_fused' computes per-shard partial reductions "
            "and must run inside a mesh: use "
            "distributed_solve(pipecg | pipecg_multi | pipecr, A, b, mesh, "
            "engine='sharded_fused') instead of the local solver entry")

    def _spmv(self, A, v):
        self._reject()

    def dots(self, V, z):
        self._reject()

    def pipecg_init(self, A, b, x0, M, ip):
        self._reject()

    def pipecg_iter(self, A, M, ip, vecs, alpha, beta):
        self._reject()

    # table-driven dispatch: (solver family, operator format) -> the name
    # of the per-shard body in core/krylov/distributed.py.  "dia2d" is the
    # DIA format on a 2-D process grid (N/S/W/E halo pairs per body); new
    # (family, format) engines add a row here, not a fourth solve_* copy.
    _BODIES = {
        ("pipecg", "dia"): "sharded_pipecg_solve",
        ("pipecg", "dia2d"): "sharded_pipecg_solve_2d",
        ("pipecg", "bsr"): "sharded_pipecg_bsr_solve",
        ("pipecg_l", "dia"): "sharded_pipecg_depth_solve",
        ("pipebicgstab", "dia"): "sharded_pipebicgstab_solve",
    }

    def body(self, family: str, fmt: str = "dia"):
        """Per-shard solve body for a (solver family, operator format).

        Families: "pipecg" (the CG/CR single-sweep body — ``ip`` selects
        CR), "pipecg_l" (depth-l ghost-basis blocks), "pipebicgstab".
        Formats: "dia", "dia2d" (DIA on a 2-D process grid), "bsr".
        """
        from repro.core.krylov import distributed
        try:
            return getattr(distributed, self._BODIES[(family, fmt)])
        except KeyError:
            supported = sorted(self._BODIES)
            raise ValueError(
                f"no sharded body for solver family {family!r} with "
                f"operator format {fmt!r}; supported: {supported}"
            ) from None

    def solve(self, offsets, bands_local, b_local, **kw):
        """Per-shard solve body; see distributed.sharded_pipecg_solve."""
        return self.body("pipecg")(offsets, bands_local, b_local, **kw)

    def solve_depth(self, offsets, bands_local, b_local, **kw):
        """Depth-l per-shard body: one Gram psum + one l*halo ppermute
        per l iterations; see distributed.sharded_pipecg_depth_solve."""
        return self.body("pipecg_l")(offsets, bands_local, b_local, **kw)

    def solve_bicgstab(self, offsets, bands_local, b_local, **kw):
        """Pipelined BiCGStab per-shard body: one (6, 6) Gram psum hides
        the FOUR classical synchronizations per iteration; see
        distributed.sharded_pipebicgstab_solve."""
        return self.body("pipebicgstab")(offsets, bands_local, b_local,
                                         **kw)
