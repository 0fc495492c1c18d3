"""Continuous batcher: k RHS slots advancing through ONE compiled step.

The batcher holds a (k, n) PIPECG state — the engine-driven batch state
of ``core/krylov/cg.py::_pipecg_engine`` with the per-column tol-freeze
machinery generalized so every column also carries its OWN ``first``
flag (columns are admitted mid-flight, so "is this my first iteration"
is per-column, not per-batch).  Columns are independent: every engine op
is row-wise (elementwise AXPYs, ``axis=-1`` reductions, per-row SpMV),
so admitting a request into a free column or retiring a converged one
cannot perturb the in-flight columns' recurrences — bit-exactly, which
tests/test_serve.py pins.

Compiled executables are cached at module scope keyed on the STATIC
configuration (engine, offsets, n, k, dtype, M, ip, step_block); the
operator bands are a runtime operand, so a second batcher over any
same-family operator reuses the first one's executables (warm serve
path).  Each cache entry counts its traces — the re-compile pin of the
warm-reuse tests.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.krylov.cg import _pipecg_scalars
from repro.core.krylov.engine import get_engine
from repro.core.krylov.operators import DiaMatrix
from repro.kernels.checksum import dia_column_checksum
from repro.serve.request import SolveRequest

_STEP_CACHE: Dict[Tuple, "_Compiled"] = {}


@dataclasses.dataclass
class _Compiled:
    """Jitted executables + trace counters for one static batch config."""

    step: Callable
    init: Callable
    admit: Callable
    mark_done: Callable
    poison: Callable
    corrupt: Callable
    trace_counts: Dict[str, int]


def clear_compile_cache() -> None:
    """Drop every cached executable (tests)."""
    _STEP_CACHE.clear()


def _build(engine: str, offsets: Tuple[int, ...], n: int, k: int,
           dtype, M, ip: str, step_block: int) -> _Compiled:
    eng = get_engine(engine)
    counts = {"step": 0, "init": 0, "admit": 0}

    def step_fn(bands, state, tol2):
        counts["step"] += 1
        A = eng.pipecg_operator(DiaMatrix(offsets=offsets, bands=bands), M,
                                state["vecs"])

        def body(st, _):
            alpha, beta = _pipecg_scalars(st)
            vecs, gamma_new, delta_new, rr, _aux = eng.pipecg_iter(
                A, M, ip, st["vecs"], alpha, beta)
            done = st["done"] | (rr <= tol2)
            mask = st["done"]

            def frz(nv, ov):  # freeze converged/free columns
                m = (mask.reshape(mask.shape + (1,) * (nv.ndim - mask.ndim))
                     if nv.ndim > mask.ndim else mask)
                return jnp.where(m, ov, nv)

            new = dict(vecs=jax.tree.map(frz, vecs, st["vecs"]),
                       gamma=frz(gamma_new, st["gamma"]),
                       delta=frz(delta_new, st["delta"]),
                       gamma_prev=frz(st["gamma"], st["gamma_prev"]),
                       alpha_prev=frz(alpha, st["alpha_prev"]),
                       # a stepped column is past its first iteration;
                       # frozen columns keep their flag for re-admission
                       first=st["first"] & mask,
                       done=done,
                       iters=st["iters"] + (~done).astype(jnp.int32))
            return new, None

        st, _ = jax.lax.scan(body, state, None, length=step_block)
        r = st["vecs"]["r"]
        rr = jnp.sum(r * r, axis=-1)
        # per-column ABFT state-deviation partials: the server combines
        # them with its host-side 1^T b to form delta = 1^T(b - A x - r)
        # (exact via c = A^T 1 — no SpMV), plus the |.|-sums that scale
        # its trip threshold (signed sums cancel; see abft.py)
        c = dia_column_checksum(offsets, bands)
        x = st["vecs"]["x"]
        det = jnp.stack([jnp.sum(c * x, axis=-1), jnp.sum(r, axis=-1),
                         jnp.sum(jnp.abs(c * x), axis=-1),
                         jnp.sum(jnp.abs(r), axis=-1)], axis=-1)
        return st, (st["done"], st["iters"], rr, det)

    def init_fn(bands, B):
        counts["init"] += 1
        A = DiaMatrix(offsets=offsets, bands=bands)
        return eng.pipecg_init(A, B, None, M, ip)

    def admit_fn(state, slot, col_vecs, gamma0, delta0):
        counts["admit"] += 1
        one = jnp.ones((), state["gamma"].dtype)
        vecs = jax.tree.map(lambda leaf, col: leaf.at[slot].set(col[0]),
                            state["vecs"], col_vecs)
        return dict(vecs=vecs,
                    gamma=state["gamma"].at[slot].set(gamma0[0]),
                    delta=state["delta"].at[slot].set(delta0[0]),
                    gamma_prev=state["gamma_prev"].at[slot].set(one),
                    alpha_prev=state["alpha_prev"].at[slot].set(one),
                    first=state["first"].at[slot].set(True),
                    done=state["done"].at[slot].set(False),
                    iters=state["iters"].at[slot].set(0))

    def mark_done_fn(state, slot):
        return dict(state, done=state["done"].at[slot].set(True))

    def poison_fn(state, slot):
        nan = jnp.asarray(float("nan"), state["vecs"]["r"].dtype)
        vecs = jax.tree.map(lambda leaf: leaf.at[slot].set(nan),
                            state["vecs"])
        return dict(state, vecs=vecs)

    def corrupt_fn(state, slot, magnitude):
        # the carried SOLUTION is the silent target: the recurrence
        # (r, u, w, ...) never sees it, so the column still "converges"
        # — only the server's host-side true-residual check catches it
        vecs = dict(state["vecs"])
        vecs["x"] = vecs["x"].at[slot].add(magnitude)
        return dict(state, vecs=vecs)

    return _Compiled(step=jax.jit(step_fn), init=jax.jit(init_fn),
                     admit=jax.jit(admit_fn),
                     mark_done=jax.jit(mark_done_fn),
                     poison=jax.jit(poison_fn),
                     corrupt=jax.jit(corrupt_fn), trace_counts=counts)


def get_compiled(engine: str, offsets: Tuple[int, ...], n: int, k: int,
                 dtype, M, ip: str, step_block: int) -> _Compiled:
    """Cached executables for one static batch configuration."""
    key = (engine, tuple(offsets), int(n), int(k),
           jnp.dtype(dtype).name, M, ip, int(step_block))
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = _build(engine, tuple(offsets), int(n), int(k),
                                  dtype, M, ip, int(step_block))
    return _STEP_CACHE[key]


class ContinuousBatcher:
    """k-slot multi-RHS PIPECG batch with mid-flight admit/retire.

    One instance is bound to one operator (its bands are the runtime
    operand of the shared executables).  The server drives it:
    ``admit`` fills a free column from a request, ``step`` advances every
    column by ``step_block`` iterations (free/converged columns stay
    frozen), and the returned (done, iters, rr) triple tells the caller
    which columns to retire via ``take``/``release``.
    """

    def __init__(self, A: DiaMatrix, k_slots: int, *, engine: str = "naive",
                 M: Optional[str] = None, ip: str = "id",
                 step_block: int = 8):
        self.A = A
        self.k = int(k_slots)
        self.engine = engine
        self.M = M
        self.ip = ip
        self.step_block = int(step_block)
        self.dtype = A.bands.dtype
        self.bands = jnp.asarray(A.bands)
        self.compiled = get_compiled(engine, tuple(A.offsets), A.n, self.k,
                                     self.dtype, M, ip, self.step_block)
        zero = jnp.zeros((self.k, A.n), self.dtype)
        vecs, _, _ = self.compiled.init(self.bands, zero)
        one = jnp.ones((self.k,), self.dtype)
        self.state = dict(vecs=vecs, gamma=one, delta=one,
                          gamma_prev=one, alpha_prev=one,
                          first=jnp.ones((self.k,), bool),
                          done=jnp.ones((self.k,), bool),
                          iters=jnp.zeros((self.k,), jnp.int32))
        self.tol2 = np.zeros((self.k,), np.float64)
        # host-side 1^T b and sum |b| per slot (the b-leg of the ABFT
        # state deviation; device returns the x/r legs from step())
        self.bsum = np.zeros((self.k,), np.float64)
        self.babs = np.zeros((self.k,), np.float64)
        self.slots: List[Optional[SolveRequest]] = [None] * self.k
        self.blocks = 0
        self.deviation = np.zeros((self.k,), np.float64)
        self.dev_scale = np.zeros((self.k,), np.float64)

    @property
    def trace_counts(self) -> Dict[str, int]:
        """Trace counters of the shared compiled executables."""
        return self.compiled.trace_counts

    def free_slots(self) -> List[int]:
        """Indices of unoccupied columns."""
        return [i for i, r in enumerate(self.slots) if r is None]

    @property
    def active(self) -> int:
        """Number of occupied columns."""
        return self.k - len(self.free_slots())

    def admit(self, slot: int, req: SolveRequest) -> None:
        """Initialize column ``slot`` from ``req`` (never touches others)."""
        if self.slots[slot] is not None:
            raise ValueError(f"slot {slot} is occupied")
        b = jnp.asarray(req.b, self.dtype)[None, :]
        col_vecs, gamma0, delta0 = self.compiled.init(self.bands, b)
        self.state = self.compiled.admit(self.state, slot, col_vecs,
                                         gamma0, delta0)
        bb = float(np.dot(np.asarray(req.b, np.float64),
                          np.asarray(req.b, np.float64)))
        self.tol2[slot] = req.tol ** 2 * bb
        b64 = np.asarray(req.b, np.float64)
        self.bsum[slot] = float(b64.sum())
        self.babs[slot] = float(np.abs(b64).sum())
        self.slots[slot] = req

    def step(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Advance every column by ``step_block`` iterations.

        Returns host copies of (done, iters, rr) — the per-column freeze
        flags, per-column iteration counts since admission, and squared
        residual norms.  The per-column ABFT deviations of the same block
        are cached on ``self.deviation`` / ``self.dev_scale`` (combined
        with the host-side b-sums stored at admit).
        """
        self.state, (done, iters, rr, det) = self.compiled.step(
            self.bands, self.state, jnp.asarray(self.tol2))
        self.blocks += 1
        det = np.asarray(det, np.float64)
        # delta = 1^T b - c^T x - 1^T r == 1^T (b - A x - r); rounding-level
        # for any state the recurrence produced, O(corruption) otherwise
        self.deviation = self.bsum - det[:, 0] - det[:, 1]
        self.dev_scale = self.babs + det[:, 2] + det[:, 3]
        return np.asarray(done), np.asarray(iters), np.asarray(rr)

    def take(self, slot: int) -> np.ndarray:
        """Host copy of column ``slot``'s current solution iterate."""
        return np.asarray(self.state["vecs"]["x"][slot])

    def release(self, slot: int) -> None:
        """Retire column ``slot``: freeze it and free the slot."""
        self.state = self.compiled.mark_done(self.state, slot)
        self.tol2[slot] = 0.0
        self.slots[slot] = None

    def poison(self, slot: int) -> None:
        """Chaos hook: corrupt column ``slot``'s vectors with NaNs."""
        self.state = self.compiled.poison(self.state, slot)

    def corrupt(self, slot: int, magnitude: float) -> None:
        """Chaos hook: silently derail column ``slot``'s solution."""
        self.state = self.compiled.corrupt(
            self.state, slot, jnp.asarray(magnitude, self.dtype))
