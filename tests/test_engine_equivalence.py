"""FusedEngine == NaiveEngine: trajectories of the engine-routed solvers.

The FusedEngine single-sweep kernel uses the derived-vector formulation
(s = A p, q = M s, w = A u recomputed in-tile) which equals the
Ghysels-Vanroose recurrences in exact arithmetic; in fp64 the histories
agree far below the fp32-tolerance gate of the acceptance criteria, until
the residual hits the roundoff floor (where the derived-vector variant is
the MORE stable of the two — it stagnates flat instead of wandering).

The sharded sections cover the ShardedFusedEngine two ways: the halo
kernel chunk-by-chunk against the full-vector sweep in-process (no mesh
needed — halos are built by hand), and the whole
``distributed_solve(..., engine="sharded_fused")`` path against the
naive engine on 1/2/4/8 forced host devices in a subprocess, including
the split-phase HLO assertion.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krylov import (
    ENGINES,
    cg,
    get_engine,
    gmres,
    pgmres,
    pipecg,
    pipecg_multi,
    pipecr,
    glen_law_band,
    laplacian_2d,
    tridiagonal_laplacian,
)

SRC = str(Path(__file__).resolve().parents[1] / "src")

RTOL = 1e-4  # the acceptance gate; fp64 actually achieves ~1e-8


def _hist_close(a, b, rtol=RTOL, floor_rel=1e-10):
    """Residual histories equal to rtol, above the roundoff floor."""
    ha, hb = np.asarray(a), np.asarray(b)
    floor = floor_rel * max(ha.max(), 1.0)
    mask = ha > floor
    assert mask.sum() > 0
    np.testing.assert_allclose(ha[mask], hb[mask], rtol=rtol)


@pytest.fixture(scope="module")
def tri_system():
    A = tridiagonal_laplacian(200)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(200))
    return A, b


def test_engine_registry():
    assert set(ENGINES) >= {"naive", "fused"}
    assert get_engine("fused") is ENGINES["fused"]
    assert get_engine(None) is None
    assert get_engine(ENGINES["naive"]) is ENGINES["naive"]
    with pytest.raises(ValueError):
        get_engine("warp-drive")


def test_naive_engine_matches_legacy_pipecg(tri_system):
    A, b = tri_system
    r0 = pipecg(A, b, maxiter=80)
    r1 = pipecg(A, b, maxiter=80, engine="naive")
    np.testing.assert_allclose(np.asarray(r0.res_history),
                               np.asarray(r1.res_history), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(r0.x), np.asarray(r1.x),
                               rtol=1e-12, atol=1e-12)


def test_fused_engine_matches_naive_pipecg(tri_system):
    A, b = tri_system
    r1 = pipecg(A, b, maxiter=80, engine="naive")
    r2 = pipecg(A, b, maxiter=80, engine="fused")
    _hist_close(r1.res_history, r2.res_history)
    scale = float(jnp.max(jnp.abs(r1.x)))
    assert float(jnp.max(jnp.abs(r1.x - r2.x))) / scale < RTOL


def test_fused_engine_pipecr(tri_system):
    A, b = tri_system
    r1 = pipecr(A, b, maxiter=60, engine="naive")
    r2 = pipecr(A, b, maxiter=60, engine="fused")
    _hist_close(r1.res_history, r2.res_history)


def test_fused_engine_jacobi_preconditioned():
    """Denser band (halo=10) + in-kernel Jacobi M."""
    A = glen_law_band(300, bandwidth=10)
    b = jnp.asarray(np.random.default_rng(1).standard_normal(300))
    r1 = pipecg(A, b, maxiter=60, M="jacobi", engine="naive")
    r2 = pipecg(A, b, maxiter=60, M="jacobi", engine="fused")
    _hist_close(r1.res_history, r2.res_history)
    assert float(r2.res_norm) < 1e-10  # fully converges


@pytest.mark.parametrize("n", [200, 777, 1024])
def test_fused_engine_non_multiple_block_sizes(n):
    """Sizes that do / do not divide the kernel block (wrapper pads)."""
    A = tridiagonal_laplacian(n)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(n))
    r1 = pipecg(A, b, maxiter=50, engine="naive")
    r2 = pipecg(A, b, maxiter=50, engine="fused")
    _hist_close(r1.res_history, r2.res_history)


def test_fused_engine_tol_freezing(tri_system):
    A, b = tri_system
    r = pipecg(A, b, maxiter=200, tol=1e-6, engine="fused")
    assert int(r.iters) < 200
    assert float(r.res_norm) <= 1e-6 * float(jnp.linalg.norm(b)) * 1.01


def test_multi_rhs_batched_matches_single(tri_system):
    """The batched kernel grid dimension: each RHS == its single-RHS solve,
    and the fused batch == the vmapped naive batch."""
    A, b = tri_system
    B = jnp.stack([b, 2.0 * b + 1.0, jnp.flip(b)])
    mF = pipecg_multi(A, B, maxiter=60, engine="fused")
    mN = pipecg_multi(A, B, maxiter=60, engine="naive")
    assert mF.x.shape == B.shape
    assert mF.res_history.shape == (3, 60)
    for j in range(B.shape[0]):
        single = pipecg(A, B[j], maxiter=60, engine="fused")
        np.testing.assert_allclose(np.asarray(single.x), np.asarray(mF.x[j]),
                                   rtol=1e-12, atol=1e-12)
        _hist_close(mN.res_history[j], mF.res_history[j])


def test_multi_rhs_non_multiple_block(tri_system):
    A = tridiagonal_laplacian(777)
    B = jnp.asarray(np.random.default_rng(3).standard_normal((2, 777)))
    mF = pipecg_multi(A, B, maxiter=40, engine="fused")
    mN = pipecg_multi(A, B, maxiter=40, engine="naive")
    for j in range(2):
        _hist_close(mN.res_history[j], mF.res_history[j])


def test_cg_engine_spmv_routing(tri_system):
    A, b = tri_system
    g0 = cg(A, b, maxiter=80)
    gF = cg(A, b, maxiter=80, engine="fused")
    np.testing.assert_allclose(np.asarray(g0.x), np.asarray(gF.x),
                               rtol=1e-10, atol=1e-10)


def test_gmres_engine_orthogonalization(tri_system):
    """Engine GMRES uses one-pass CGS dots; same minimizer as MGS."""
    A, b = tri_system
    g0 = gmres(A, b, restart=60)
    gF = gmres(A, b, restart=60, engine="fused")
    assert abs(float(g0.res_norm) - float(gF.res_norm)) < 1e-8
    np.testing.assert_allclose(np.asarray(g0.x), np.asarray(gF.x),
                               rtol=1e-6, atol=1e-8)


def test_pgmres_engine_fused_dots(tri_system):
    A, b = tri_system
    p0 = pgmres(A, b, restart=60)
    pF = pgmres(A, b, restart=60, engine="fused")
    assert abs(float(p0.res_norm) - float(pF.res_norm)) < 1e-8
    np.testing.assert_allclose(np.asarray(p0.x), np.asarray(pF.x),
                               rtol=1e-6, atol=1e-8)


# ---------------------------------------------------------------------------
# ShardedFusedEngine
# ---------------------------------------------------------------------------

def test_sharded_engine_registered_and_rejects_local_use(tri_system):
    """The registry knows it; local solvers refuse it with a pointer to
    distributed_solve (its reductions are per-shard partials)."""
    assert "sharded_fused" in ENGINES
    A, b = tri_system
    with pytest.raises(ValueError, match="distributed_solve"):
        pipecg(A, b, maxiter=5, engine="sharded_fused")


def _manual_sharded_step(A, invd, x, r, u, p, alpha, beta, shards,
                         block=None):
    """Chunk the global state, hand-build the neighbor halos, run the halo
    kernel per chunk, reassemble — exactly what shard_map does, without a
    mesh."""
    from repro.kernels import ops as kops

    offsets = A.offsets
    h = A.halo
    k, n = x.shape
    nl = n // shards
    bands_g = jnp.pad(A.bands, ((0, 0), (h, h)))
    invd_g = jnp.pad(invd, (h, h))
    u_g = jnp.pad(u, ((0, 0), (2 * h, 2 * h)))
    p_g = jnp.pad(p, ((0, 0), (2 * h, 2 * h)))
    outs, red = [], 0.0
    for s in range(shards):
        lo = s * nl
        blk, op = kops.pipecg_halo_operator(
            offsets, bands_g[:, lo:lo + nl + 2 * h],
            invd_g[lo:lo + nl + 2 * h], x[:, lo:lo + nl], u[:, lo:lo + nl],
            block=block, n_shards=shards)
        piece = kops.pipecg_spmv_halo_step(
            offsets, op,
            x[:, lo:lo + nl], r[:, lo:lo + nl], u[:, lo:lo + nl],
            p[:, lo:lo + nl],
            u_g[:, lo:lo + 2 * h], u_g[:, lo + nl + 2 * h:lo + nl + 4 * h],
            p_g[:, lo:lo + 2 * h], p_g[:, lo + nl + 2 * h:lo + nl + 4 * h],
            alpha, beta, block=blk)
        outs.append(piece[:4])
        red = red + piece[4]
    return tuple(jnp.concatenate([o[i] for o in outs], axis=-1)
                 for i in range(4)) + (red,)


@pytest.mark.parametrize("n,k,shards,block,mk", [
    (512, 1, 4, None, tridiagonal_laplacian),
    (512, 3, 8, None, tridiagonal_laplacian),
    # 65 rows/shard with block=32: pads to 96, exercising the n_valid
    # reduction mask (halo rows leak real data into the pad region)
    (520, 2, 8, 32, tridiagonal_laplacian),
    (480, 1, 4, None, lambda n: glen_law_band(n, bandwidth=10)),
    # several tiles per shard: windows cross tile boundaries inside a
    # shard as well as at its edges (the default block takes one tile)
    (8192, 2, 2, 1024, tridiagonal_laplacian),
    (16384, 1, 2, 2048, lambda n: laplacian_2d(1024, n // 1024)),
])
def test_sharded_halo_kernel_chunks_match_full_sweep(n, k, shards, block, mk):
    """Per-chunk halo kernel == full-vector single-sweep kernel: the halo
    operands substitute exactly for the zero extension, and the summed
    partial reductions equal the global ones."""
    A = mk(n)
    rng = np.random.default_rng(7)
    x, r, u, p = (jnp.asarray(rng.standard_normal((k, n))) for _ in range(4))
    alpha = jnp.asarray(rng.standard_normal(k))
    beta = jnp.asarray(rng.standard_normal(k))
    invd = jnp.ones((n,), x.dtype)
    from repro.kernels import ops as kops
    want = kops.pipecg_spmv_fused_step(A.offsets, A.bands, invd, x, r, u, p,
                                       alpha, beta)
    got = _manual_sharded_step(A, invd, x, r, u, p, alpha, beta, shards,
                               block=block)
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-12


@pytest.mark.parametrize("mk,k", [
    (lambda: tridiagonal_laplacian(8192), 1),
    (lambda: laplacian_2d(1024, 16), 2),      # +-1024 offsets: hb = 16
], ids=["tridiagonal", "laplacian_2d"])
def test_fused_sweep_across_tiles_matches_default_block(mk, k):
    """A forced small block splits the one-device sweep into several
    tiles whose windows cross tile boundaries; it gives the default
    block's result."""
    from repro.kernels import ops as kops

    A = mk()
    n = A.bands.shape[1]
    rng = np.random.default_rng(11)
    x, r, u, p = (jnp.asarray(rng.standard_normal((k, n))) for _ in range(4))
    alpha = jnp.asarray(rng.standard_normal(k))
    beta = jnp.asarray(rng.standard_normal(k))
    invd = 1.0 / A.bands[A.offsets.index(0)]
    hb = kops._ps.halo_rows(A.offsets, x.dtype)
    small, op_small = kops.pipecg_sweep_operator(A.offsets, A.bands, invd,
                                                 x, u, block=hb * 128)
    block, op = kops.pipecg_sweep_operator(A.offsets, A.bands, invd, x, u)
    assert n // small >= 4 and block > small
    got = kops.pipecg_sweep_step(A.offsets, op_small, x, r, u, p, alpha,
                                 beta, block=small)
    want = kops.pipecg_sweep_step(A.offsets, op, x, r, u, p, alpha, beta,
                                  block=block)
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w))) + 1e-30
        assert float(jnp.max(jnp.abs(g - w))) / scale < 1e-12


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp, numpy as np
    from repro.core.krylov import (tridiagonal_laplacian, pipecg, pipecr,
                                   pipecg_multi, distributed_solve)
    from repro.launch.hlo_analysis import split_phase_overlap

    RTOL = 1e-5  # the acceptance gate; fp64 lands around 1e-12

    def rel(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-30)))

    n = 512
    A = tridiagonal_laplacian(n)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    loc = pipecg(A, b, maxiter=40, engine="naive")
    for shards in (1, 2, 4, 8):
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:shards]),
                                 ("shards",))
        dist = distributed_solve(pipecg, A, b, mesh, engine="sharded_fused",
                                 maxiter=40)
        assert rel(loc.res_history, dist.res_history) < RTOL, shards
        xs = float(jnp.max(jnp.abs(loc.x))) + 1e-30
        assert float(jnp.max(jnp.abs(loc.x - dist.x))) / xs < RTOL, shards
        print("pipecg shards", shards, "ok")

    mesh4 = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("shards",))
    locr = pipecr(A, b, maxiter=30, engine="naive")
    distr = distributed_solve(pipecr, A, b, mesh4, engine="sharded_fused",
                              maxiter=30)
    assert rel(locr.res_history, distr.res_history) < RTOL
    print("pipecr ok")

    B = jnp.stack([b, 2.0 * b + 1.0])
    locm = pipecg_multi(A, B, maxiter=30, engine="naive")
    distm = distributed_solve(pipecg_multi, A, B, mesh4,
                              engine="sharded_fused", maxiter=30)
    assert distm.x.shape == B.shape
    assert rel(locm.res_history, distm.res_history) < RTOL
    print("pipecg_multi ok")

    # non-divisible n_local (520 / 8 = 65 rows/shard) + forced small block
    # (pad path + reduction mask) + in-kernel Jacobi
    n2 = 520
    A2 = tridiagonal_laplacian(n2)
    b2 = jnp.asarray(np.random.default_rng(1).standard_normal(n2))
    mesh8 = jax.sharding.Mesh(np.asarray(jax.devices()), ("shards",))
    loc2 = pipecg(A2, b2, maxiter=30, M="jacobi", engine="naive")
    dist2 = distributed_solve(pipecg, A2, b2, mesh8, engine="sharded_fused",
                              M="jacobi", maxiter=30, block=32)
    assert rel(loc2.res_history, dist2.res_history) < RTOL
    print("nondivisible ok")

    # tol freezing: converges and freezes well before maxiter (the split-
    # phase reduction is consumed one body late, so detection lags the
    # single-device engines by exactly one iteration)
    n3 = 200  # 25 rows/shard
    A3 = tridiagonal_laplacian(n3)
    b3 = jnp.asarray(np.random.default_rng(2).standard_normal(n3))
    dtol = distributed_solve(pipecg, A3, b3, mesh8, engine="sharded_fused",
                             maxiter=300, tol=1e-6)
    assert int(dtol.iters) <= 201, int(dtol.iters)
    assert float(dtol.res_norm) <= 1e-6 * float(jnp.linalg.norm(b3)) * 1.01
    print("tol ok")

    # split-phase: in the compiled while body the all-reduce and the halo
    # permutes are mutually independent (the overlap window exists)
    txt = jax.jit(functools.partial(
        distributed_solve, pipecg, A, mesh=mesh8, engine="sharded_fused",
        maxiter=5)).lower(b).compile().as_text()
    ov = split_phase_overlap(txt)
    assert ov["overlap_ok"], ov
    assert "collective-permute" in txt and "all-reduce" in txt
    print("overlap ok")
""")


@pytest.mark.slow
def test_sharded_engine_distributed_equivalence():
    """naive vs ShardedFusedEngine across 1/2/4/8 shards (subprocess with 8
    forced host devices): pipecg / pipecg_multi / pipecr, non-divisible
    n, tol freezing, and the split-phase HLO assertion.  Runs through the
    shared timeout + one-retry helper (conftest) so a cold-compile stall
    under CI load flakes at most once instead of hanging the lane."""
    from conftest import run_subprocess_with_retry

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = run_subprocess_with_retry(SHARDED_SCRIPT, env=env)
    for tag in ("pipecg shards 8 ok", "pipecr ok", "pipecg_multi ok",
                "nondivisible ok", "tol ok", "overlap ok"):
        assert tag in out.stdout, out.stdout


OPERATOR_GEOMETRY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core.krylov import (distributed_solve, pipecg, dia_to_bsr,
                                   glen_law_band, laplacian_2d)
    from repro.launch.hlo_analysis import split_phase_overlap

    TOL = 1e-10  # the PR acceptance gate (fp64)
    devs = np.array(jax.devices())

    def solver_body(A, b, mesh, **kw):
        txt = jax.jit(functools.partial(
            distributed_solve, pipecg, A, mesh=mesh, engine="sharded_fused",
            maxiter=5, **kw)).lower(b).compile().as_text()
        rep = split_phase_overlap(txt)
        assert rep["overlap_ok"], rep
        mixed = [r for r in rep["bodies"].values() if r["all_reduce"] > 0]
        assert len(mixed) == 1, rep["bodies"]
        return mixed[0]

    # ---- DIA on a 2-D process grid vs the single-device solve ----
    A = laplacian_2d(nx=16, ny=8)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(A.n))
    ref = pipecg(lambda v: A.matvec(v), b, maxiter=60, tol=0.0)
    for (py, px) in ((1, 2), (2, 1), (2, 2), (2, 4)):
        mesh = Mesh(devs[: py * px].reshape(py, px), ("gy", "gx"))
        out = distributed_solve(pipecg, A, b, mesh, engine="sharded_fused",
                                maxiter=60, tol=0.0, M=None)
        err = float(jnp.max(jnp.abs(out.x - ref.x)))
        assert err < TOL, (py, px, err)
        print("2d grid", (py, px), "ok")

    # the (2, 2) body: ONE split-phase all-reduce; 8 ppermutes = 2
    # vectors x 2 messages per decomposed axis x 2 active axes (a size-1
    # axis has no neighbor, so XLA elides its permutes: (1, 2) -> 4)
    body = solver_body(A, b, Mesh(devs[:4].reshape(2, 2), ("gy", "gx")))
    assert body["all_reduce"] == 1, body
    assert body["collective_permute"] == 8, body
    body = solver_body(A, b, Mesh(devs[:2].reshape(1, 2), ("gy", "gx")))
    assert body["collective_permute"] == 4, body
    print("2d hlo ok")

    # Jacobi variant stays equivalent on the 2-D grid
    refj = pipecg(lambda v: A.matvec(v), b, maxiter=60, tol=0.0,
                  M=lambda v: v / A.diagonal())
    outj = distributed_solve(pipecg, A, b,
                             Mesh(devs[:4].reshape(2, 2), ("gy", "gx")),
                             engine="sharded_fused", maxiter=60, tol=0.0,
                             M="jacobi")
    assert float(jnp.max(jnp.abs(outj.x - refj.x))) < TOL
    print("2d jacobi ok")

    # ---- BSR on the 1-D block chain vs the single-device solve ----
    B = dia_to_bsr(glen_law_band(256, bandwidth=8), bs=4)
    b2 = jnp.asarray(np.random.default_rng(0).standard_normal(256))
    ref2 = pipecg(lambda v: B.matvec(v), b2, maxiter=80, tol=0.0)
    for ns in (1, 2, 4):
        mesh = Mesh(devs[:ns], ("shards",))
        out = distributed_solve(pipecg, B, b2, mesh, engine="sharded_fused",
                                maxiter=80, tol=0.0, M=None)
        err = float(jnp.max(jnp.abs(out.x - ref2.x)))
        assert err < TOL, (ns, err)
        print("bsr shards", ns, "ok")

    body = solver_body(B, b2, Mesh(devs[:4], ("shards",)))
    assert body["all_reduce"] == 1, body
    assert body["collective_permute"] == 4, body  # u, p x W/E
    print("bsr hlo ok")

    refj2 = pipecg(lambda v: B.matvec(v), b2, maxiter=80, tol=1e-12,
                   M=lambda v: v / B.diagonal())
    outj2 = distributed_solve(pipecg, B, b2, Mesh(devs[:4], ("shards",)),
                              engine="sharded_fused", maxiter=80,
                              tol=1e-12, M="jacobi")
    assert float(jnp.max(jnp.abs(outj2.x - refj2.x))) < TOL
    print("bsr jacobi ok")
""")


@pytest.mark.slow
def test_operator_geometry_distributed_equivalence():
    """The PR-10 operator decompositions end to end (subprocess with 8
    forced host devices): DIA on (1,2)/(2,1)/(2,2)/(2,4) process grids
    and BSR on 1/2/4 block-chain shards each match the single-device
    solve to 1e-10, plain and Jacobi-preconditioned, and the compiled
    while bodies carry exactly ONE split-phase all-reduce with the
    surface-law ppermute counts (8 on a 2-axis grid, 4 on the chain)."""
    from conftest import run_subprocess_with_retry

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = run_subprocess_with_retry(OPERATOR_GEOMETRY_SCRIPT, env=env)
    for tag in ("2d grid (2, 4) ok", "2d hlo ok", "2d jacobi ok",
                "bsr shards 4 ok", "bsr hlo ok", "bsr jacobi ok"):
        assert tag in out.stdout, out.stdout


def test_fused_engine_callable_M_fallback(tri_system):
    """An opaque callable M cannot run in-kernel: the FusedEngine falls
    back to the update-kernel path and must still match naive."""
    A, b = tri_system
    inv_d = 1.0 / A.diagonal()
    M = lambda r: inv_d * r
    r1 = pipecg(A, b, maxiter=60, M=M, engine="naive")
    r2 = pipecg(A, b, maxiter=60, M=M, engine="fused")
    _hist_close(r1.res_history, r2.res_history)
