"""Per-kernel allclose vs the ref.py oracle, swept over shapes/dtypes
(parametrized + hypothesis-driven shape fuzzing), interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # only the shape-fuzz test needs hypothesis (see requirements-dev.txt)
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.float64]


def _tol(dt):
    return dict(rtol=2e-5, atol=2e-5) if dt == jnp.float32 else dict(rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n,offsets", [
    (1024, (-1, 0, 1)),
    (4096, (-1, 0, 1)),
    (777, (-1, 0, 1)),
    (2048, tuple(range(-5, 6))),
    (1000, (-10, -3, 0, 3, 10)),
])
def test_spmv_dia_matches_ref(rng, n, offsets, dtype):
    halo = max(abs(o) for o in offsets)
    bands = jnp.asarray(rng.standard_normal((len(offsets), n)), dtype)
    x_ext = jnp.asarray(rng.standard_normal(n + 2 * halo), dtype)
    got = ops.spmv_dia_ext(offsets, bands, x_ext, halo)
    want = ref.spmv_dia_ref(offsets, bands, x_ext, halo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("m,n", [(1, 2048), (8, 4096), (31, 5000), (33, 4096)])
def test_fused_dots_matches_ref(rng, m, n, dtype):
    V = jnp.asarray(rng.standard_normal((m, n)), dtype)
    z = jnp.asarray(rng.standard_normal(n), dtype)
    got = ops.fused_dots(V, z)
    want = ref.fused_dots_ref(V, z)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5 if dtype == jnp.float32 else 1e-11,
                               atol=2e-3 if dtype == jnp.float32 else 1e-9)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n", [1024, 4096, 3333])
def test_pipecg_fused_matches_ref(rng, n, dtype):
    vs = [jnp.asarray(rng.standard_normal(n), dtype) for _ in range(10)]
    got = ops.pipecg_fused_step(*vs, 0.37, -0.21)
    want = ref.pipecg_fused_ref(*vs, 0.37, -0.21)
    for g, w in zip(got[:8], want[:8]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got[8]), np.asarray(want[8]),
                               rtol=3e-4 if dtype == jnp.float32 else 1e-10,
                               atol=1e-2 if dtype == jnp.float32 else 1e-8)


def _spmv_fuzz_case(n, nb, seed):
    r = np.random.default_rng(seed)
    offsets = tuple(sorted(r.choice(np.arange(-4, 5), size=nb, replace=False).tolist()))
    halo = max(abs(o) for o in offsets)
    bands = jnp.asarray(r.standard_normal((len(offsets), n)))
    x_ext = jnp.asarray(r.standard_normal(n + 2 * halo))
    got = ops.spmv_dia_ext(offsets, bands, x_ext, halo)
    want = ref.spmv_dia_ref(offsets, bands, x_ext, halo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-10,
                               atol=1e-10)


if HAVE_HYPOTHESIS:
    @given(n=st.integers(8, 600), nb=st.integers(1, 4), seed=st.integers(0, 100))
    @settings(max_examples=20, deadline=None)
    def test_spmv_dia_shape_fuzz(n, nb, seed):
        """Hypothesis sweep: arbitrary sizes/band counts stay allclose."""
        _spmv_fuzz_case(n, nb, seed)
else:
    @pytest.mark.parametrize("n,nb,seed", [(8, 1, 0), (97, 2, 1), (600, 4, 2)])
    def test_spmv_dia_shape_fuzz(n, nb, seed):
        """Deterministic fallback sweep (hypothesis not installed)."""
        _spmv_fuzz_case(n, nb, seed)


def test_kernel_backed_operator_in_solver(rng):
    """pipecg with the kernel-backed local SpMV reproduces the jnp path."""
    from repro.core.krylov import tridiagonal_laplacian, pipecg
    from repro.core.krylov.distributed import dia_matvec_local
    import functools

    A = tridiagonal_laplacian(256)
    b = jnp.asarray(rng.standard_normal(256))
    x_ext = jnp.pad(b, (1, 1))
    got = ops.spmv_dia_ext(A.offsets, A.bands, x_ext, 1)
    want = A.matvec(b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


@pytest.mark.parametrize("off", [-300, -129, -128, -1, 1, 7, 127, 128, 130,
                                 257])
def test_stencil_shift_is_a_flat_shift(off):
    """The rolled window shift equals a flat shift away from the ends."""
    from jax.experimental import pallas as pl
    from repro.kernels import stencil

    W = 16
    x = jnp.arange(W * 128, dtype=jnp.float32).reshape(W, 128)

    def kern(x_ref, o_ref):
        o_ref[...] = stencil.shift(x_ref[...], off)

    got = pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(x.shape,
                                                              x.dtype),
                         interpret=True)(x)
    want = jnp.roll(x.reshape(-1), -off).reshape(W, 128)
    reach = -(-abs(off) // 128)    # rows within reach of an end wrap
    np.testing.assert_array_equal(np.asarray(got)[reach:W - reach],
                                  np.asarray(want)[reach:W - reach])


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret() is interpret


def test_no_interpreted_fallback_off_cpu(monkeypatch):
    """A backend with no Mosaic lowering raises instead of interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


# -- the PIPECG sweep's block rule --------------------------------------------

EX23 = (-1, 0, 1)
POISSON2D = (-2048, -1, 0, 1, 2048)     # 2048 x 4096 grid, 5-point stencil


def _rule_block(offsets, n, k=1, storage=jnp.float32, acc=jnp.float32,
                kind="pipecg_spmv", **key):
    """The rule's block and window rows for these shapes (no arrays)."""
    from repro.kernels import pipecg_spmv_fused as ps

    S = jax.ShapeDtypeStruct
    nb = len(offsets)
    block = ops._sweep_block(kind, offsets, S((nb, n), storage),
                             S((n,), storage), S((k, n), acc),
                             S((k, n), storage), **key)
    hb = ps.halo_rows(offsets, storage, acc)
    # what the sweep runs: pipecg_sweep_operator / pipecg_halo_operator
    return ops.stencil.legal_block(min(block, n), hb), hb


@pytest.mark.parametrize("offsets,n,k,storage,acc,key", [
    (EX23, 2 ** 22, 1, jnp.float32, jnp.float32, {}),
    (POISSON2D, 2 ** 23, 1, jnp.float32, jnp.float32, {}),
    (POISSON2D, 2 ** 23, 8, jnp.float32, jnp.float32, {}),
    (POISSON2D, 2 ** 23, 1, jnp.bfloat16, jnp.float32, {}),
    (EX23, 2 ** 20, 1, jnp.float32, jnp.float32,
     dict(kind="pipecg_spmv_halo", n_shards=4)),
    (EX23, 2 ** 16, 1, jnp.float64, jnp.float64, {}),
    (EX23, 4096, 1, jnp.float64, jnp.float64, {}),
    (EX23, 100003, 1, jnp.float32, jnp.float32, {}),
], ids=["ex23", "poisson2d", "poisson2d-k8", "poisson2d-bf16", "ex23-4chip",
        "ex23-f64-cpu", "short", "ragged"])
def test_sweep_block_rule(offsets, n, k, storage, acc, key):
    """The block is whole windows of hb rows, at least 64 rows (or the
    whole vector), at most the vector, within the default scoped VMEM by
    its footprint model, and pads no vector that 64-row tiles cover
    exactly."""
    from repro.kernels import autotune, pipecg_spmv_fused as ps

    autotune.clear_cache()
    block, hb = _rule_block(offsets, n, k, storage, acc, **key)
    rows, lane = block // 128, 128
    n_rows = -(-n // lane)
    assert rows % hb == 0
    assert rows >= min(ops.MIN_SWEEP_ROWS, -(-n_rows // hb) * hb)
    assert block <= ops.stencil.legal_block(n, hb)
    if rows > ops.MIN_SWEEP_ROWS:
        assert ps.sweep_vmem_bytes(rows, hb, len(offsets), acc, storage,
                                   storage) <= ops.SCOPED_VMEM_BYTES
    if n % (ops.MIN_SWEEP_ROWS * lane) == 0:
        assert n % block == 0


def test_sweep_block_grows_where_vmem_allows():
    """On the 2-D stencil's 32-row halos the tile is far past 64 rows,
    and bf16 storage, with the smaller footprint, takes a larger one."""
    from repro.kernels import autotune

    autotune.clear_cache()
    f32, hb = _rule_block(POISSON2D, 2 ** 23)
    bf16, _ = _rule_block(POISSON2D, 2 ** 23, storage=jnp.bfloat16)
    assert hb == 32
    assert f32 // 128 >= 256
    assert bf16 > f32


def test_sweep_block_cache_keeps_band_counts_apart():
    """A 3-band and a 5-band operator of equal n and hb share no cache
    entry: the wider one's footprint is larger."""
    from repro.kernels import autotune, pipecg_spmv_fused as ps

    five = (-2, -1, 0, 1, 2)
    assert ps.halo_rows(EX23, jnp.float32) == ps.halo_rows(five, jnp.float32)
    autotune.clear_cache()
    _rule_block(EX23, 2 ** 20)
    _rule_block(five, 2 ** 20)
    assert autotune.cache_stats()["misses"] == 2
    keys = [k for k in autotune._CACHE if k.startswith("pipecg_spmv|")]
    assert sorted(k.rsplit("|", 1)[1] for k in keys) == ["bands=3",
                                                         "bands=5"]
