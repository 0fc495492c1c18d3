"""Compile the main-path kernels for a TPU v5e that is described, not attached.

The TPU compiler (Mosaic for the Pallas kernels, XLA for the rest) is
installed with JAX, so these tests refuse what interpret mode accepts:
unaligned dynamic loads, ``dynamic_slice`` on in-register values, scalar
stores into VMEM, and kernels whose blocks overflow the scoped VMEM.
Nothing runs; a pass says only that the chip's compiler takes the
program.  Sizes are a deployment's per-chip share (2^20 fp32 rows), the
largest rows per chip that fit, and the 2-D benchmark operator (2^23
rows, +-2048 offsets), each at the block the program picks.  The PIPECG
sweep's block is the largest whose modeled footprint
(``pipecg_spmv_fused.sweep_vmem_bytes``) fits the default scoped VMEM of
16 MiB, so these compiles also check that model against Mosaic.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library at a time.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch import hlo_analysis

N = 2 ** 20
OFFSETS = (-1, 0, 1)          # the ex23 tridiagonal Laplacian
HALO = 1
OFFSETS_2D = (-2048, -1, 0, 1, 2048)   # ex2's 5-point stencil, 2048 x 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # keep the compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # the chip path is fp32: Mosaic cannot lower the 64-bit grid
        # indices that x64 mode (on for the rest of the suite) produces
        x64 = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", False)
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_x64", x64)


@pytest.fixture
def one_chip(topo, monkeypatch):
    # the kernels must lower through Mosaic, not the CPU interpreter
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _band_shaped_in_loops(text: str) -> list:
    """Lines of a while body that compute a band-shaped (3, ...) array.

    The operator is split and tiled once per solve, so no loop body
    should copy, slice or fuse one.
    """
    comps = hlo_analysis._split_computations(text)
    bodies = {m.group(2) for m in hlo_analysis._WHILE_RE.finditer(text)}
    return [ln for body in bodies for ln in comps[body]
            if re.match(r"%?[\w.\-]+ = f32\[3,\S* (?!get-tuple-element|"
                        r"bitcast|parameter)", ln)]


def _loop_computations(text: str) -> dict:
    """Every computation a while body runs: the bodies and, transitively,
    the fusions and calls inside them."""
    comps = hlo_analysis._split_computations(text)
    todo = [m.group(2) for m in hlo_analysis._WHILE_RE.finditer(text)]
    seen = {}
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen[name] = comps[name]
        for ln in comps[name]:
            for cm in hlo_analysis._CALL_RE.finditer(ln):
                todo += [c.lstrip("%") for c in re.split(r",\s*", cm.group(1))]
    return seen


def _vector_selects_in_loops(text: str, n: int) -> list:
    """``select`` ops inside a while body whose result holds n or more
    elements: a masked update of whole vectors."""
    hits = []
    for lines in _loop_computations(text).values():
        for ln in lines:
            m = re.match(r"%?[\w.\-]+ = \w+\[([\d,]*)\]\S* select\(", ln)
            dims = [int(d) for d in m.group(1).split(",") if d] if m else []
            if m and np.prod(dims) >= n:
                hits.append(ln)
    return hits


def test_spmv_dia_compiles(one_chip):
    text = _compiled_text(
        lambda bands, x_ext: ops.spmv_dia_ext(OFFSETS, bands, x_ext, HALO),
        _spec(one_chip, len(OFFSETS), N), _spec(one_chip, N + 2 * HALO))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k_rhs,n,offsets,storage", [
    (1, N, OFFSETS, jnp.float32), (8, N, OFFSETS, jnp.float32),
    (1, 2 ** 26, OFFSETS, jnp.float32), (8, 2 ** 24, OFFSETS, jnp.float32),
    (1, 2 ** 23, OFFSETS_2D, jnp.float32),
    (8, 2 ** 23, OFFSETS_2D, jnp.float32),
    (1, 2 ** 23, OFFSETS_2D, jnp.bfloat16),
], ids=["1", "8", "1-largest", "8-largest", "2d", "2d-8", "2d-bf16"])
def test_pipecg_spmv_fused_compiles(one_chip, k_rhs, n, offsets, storage):
    """Single-chip sweep; k = 8 is the SolverServer's multi-RHS batch.

    The largest rows per chip that compile: nothing is VMEM-resident, so
    the bound is the 16 GB of HBM (k = 8 at 2^26 rows exceeds it).  The
    2-D cases are the benchmark's operator with its 32-row halos, in
    fp32 and with bf16 storage of r, u, p and the operator (x and the
    reductions stay fp32), at the block the footprint model picks.
    """
    vec = _spec(one_chip, k_rhs, n)
    store = jax.ShapeDtypeStruct((k_rhs, n), storage, sharding=one_chip)
    op = lambda *shape: jax.ShapeDtypeStruct(shape, storage,
                                             sharding=one_chip)
    text = _compiled_text(
        lambda *a: ops.pipecg_spmv_fused_step(offsets, *a),
        op(len(offsets), n), op(n), vec, store, store, store,
        _spec(one_chip, k_rhs), _spec(one_chip, k_rhs))
    assert "tpu_custom_call" in text


def test_pipecg_sweep_block_on_the_2d_operator(one_chip):
    """The rule sizes the 2-D operator's tile well past its 2 x 32 halo
    rows: at least 256 rows of 128 lanes, dividing the vector."""
    from repro.kernels import autotune

    autotune.clear_cache()
    n = 2 ** 23
    vec = _spec(one_chip, 1, n)
    block = ops._sweep_block("pipecg_spmv", OFFSETS_2D,
                             _spec(one_chip, len(OFFSETS_2D), n),
                             _spec(one_chip, n), vec, vec)
    assert block // 128 >= 256 and n % block == 0, block


def _model_rows(offsets, storage, budget):
    """The largest block rows the sweep's footprint model fits in
    ``budget`` bytes (fp32 accumulation)."""
    from repro.kernels import pipecg_spmv_fused as ps

    hb = ps.halo_rows(offsets, storage, jnp.float32)
    fits = lambda r: ps.sweep_vmem_bytes(r, hb, len(offsets), jnp.float32,
                                         storage, storage) <= budget
    rows = hb
    while fits(rows + hb):
        rows += hb
    return rows


def _compile_sweep_at(one_chip, offsets, storage, rows, n=2 ** 20):
    store = lambda *shape: jax.ShapeDtypeStruct(shape, storage,
                                                sharding=one_chip)
    vec = _spec(one_chip, 1, n)
    return _compiled_text(
        lambda *a: ops.pipecg_spmv_fused_step(offsets, *a, block=rows * 128),
        store(len(offsets), n), store(n), vec, store(1, n), store(1, n),
        store(1, n), _spec(one_chip, 1), _spec(one_chip, 1))


@pytest.mark.parametrize("offsets,storage", [
    (OFFSETS, jnp.float32), (OFFSETS_2D, jnp.float32),
    (OFFSETS_2D, jnp.bfloat16), ((-256, -1, 0, 1, 256), jnp.float32),
], ids=["ex23", "2d", "2d-bf16", "5-band-hb8"])
def test_sweep_footprint_model_fits_what_mosaic_accepts(one_chip, offsets,
                                                        storage):
    """Mosaic takes the largest block the footprint model fits in the
    default scoped VMEM: the rule never picks a block that overflows."""
    rows = _model_rows(offsets, storage, ops.SCOPED_VMEM_BYTES)
    assert rows >= ops.MIN_SWEEP_ROWS
    assert "tpu_custom_call" in _compile_sweep_at(one_chip, offsets, storage,
                                                  rows)


def test_sweep_footprint_model_refuses_what_mosaic_refuses(one_chip):
    """A 2048-row block of the 2-D sweep overflows the scoped VMEM, and
    the model does not fit it either."""
    from repro.kernels import pipecg_spmv_fused as ps

    assert ps.sweep_vmem_bytes(2048, 32, 5, jnp.float32, jnp.float32,
                               jnp.float32) > ops.SCOPED_VMEM_BYTES
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile_sweep_at(one_chip, OFFSETS_2D, jnp.float32, 2048,
                          n=2 ** 23)


def test_fused_solve_compiles(one_chip):
    """pipecg(engine="fused", M="jacobi"): the kernel in the loop, and
    the operator split once per solve, not in every iteration."""
    from repro.core.krylov import pipecg
    from repro.core.krylov.operators import DiaMatrix
    from repro.core.krylov.options import SolverOptions

    opts = SolverOptions(engine="fused", M="jacobi", maxiter=50, tol=1e-6)
    text = _compiled_text(
        lambda bands, b: pipecg(DiaMatrix(OFFSETS, bands), b, options=opts),
        _spec(one_chip, len(OFFSETS), N), _spec(one_chip, N))
    assert "tpu_custom_call" in text
    assert "while(" in text
    assert not _band_shaped_in_loops(text)


@pytest.mark.parametrize("k_rhs", [1, 2], ids=["one-rhs", "batched"])
def test_fused_solve_loop_stops_at_convergence(one_chip, k_rhs):
    """The one-chip fused solve is a while loop bounded by maxiter.  With
    one right-hand side at the default precision it exits after the
    converging step, so its body holds no masked update of a vector; a
    batch still freezes each converged column."""
    from repro.core.krylov import pipecg, pipecg_multi
    from repro.core.krylov.operators import DiaMatrix
    from repro.core.krylov.options import SolverOptions

    maxiter = 50
    if k_rhs == 1:
        opts = SolverOptions(engine="fused", M="jacobi", maxiter=maxiter,
                             tol=1e-6)
        fn = lambda bands, b: pipecg(DiaMatrix(OFFSETS, bands), b,
                                     options=opts)
        rhs = _spec(one_chip, N)
    else:
        fn = lambda bands, b: pipecg_multi(DiaMatrix(OFFSETS, bands), b,
                                           maxiter=maxiter, tol=1e-6,
                                           M="jacobi", engine="fused")
        rhs = _spec(one_chip, k_rhs, N)
    text = _compiled_text(fn, _spec(one_chip, len(OFFSETS), N), rhs)
    trips = hlo_analysis.analyze_collectives(text)["while_trip_counts"]
    assert list(trips.values()) == [maxiter], trips
    selects = _vector_selects_in_loops(text, N)
    if k_rhs == 1:
        assert not selects, selects
    else:
        assert selects


def test_pipecg_fused_update_kernel_compiles(one_chip):
    """The FusedEngine's two-sweep fallback (opaque M or operator)."""
    vec = _spec(one_chip, N)
    text = _compiled_text(ops.pipecg_fused_step, *[vec] * 10,
                          _spec(one_chip), _spec(one_chip))
    assert "tpu_custom_call" in text


def test_pipecg_spmv_halo_compiles(one_chip):
    """Per-shard sweep at 2^20 local rows, neighbor halo payloads."""
    vec, edge = _spec(one_chip, 1, N), _spec(one_chip, 1, 2 * HALO)

    def sweep(bands_ext, invd_ext, x, r, u, p, *rest):
        blk, op = ops.pipecg_halo_operator(OFFSETS, bands_ext, invd_ext, x, u,
                                           n_shards=4)
        return ops.pipecg_spmv_halo_step(OFFSETS, op, x, r, u, p, *rest,
                                         block=blk)

    text = _compiled_text(
        sweep, _spec(one_chip, len(OFFSETS), N + 2 * HALO),
        _spec(one_chip, N + 2 * HALO), vec, vec, vec, vec,
        edge, edge, edge, edge, _spec(one_chip, 1), _spec(one_chip, 1))
    assert "tpu_custom_call" in text


def test_sharded_fused_solve_compiles_on_four_chips(topo, monkeypatch):
    """distributed_solve over a 2x2 v5e mesh: kernel + one all-reduce
    per loop body that no halo permute waits on."""
    from repro.core.krylov import pipecg
    from repro.core.krylov.distributed import distributed_solve
    from repro.core.krylov.operators import DiaMatrix
    from repro.core.krylov.options import SolverOptions

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("shards",))
    n = len(topo.devices) * N
    opts = SolverOptions(engine="sharded_fused", M="jacobi", maxiter=50,
                         tol=1e-6)
    text = _compiled_text(
        lambda bands, b: distributed_solve(
            pipecg, DiaMatrix(OFFSETS, bands), b, mesh, options=opts),
        _spec(NamedSharding(mesh, P(None, "shards")), len(OFFSETS), n),
        _spec(NamedSharding(mesh, P("shards")), n))
    assert "tpu_custom_call" in text
    overlap = hlo_analysis.split_phase_overlap(text)
    assert overlap["overlap_ok"], overlap
    assert [b["all_reduce"] for b in overlap["bodies"].values()] == [1]
    assert not _band_shaped_in_loops(text)
    # the loop stops at convergence, bounded by maxiter, with no masked
    # update of a shard's vectors
    trips = hlo_analysis.analyze_collectives(text)["while_trip_counts"]
    assert list(trips.values()) == [50], trips
    assert not _vector_selects_in_loops(text, N)
