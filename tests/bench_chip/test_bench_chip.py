"""CPU tests of the chip benchmark's own code (``bench/``).

Run from the checkout's root: ``JAX_PLATFORMS=cpu python -m pytest -q
tests/bench_chip``.  They cover the byte accounting, the trace reduction on a
trace recorded on a TPU v5e (``fixtures/``), the generators, the refusal
to run without a TPU, and that a run whose timed path is broken, or the
control in the program's place, comes out not correct.  Cells run here
at a size the CPU holds (Pallas kernels interpreted).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from lib import accounting, harness, reference, trace as tr  # noqa: E402
from lib import traffic as gen  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "ex23_2e16_1chip.xplane.pb")
# each cell at a size the CPU holds (Pallas kernels interpreted)
SMALL = {"n": 4096, "nx": 64, "ny": 64, "pool": 2}
PEAKS = {"hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.fixture(autouse=True, scope="module")
def fp32():
    """The benchmark runs with x64 off, as on the chip."""
    import jax

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", x64)


def run_small(workload, **overrides):
    import jax

    s = harness.cell_spec(workload, ROOT)
    ov = {k: v for k, v in dict(SMALL, **overrides).items()
          if k in s["config"] or k in s["traffic"]}
    return harness.run_cell(workload, 2 ** 40 + 7, 0.2, False, jax.devices(),
                            0.0, root=ROOT, overrides=ov, peaks=PEAKS)


@pytest.mark.parametrize("bands,words", [(3, 13), (5, 15), (21, 31)])
def test_sweep_words_per_row(bands, words):
    n = 2 ** 22
    cost = accounting.pipecg_sweep(n, bands)
    assert cost["words"] == words * n
    assert cost["bytes"] == 4 * words * n
    assert cost["flops"] == (4 * bands + 23) * n


def test_least_seconds_is_hbm_bound_for_the_sweep():
    cost = accounting.pipecg_sweep(2 ** 23, 5)
    peaks = harness.load_json(BENCH, "peaks.json")["TPU v5 lite"]
    t, bound = accounting.least_seconds(cost, peaks)
    assert bound == "hbm"
    assert t == pytest.approx(15 * 4 * 2 ** 23 / 819e9)


def test_interval_union_intersection_and_exposed_collectives():
    ops = {0: [("while.7", 0, 36), ("fusion.1", 0, 10),
               ("all-reduce.2", 8, 20), ("fusion.3", 15, 18),
               ("collective-permute-done.4", 30, 35)]}
    t = tr.Trace(ops, [("host", 19, 31)], 40e-9)
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.intersect([(0, 10)], [(5, 15)]) == [(5, 10)]
    # collectives cover [8, 20] + [30, 35]; compute covers [0, 10] + [15, 18]
    assert tr.exposed_collective_ns(t, 0) == pytest.approx(17 - 5)
    assert [n for n, _ in tr.top_ops(t)][0] == "all-reduce.2"
    t.ops[0] = t.ops[0][1:]
    assert t.busy_s(0) == pytest.approx(25e-9)
    assert tr.idle_gaps(t) == [["host", pytest.approx(10e-9)]]


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded trace")
def test_reduction_of_a_recorded_tpu_trace():
    """Three 16-step fused PIPECG solves at 2^16 rows on one TPU v5e."""
    t = tr.load(FIXTURE, 1.0)
    assert t.chips == [0]
    sweeps = t.events(0, __import__("lib.solve_loop").solve_loop.SWEEP)
    assert len(sweeps) == 3 * 16
    assert all(n.startswith("pipecg_sweep_step") for n, _, _ in sweeps)
    assert 0 < t.busy_s(0) < 1.0
    assert tr.exposed_collective_ns(t, 0) == 0.0
    names = [n for n, _ in tr.top_ops(t)]
    assert names[0].startswith("pipecg_sweep_step")
    assert not any(n.startswith("while") for n in names)
    assert any(name == "bench.solve" for name, _, _ in t.host)


def test_mode_pool_matches_the_direct_sum():
    n, lo, hi = 4096, 3, 5
    (b,) = gen.rhs_pool({"pool": 1, "rhs": "modes", "modes": [lo, hi]},
                        {"n": n}, 11)
    rng = np.random.default_rng(gen.sub_seed(11, 1))
    m = int(rng.integers(lo, hi + 1))
    ks = rng.choice(n, size=m, replace=False) + 1
    cs = rng.standard_normal(m)
    i = np.arange(1, n + 1)
    want = sum(c * np.sin(np.pi * k * i / (n + 1)) for k, c in zip(ks, cs))
    want /= np.linalg.norm(want)
    np.testing.assert_allclose(np.asarray(b), want, atol=1e-6)


def test_mode_pool_2d_matches_the_direct_sum():
    nx, ny, m = 32, 16, 4
    (b,) = gen.rhs_pool({"pool": 1, "rhs": "modes2d", "modes": [m, m]},
                        {"n": nx * ny, "nx": nx, "ny": ny}, 2 ** 34 + 5)
    rng = np.random.default_rng(gen.sub_seed(2 ** 34 + 5, 1))
    assert int(rng.integers(m, m + 1)) == m
    idx = rng.choice(nx * ny, size=m, replace=False)
    cs = rng.standard_normal(m)
    x, y = np.arange(1, nx + 1), np.arange(1, ny + 1)
    want = sum(c * np.outer(np.sin(np.pi * (k // nx + 1) * y / (ny + 1)),
                            np.sin(np.pi * (k % nx + 1) * x / (nx + 1)))
               for k, c in zip(idx, cs)).ravel()
    want /= np.linalg.norm(want)
    np.testing.assert_allclose(np.asarray(b), want, atol=1e-6)


def test_a_2d_mode_is_an_eigenvector_of_the_2d_operator():
    from operators import laplacian_2d

    nx, ny = 8, 6
    offs, bands = laplacian_2d.build({"nx": nx, "ny": ny}, 0)
    kx, ky = 3, 2
    v = np.outer(np.sin(np.pi * ky * np.arange(1, ny + 1) / (ny + 1)),
                 np.sin(np.pi * kx * np.arange(1, nx + 1) / (nx + 1))).ravel()
    lam = (4 - 2 * np.cos(np.pi * kx / (nx + 1))
           - 2 * np.cos(np.pi * ky / (ny + 1)))
    av = reference.dia_matvec(offs, np.asarray(bands, np.float64), v)
    np.testing.assert_allclose(av, lam * v, atol=1e-12)


def test_the_2d_operator_matches_the_program_layout():
    from operators import laplacian_2d
    from repro.core.krylov.operators import laplacian_2d as program

    A = program(16, 8)
    offs, bands = laplacian_2d.build({"nx": 16, "ny": 8}, 0)
    assert tuple(A.offsets) == offs
    np.testing.assert_array_equal(np.asarray(A.bands, np.float32),
                                  np.asarray(bands))


@pytest.mark.parametrize("kind", ["modes", "modes2d"])
def test_max_condition_leaves_out_the_low_modes(kind):
    nx, ny = 64, 32
    traffic = {"pool": 3, "rhs": kind, "modes": [8, 12], "max_condition": 40}
    pool = gen.rhs_pool(traffic, {"n": nx * ny, "nx": nx, "ny": ny}, 2 ** 35)
    if kind == "modes":
        eigs = gen.laplacian_1d_eigs(nx * ny)
        basis = gen.sines(np.arange(1, nx * ny + 1), nx * ny)
    else:
        eigs = (gen.laplacian_1d_eigs(ny)[:, None]
                + gen.laplacian_1d_eigs(nx)).ravel()
        basis = np.einsum("yi,xj->yxij", gen.sines(np.arange(1, ny + 1), ny),
                          gen.sines(np.arange(1, nx + 1), nx))
        basis = basis.reshape(nx * ny, nx * ny)
    low = eigs < eigs.max() / 40
    assert 0 < low.sum() < low.size // 4
    for b in pool:
        coef = np.abs(basis.astype(np.float64) @ np.asarray(b, np.float64))
        assert coef[low].max() < 1e-4 * coef.max()
        assert 8 <= (coef > 1e-3 * coef.max()).sum() <= 12


def test_eligible_modes_lie_within_the_condition():
    eigs = gen.laplacian_1d_eigs(4096)
    ok = gen.eligible(eigs, 400)
    assert eigs.max() / eigs[ok].min() <= 400
    assert eigs.max() / eigs[ok[0] - 1] > 400
    assert np.array_equal(ok, np.arange(ok[0], 4096))
    assert np.array_equal(gen.eligible(eigs, None), np.arange(4096))


def test_reference_backward_error_of_the_exact_answer():
    op = reference.Operator((-1, 0, 1), np.array(
        [[0, -1, -1], [2, 2, 2], [-1, -1, 0]], np.float64))
    x = np.array([1.0, 2.0, 3.0])
    b = reference.dia_matvec(op.offsets, op.bands, x)
    assert op.residuals(b, x) == (0.0, 0.0)
    assert op.residuals(b, np.zeros(3))[1] == pytest.approx(1.0)


def test_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "ex23.solve.1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=CPU_ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_refuses_to_run_without_the_program(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ex23.solve.1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=CPU_ENV, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("workload", ["ex23.solve.1chip",
                                      "poisson2d.solve.1chip"])
def test_a_sound_small_run_is_correct(workload):
    res = run_small(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


def _unchanged(solve):
    def broken(A, b, **kw):
        res = solve(A, b, **kw)
        return res._replace(x=res.x * 0.0)     # the state as it started
    return broken


def _altered(solve):
    def broken(A, b, **kw):
        res = solve(A, b, **kw)
        return res._replace(x=res.x.at[b.shape[-1] // 2].add(1.0))
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _altered])
@pytest.mark.parametrize("workload", ["ex23.solve.1chip",
                                      "poisson2d.solve.1chip"])
def test_a_broken_solve_is_not_correct(monkeypatch, workload, fault):
    import repro.core.krylov as krylov

    monkeypatch.setattr(krylov, "pipecg", fault(krylov.pipecg))
    res = run_small(workload)
    assert not res["correct"], res["checks"]


EXCHANGE_LEFT_OUT = """
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import jax, jax.numpy as jnp
from lib import harness
if {broken}:
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
res = harness.run_cell("ex23.solve.4chip", 2 ** 33 + 1, 0.2, False,
                       jax.devices(), 0.0, root={root!r},
                       overrides={{"n": 16384, "pool": 2}},
                       peaks={{"hbm_bytes_per_s": 1e11, "flops_per_s": 1e12}})
print(json.dumps(res["correct"]))
"""

@pytest.mark.parametrize("broken", [False, True])
def test_the_exchange_between_chips_left_out_is_not_correct(broken):
    env = dict(CPU_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE_LEFT_OUT.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                                    broken=broken, root=ROOT)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) is (not broken)


@pytest.mark.parametrize("workload", ["ex23.solve.1chip",
                                      "poisson2d.solve.1chip",
                                      "ex23.solve.4chip"])
def test_the_control_is_not_correct(workload):
    import jax

    import control

    s = harness.cell_spec(workload, ROOT)
    ov = {k: v for k, v in SMALL.items()
          if k in s["config"] or k in s["traffic"]}
    out = control.run(workload, 2 ** 36 + 3, jax.devices(), root=ROOT,
                      overrides=ov)
    assert not out["correct"], out["numbers"]
