"""Campaign precision stage: mixed-precision attainable-accuracy floors.

Sweeps ``PrecisionPolicy`` preset x solver over REAL multi-device
shard_map solves (subprocess with forced host devices, the same trick as
fault_exec.py / abft_exec.py).  Per cell the worker runs the sharded
solve to its accuracy plateau (no tolerance, fixed iteration budget) and
measures the TRUE residual ``|b - A x| / |b|`` from the returned
solution — the carried recurrence residual UNDERFLOWS to exact zero past
the storage floor, so it cannot gate anything here.

The gate is the attainable-accuracy floor of Cools et al.
(arXiv:1804.02962 pipelined-CG rounding-error analysis; arXiv:1809.01948
for p-BiCGStab): a pipelined recurrence carried at storage precision
with unit roundoff ``eps`` plateaus at ``C_solver * eps`` relative true
residual on a well-conditioned operator, where the amplification
constant ``C_solver`` is a property of the RECURRENCE — measured here
at ~1.2 for p-CG and ~10-19 for p-BiCGStab (its two-SpMV recurrence;
the constant is the same order across fp64 and bf16 storage, which is
what makes it a solver constant and not a dtype artifact).  The stage
checks each cell against ``FLOOR_FACTORS[solver] * eps_storage`` and
classifies three expectations:

* SAFE policies (fp32; bf16 storage; bf16 + int8 halo WIRE with error
  feedback) must land within the solver's floor;
* DEGRADED demonstrators must land within the floor but measurably
  above their error-feedback partner — int8 wire WITHOUT error feedback
  (the quantization bias enters the recurrence; at 128-lane strips the
  measured plateau sits ``NOEF_MIN_RATIO``+ above the EF plateau, and
  error feedback recovers the plain-bf16 floor to within ~5%);
* UNSAFE demonstrators must land outside the floor — int8 on the
  carried GRAM psum (consumed once per iteration, corrupting
  alpha/beta directly: the solve freezes ~1e6 eps off; the measured
  reason ``PrecisionPolicy`` splits ``wire`` from ``wire_gram``).

The worker also compiles the bf16+int8-wire pipecg solve and asserts the
split-phase overlap invariant on its HLO — compressing the ppermute
strips must not break the one-all-reduce-per-body window.  The parent
adds the perfmodel side: ``predict_speedup(precision=...)`` at a
bandwidth-dominated operating point, where shrinking storage/wire bytes
converts the pipelined step into the latency-dominated regime
(``pipe_latency_bound`` flips to 1) and the predicted speedup crosses
the fp32 baseline.

CLI (writes ``BENCH_precision.json``; the campaign embeds the same rows
as the ``precision`` container of ``BENCH_campaign.json`` for
``check_regression.py --key precision``)::

    PYTHONPATH=src python -m repro.experiments.precision_exec \
        [--preset smoke] [--out BENCH_precision.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from typing import Dict, List

_MARK = "PRECISION_STAGE_JSON:"

#: attainable-accuracy floor per solver, in storage-eps units (the Cools
#: amplification constant with ~2x headroom).  Measured plateaus on the
#: stage operators: p-CG bf16 1.20 eps / +int8wire(EF) 1.26 eps (floor
#: 2.0); p-BiCGStab fp64 18.8 eps_fp32 and bf16 10.6 eps_bf16 — the
#: two-SpMV recurrence's ~10-19x amplification, budget-independent once
#: saturated (identical at 200/400/600 fp64; 450 vs 600 bf16 within
#: 1.1%) — so its floor is 32.  The UNSAFE demonstrator (int8 Gram)
#: lands ~3e6 eps off: orders outside any floor.
FLOOR_FACTORS = {"pipecg": 2.0, "pipebicgstab": 32.0}

#: a DEGRADED cell must land at least this factor above its
#: error-feedback partner's plateau (measured no-EF/EF ratio 1.151 at
#: 128-lane strips; 1.05 leaves ~10% headroom)
NOEF_MIN_RATIO = 1.05

#: solver -> policies expected to sit WITHIN the floor
SAFE_POLICIES = {
    "pipecg": ("fp32", "bf16", "bf16_int8wire"),
    "pipebicgstab": ("fp32", "bf16"),
}

#: solver -> policies expected within the floor but measurably above
#: their error-feedback partner (see NOEF_MIN_RATIO)
DEGRADED_POLICIES = {
    "pipecg": ("bf16_int8wire_noef",),
    "pipebicgstab": (),
}

#: policies each solver sweeps (p-BiCGStab stops at the storage ladder:
#: p-CG's cells already pin the wire-compression safety contract, and
#: each p-BiCGStab cell costs two SpMVs per iteration)
SOLVER_POLICIES = {
    "pipecg": None,          # None = the full spec.precision_policies
    "pipebicgstab": ("fp32", "bf16"),
}


def _dd_pentadiagonal(n: int, halo: int = 128):
    """Diagonally dominant pentadiagonal band, half-bandwidth ``halo``.

    SPD with small condition number: the precision floors are ROUNDING
    limits, and an ill-conditioned operator hides them behind the
    ``kappa * eps`` conditioning limit (bf16 cannot converge at all once
    ``kappa`` exceeds ``1/eps_bf16`` ~ 256).  The +-128 offsets give the
    int8 halo strips real payload (128 lanes x 2 sides x 2 vectors) —
    the quantization surface where the no-error-feedback bias becomes
    measurable (the no-EF/EF plateau ratio is 1.04 at 32-lane strips vs
    1.15 at 128).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.krylov.operators import DiaMatrix

    offsets = (-halo, -1, 0, 1, halo)
    i = np.arange(n)
    bands = np.zeros((len(offsets), n))
    for k, o in enumerate(offsets):
        if o == 0:
            bands[k] = 4.1
        else:
            bands[k] = np.where((i + o >= 0) & (i + o < n), -1.0, 0.0)
    return DiaMatrix(offsets=offsets, bands=jnp.asarray(bands))


def _spd_tridiagonal(n: int):
    """Shifted tridiagonal Laplacian (diag 3): the p-BiCGStab operator.

    The sharded p-BiCGStab recurrence BREAKS DOWN (residual freeze, far
    above any rounding floor) on the pentadiagonal operator with a
    Gaussian RHS — measured, budget-independent — while on this
    operator with ``b = ones`` it converges to its genuine
    ``C_solver * eps`` plateau at every storage precision, which is the
    quantity the stage pins.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.krylov.operators import DiaMatrix

    offsets = (-1, 0, 1)
    i = np.arange(n)
    bands = np.zeros((len(offsets), n))
    for k, o in enumerate(offsets):
        if o == 0:
            bands[k] = 3.0
        else:
            bands[k] = np.where((i + o >= 0) & (i + o < n), -1.0, 0.0)
    return DiaMatrix(offsets=offsets, bands=jnp.asarray(bands))


def _true_residual(offsets, bands, x, b) -> float:
    """``|b - A x| / |b|`` in float64 numpy (DIA convention)."""
    import numpy as np

    bands = np.asarray(bands, np.float64)
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    n = x.size
    y = np.zeros(n)
    i = np.arange(n)
    for k, o in enumerate(offsets):
        ok = (i + o >= 0) & (i + o < n)
        y[ok] += bands[k][ok] * x[(i + o)[ok]]
    return float(np.linalg.norm(b - y) / np.linalg.norm(b))


def _run_cells(cfg: Dict) -> Dict:
    """Execute every precision cell in-process (the subprocess worker)."""
    import functools

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.krylov.bicgstab import pipebicgstab
    from repro.core.krylov.cg import pipecg
    from repro.core.krylov.distributed import distributed_solve
    from repro.core.krylov.options import PrecisionPolicy, SolverOptions
    from repro.launch.hlo_analysis import split_phase_overlap

    n = int(cfg["n"])
    P = int(cfg["shards"])
    maxiter = int(cfg["maxiter"])
    seed = int(cfg["seed"])
    devices = jax.devices()
    rng = np.random.default_rng(seed + 1)
    # per-solver (operator, RHS, iteration budget): p-CG on the
    # wide-halo pentadiagonal band with a Gaussian RHS; p-BiCGStab on
    # the shifted tridiagonal Laplacian with b = ones (see
    # _spd_tridiagonal) at 1.5x the budget, past the saturation knee of
    # its drifting bf16 plateau (measured: still climbing at 300, flat
    # within 1.1% from 450 to 600)
    problems = {
        "pipecg": (_dd_pentadiagonal(n),
                   jnp.asarray(rng.standard_normal(n)), maxiter),
        "pipebicgstab": (_spd_tridiagonal(n), jnp.ones(n),
                         (3 * maxiter) // 2),
    }
    solver_fns = {"pipecg": pipecg, "pipebicgstab": pipebicgstab}

    cells: List[Dict] = []
    mesh = (Mesh(np.asarray(devices[:P]), ("shards",))
            if P <= len(devices) else None)
    for cell in cfg["cells"]:
        solver, policy_name = cell["solver"], cell["policy"]
        if mesh is None or n % P:
            cells.append({**cell, "skipped": True,
                          "reason": f"{len(devices)} devices, n={n}"})
            continue
        A, b, iters = problems[solver]
        policy = PrecisionPolicy.from_name(policy_name)
        opts = SolverOptions(maxiter=iters, precision=policy,
                             engine="sharded_fused")
        res = distributed_solve(solver_fns[solver], A, b, mesh,
                                options=opts)
        true_res = _true_residual(A.offsets, A.bands, res.x, b)
        eps = policy.storage_eps
        floor = FLOOR_FACTORS[solver] * eps
        cells.append({
            **cell,
            "iters": int(res.iters),
            "true_res_rel": true_res,
            "eps_storage": float(eps),
            "floor_rel": float(floor),
            "res_over_eps": true_res / eps,
            "within_floor": bool(true_res <= floor),
            "storage_words": float(policy.storage_words),
            "wire_words": float(policy.wire_words),
            "skipped": False,
        })
    _classify(cells)

    # split-phase invariant under the compressed wire: the int8 halo
    # strips (and their per-strip scales) must not add a second
    # all-reduce to the scan body
    hlo: Dict = {}
    if mesh is not None and any(
            c["solver"] == "pipecg" and c["policy"] == "bf16_int8wire"
            and not c.get("skipped") for c in cells):
        A_cg, b_cg, _ = problems["pipecg"]
        opts = SolverOptions(
            maxiter=5, engine="sharded_fused",
            precision=PrecisionPolicy.from_name("bf16_int8wire"))
        txt = jax.jit(functools.partial(
            distributed_solve, pipecg, A_cg, mesh=mesh,
            options=opts)).lower(b_cg).compile().as_text()
        hlo = split_phase_overlap(txt)

    return {"cells": cells, "hlo_bf16_int8wire": hlo,
            "n": n, "shards": P, "maxiter": maxiter,
            "floor_factors": dict(FLOOR_FACTORS),
            "noef_min_ratio": NOEF_MIN_RATIO}


def _classify(cells: List[Dict]) -> None:
    """Annotate each measured cell with its ``precision_ok`` verdict.

    ``safe``: within the solver's floor.  ``unsafe``: outside it.
    ``degraded`` (int8 wire without error feedback): within the floor
    AND at least ``NOEF_MIN_RATIO`` above its error-feedback partner's
    plateau — the pin that error feedback buys a measurable accuracy
    improvement at equal wire bytes.
    """
    by_key = {(c["solver"], c["policy"]): c for c in cells}
    for c in cells:
        if c.get("skipped"):
            continue
        expect = c["expect"]
        if expect == "safe":
            c["precision_ok"] = bool(c["within_floor"])
        elif expect == "unsafe":
            c["precision_ok"] = bool(not c["within_floor"])
        else:                                   # degraded
            ef = by_key.get((c["solver"], "bf16_int8wire"))
            ok = bool(c["within_floor"]) and ef is not None \
                and not ef.get("skipped")
            if ok:
                c["noef_over_ef"] = (c["true_res_rel"]
                                     / max(ef["true_res_rel"], 1e-300))
                ok = c["noef_over_ef"] >= NOEF_MIN_RATIO
            c["precision_ok"] = bool(ok)


def worker_main(argv=None) -> int:
    """Subprocess entry: run the cells of the JSON config in argv[0]."""
    argv = sys.argv[1:] if argv is None else argv
    cfg = json.loads(argv[0])
    out = _run_cells(cfg)
    print(_MARK + json.dumps(out))
    return 0


def stage_cells(spec) -> List[Dict]:
    """The (solver, policy) grid of ``spec`` with expected classes."""
    cells = []
    for solver in spec.precision_solvers:
        policies = SOLVER_POLICIES.get(solver) or spec.precision_policies
        policies = [p for p in policies if p in spec.precision_policies]
        safe = SAFE_POLICIES.get(solver, ("fp32",))
        degraded = DEGRADED_POLICIES.get(solver, ())
        for policy in policies:
            expect = ("safe" if policy in safe
                      else "degraded" if policy in degraded else "unsafe")
            cells.append({"solver": solver, "policy": policy,
                          "expect": expect,
                          "expect_safe": expect == "safe"})
    return cells


def model_cells(policies, P: int = 256, n: int = 50_000_000,
                halo: int = 32) -> Dict[str, Dict]:
    """``predict_speedup(precision=...)`` at a bandwidth-bound point.

    A large-n, wide-halo pipecg pair under light exponential noise: at
    fp32 the pipelined step is bandwidth-dominated (sweep + halo bytes
    exceed the overlapped reduction, speedup < 1 against the 2-sync
    baseline); shrinking the carried-vector sweep to bf16 and the halo
    wire to int8 drops ``t_compute`` below the reduction floor —
    ``pipe_latency_bound`` flips and the predicted speedup crosses 1.
    The measured cells validate the ACCURACY side of each policy; this
    is the model's PERFORMANCE side of the same sweep.
    """
    from repro.core.noise.simulator import SolverPhaseModel, predict_speedup
    from repro.core.perfmodel.distributions import Exponential

    sync = SolverPhaseModel(n=n, nnz_per_row=5, p=P, dtype_bytes=4,
                            n_vec_reads=6, n_reductions=2,
                            halo=halo, n_halo_vecs=2)
    pipe = dataclasses.replace(sync, n_vec_reads=14, n_reductions=1)
    noise = Exponential(lam=1.0 / 2e-6)   # 2 us mean per-step wait
    out: Dict[str, Dict] = {}
    for policy in policies:
        pred = predict_speedup(sync, pipe, noise, K=1, precision=policy)
        out[policy] = {
            "speedup": float(pred["speedup"]),
            "t_pipe_compute": float(pred["t_pipe_compute"]),
            "t_pipe_halo": float(pred["t_pipe_halo"]),
            "t_reduction": float(pred["t_reduction"]),
            "pipe_latency_bound": float(pred["pipe_latency_bound"]),
        }
    return out


def run_precision_exec(spec, timeout_s: float = 900.0) -> Dict:
    """Launch the precision stage subprocess and parse its record.

    The subprocess forces ``spec.precision_shards`` host devices; raises
    RuntimeError with the stderr tail if the worker dies.  The modeled
    ``predict_speedup`` cells are added parent-side (pure numpy).
    """
    cells = stage_cells(spec)
    if not cells:
        return {"cells": [], "model": {}, "hlo_bf16_int8wire": {}}
    cfg = {"n": spec.precision_n, "shards": spec.precision_shards,
           "maxiter": spec.precision_maxiter, "seed": spec.seed,
           "cells": cells}
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices, one per shard
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.precision_shards} "
        + env.get("XLA_FLAGS", "")).strip()
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        [src_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p])
    proc = subprocess.run(
        [sys.executable, "-m", "repro.experiments.precision_exec",
         json.dumps(cfg)],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    record = None
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            record = json.loads(line[len(_MARK):])
            break
    if record is None:
        raise RuntimeError(
            f"precision stage worker failed (rc={proc.returncode}); "
            "stderr tail:\n"
            + "\n".join(proc.stderr.splitlines()[-15:]))
    record["model"] = model_cells(tuple(spec.precision_policies))
    return record


def bench_record(precision: Dict) -> Dict:
    """Flatten a precision-stage record into gate rows.

    ``precision_ok`` is each cell's ``_classify`` verdict (within the
    solver's floor for safe cells, outside it for unsafe demonstrators,
    floor + no-EF/EF ratio for degraded ones).  ``res_over_eps`` (lower
    is better) is only gated on safe/degraded cells — an unsafe cell's
    divergence magnitude is pinned by the flag, not by a relative band
    on a blow-up.
    """
    rows: Dict[str, Dict] = {}
    for c in precision.get("cells", []):
        if c.get("skipped"):
            continue
        key = f"{c['solver']}_{c['policy']}"
        rows[key] = {
            "expect": c["expect"],
            "expect_safe": bool(c["expect_safe"]),
            "within_floor": bool(c["within_floor"]),
            "precision_ok": bool(c["precision_ok"]),
            "storage_words": float(c["storage_words"]),
            "wire_words": float(c["wire_words"]),
        }
        if c["expect"] in ("safe", "degraded"):
            rows[key]["res_over_eps"] = float(c["res_over_eps"])
        if "noef_over_ef" in c:
            rows[key]["noef_over_ef"] = float(c["noef_over_ef"])
    hlo = precision.get("hlo_bf16_int8wire") or {}
    if "pipecg_bf16_int8wire" in rows:
        rows["pipecg_bf16_int8wire"]["hlo_split_phase_overlap"] = bool(
            hlo.get("overlap_ok"))
    return {"precision": rows}


def main(argv=None) -> int:
    """CLI entry point (``python -m repro.experiments.precision_exec``)."""
    if argv is None and len(sys.argv) > 1 and sys.argv[1].startswith("{"):
        return worker_main()       # subprocess worker invocation
    ap = argparse.ArgumentParser(
        prog="python -m repro.experiments.precision_exec",
        description="Mixed-precision attainable-accuracy benchmark: "
                    "PrecisionPolicy x solver over sharded solves.")
    ap.add_argument("--preset", default="smoke")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--out", default="BENCH_precision.json")
    args = ap.parse_args(argv)

    from repro.experiments.spec import get_preset
    spec = get_preset(args.preset)
    if args.seed is not None:
        spec = dataclasses.replace(spec, seed=args.seed)

    precision = run_precision_exec(spec)
    record = bench_record(precision)
    record["detail"] = precision
    from repro.experiments.report import _jsonable
    with open(args.out, "w") as f:
        json.dump(_jsonable(record), f, indent=1, sort_keys=True)

    ok = all(r["precision_ok"] for r in record["precision"].values())
    for key, r in sorted(record["precision"].items()):
        print(f"{key}: expect={r['expect']} "
              f"within_floor={int(r['within_floor'])} "
              f"res_over_eps={r.get('res_over_eps', float('nan')):.3f} "
              f"ok={int(r['precision_ok'])}")
    print(f"precision stage: {'OK' if ok else 'FAILED'} "
          f"({len(record['precision'])} cells)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
