"""Campaign ABFT stage: detection coverage of the in-flight detectors.

Sweeps corruption magnitude x solver x detector over REAL multi-device
shard_map solves (subprocess with forced host devices, the same trick as
fault_exec.py).  Per cell the worker runs:

* a CLEAN twin — the same sharded solve with no injector.  Its carried
  detector history (``SolveResult.detect_history``: the checksum row
  ``1^T w - c^T u`` for the depth-1 pipecg/pipebicgstab bodies, the
  state deviation ``1^T(b - A x - r)`` for the depth-l blocks) must
  never cross the trip threshold: the measured FALSE-POSITIVE rate of
  the acceptance gate is the fraction of clean cells that trip.
* a CORRUPT run — one silent ``corrupt`` fault of the cell's magnitude
  injected into the carried reduction mid-solve.  The measured
  detection latency is the gap between the fault onset and the first
  detector-history trip; a supra-threshold corruption must trip within
  the modeled window (1 iteration for the depth-1 bodies, l for the
  block-granular depth path — ``resync.abft_detection_iters``), while a
  sub-threshold one is expected NOT to trip (it is below the rounding
  floor the threshold guards).
* for pipecg, the elastic controller (``resilient_distributed_solve``)
  under the same fault — its RecoveryEvent must name the ``checksum``
  fast path, and its in-flight ``detect_iters`` is compared against the
  boundary-synchronous ``(period + 1) / 2`` of PR 6's detection
  (``resync.detection_iters``): the latency the carried checksum buys
  back.

CLI (writes ``BENCH_abft.json`` for ``check_regression.py --key abft``)::

    PYTHONPATH=src python -m repro.experiments.abft_exec \
        [--preset smoke] [--out BENCH_abft.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from typing import Dict, List

_MARK = "ABFT_STAGE_JSON:"

#: detection-window bound, in iterations, per sharded solver family
#: (depth-1 bodies trip on the next carried psum; the depth-l path
#: reduces once per l-iteration block, plus one-iteration slack for the
#: carried-unreduced handoff)
def detection_window(solver: str, depth: int) -> int:
    """Modeled in-flight detection window, in iterations."""
    return (depth if solver == "pipecg_l" else 1) + 1


def _run_cells(cfg: Dict) -> Dict:
    """Execute every ABFT cell in-process (the subprocess worker body)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.krylov import abft
    from repro.core.krylov.bicgstab import pipebicgstab
    from repro.core.krylov.cg import pipecg
    from repro.core.krylov.distributed import distributed_solve
    from repro.core.krylov.options import SolverOptions
    from repro.core.krylov.pipeline import pipecg_l
    from repro.core.noise.faults import FaultInjector, FaultSpec
    from repro.core.perfmodel.resync import (
        abft_detection_iters,
        detection_iters,
    )
    from repro.distributed.fault import resilient_distributed_solve
    from repro.experiments.fault_exec import _shifted_laplacian

    n = int(cfg["n"])
    P = int(cfg["shards"])
    maxiter = int(cfg["maxiter"])
    tol = float(cfg["tol"])
    depth = int(cfg["depth"])
    period = int(cfg["checkpoint_period"])
    seed = int(cfg["seed"])
    A = _shifted_laplacian(n)
    b = jnp.ones((n,), A.bands.dtype)
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices[:P]), ("shards",))
    a_inf = float(np.abs(np.asarray(A.bands)).sum(axis=0).max())
    norm_b = float(np.linalg.norm(np.asarray(b)))

    solver_fns = {"pipecg": pipecg, "pipebicgstab": pipebicgstab,
                  "pipecg_l": pipecg_l}

    def solve(solver, injector=None):
        opts = SolverOptions(
            engine="sharded_fused", tol=tol, maxiter=maxiter,
            noise=injector, depth=depth if solver == "pipecg_l" else 1)
        res = distributed_solve(solver_fns[solver], A, b, mesh,
                                options=opts)
        det = np.abs(np.asarray(res.detect_history, np.float64))
        hist = np.asarray(res.res_history, np.float64)
        return res, det, hist

    clean: Dict[str, Dict] = {}
    cells: List[Dict] = []
    for ci, cell in enumerate(cfg["cells"]):
        solver = cell["solver"]
        mag = float(cell["magnitude"])
        if P > len(devices) or n % P:
            cells.append({**cell, "skipped": True,
                          "reason": f"{len(devices)} devices, n={n}"})
            continue
        detector = ("state_deviation" if solver == "pipecg_l"
                    else "checksum")
        if solver not in clean:
            res0, det0, hist0 = solve(solver)
            # trip threshold: rounding floor of an n-term checksum at the
            # solve's own scale (||A||_inf x the largest residual seen),
            # with the abft.DEFAULT_TAU headroom — shared by the clean
            # false-positive gate and the corrupt-run trip scan
            scale = a_inf * max(float(hist0.max()), norm_b)
            thr = abft.checksum_threshold(scale, n, np.float64)
            clean[solver] = {
                "threshold": thr,
                "clean_trip": abft.first_trip(det0, thr),
                "clean_max": float(det0.max()),
                "clean_iters": int(res0.iters),
                "converged": bool(np.asarray(res0.res_norm)
                                  <= tol * norm_b),
            }
        base = clean[solver]
        thr = base["threshold"]

        rng = np.random.default_rng((seed, ci))
        # the fault must land mid-solve: a corruption injected after the
        # trajectory froze (converged) never enters the carried
        # reduction.  The injector counts REDUCTIONS, and the depth-l
        # body reduces once per l-iteration block, so its onset is drawn
        # (and converted back) in block units.
        ticks_per = depth if solver == "pipecg_l" else 1
        hi = max(3, int(0.6 * base["clean_iters"] / ticks_per))
        onset = int(rng.integers(2, hi))
        onset_iters = onset * ticks_per
        shard = int(rng.integers(0, P))
        inj = FaultInjector(
            faults=[FaultSpec(kind="corrupt", shard=shard, at_iter=onset,
                              magnitude=mag)],
            n_shards=P, seed=seed + ci)
        res, det, hist = solve(solver, injector=inj)
        trip = abft.first_trip(det, thr)
        window = detection_window(solver, depth)
        expect_trip = mag > thr
        detect_lag = (trip + 1 - onset_iters) if trip >= 0 else -1
        modeled = abft_detection_iters(mag, thr, period)
        row = {
            "solver": solver, "detector": detector, "magnitude": mag,
            "onset_iter": onset_iters, "fault_shard": shard,
            "threshold": thr, "trip_iter": trip,
            "detect_lag_iters": detect_lag,
            "window_iters": window,
            "expect_trip": bool(expect_trip),
            "tripped": bool(trip >= 0),
            "detected_in_window": bool(
                trip >= 0 and 0 <= detect_lag <= window),
            "modeled_detect_iters": float(modeled),
            "boundary_detect_iters": float(detection_iters(period)),
            "clean_trip_iter": int(base["clean_trip"]),
            "clean_max_value": base["clean_max"],
            "false_positive": bool(base["clean_trip"] >= 0),
            "converged": bool(np.asarray(res.res_norm) <= tol * norm_b),
            "skipped": False,
        }
        # pipecg only: close the loop through the elastic controller —
        # the fast path must drive the recovery and beat the boundary
        # latency of PR 6's every-segment true-residual check
        if solver == "pipecg" and expect_trip:
            inj2 = FaultInjector(
                faults=[FaultSpec(kind="corrupt", shard=shard,
                                  at_iter=onset, magnitude=mag)],
                n_shards=P, seed=seed + ci)
            _, rep = resilient_distributed_solve(
                A, b, devices[:P],
                options=SolverOptions(tol=tol, maxiter=maxiter,
                                      noise=inj2),
                checkpoint_period=period)
            ev = [e for e in rep.recoveries if e.kind == "corrupt"]
            row.update({
                "recovered": bool(ev),
                "recovery_detector": ev[0].detector if ev else "",
                "recovery_detect_iters": (float(ev[0].detect_iters)
                                          if ev else -1.0),
                "recovery_converged": bool(rep.converged),
                "recovery_overhead_iters": float(
                    rep.executed_iters - rep.productive_iters),
            })
        cells.append(row)

    return {"cells": cells,
            "clean": clean,
            "n": n, "shards": P, "maxiter": maxiter, "tol": tol,
            "depth": depth, "checkpoint_period": period}


def worker_main(argv=None) -> int:
    """Subprocess entry: run the cells of the JSON config in argv[1]."""
    argv = sys.argv[1:] if argv is None else argv
    cfg = json.loads(argv[0])
    out = _run_cells(cfg)
    print(_MARK + json.dumps(out))
    return 0


def run_abft_exec(spec, timeout_s: float = 900.0) -> Dict:
    """Launch the ABFT stage subprocess for ``spec`` and parse its output.

    The subprocess forces ``spec.abft_shards`` host devices; raises
    RuntimeError with the stderr tail if the worker dies.
    """
    solvers = tuple(spec.abft_solvers)
    if not solvers:
        return {"cells": [], "clean": {}}
    cfg = {
        "n": spec.abft_n, "shards": spec.abft_shards,
        "maxiter": spec.abft_maxiter, "tol": spec.abft_tol,
        "depth": spec.abft_depth,
        "checkpoint_period": spec.fault_checkpoint_period,
        "seed": spec.seed,
        "cells": [{"solver": s, "magnitude": m}
                  for s in solvers
                  for m in spec.abft_magnitudes],
    }
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices, one per shard
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.abft_shards} "
        + env.get("XLA_FLAGS", "")).strip()
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        [src_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p])
    proc = subprocess.run(
        [sys.executable, "-m", "repro.experiments.abft_exec",
         json.dumps(cfg)],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"abft stage worker failed (rc={proc.returncode}); stderr tail:\n"
        + "\n".join(proc.stderr.splitlines()[-15:]))


def bench_record(abft: Dict) -> Dict:
    """Flatten an ABFT stage record into ``BENCH_abft.json`` gate rows."""
    rows: Dict[str, Dict] = {}
    for c in abft.get("cells", []):
        if c.get("skipped"):
            continue
        key = f"{c['solver']}_mag{c['magnitude']:g}"
        rows[key] = {
            "detector": c["detector"],
            "tripped": bool(c["tripped"]),
            "expect_trip": bool(c["expect_trip"]),
            "detected_in_window": bool(c["detected_in_window"]),
            "modeled_detect_iters": float(c["modeled_detect_iters"]),
            "boundary_detect_iters": float(c["boundary_detect_iters"]),
            "false_positive": bool(c["false_positive"]),
            "detection_ok": bool(
                (c["detected_in_window"] if c["expect_trip"]
                 else not c["tripped"])
                and not c["false_positive"]),
        }
        # the lag is gated "lower is better"; no-trip cells carry -1,
        # which a relative tolerance band would flag spuriously — omit
        # the metric there (compare() skips metrics absent from both)
        if c["tripped"]:
            rows[key]["detect_lag_iters"] = float(c["detect_lag_iters"])
        if "recovered" in c:
            rows[key].update({
                "recovered": bool(c["recovered"]),
                "recovery_detector": c["recovery_detector"],
                "recovery_detect_iters": float(
                    c["recovery_detect_iters"]),
                "recovery_converged": bool(c["recovery_converged"]),
            })
    return {"abft": rows}


def main(argv=None) -> int:
    """CLI entry point (``python -m repro.experiments.abft_exec``)."""
    if argv is None and len(sys.argv) > 1 and sys.argv[1].startswith("{"):
        return worker_main()       # subprocess worker invocation
    ap = argparse.ArgumentParser(
        prog="python -m repro.experiments.abft_exec",
        description="ABFT detection-coverage benchmark: corruption "
                    "magnitude x solver x detector over sharded solves.")
    ap.add_argument("--preset", default="smoke")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--out", default="BENCH_abft.json")
    args = ap.parse_args(argv)

    from repro.experiments.spec import get_preset
    spec = get_preset(args.preset)
    if args.seed is not None:
        spec = dataclasses.replace(spec, seed=args.seed)

    abft = run_abft_exec(spec)
    record = bench_record(abft)
    record["detail"] = abft
    from repro.experiments.report import _jsonable
    with open(args.out, "w") as f:
        json.dump(_jsonable(record), f, indent=1, sort_keys=True)

    ok = all(r["detection_ok"] for r in record["abft"].values())
    for key, r in sorted(record["abft"].items()):
        lag = r.get("detect_lag_iters", -1.0)
        print(f"{key}: tripped={int(r['tripped'])} "
              f"lag={lag:.0f} (window ok={int(r['detected_in_window'])}, "
              f"boundary={r['boundary_detect_iters']:.1f}) "
              f"fp={int(r['false_positive'])}")
    print(f"abft stage: {'OK' if ok else 'FAILED'} "
          f"({len(record['abft'])} cells)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
