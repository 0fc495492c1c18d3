"""Campaign fault stage: real shard-loss recovery, measured vs modeled.

Sweeps fault kind x rate x shard count over REAL multi-device shard_map
solves.  The local host exposes a single JAX device, so the stage runs in
a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=P``
(the same trick as tests/test_elastic.py): the worker half of this module
(``python -m repro.experiments.fault_exec '<json cfg>'``) executes every
cell and prints one machine-readable result line; the parent half
(:func:`run_fault_exec`) launches it and parses that line.

Per cell the worker runs the elastic controller
(``distributed/fault.py::resilient_distributed_solve``) twice on a
shifted tridiagonal Laplacian (kappa ~ 5, so the solve converges to
1e-10 in a few dozen iterations):

* a CLEAN baseline (no injector) — its executed-iteration count and wall
  time are the zero-fault reference;
* a FAULTY run with one scheduled fault whose onset iteration is drawn
  geometrically from the cell's rate (one fault per run: the model's
  bound is per fault).

The measured recovery overhead is iteration-denominated — rolled-back +
re-executed iterations for kill/corrupt (``executed_faulty -
executed_clean``), boundary detection latency for stall (the iterations
run at degraded speed before eviction) — and validated against
``core/perfmodel/resync.py::recovery_overhead_bound``, the
implementation-agnostic floor (campaign acceptance: within 2x).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

_MARK = "FAULT_STAGE_JSON:"


def _shifted_laplacian(n: int):
    """Tridiagonal Laplacian + identity: SPD with kappa ~ 5.

    The plain Laplacian's kappa ~ n^2 would need O(n) iterations; the
    unit shift keeps every fault cell's solve at a few dozen iterations
    so the subprocess stage stays CI-sized.
    """
    from repro.core.krylov import tridiagonal_laplacian
    from repro.core.krylov.operators import DiaMatrix

    A0 = tridiagonal_laplacian(n)
    diag = A0.offsets.index(0)
    return DiaMatrix(offsets=A0.offsets,
                     bands=A0.bands.at[diag].add(1.0))


def _run_cells(cfg: Dict) -> Dict:
    """Execute every fault cell in-process (the subprocess worker body)."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    from repro.core.krylov.options import SolverOptions
    from repro.core.noise.faults import FaultInjector, FaultSpec
    from repro.core.perfmodel.resync import recovery_overhead_bound
    from repro.distributed.fault import resilient_distributed_solve

    n = int(cfg["n"])
    maxiter = int(cfg["maxiter"])
    period = int(cfg["checkpoint_period"])
    tol = float(cfg["tol"])
    stall_s = float(cfg["stall_s"])
    seed = int(cfg["seed"])
    A = _shifted_laplacian(n)
    b = jnp.ones((n,), A.bands.dtype)
    devices = jax.devices()

    clean: Dict[int, Dict] = {}      # per shard count: baseline stats
    cells: List[Dict] = []
    for ci, cell in enumerate(cfg["cells"]):
        kind = cell["kind"]
        rate = float(cell["rate"])
        P = int(cell["n_shards"])
        if P > len(devices) or n % P:
            cells.append({**cell, "skipped": True,
                          "reason": f"{len(devices)} devices, n={n}"})
            continue
        if P not in clean:
            res0, rep0 = resilient_distributed_solve(
                A, b, devices[:P],
                options=SolverOptions(tol=tol, maxiter=maxiter),
                checkpoint_period=period)
            clean[P] = {"executed_iters": rep0.executed_iters,
                        "productive_iters": rep0.productive_iters,
                        "wall_s": rep0.wall_s,
                        "true_res": rep0.true_res_norm,
                        "converged": rep0.converged}
        base = clean[P]

        # one fault per run; the rate parameterizes the onset draw
        # (geometric = discretized Poisson), capped to land mid-solve so
        # the fault cannot miss an already-converged trajectory
        rng = np.random.default_rng((seed, ci))
        onset = int(rng.geometric(min(max(rate, 1e-6), 0.5)))
        onset = max(2, min(onset,
                           max(2, int(0.6 * base["productive_iters"]))))
        shard = int(rng.integers(0, P))
        inj = FaultInjector(
            faults=[FaultSpec(kind=kind, shard=shard, at_iter=onset,
                              stall_s=stall_s)],
            n_shards=P, seed=seed + ci)
        res, rep = resilient_distributed_solve(
            A, b, devices[:P],
            options=SolverOptions(tol=tol, maxiter=maxiter, noise=inj),
            checkpoint_period=period)
        events = [e for e in rep.recoveries if e.kind == kind]
        recovered = bool(events)
        if kind == "stall":
            # no rollback: the cost is the detection latency itself
            overhead_iters = float(events[0].detect_iters) if events else 0.0
        else:
            overhead_iters = float(rep.executed_iters
                                   - base["executed_iters"])
        bound = recovery_overhead_bound(kind, period)
        cells.append({
            "kind": kind, "rate": rate, "n_shards": P,
            "fault_shard": shard, "onset_iter": onset,
            "recovered": recovered, "converged": rep.converged,
            "res_norm": rep.res_norm, "true_res": rep.true_res_norm,
            "clean_true_res": base["true_res"],
            "executed_iters": rep.executed_iters,
            "clean_executed_iters": base["executed_iters"],
            "productive_iters": rep.productive_iters,
            "n_shards_final": rep.n_shards_final,
            "detect_iters": (float(events[0].detect_iters)
                             if events else -1.0),
            "overhead_iters": overhead_iters,
            "bound_iters": float(bound),
            "overhead_ratio": (overhead_iters / bound if bound > 0
                               else 0.0),
            "wall_s": rep.wall_s, "clean_wall_s": base["wall_s"],
            "wall_ratio": rep.wall_s / max(base["wall_s"], 1e-12),
            "skipped": False,
        })
    return {"cells": cells, "clean": {str(k): v for k, v in clean.items()},
            "n": n, "maxiter": maxiter, "checkpoint_period": period,
            "tol": tol, "stall_s": stall_s}


def worker_main(argv=None) -> int:
    """Subprocess entry: run the cells of the JSON config in argv[1]."""
    argv = sys.argv[1:] if argv is None else argv
    cfg = json.loads(argv[0])
    out = _run_cells(cfg)
    print(_MARK + json.dumps(out))
    return 0


def run_fault_exec(spec, timeout_s: float = 900.0) -> Dict:
    """Launch the fault stage subprocess for ``spec`` and parse its output.

    The subprocess forces ``max(spec.fault_shard_counts)`` host devices;
    all shard counts of the sweep run inside that one process (smaller
    meshes use device subsets), so the JAX startup + compile cost is paid
    once.  Raises RuntimeError with the stderr tail if the worker dies.
    """
    kinds = tuple(spec.fault_kinds)
    if not kinds:
        return {"cells": [], "clean": {}}
    cfg = {
        "n": spec.fault_n, "maxiter": spec.fault_maxiter,
        "checkpoint_period": spec.fault_checkpoint_period,
        "tol": spec.fault_tol, "stall_s": spec.fault_stall_s,
        "seed": spec.seed,
        "cells": [{"kind": k, "rate": r, "n_shards": p}
                  for k in kinds
                  for r in spec.fault_rates
                  for p in spec.fault_shard_counts],
    }
    max_p = max(spec.fault_shard_counts)
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices, one per shard
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={max_p} "
                        + env.get("XLA_FLAGS", "")).strip()
    # the worker must resolve the same repro package as this process
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        [src_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                      if p])
    proc = subprocess.run(
        [sys.executable, "-m", "repro.experiments.fault_exec",
         json.dumps(cfg)],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"fault stage worker failed (rc={proc.returncode}); stderr tail:\n"
        + "\n".join(proc.stderr.splitlines()[-15:]))


if __name__ == "__main__":
    sys.exit(worker_main())
