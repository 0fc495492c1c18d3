"""Campaign geometry stage: operator format x process grid x noise.

Sweeps the operator-layer decompositions of PR 10 — DIA on a 1-D chain,
BSR on a 1-D block chain, DIA on a 2-D process grid — over REAL
multi-device shard_map solves and validates each against the
surface-to-volume communication model (``core/perfmodel/comm.py``).
The local host exposes a single JAX device, so the stage runs in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=P``
(the fault-stage pattern): the worker half
(``python -m repro.experiments.geometry_exec '<json cfg>'``) executes
every cell and prints one machine-readable result line; the parent half
(:func:`run_geometry_exec`) launches it and parses that line.

Per cell the worker runs ``distributed_solve(engine="sharded_fused")``
on the format's shifted-Laplacian problem and records

* accuracy — max |x_sharded - x_naive| against the single-device
  reference (the PR's <= 1e-8 equivalence gate);
* the compiled HLO's collective counts via
  ``launch/hlo_analysis.split_phase_overlap``: exactly ONE all-reduce
  per while body (the split-phase Gram psum) and a ppermute count that
  must equal ``n_halo_vecs * halo_messages(1) * active_dims`` — the
  measured-vs-modeled message-count gate (a size-1 grid axis has no
  neighbor, so XLA elides its permutes and the model must not count
  them);
* per-iteration wall time, clean and with a wall-clock ``NoiseHook``
  stall per iteration (the noise axis of the sweep);
* the modeled geometry terms: ``halo_elems``, ``surface_to_volume`` and
  ``halo_wire_time`` for the cell's local tile extents.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
from typing import Dict, List

_MARK = "GEOMETRY_STAGE_JSON:"

# halo-carrying vectors per pipelined iteration (u and p — what every
# sharded body exchanges at double reach for the recompute trick)
_N_HALO_VECS = 2


def _problems(cfg: Dict):
    """Build the per-format (operator, b, extents-fn) table once."""
    import jax.numpy as jnp

    from repro.core.krylov import dia_to_bsr, laplacian_2d
    from repro.core.krylov.operators import DiaMatrix
    from repro.experiments.fault_exec import _shifted_laplacian

    ny, nx = (int(v) for v in cfg["points"])
    n = ny * nx
    A1 = _shifted_laplacian(n)
    A2d0 = laplacian_2d(nx=nx, ny=ny)
    diag = A2d0.offsets.index(0)
    A2d = DiaMatrix(offsets=A2d0.offsets,
                    bands=A2d0.bands.at[diag].add(1.0),
                    grid_shape=A2d0.grid_shape)
    Ab = dia_to_bsr(A1, bs=int(cfg["bs"]))
    b = jnp.ones((n,), A1.bands.dtype)
    return {"dia": A1, "dia2d": A2d, "bsr": Ab}, b


def _cell_geometry(fmt: str, grid, cfg: Dict, A) -> Dict:
    """Modeled comm terms for one cell's local tile (comm.py surface law)."""
    from repro.core.noise.simulator import Hardware
    from repro.core.perfmodel import comm

    ny, nx = (int(v) for v in cfg["points"])
    n = ny * nx
    if fmt == "dia2d":
        extents = comm.local_extents((ny, nx), tuple(grid))
        hs = A.halo_spec()          # N/S/W/E strip widths
        widths = (hs.widths[0], hs.widths[2])
    elif fmt == "bsr":
        # the wire moves block rows: block_halo * bs elements per side
        extents = (n // int(grid[0]),)
        widths = (A.block_halo * A.bs,)
    else:
        extents = (n // int(grid[0]),)
        widths = (max(abs(o) for o in A.offsets),)
    hw = Hardware()
    # a size-1 grid axis has no neighbor: XLA elides its ppermutes, so
    # the message gate only counts the decomposed (active) dimensions
    active = sum(1 for g in grid if int(g) > 1)
    return {
        "extents": list(extents),
        "widths": list(widths),
        "halo_elems": comm.halo_elems(extents, widths),
        "surface_to_volume": comm.surface_to_volume(extents, widths),
        "msgs_modeled": comm.halo_messages(len(extents)),
        "msgs_active": comm.halo_messages(1) * active,
        "t_halo_modeled_s": comm.halo_wire_time(
            extents, widths, n_halo_vecs=_N_HALO_VECS, dtype_bytes=8,
            link_bw=hw.link_bw, hop_latency=hw.hop_latency),
    }


def _solver_body_counts(hlo: str) -> Dict:
    """Collective counts of the while body carrying the Gram all-reduce."""
    from repro.launch.hlo_analysis import split_phase_overlap

    rep = split_phase_overlap(hlo)
    mixed = [row for row in rep["bodies"].values() if row["all_reduce"] > 0]
    # the solver scan is the unique reduce-carrying body
    row = mixed[0] if len(mixed) == 1 else {
        "all_reduce": -1, "collective_permute": -1,
        "permute_depends_on_reduce": True}
    return {
        "hlo_all_reduce": int(row["all_reduce"]),
        "hlo_ppermute": int(row["collective_permute"]),
        "permute_depends_on_reduce": bool(
            row["permute_depends_on_reduce"]),
        "overlap_ok": bool(rep["overlap_ok"]),
    }


def _executed_steps(fmt: str, iters, maxiter: int) -> int:
    """Loop steps a sharded solve ran.  The 1-D body stops after the step
    that sets ``done``: ``iters + 1`` steps, at most ``maxiter``.  The 2-D
    and BSR bodies run every one of their ``maxiter`` scan steps."""
    if fmt in ("dia2d", "bsr"):
        return maxiter
    return min(int(iters) + 1, maxiter)


def _run_cells(cfg: Dict) -> Dict:
    """Execute every geometry cell in-process (the subprocess worker)."""
    import functools
    import time

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.krylov import distributed_solve, pipecg
    from repro.core.noise.injection import NoiseHook
    from repro.core.perfmodel.distributions import Exponential

    maxiter = int(cfg["maxiter"])
    tol = float(cfg["tol"])
    repeats = int(cfg["repeats"])
    noise_scale = float(cfg["noise_scale"])
    seed = int(cfg["seed"])
    ops, b = _problems(cfg)
    devices = np.array(jax.devices())

    refs: Dict[str, object] = {}
    cells: List[Dict] = []
    for ci, cell in enumerate(cfg["cells"]):
        fmt = cell["format"]
        grid = tuple(int(g) for g in cell["grid"])
        P = math.prod(grid)
        if P > len(devices):
            cells.append({**cell, "skipped": True,
                          "reason": f"{len(devices)} devices < P={P}"})
            continue
        A = ops[fmt]
        if fmt not in refs:
            refs[fmt] = pipecg(lambda v, A=A: A.matvec(v), b,
                               maxiter=maxiter, tol=tol)
        ref = refs[fmt]

        if fmt == "dia2d":
            mesh = Mesh(devices[:P].reshape(grid), ("gy", "gx"))
        else:
            mesh = Mesh(devices[:P], ("shards",))
        solve = functools.partial(distributed_solve, pipecg, A, mesh=mesh,
                                  engine="sharded_fused", maxiter=maxiter,
                                  tol=tol, M=None)
        compiled = jax.jit(solve).lower(b).compile()
        out = compiled(b)
        jax.block_until_ready(out.x)
        err = float(jnp.max(jnp.abs(out.x - ref.x)))
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(b).x)
            times.append(time.perf_counter() - t0)
        t_iter = min(times) / _executed_steps(fmt, out.iters, maxiter)

        hook = NoiseHook(Exponential(1.0), scale=noise_scale,
                         seed=seed + 13 * ci)
        noisy = jax.jit(functools.partial(solve, noise=hook))
        jax.block_until_ready(noisy(b).x)   # compile + first stalled run
        t0 = time.perf_counter()
        out_noisy = jax.block_until_ready(noisy(b))
        t_iter_noisy = ((time.perf_counter() - t0)
                        / _executed_steps(fmt, out_noisy.iters, maxiter))

        geom = _cell_geometry(fmt, grid, cfg, A)
        counts = _solver_body_counts(compiled.as_text())
        cells.append({
            "format": fmt, "grid": list(grid), "P": P,
            "res_norm": float(out.res_norm),
            "ref_res_norm": float(ref.res_norm),
            "accuracy_err": err,
            "t_iter_us": t_iter * 1e6,
            "t_iter_noisy_us": t_iter_noisy * 1e6,
            "ppermute_expected": _N_HALO_VECS * geom["msgs_active"],
            "skipped": False,
            **geom, **counts,
        })
    return {"cells": cells, "points": list(cfg["points"]),
            "maxiter": maxiter, "tol": tol,
            "noise_scale": noise_scale, "bs": int(cfg["bs"])}


def worker_main(argv=None) -> int:
    """Subprocess entry: run the cells of the JSON config in argv[0]."""
    argv = sys.argv[1:] if argv is None else argv
    cfg = json.loads(argv[0])
    out = _run_cells(cfg)
    print(_MARK + json.dumps(out))
    return 0


def run_geometry_exec(spec, timeout_s: float = 900.0) -> Dict:
    """Launch the geometry-stage subprocess for ``spec``; parse its output.

    The subprocess forces enough host devices for the largest swept
    grid; all cells run inside that one process so the JAX startup +
    compile cost is paid once.  Raises RuntimeError with the stderr tail
    if the worker dies.
    """
    if not spec.geometry_formats:
        return {"cells": []}
    cells = []
    for fmt in spec.geometry_formats:
        if fmt == "dia2d":
            cells.extend({"format": fmt, "grid": list(g)}
                         for g in spec.geometry_grids)
        else:
            cells.append({"format": fmt,
                          "grid": [int(spec.geometry_shards)]})
    cfg = {
        "points": list(spec.geometry_points),
        "maxiter": spec.geometry_maxiter, "tol": spec.geometry_tol,
        "repeats": spec.geometry_repeats, "bs": spec.geometry_bs,
        "noise_scale": spec.geometry_noise_scale, "seed": spec.seed,
        "cells": cells,
    }
    max_p = max(math.prod(c["grid"]) for c in cells)
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
        [sys.executable, "-m", "repro.experiments.geometry_exec",
         json.dumps(cfg)],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(
        f"geometry stage worker failed (rc={proc.returncode}); stderr "
        "tail:\n" + "\n".join(proc.stderr.splitlines()[-15:]))


if __name__ == "__main__":
    sys.exit(worker_main())
