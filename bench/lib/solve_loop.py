"""Closed loop: one caller solving fresh right-hand sides back to back.

Set-up builds the operator and a seeded pool of right-hand sides on the
device, compiles the solve (or loads it from the cache) and runs it
once.  The window then calls the same compiled solve on pool entries in
a seeded order (one permutation of the pool, repeated), each call ending
in ``block_until_ready``, until ``seconds`` have passed.  The answers of
the window's first pass over the pool and of its last (every entry
twice) are kept; once the window has closed each is checked on the host
against the float64 reference (``lib/reference.py``).
"""
from __future__ import annotations

import json
import re
import sys
import time
import types

import numpy as np

from lib import accounting, reference, traffic as gen
from lib.harness import TRACE_S, device_info, start_trace, stop_trace

SWEEP = re.compile(r"^(pipecg_sweep_step|pipecg_spmv_halo_step)\b")


def build(ctx):
    """``(solve, bands, offsets, rhs)``: the program's entry, compiled.

    Marks the end of each set-up phase in ``ctx.marks``.
    """
    import importlib

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import repro.core.krylov as krylov
    from repro.core.krylov.distributed import distributed_solve
    from repro.core.krylov.operators import DiaMatrix
    from repro.core.krylov.options import SolverOptions

    cfg, tr = ctx.cfg, ctx.traffic
    if tr["driver"] == "distributed":
        mesh = Mesh(np.array(ctx.devices), ("shards",))
        band_sh = NamedSharding(mesh, P(None, "shards"))
        vec_sh = NamedSharding(mesh, P("shards"))
    else:
        band_sh = vec_sh = SingleDeviceSharding(ctx.devices[0])
    marks = ctx.marks
    family = importlib.import_module(f"operators.{cfg['family']}")
    offsets, bands = family.build(cfg, ctx.seed, band_sh)
    jax.block_until_ready(bands)
    marks["operator"] = time.perf_counter()
    rhs = jax.block_until_ready(gen.rhs_pool(tr, cfg, ctx.seed, vec_sh))
    marks["pool"] = time.perf_counter()
    opts = SolverOptions(engine=tr["engine"], M=tr["M"], tol=tr["tol"],
                         maxiter=tr["maxiter"],
                         precision=getattr(ctx, "precision", "fp32"))
    solver = getattr(krylov, tr["solver"])
    if tr["driver"] == "distributed":
        fn = lambda bd, b: distributed_solve(
            solver, DiaMatrix(offsets, bd), b, mesh, options=opts)
    else:
        fn = lambda bd, b: solver(DiaMatrix(offsets, bd), b, options=opts)
    solve = jax.jit(fn).lower(bands, rhs[0]).compile()
    marks["compile"] = time.perf_counter()
    return solve, bands, offsets, rhs


def judge(op, answers, b_host, limits) -> tuple:
    """``(checks, rows)`` of the answers ``(pool entry, x, iters)``.

    Each answer's normwise backward error (in fp32 eps) against the
    float64 reference.  Two numbers, each held to a limit of its own:
    the worst answer, which swings with the conditioning of the pool
    (an fp32 PIPECG solve left at ``maxiter`` on a right-hand side with
    a very low mode can read hundreds of eps), and the median answer,
    which stays put from seed to seed.  ``rows`` is ``[entry, iters,
    eta_eps]`` per answer.
    """
    rows = [[int(j), int(k), op.residuals(b_host[j], x)[1] / reference.EPS32]
            for j, x, k in answers]
    etas = [r[2] for r in rows]
    numbers = {"eta_max_eps": max(etas),
               "eta_median_eps": float(np.median(etas))}
    checks = [dict(name=k, value=v, limit=limits[k], ok=bool(v <= limits[k]))
              for k, v in numbers.items()]
    return checks, rows


def run(ctx):
    import jax

    cfg, tr = ctx.cfg, ctx.traffic
    maxiter, n = int(tr["maxiter"]), int(cfg["n"])
    solve, bands, offsets, rhs = build(ctx)
    order = np.random.default_rng(gen.sub_seed(ctx.seed, 4)).permutation(
        len(rhs))
    jax.block_until_ready(solve(bands, rhs[order[0]]))
    ctx.marks["warm"] = time.perf_counter()
    setup_s = ctx.marks["warm"] - ctx.t0

    first, last, times, iters = {}, {}, [], []
    window = TRACE_S if ctx.trace else ctx.seconds
    tdir = start_trace() if ctx.trace else None
    ctx.compiles.on = True
    t_start = t_end = time.perf_counter()
    i = 0
    while t_end - t_start < window or i < 2:
        j = int(order[i % len(order)])
        with jax.profiler.TraceAnnotation("bench.solve"):
            t = time.perf_counter()
            res = jax.block_until_ready(solve(bands, rhs[j]))
            t_end = time.perf_counter()
        times.append(t_end - t)
        iters.append(res.iters)
        first.setdefault(j, (res.x, res.iters))
        last[j] = (res.x, res.iters)
        i += 1
    ctx.compiles.on = False
    elapsed = t_end - t_start
    trace = stop_trace(tdir, elapsed) if ctx.trace else None

    device = device_info(jax.devices(), ctx.devices)
    iters = [int(k) for k in iters]
    # the reference runs after the window, on host copies
    kept = list(first.items()) + [(j, a) for j, a in last.items()
                                  if a[0] is not first[j][0]]
    answers = [(j, np.asarray(x), int(k)) for j, (x, k) in kept]
    b_host = {j: np.asarray(rhs[j]) for j in first}
    op = reference.Operator(offsets, np.asarray(bands))
    del solve, bands, rhs, first, last, kept, res
    checks, rows = judge(op, answers, b_host, tr["limits"])
    unconverged = sum(k >= maxiter for k in iters)
    slow = sorted(range(len(times)), key=times.__getitem__)[-3:]
    print("bench: solves", json.dumps(
        {"solves": len(iters), "unconverged": unconverged,
         "iters_median": float(np.median(iters)),
         "slowest_s": [[k, times[k]] for k in slow],
         "checked": rows}), file=sys.stderr)
    rows_per_chip = n // ctx.chips
    run = types.SimpleNamespace(
        kind="solve", trace=trace, maxiter=maxiter, iters=iters,
        steps=len(iters) * maxiter, sweep=SWEEP, peaks=ctx.peaks,
        cost=accounting.pipecg_sweep(rows_per_chip, len(offsets)))
    return types.SimpleNamespace(
        metrics={"setup_s": {"value": setup_s, "unit": "s"},
                 "solve_s": {"value": elapsed / len(times), "unit": "s"},
                 "solve_p95_s": {"value": float(np.percentile(times, 95)),
                                 "unit": "s"}},
        attempted=len(times), failed=unconverged, checks=checks,
        device=device, run=run, window_compiles=ctx.compiles.count)
