"""The harness: find a cell by name, run it, print the result line.

Everything specific to a configuration, a traffic mix or a per-layer
metric is data or a module of its own, found by name:

* ``BENCHMARK.json`` (the checkout's root): cells, metrics, bounds;
* ``bench/configs/<config>.json``: sizes, dtype, operator ``family``;
* ``bench/operators/<family>.py``: builds the operator on the device;
* ``bench/traffic/<traffic>.json``: right-hand sides, solver call,
  the ``loop`` that drives it and the correctness limits;
* ``bench/lib/<loop>_loop.py``: the loop around the program's entry
  point (``solve``: a closed loop of back-to-back solves);
* ``bench/metrics/<metric>.py``: ``read(run)`` for one per-layer metric,
  returning None where it finds nothing to read.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types
from typing import List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TRACE_S = 2.0      # the traced window of a --trace 1 solve cell, at most
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration and traffic, by name."""
    spec = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    return dict(spec=spec, cell=cell,
                config=load_json(BENCH, "configs", cell["config"] + ".json"),
                traffic=load_json(BENCH, "traffic",
                                  cell["traffic"] + ".json"))


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache`` in the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def configure_jax() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devices, used) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts backend compilations (cache misses) while ``on``."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0

        def listen(event, duration, **_):
            if self.on and event == BACKEND_COMPILE:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def start_trace():
    import jax

    d = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(d)
    return d


def stop_trace(d: str, window_s: float):
    import jax

    from lib import trace as tr

    jax.profiler.stop_trace()
    try:
        path = tr.find_xplane(d)
        return tr.load(path, window_s) if path else None
    finally:
        shutil.rmtree(d, ignore_errors=True)


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    sp = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def per_layer(spec: dict, workload: str, run) -> dict:
    """Every per-layer metric of this cell that its reader finds."""
    out = {}
    for m in spec["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def end_to_end(spec: dict, workload: str, metrics: dict) -> dict:
    """The loop's end-to-end ``metrics`` that this cell reports."""
    names = [m["name"] for m in spec["end_to_end"]
             if workload in m.get("workloads", [workload])]
    return {k: metrics[k] for k in names if k in metrics}


def setup_phases(t0: float, marks: dict) -> dict:
    """Seconds of each set-up phase, from the times marked at its end."""
    out, prev = {}, t0
    for name, t in sorted(marks.items(), key=lambda kv: kv[1]):
        out[name] = t - prev
        prev = t
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             devices, t0: float, root: str = ROOT,
             overrides: Optional[dict] = None,
             peaks: Optional[dict] = None,
             marks: Optional[dict] = None) -> dict:
    """Run ``workload`` on ``devices`` and return the result object.

    ``overrides`` replaces configuration or traffic keys and ``peaks``
    the device's row of ``peaks.json`` (tests run a cell on the CPU at a
    size it can hold).  ``marks`` holds the times at which set-up phases
    before the call ended; the loop adds its own.
    """
    kind = devices[0].device_kind
    if peaks is None:
        table = load_json(BENCH, "peaks.json")
        if kind not in table:
            raise SystemExit(f"no peaks for device kind {kind!r} in "
                             f"bench/peaks.json")
        peaks = table[kind]
    s = cell_spec(workload, root)
    cfg, traffic = dict(s["config"]), dict(s["traffic"])
    for k, v in (overrides or {}).items():
        (cfg if k in cfg else traffic)[k] = v
    chips = int(s["cell"]["chips"])
    used = devices[:chips]
    loop = importlib.import_module(f"lib.{traffic['loop']}_loop")
    ctx = types.SimpleNamespace(
        cfg=cfg, traffic=traffic, chips=chips, devices=used, seed=seed,
        seconds=float(seconds), trace=trace, t0=t0, marks=dict(marks or {}),
        peaks=peaks, compiles=CompileCounter())
    out = loop.run(ctx)
    res = {"correct": all(c["ok"] for c in out.checks),
           "attempted": out.attempted, "failed": out.failed}
    if trace:
        res["metrics"] = per_layer(s["spec"], workload, out.run)
    else:
        res["metrics"] = end_to_end(s["spec"], workload, out.metrics)
    res["device"] = out.device
    if trace and out.run.trace is not None:
        from lib import trace as tr

        t = out.run.trace
        res["device"]["busy_s"] = t.mean_busy_s()
        res["device"]["window_s"] = t.window_s
        res["breakdown"] = {"device_ops": tr.top_ops(t),
                            "idle_gaps": tr.idle_gaps(t)}
    res["setup_phases_s"] = setup_phases(t0, ctx.marks)
    res["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in out.checks}
    res["_window_compiles"] = out.window_compiles
    return res


def main(argv: List[str], t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    chips = int(cell_spec(args.workload)["cell"]["chips"])
    import jax

    configure_jax()
    devices = jax.devices()
    marks = {"jax": time.perf_counter()}
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    res = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, t0, marks=marks)
    compiles = res.pop("_window_compiles")
    print(f"bench: compilations inside the window: {compiles}",
          file=sys.stderr)
    print("bench: set-up phases (s): "
          + json.dumps(res["setup_phases_s"]), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0
