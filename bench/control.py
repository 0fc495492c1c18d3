"""The control of a cell: the computation its comparison has to reject.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...] [--sound]

Solve cells run the program's own bfloat16 storage path
(``SolverOptions(precision="bf16")``: carried vectors and bands in
bfloat16) on every right-hand side of each seed's pool; with
``--sound`` they run the cell's own fp32 solve instead, which gives the
readings of sound runs on many seeds in one process.  Each seed's
answers go through the cell's own comparison; one JSON line per seed
gives the numbers and ``correct``, which a sound control reads false.
The benchmark's runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

import numpy as np  # noqa: E402

from lib import harness, reference, solve_loop  # noqa: E402


def control_solve(ctx, sound: bool) -> dict:
    import jax

    ctx.precision = "fp32" if sound else "bf16"
    solve, bands, offsets, rhs = solve_loop.build(ctx)
    answers = []
    for j, b in enumerate(rhs):
        res = jax.block_until_ready(solve(bands, b))
        answers.append((j, np.asarray(res.x), int(res.iters)))
    op = reference.Operator(offsets, np.asarray(bands))
    b_host = {j: np.asarray(b) for j, b in enumerate(rhs)}
    checks, rows = solve_loop.judge(op, answers, b_host,
                                    ctx.traffic["limits"])
    numbers = {c["name"]: c["value"] for c in checks}
    numbers["answers"] = rows
    return numbers, all(c["ok"] for c in checks)


def run(workload: str, seed: int, devices, overrides=None,
        root: str = harness.ROOT, sound: bool = False) -> dict:
    s = harness.cell_spec(workload, root)
    cfg, tr = dict(s["config"]), dict(s["traffic"])
    for k, v in (overrides or {}).items():
        (cfg if k in cfg else tr)[k] = v
    chips = int(s["cell"]["chips"])
    ctx = types.SimpleNamespace(cfg=cfg, traffic=tr, chips=chips,
                                devices=devices[:chips], seed=seed, marks={})
    numbers, ok = control_solve(ctx, sound)
    return {"workload": workload, "seed": seed, "numbers": numbers,
            "limits": tr["limits"], "correct": bool(ok)}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description="Run a cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sound", action="store_true",
                    help="run the cell's own solve, not the control")
    args = ap.parse_args(argv)
    import jax

    harness.configure_jax()
    for seed in args.seeds:
        print(json.dumps(run(args.workload, seed, jax.devices(),
                             sound=args.sound)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
