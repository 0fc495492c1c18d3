"""Kernel benchmarks: correctness deltas vs oracle + HBM-traffic model.

interpret-mode wall time is meaningless for TPU perf, so the 'derived'
column reports the MODELED v5e time from the kernel's HBM byte count —
the quantity the fusion actually improves (see kernels/pipecg_fused.py and
kernels/pipecg_spmv_fused.py).

Traffic accounting for one PIPECG iteration (words, n = vector length,
nb = number of bands; Jacobi-preconditioned DIA operator):

  naive (engine="naive", separate XLA ops):
      8 AXPYs x 3 + 3 dots x 2              = 30 n   (update + dots)
    + M-apply (2 reads + 1 write)           =  3 n
    + SpMV (nb bands + x read + y write)    = (nb+2) n
    + ABFT aux: ww self-dot + chk(w,c,u)    =  4 n
                                     total  = (39+nb) n   -> 42 n tridiag
  pipecg_fused (update-kernel engine path):
      10 reads + 8 writes                   = 18 n
    + M-apply + SpMV as above               = (nb+5) n    -> 26 n tridiag
  pipecg_spmv_fused (single sweep, k RHS batched):
      x,r reads + x,r,u,p writes            =  6 n  per RHS
    + u,p resident reads                    =  2 n  per RHS
    + bands + diag^-1 + c=A^T 1 resident    = (nb+2) n / k
                                     total  = (8 + (nb+2)/k) n -> 13 n
                                              tridiag at k=1, 8.6 n at k=8
  bf16 storage (PrecisionPolicy(storage='bf16')): the r/u/p (resp.
  BiCGStab chain) streams and the resident operator move at 0.5
  fp32-equivalent words while x and the reduction rows stay fp32 —
  13 n -> 7.5 n for the single sweep, 19 n -> 10.5 n for p-BiCGStab.
  pipecg_spmv_halo (sharded single sweep, per shard of n_l rows):
      same (8 + nb + 2) n_l kernel traffic
    + halo operands u,p (2h x 2 sides x 2)  =  8 h          (ppermute wire)
    + psum payload (5 dots + ABFT chk)      =  6 k  words   (all-reduce)
                                     total  -> 13 n_l + O(h) <= 14 n_l
  BSR operator (blocked-ELL, deg blocks of bs x bs per block row —
  core/krylov/operator.py BsrMatrix.words_per_iter): the band sweep
  (nb+2) n becomes (2 + deg*bs + deg/bs) n — dense blocks at deg*bs
  words/row plus the int32 ELL indices at deg/bs — so the fused
  iteration is (10 + deg*bs + deg/bs) n and the sharded wire moves
  block_halo*bs elements per side.  2-D process grids swap the 1-D 8h
  wire for the surface term 4 * halo_elems(extents, widths)
  (core/perfmodel/comm.py; 2 vectors at double reach).

Emits BENCH_kernels.json next to the repo root so the perf trajectory is
tracked PR over PR.  Autotuner choices are persisted to
``results/autotune_cache.json`` (or ``--out-dir``) and loaded BEFORE any
tuning, so repeated campaign/bench runs skip the search.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.noise import Hardware
from repro.kernels import ops, ref

HW = Hardware()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(REPO_ROOT, "BENCH_kernels.json")

# the split-phase HLO check needs real collectives, i.e. >1 device — run
# it in a subprocess with forced host devices (the parent keeps 1)
_OVERLAP_SCRIPT = textwrap.dedent("""
    import os, json, functools
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp, numpy as np
    from repro.core.krylov import (tridiagonal_laplacian, laplacian_2d,
                                   dia_to_bsr, pipecg, pipebicgstab,
                                   distributed_solve)
    from repro.core.krylov.operators import DiaMatrix
    from repro.launch.hlo_analysis import split_phase_overlap
    n = 1024
    A = tridiagonal_laplacian(n, dtype=jnp.float32)
    b = jnp.ones((n,), jnp.float32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("shards",))
    out = {}
    for name, solver in (("pipecg", pipecg), ("pipebicgstab", pipebicgstab)):
        txt = jax.jit(functools.partial(
            distributed_solve, solver, A, mesh=mesh, engine="sharded_fused",
            maxiter=5)).lower(b).compile().as_text()
        out[name] = split_phase_overlap(txt)
    # BSR operator on the same 1-D shard chain
    Ab = dia_to_bsr(A, bs=4)
    txt = jax.jit(functools.partial(
        distributed_solve, pipecg, Ab, mesh=mesh, engine="sharded_fused",
        maxiter=5)).lower(b).compile().as_text()
    out["pipecg_bsr"] = split_phase_overlap(txt)
    # DIA operator on a 2-D (2, 4) process grid (gy, gx halo pairs)
    A0 = laplacian_2d(nx=32, ny=32)
    A2 = DiaMatrix(offsets=A0.offsets,
                   bands=A0.bands.at[A0.offsets.index(0)].add(1.0),
                   grid_shape=A0.grid_shape)
    b2 = jnp.ones((A2.n,), A2.bands.dtype)
    mesh2 = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(2, 4),
                              ("gy", "gx"))
    txt = jax.jit(functools.partial(
        distributed_solve, pipecg, A2, mesh=mesh2, engine="sharded_fused",
        maxiter=5)).lower(b2).compile().as_text()
    out["pipecg_2d"] = split_phase_overlap(txt)
    print(json.dumps(out))
""")

_OVERLAP_KEYS = ("pipecg", "pipebicgstab", "pipecg_bsr", "pipecg_2d")


def _hlo_overlap_flags():
    """{solver: {'overlap_ok': bool, ...}} from the 8-device subprocess.

    The probe forces 8 CPU host devices, so it runs on the CPU whatever
    the parent's backend is; a failed probe raises (no bench row may
    record an overlap verdict that was never computed).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO_ROOT, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _OVERLAP_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    if out.returncode != 0:
        raise RuntimeError("split-phase HLO overlap probe failed:\n"
                           + out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def _words_naive_iter(n, nb):
    return (39 + nb) * n


def _words_update_kernel_iter(n, nb):
    return (23 + nb) * n


def _words_single_sweep_iter(n, nb, k=1):
    return (8 + (nb + 2) / k) * n


def _modeled_us(words, dtype_bytes=4):
    return words * dtype_bytes / HW.hbm_bw * 1e6


def _words_sharded_iter(n_local, nb, halo, k=1):
    """Per-shard words of one sharded single-sweep iteration: the kernel
    sweep + the ppermute'd halo operands + the psum payload."""
    return ((8 + (nb + 2) / k) * n_local   # kernel sweep (per RHS)
            + 8 * halo                     # u/p halos, 2h x 2 sides x 2 vecs
            + 6)                           # partial row + ABFT chk (psum)


def _words_bsr_spmv(n, bs, deg):
    """BSR SpMV words/row: x read + y write + deg dense (bs, bs) blocks
    (deg*bs words/row) + the int32 ELL indices (deg/bs words/row)."""
    return (2.0 + deg * bs + deg / bs) * n


def _words_bsr_fused_iter(n, bs, deg):
    """Fused BSR PIPECG iteration — BsrMatrix.words_per_iter * n."""
    return (10.0 + deg * bs + deg / bs) * n


def _words_bsr_naive_iter(n, bs, deg):
    """Separate-ops BSR PIPECG: the (39+nb) n DIA accounting with the
    band sweep replaced by the blocked-ELL SpMV traffic."""
    return (37.0 + 2.0 + deg * bs + deg / bs) * n


def _words_bsr_sharded_iter(n_local, bs, deg, block_halo):
    """Per-shard fused BSR sweep + u/p block halos + Gram psum: the wire
    moves block_halo*bs elements per side at double reach x 2 vectors."""
    return ((10.0 + deg * bs + deg / bs) * n_local
            + 8 * block_halo * bs          # u/p halos, 2h x 2 sides x 2 vecs
            + 6)                           # partial row + ABFT chk (psum)


def _words_2d_sharded_iter(n_local, nb, halo_el):
    """Per-shard 2-D-grid sweep + the surface-law halo wire + Gram psum:
    ``halo_el = comm.halo_elems(extents, widths)`` already sums both
    sides of every decomposed axis, so u/p at double reach cost
    ``4 * halo_el`` wire words."""
    return ((8 + (nb + 2)) * n_local       # kernel sweep (k=1)
            + 4 * halo_el                  # u/p halos, 2 vecs x double reach
            + 6)                           # partial row + ABFT chk (psum)


def _words_single_sweep_policy_iter(n, nb, k=1, sw=1.0):
    """Policy-scaled single-sweep words: x read/write stays at accum
    (2 words/row), the r/u/p streams (6) and the resident operator
    (nb+2 per k RHS) move at ``sw`` fp32-equivalent words per element
    (PrecisionPolicy.storage_words; 0.5 for bf16)."""
    return (2.0 + 6.0 * sw + sw * (nb + 2) / k) * n


def _words_pipebicgstab_policy_iter(n, nb, sw=1.0):
    """Policy-scaled fused p-BiCGStab words: x at accum (2), the 13
    carried-chain streams and the (nb+1) resident operator at ``sw``."""
    return (2.0 + 13.0 * sw + sw * (nb + 1)) * n


def _words_bicgstab_naive_iter(n, nb):
    """Classical BiCGStab as separate XLA ops (words/iteration):
    2 SpMVs (nb+2 each) + 4 vector updates (p:4, s:3, x:4, r:3)
    + 5 dots x 2."""
    return (2 * (nb + 2) + 14 + 10) * n


def _words_pipebicgstab_iter(n, nb):
    """Fused p-BiCGStab sweep: x,r,pa,a,r_hat tiled reads + 7 writes
    + w,t,c + bands + ABFT column-sum vector resident
    (kernels/pipebicgstab_fused.py)."""
    return (16 + nb) * n


def _words_pipebicgstab_sharded_iter(n_local, nb, halo):
    """Per-shard fused p-BiCGStab sweep + w/t/c halos + Gram psum."""
    return ((16 + nb) * n_local
            + 12 * halo                    # w/t/c halos, 2h x 2 sides x 3
            + 42)                          # (7, 6) Gram + chk row (psum)


def run(out_dir=None):
    from repro.kernels import autotune

    json_path = (JSON_PATH if out_dir is None
                 else os.path.join(out_dir, "BENCH_kernels.json"))
    cache_path = os.path.join(out_dir or os.path.join(REPO_ROOT, "results"),
                              "autotune_cache.json")
    # load-before-tune: repeated runs reuse persisted block choices
    cache_hits = autotune.load_cache(cache_path)
    rows = []
    record = {"hw": {"hbm_bw_Bps": HW.hbm_bw}, "kernels": {}}
    rng = np.random.default_rng(0)
    n = 1 << 16

    # spmv_dia
    offsets = (-1, 0, 1)
    bands = jnp.asarray(rng.standard_normal((3, n)), jnp.float32)
    x_ext = jnp.asarray(rng.standard_normal(n + 2), jnp.float32)
    got = ops.spmv_dia_ext(offsets, bands, x_ext, 1)
    err = float(jnp.max(jnp.abs(got - ref.spmv_dia_ref(offsets, bands, x_ext, 1))))
    bytes_moved = (3 * n + n + n) * 4  # bands + x + y
    rows.append(("kernel/spmv_dia/n65536", bytes_moved / HW.hbm_bw * 1e6,
                 f"err={err:.1e} modeled_us_v5e={bytes_moved/HW.hbm_bw*1e6:.2f}"))
    record["kernels"]["spmv_dia"] = {"n": n, "err": err,
                                     "words_per_row": 5.0,
                                     "modeled_us_v5e": bytes_moved / HW.hbm_bw * 1e6}

    # fused_dots (m=32)
    V = jnp.asarray(rng.standard_normal((32, n)), jnp.float32)
    z = jnp.asarray(rng.standard_normal(n), jnp.float32)
    err = float(jnp.max(jnp.abs(ops.fused_dots(V, z) - ref.fused_dots_ref(V, z))))
    fused_bytes = (32 * n + n) * 4
    mgs_bytes = 32 * (n + n) * 4  # re-reading z per row
    rows.append(("kernel/fused_dots/m32", fused_bytes / HW.hbm_bw * 1e6,
                 f"err={err:.1e} vs_mgs_sweeps={mgs_bytes/fused_bytes:.2f}x"))
    record["kernels"]["fused_dots"] = {"n": n, "m": 32, "err": err,
                                       "traffic_vs_mgs": mgs_bytes / fused_bytes}

    # pipecg_fused (update-only fusion)
    vs = [jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in range(10)]
    got = ops.pipecg_fused_step(*vs, 0.3, 0.1)
    want = ref.pipecg_fused_ref(*vs, 0.3, 0.1)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float64) - b.astype(jnp.float64))))
              for a, b in zip(got, want))
    fused_bytes = (10 + 8) * n * 4
    naive_bytes = (8 * 3 + 3 * 2) * n * 4  # 8 AXPYs + 3 dots, unfused
    rows.append(("kernel/pipecg_fused", fused_bytes / HW.hbm_bw * 1e6,
                 f"err={err:.1e} traffic_reduction={naive_bytes/fused_bytes:.2f}x"))
    record["kernels"]["pipecg_fused"] = {"n": n, "err": err,
                                         "traffic_vs_naive": naive_bytes / fused_bytes}

    # pipecg_spmv_fused (single sweep, whole preconditioned iteration)
    nb = 3
    bands_np = rng.standard_normal((nb, n))
    bands_np[0, 0] = 0.0
    bands_np[2, -1] = 0.0
    bands_f = jnp.asarray(bands_np, jnp.float32)
    inv_d = jnp.asarray(1.0 / (1.0 + np.abs(rng.standard_normal(n))), jnp.float32)
    for k_rhs in (1, 8):
        xs = [jnp.asarray(rng.standard_normal((k_rhs, n)), jnp.float32)
              for _ in range(4)]
        al = jnp.asarray(rng.standard_normal(k_rhs), jnp.float32)
        be = jnp.asarray(rng.standard_normal(k_rhs), jnp.float32)
        got = ops.pipecg_spmv_fused_step(offsets, bands_f, inv_d, *xs, al, be)
        want = ref.pipecg_spmv_fused_ref(offsets, bands_f, inv_d, *xs, al, be)
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                        - b.astype(jnp.float64))))
                  for a, b in zip(got, want))
        w_naive = _words_naive_iter(n, nb)
        w_fused = _words_single_sweep_iter(n, nb, k_rhs)
        us = _modeled_us(w_fused)
        rows.append((f"kernel/pipecg_spmv_fused/k{k_rhs}", us,
                     f"err={err:.1e} words_per_iter={w_fused/n:.1f}n "
                     f"naive={w_naive/n:.0f}n "
                     f"modeled_speedup={w_naive/w_fused:.2f}x"))
        record["kernels"][f"pipecg_spmv_fused_k{k_rhs}"] = {
            "n": n, "k_rhs": k_rhs, "err": err,
            "dtype_storage": "fp32", "dtype_accum": "fp32",
            "words_per_iter_over_n": w_fused / n,
            "naive_words_over_n": w_naive / n,
            "update_kernel_words_over_n": _words_update_kernel_iter(n, nb) / n,
            "modeled_speedup_vs_naive": w_naive / w_fused,
            "modeled_us_v5e": us,
        }

    # mixed-precision storage row: the same single-sweep kernel with the
    # carried r/u/p vectors and the resident operator at bf16 (x and the
    # reduction row stay fp32 — PrecisionPolicy accum).  Arithmetic
    # up-casts every load, so vs the fp32 oracle on the SAME
    # bf16-rounded inputs only the bf16 write-back rounding remains.
    bf16 = jnp.bfloat16
    xs1 = [jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
           for _ in range(4)]
    al1 = jnp.asarray(rng.standard_normal(1), jnp.float32)
    be1 = jnp.asarray(rng.standard_normal(1), jnp.float32)
    stored = [xs1[0]] + [v.astype(bf16) for v in xs1[1:]]
    bands16, invd16 = bands_f.astype(bf16), inv_d.astype(bf16)
    got = ops.pipecg_spmv_fused_step(offsets, bands16, invd16, *stored,
                                     al1, be1)
    want = ref.pipecg_spmv_fused_ref(
        offsets, bands16.astype(jnp.float32), invd16.astype(jnp.float32),
        *(v.astype(jnp.float32) for v in stored), al1, be1)
    err16 = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                      - b.astype(jnp.float64))))
                for a, b in zip(got, want))
    eps16 = 2.0 ** -8
    w_fused16 = _words_single_sweep_policy_iter(n, nb, 1, sw=0.5)
    w_fused32 = _words_single_sweep_iter(n, nb, 1)
    us = _modeled_us(w_fused16)
    rows.append(("kernel/pipecg_spmv_fused/k1_bf16", us,
                 f"err={err16:.1e} words_per_iter={w_fused16/n:.1f}n "
                 f"fp32={w_fused32/n:.1f}n "
                 f"modeled_speedup_vs_fp32={w_fused32/w_fused16:.2f}x"))
    record["kernels"]["pipecg_spmv_fused_k1_bf16"] = {
        "n": n, "k_rhs": 1, "err": err16,
        "err_over_eps_storage": err16 / eps16,
        "dtype_storage": "bf16", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_fused16 / n,
        "fp32_words_over_n": w_fused32 / n,
        "modeled_speedup_vs_fp32": w_fused32 / w_fused16,
        "modeled_us_v5e": us,
    }

    # pipecg_sharded_fused (halo-aware single sweep + split-phase psum):
    # correctness of the per-shard halo kernel against the full-vector
    # sweep (hand-built neighbor halos), per-shard traffic, and the
    # HLO-verified overlap flag from an 8-device subprocess
    S = 4
    n_local = n // S
    halo = 1
    invd_ones = jnp.ones((n,), jnp.float32)
    xs = [jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
          for _ in range(4)]
    al = jnp.asarray(rng.standard_normal(1), jnp.float32)
    be = jnp.asarray(rng.standard_normal(1), jnp.float32)
    want = ops.pipecg_spmv_fused_step(offsets, bands_f, invd_ones, *xs, al, be)
    bands_g = jnp.pad(bands_f, ((0, 0), (halo, halo)))
    invd_g = jnp.pad(invd_ones, (halo, halo))
    u_g = jnp.pad(xs[2], ((0, 0), (2 * halo, 2 * halo)))
    p_g = jnp.pad(xs[3], ((0, 0), (2 * halo, 2 * halo)))
    pieces, red_sum = [], 0.0
    for s in range(S):
        lo = s * n_local
        local = [v[:, lo:lo + n_local] for v in xs]
        blk, op = ops.pipecg_halo_operator(
            offsets, bands_g[:, lo:lo + n_local + 2 * halo],
            invd_g[lo:lo + n_local + 2 * halo], local[0], local[2],
            n_shards=S)
        piece = ops.pipecg_spmv_halo_step(
            offsets, op, *local,
            u_g[:, lo:lo + 2 * halo],
            u_g[:, lo + n_local + 2 * halo:lo + n_local + 4 * halo],
            p_g[:, lo:lo + 2 * halo],
            p_g[:, lo + n_local + 2 * halo:lo + n_local + 4 * halo],
            al, be, block=blk)
        pieces.append(piece[:4])
        red_sum = red_sum + piece[4]
    got_cat = [jnp.concatenate([p_[i] for p_ in pieces], axis=-1)
               for i in range(4)] + [red_sum]
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                    - b.astype(jnp.float64))))
              for a, b in zip(got_cat, want))
    overlaps = _hlo_overlap_flags()
    overlap = overlaps.get("pipecg", {})
    w_naive = _words_naive_iter(n_local, nb)
    w_shard = _words_sharded_iter(n_local, nb, halo)
    us = _modeled_us(w_shard)
    rows.append((f"kernel/pipecg_sharded_fused/S{S}", us,
                 f"err={err:.1e} words_per_iter_per_shard={w_shard/n_local:.2f}n "
                 f"naive={w_naive/n_local:.0f}n "
                 f"hlo_overlap={bool(overlap.get('overlap_ok'))}"))
    record["kernels"]["pipecg_sharded_fused"] = {
        "n_local": n_local, "n_shards": S, "err": err,
        "dtype_storage": "fp32", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_shard / n_local,
        "naive_words_over_n": w_naive / n_local,
        "modeled_speedup_vs_naive": w_naive / w_shard,
        "modeled_us_v5e": us,
        "hlo_split_phase_overlap": bool(overlap.get("overlap_ok")),
        "hlo_bodies": overlap.get("bodies", {}),
    }

    # pipebicgstab_fused (single sweep: whole pipelined BiCGStab iteration
    # = 9 updates + both SpMVs + the (6, 6) Gram partials in one pass)
    bvecs = [jnp.asarray(rng.standard_normal(n), jnp.float32)
             for _ in range(8)]
    al_b, be_b, om_b = 0.37, 0.21, -0.45
    got = ops.pipebicgstab_fused_step(offsets, bands_f, *bvecs,
                                      al_b, be_b, om_b)
    want = ref.pipebicgstab_fused_ref(offsets, bands_f, *bvecs,
                                      al_b, be_b, om_b)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                    - b.astype(jnp.float64))))
              for a, b in zip(got, want))
    w_naive_b = _words_bicgstab_naive_iter(n, nb)
    w_fused_b = _words_pipebicgstab_iter(n, nb)
    us = _modeled_us(w_fused_b)
    rows.append(("kernel/pipebicgstab_fused", us,
                 f"err={err:.1e} words_per_iter={w_fused_b/n:.1f}n "
                 f"naive={w_naive_b/n:.0f}n "
                 f"modeled_speedup={w_naive_b/w_fused_b:.2f}x"))
    record["kernels"]["pipebicgstab_fused"] = {
        "n": n, "err": err,
        "dtype_storage": "fp32", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_fused_b / n,
        "naive_words_over_n": w_naive_b / n,
        "modeled_speedup_vs_naive": w_naive_b / w_fused_b,
        "modeled_us_v5e": us,
    }

    # bf16-storage p-BiCGStab sweep: the carried chains and operator at
    # bf16, x and the (7, 6) Gram partials at fp32
    stored_b = [bvecs[0]] + [v.astype(bf16) for v in bvecs[1:]]
    got = ops.pipebicgstab_fused_step(offsets, bands16, *stored_b,
                                      al_b, be_b, om_b)
    want = ref.pipebicgstab_fused_ref(
        offsets, bands16.astype(jnp.float32),
        *(v.astype(jnp.float32) for v in stored_b), al_b, be_b, om_b)
    err16 = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                      - b.astype(jnp.float64))))
                for a, b in zip(got, want))
    w_fused_b16 = _words_pipebicgstab_policy_iter(n, nb, sw=0.5)
    us = _modeled_us(w_fused_b16)
    rows.append(("kernel/pipebicgstab_fused/bf16", us,
                 f"err={err16:.1e} words_per_iter={w_fused_b16/n:.1f}n "
                 f"fp32={w_fused_b/n:.1f}n "
                 f"modeled_speedup_vs_fp32={w_fused_b/w_fused_b16:.2f}x"))
    record["kernels"]["pipebicgstab_fused_bf16"] = {
        "n": n, "err": err16,
        "err_over_eps_storage": err16 / eps16,
        "dtype_storage": "bf16", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_fused_b16 / n,
        "fp32_words_over_n": w_fused_b / n,
        "modeled_speedup_vs_fp32": w_fused_b / w_fused_b16,
        "modeled_us_v5e": us,
    }

    # pipebicgstab_sharded_fused: per-chunk halo kernel vs the full-vector
    # sweep (hand-built neighbor halos) + the HLO overlap flag (ONE Gram
    # all-reduce per while body hiding all four classical sync points)
    x_b, r_b, w_b, t_b, pa_b, a_b, c_b, rh_b = bvecs
    want = ops.pipebicgstab_fused_step(offsets, bands_f, *bvecs,
                                       al_b, be_b, om_b)
    w_g = jnp.pad(w_b, (2 * halo, 2 * halo))
    t_g = jnp.pad(t_b, (2 * halo, 2 * halo))
    c_g = jnp.pad(c_b, (2 * halo, 2 * halo))
    pieces, gram_sum = [], 0.0
    for s in range(S):
        lo = s * n_local
        piece = ops.pipebicgstab_halo_step(
            offsets, bands_g[:, lo:lo + n_local + 2 * halo],
            *(v[lo:lo + n_local] for v in (x_b, r_b, w_b, t_b, pa_b, a_b,
                                           c_b, rh_b)),
            w_g[lo:lo + 2 * halo],
            w_g[lo + n_local + 2 * halo:lo + n_local + 4 * halo],
            t_g[lo:lo + 2 * halo],
            t_g[lo + n_local + 2 * halo:lo + n_local + 4 * halo],
            c_g[lo:lo + 2 * halo],
            c_g[lo + n_local + 2 * halo:lo + n_local + 4 * halo],
            al_b, be_b, om_b, n_shards=S)
        pieces.append(piece[:7])
        gram_sum = gram_sum + piece[7]
    got_cat = [jnp.concatenate([p_[i] for p_ in pieces])
               for i in range(7)] + [gram_sum]
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                    - b.astype(jnp.float64))))
              for a, b in zip(got_cat, want))
    overlap_b = overlaps.get("pipebicgstab", {})
    w_naive_b = _words_bicgstab_naive_iter(n_local, nb)
    w_shard_b = _words_pipebicgstab_sharded_iter(n_local, nb, halo)
    us = _modeled_us(w_shard_b)
    rows.append((f"kernel/pipebicgstab_sharded_fused/S{S}", us,
                 f"err={err:.1e} "
                 f"words_per_iter_per_shard={w_shard_b/n_local:.2f}n "
                 f"naive={w_naive_b/n_local:.0f}n "
                 f"hlo_overlap={bool(overlap_b.get('overlap_ok'))}"))
    bodies_b = overlap_b.get("bodies", {})
    record["kernels"]["pipebicgstab_sharded_fused"] = {
        "n_local": n_local, "n_shards": S, "err": err,
        "dtype_storage": "fp32", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_shard_b / n_local,
        "naive_words_over_n": w_naive_b / n_local,
        "modeled_speedup_vs_naive": w_naive_b / w_shard_b,
        "modeled_us_v5e": us,
        "hlo_split_phase_overlap": bool(overlap_b.get("overlap_ok")),
        # the four classical sync points travel as ONE fused Gram psum
        "reductions_per_iter": 1.0,
        "classical_syncs_per_iter": 4.0,
        "hlo_all_reduce_per_body": (
            max(v.get("all_reduce", 0) for v in bodies_b.values())
            if bodies_b else None),
        "hlo_bodies": bodies_b,
    }

    # BSR operator lane (PR 10): the blocked-ELL kernels behind the
    # SparseOperator layer, on the lossless DIA->BSR rendering of the
    # same tridiagonal test operator (block reach 1 -> deg=3 at bs=4)
    from repro.core.krylov import dia_to_bsr
    from repro.core.krylov.operators import DiaMatrix

    bs_b = 4
    Absr = dia_to_bsr(DiaMatrix(offsets=offsets, bands=bands_f), bs=bs_b)
    deg = Absr.max_deg

    # spmv_bsr: gather + batched block-GEMV kernel vs the jnp oracle
    x_v = jnp.asarray(rng.standard_normal(n), jnp.float32)
    got = ops.spmv_bsr(Absr.indices, Absr.blocks, x_v)
    err = float(jnp.max(jnp.abs(
        got.astype(jnp.float64)
        - ref.spmv_bsr_ref(Absr.indices, Absr.blocks,
                           x_v).astype(jnp.float64))))
    w_spmv_b = _words_bsr_spmv(n, bs_b, deg)
    us = _modeled_us(w_spmv_b)
    rows.append((f"kernel/spmv_bsr/bs{bs_b}", us,
                 f"err={err:.1e} deg={deg} "
                 f"words_per_row={w_spmv_b/n:.2f} "
                 f"modeled_us_v5e={us:.2f}"))
    record["kernels"]["spmv_bsr"] = {
        "n": n, "bs": bs_b, "deg": deg, "err": err,
        "words_per_row": w_spmv_b / n,
        "modeled_us_v5e": us,
    }

    # pipecg_bsr_fused: whole preconditioned iteration on the BSR
    # operator in one sweep (words/iter = BsrMatrix.words_per_iter —
    # the measured value the README format table quotes)
    xs_b = [jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
            for _ in range(4)]
    al1b = jnp.asarray(rng.standard_normal(1), jnp.float32)
    be1b = jnp.asarray(rng.standard_normal(1), jnp.float32)
    got = ops.pipecg_bsr_fused_step(Absr.indices, Absr.blocks, inv_d,
                                    *xs_b, al1b, be1b)
    want = ref.pipecg_bsr_fused_ref(Absr.indices, Absr.blocks, inv_d,
                                    *xs_b, al1b, be1b)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float64)
                                    - b.astype(jnp.float64))))
              for a, b in zip(got, want))
    w_bsr = _words_bsr_fused_iter(n, bs_b, deg)
    w_bsr_naive = _words_bsr_naive_iter(n, bs_b, deg)
    assert abs(w_bsr / n - Absr.words_per_iter()) < 1e-12
    us = _modeled_us(w_bsr)
    rows.append((f"kernel/pipecg_bsr_fused/bs{bs_b}", us,
                 f"err={err:.1e} words_per_iter={w_bsr/n:.2f}n "
                 f"naive={w_bsr_naive/n:.2f}n "
                 f"modeled_speedup={w_bsr_naive/w_bsr:.2f}x"))
    record["kernels"]["pipecg_bsr_fused"] = {
        "n": n, "bs": bs_b, "deg": deg, "err": err,
        "dtype_storage": "fp32", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_bsr / n,
        "naive_words_over_n": w_bsr_naive / n,
        "modeled_speedup_vs_naive": w_bsr_naive / w_bsr,
        "modeled_us_v5e": us,
    }

    # pipecg_bsr_sharded: the BSR operator through the sharded engine —
    # per-shard traffic model + the HLO overlap/collective counts from
    # the 8-device subprocess probe (correctness is pinned at 1e-10 by
    # tests/test_engine_equivalence.py)
    overlap_bsr = overlaps.get("pipecg_bsr", {})
    bodies_bsr = overlap_bsr.get("bodies", {})
    w_bsr_sh = _words_bsr_sharded_iter(n_local, bs_b, deg, Absr.block_halo)
    us = _modeled_us(w_bsr_sh)
    rows.append((f"kernel/pipecg_bsr_sharded/S{S}", us,
                 f"words_per_iter_per_shard={w_bsr_sh/n_local:.2f}n "
                 f"naive={_words_bsr_naive_iter(n_local, bs_b, deg)/n_local:.0f}n "
                 f"hlo_overlap={bool(overlap_bsr.get('overlap_ok'))}"))
    record["kernels"]["pipecg_bsr_sharded"] = {
        "n_local": n_local, "n_shards": S, "bs": bs_b, "deg": deg,
        "dtype_storage": "fp32", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_bsr_sh / n_local,
        "naive_words_over_n": _words_bsr_naive_iter(n_local, bs_b,
                                                    deg) / n_local,
        "modeled_speedup_vs_naive": (
            _words_bsr_naive_iter(n_local, bs_b, deg) / w_bsr_sh),
        "modeled_us_v5e": us,
        "hlo_split_phase_overlap": bool(overlap_bsr.get("overlap_ok")),
        "hlo_all_reduce_per_body": (
            max(v.get("all_reduce", 0) for v in bodies_bsr.values())
            if bodies_bsr else None),
        "hlo_bodies": bodies_bsr,
    }

    # pipecg_2d_sharded: the DIA operator on a (2, 4) process grid — the
    # surface-to-volume wire model (core/perfmodel/comm.py) + the HLO
    # counts of the 2-axis mesh body (8 ppermutes: 2 vectors x 2
    # messages per decomposed axis x 2 axes)
    from repro.core.perfmodel import comm

    grid_2d = (2, 4)
    pts_2d = (32, 32)
    ext_2d = comm.local_extents(pts_2d, grid_2d)
    halo_el = comm.halo_elems(ext_2d, (1, 1))
    n_loc2 = ext_2d[0] * ext_2d[1]
    nb_2d = 5  # 5-point Laplacian bands
    overlap_2d = overlaps.get("pipecg_2d", {})
    bodies_2d = overlap_2d.get("bodies", {})
    w_2d = _words_2d_sharded_iter(n_loc2, nb_2d, halo_el)
    w_2d_naive = _words_naive_iter(n_loc2, nb_2d)
    us = _modeled_us(w_2d)
    rows.append((f"kernel/pipecg_2d_sharded/{grid_2d[0]}x{grid_2d[1]}", us,
                 f"words_per_iter_per_shard={w_2d/n_loc2:.2f}n "
                 f"surface_to_volume={comm.surface_to_volume(ext_2d, (1, 1)):.3f} "
                 f"hlo_overlap={bool(overlap_2d.get('overlap_ok'))}"))
    record["kernels"]["pipecg_2d_sharded"] = {
        "grid": list(grid_2d), "points": list(pts_2d),
        "n_local": n_loc2, "halo_elems": halo_el,
        "surface_to_volume": comm.surface_to_volume(ext_2d, (1, 1)),
        "dtype_storage": "fp32", "dtype_accum": "fp32",
        "words_per_iter_over_n": w_2d / n_loc2,
        "naive_words_over_n": w_2d_naive / n_loc2,
        "modeled_speedup_vs_naive": w_2d_naive / w_2d,
        "modeled_us_v5e": us,
        "hlo_split_phase_overlap": bool(overlap_2d.get("overlap_ok")),
        "hlo_all_reduce_per_body": (
            max(v.get("all_reduce", 0) for v in bodies_2d.values())
            if bodies_2d else None),
        "hlo_bodies": bodies_2d,
    }

    # ghost_chain (depth-l blocks): chain + Gram vs the jnp oracle, and
    # the per-iteration traffic of the depth-l path (2l+1 chain writes +
    # p,r + bands resident reads per l iterations, plus the (2l+7)n
    # block-end reconstruction)
    from repro.core.krylov import pipecg_l, tridiagonal_laplacian

    theta = 4.0
    p_v = jnp.asarray(rng.standard_normal(n), jnp.float32)
    r_v = jnp.asarray(rng.standard_normal(n), jnp.float32)

    def _oracle_chain(v0, depth):
        links = [v0]
        for _ in range(depth):
            y = jnp.zeros_like(v0)
            xe = jnp.pad(links[-1], (1, 1))
            for k, off in enumerate(offsets):
                y = y + bands_f[k] * jax.lax.dynamic_slice_in_dim(
                    xe, 1 + off, n)
            links.append(y / theta)
        return links

    for l_depth in (2, 4):
        chain, gram = ops.ghost_chain_step(offsets, bands_f, p_v, r_v,
                                           theta, l_depth)
        want_c = jnp.stack(_oracle_chain(p_v, l_depth)
                           + _oracle_chain(r_v, l_depth - 1))
        err = float(jnp.max(jnp.abs(chain.astype(jnp.float64)
                                    - want_c.astype(jnp.float64))))
        err_g = float(jnp.max(jnp.abs(
            gram.astype(jnp.float64)
            - (want_c @ want_c.T).astype(jnp.float64))))
        # per-iteration words: kernel sweep + block-end reconstruction
        # + the once-per-block ABFT state-deviation partial
        # 1^T b - c^T x - 1^T r (csum, x, r reads — distributed.py)
        w_sweep = (2 * l_depth + 3 + nb) * n
        w_recon = (2 * l_depth + 7) * n
        w_dev = 3 * n
        w_iter = (w_sweep + w_recon + w_dev) / l_depth
        w_d1 = _words_single_sweep_iter(n, nb)
        us = _modeled_us(w_iter)
        rows.append((f"kernel/ghost_chain/l{l_depth}", us,
                     f"err={err:.1e} err_gram={err_g:.1e} "
                     f"words_per_iter={w_iter/n:.1f}n "
                     f"depth1={w_d1/n:.1f}n "
                     f"reductions_per_iter=1/{l_depth}"))
        record["kernels"][f"ghost_chain_l{l_depth}"] = {
            "n": n, "l": l_depth, "err": err, "err_gram": err_g,
            "words_per_iter_over_n": w_iter / n,
            "depth1_words_over_n": w_d1 / n,
            "naive_words_over_n": _words_naive_iter(n, nb) / n,
            "modeled_speedup_vs_depth1": w_d1 / w_iter,
            "reductions_per_iter": 1.0 / l_depth,
            "modeled_us_v5e": us,
        }

    # depth-l solver sanity inside the bench: l=2 tracks the depth-1
    # trajectory on the ex23 operator (fp32 gate; tests pin fp64)
    A23 = tridiagonal_laplacian(1024, dtype=jnp.float32)
    b23 = jnp.ones((1024,), jnp.float32)
    h1 = pipecg_l(A23, b23, l=1, maxiter=30).res_history
    h2 = pipecg_l(A23, b23, l=2, maxiter=30).res_history
    depth_dev = float(jnp.max(jnp.abs(h1 - h2) / jnp.maximum(h1, 1e-6)))
    record["kernels"]["pipecg_l_depth2_vs_depth1_rel_dev"] = depth_dev

    # block-size autotuner: choice + cache behavior (+ on-disk persistence)
    blk = autotune.best_block("pipecg_spmv", n, jnp.float32,
                              words_per_row=6.0, resident_words=6.0 * n,
                              min_block=2)
    t0 = time.perf_counter()
    autotune.best_block("pipecg_spmv", n, jnp.float32,
                        words_per_row=6.0, resident_words=6.0 * n, min_block=2)
    cached_us = (time.perf_counter() - t0) * 1e6
    autotune.save_cache(cache_path)
    rows.append(("kernel/autotune/pipecg_spmv", cached_us,
                 f"block={blk} backend={jax.default_backend()} "
                 f"cache_preloaded={cache_hits} "
                 f"persisted={os.path.basename(cache_path)}"))
    record["autotune"] = {"block": blk, "backend": jax.default_backend(),
                          # basename only: the committed record must not
                          # churn with each machine's absolute paths
                          "cache_file": os.path.basename(cache_path),
                          "cache_entries_preloaded": cache_hits}

    os.makedirs(os.path.dirname(json_path) or ".", exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    rows.append(("kernel/json", float("nan"), f"wrote {os.path.basename(json_path)}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(*r, sep=",")
