"""Block-size autotuner for the Pallas kernels.

Two regimes, mirroring how the rest of the repo treats the CPU container:

* interpret mode (no TPU): wall time is meaningless, so candidates are
  ranked by MODELED HBM traffic — padded bytes actually moved for the
  given (n, block), with a small per-grid-step overhead term so that,
  at equal traffic, fewer/larger tiles win.
* TPU: candidates are compiled and timed (median of ``reps`` runs) via a
  caller-supplied ``probe(block) -> jittable thunk``.

Choices are cached per (kind, n, dtype, backend, min_block, n_shards,
k_rhs, and where set the storage dtype, format and band count) for the
process lifetime — the sharding degree and RHS batch
change both the local row count and how the resident operand reads
amortize, so they are part of the key.  ``save_cache`` / ``load_cache``
persist the table as JSON (``results/autotune_cache.json`` by default)
so repeated campaign/benchmark runs skip re-tuning; ``clear_cache``
exists for tests.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

DEFAULT_CANDIDATES = (256, 512, 1024, 2048, 4096, 8192)
# modeled fixed cost of one grid step, expressed in words of equivalent
# HBM traffic (DMA issue + kernel dispatch); only a tie-breaker.
STEP_OVERHEAD_WORDS = 512

# default on-disk location, relative to the CWD (benchmarks/run.py passes
# an explicit path derived from --out-dir)
DEFAULT_CACHE_PATH = os.path.join("results", "autotune_cache.json")

_CACHE: Dict[str, int] = {}
# hit/miss counters over the process lifetime — the serve layer's
# warm-reuse tests pin "second identical-shape request = pure hits"
_STATS: Dict[str, int] = {"hits": 0, "misses": 0}


def clear_cache() -> None:
    """Drop every cached block choice and reset counters (tests)."""
    _CACHE.clear()
    _STATS["hits"] = 0
    _STATS["misses"] = 0


def cache_stats() -> Dict[str, int]:
    """Copy of the lifetime ``{"hits", "misses"}`` lookup counters."""
    return dict(_STATS)


def _key(kind: str, n: int, dtype, backend: str, min_block: int,
         n_shards: int, k_rhs: int, dtype_storage=None,
         fmt: Optional[str] = None, n_bands: Optional[int] = None) -> str:
    """JSON-stable cache key: backend + full shape + dtype signature.

    ``dtype_storage`` names the carried-vector storage dtype of a mixed
    PrecisionPolicy, ``fmt`` a non-default operator format ("bsr") and
    ``n_bands`` the band count of a kernel whose footprint grows with it
    (``bands=5``); each is appended only when set, so the keys of every
    other kernel (and their persisted cache entries) are unchanged — the
    append-only convention for extending this key.
    """
    parts = [kind, n, jnp.dtype(dtype).name, backend, min_block, n_shards,
             k_rhs]
    if dtype_storage is not None:
        parts.append(jnp.dtype(dtype_storage).name)
    if fmt is not None:
        parts.append(str(fmt))
    if n_bands is not None:
        parts.append(f"bands={int(n_bands)}")
    return "|".join(str(v) for v in parts)


def load_cache(path: str = DEFAULT_CACHE_PATH) -> int:
    """Merge a persisted cache file into the in-memory table.

    Returns the number of entries loaded (0 if the file is missing or
    unreadable — tuning then proceeds from scratch).
    """
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return 0
    blocks = data.get("blocks", {})
    loaded = 0
    for key, blk in blocks.items():
        if isinstance(blk, int) and blk > 0:
            _CACHE.setdefault(key, blk)
            loaded += 1
    return loaded


def save_cache(path: str = DEFAULT_CACHE_PATH) -> str:
    """Write the in-memory table to ``path`` (creating parent dirs)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"version": 1, "blocks": _CACHE}, f, indent=2,
                  sort_keys=True)
    return path


def modeled_words(n: int, block: int, *, words_per_row: float,
                  resident_words: float = 0.0,
                  step_words: float = 0.0) -> float:
    """Modeled HBM words moved by a tiled sweep over ``n`` padded rows."""
    n_pad = -(-n // block) * block
    steps = n_pad // block
    return (n_pad * words_per_row + resident_words
            + steps * (STEP_OVERHEAD_WORDS + step_words))


def _measure(thunk: Callable[[], jax.Array], reps: int = 5) -> float:
    out = thunk()
    jax.block_until_ready(out)  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def best_block(kind: str, n: int, dtype, *,
               words_per_row: float, resident_words: float = 0.0,
               step_words: float = 0.0, min_block: int = 1,
               candidates: Sequence[int] = DEFAULT_CANDIDATES,
               probe: Optional[Callable[[int], Callable[[], jax.Array]]] = None,
               backend: Optional[str] = None,
               n_shards: int = 1, k_rhs: int = 1,
               dtype_storage=None, fmt: Optional[str] = None,
               n_bands: Optional[int] = None) -> int:
    """Pick a block size for a tiled kernel sweep.

    kind            — cache namespace (e.g. "pipecg_spmv", "spmv_dia")
    words_per_row   — tiled words moved per (padded) row, scaled to the
                      accum dtype (storage-dtype operands count their
                      itemsize ratio — see ops.py::_rel_words)
    resident_words  — words fetched once per sweep regardless of block
    step_words      — words fetched per grid step beyond its own rows
                      (halo rows re-read by windowed stencil kernels)
    min_block       — hard floor (e.g. 2*halo for stencil kernels)
    probe           — block -> thunk; required for measured (TPU) tuning
    n_shards, k_rhs — sharding degree / RHS batch of the caller; part of
                      the cache key (they change n_local and how resident
                      reads amortize) so a distributed caller never reuses
                      a single-device choice
    dtype_storage   — carried-vector storage dtype when it differs from
                      ``dtype`` (the accum dtype); part of the cache key
                      so a bf16 sweep never reuses an fp32 choice
    fmt             — operator format when not the default DIA ("bsr");
                      part of the cache key (block units and resident
                      footprints differ per format)
    n_bands         — band count where ``candidates`` were bounded by a
                      footprint that grows with it; part of the cache key
                      so a wider operator never reuses a narrower one's
                      block
    """
    backend = backend or jax.default_backend()
    # min_block is part of the key: the same (kind, n) tuned for a narrow
    # band must not hand its block to a caller with a wider halo floor
    key = _key(kind, n, dtype, backend, min_block, n_shards, k_rhs,
               dtype_storage=dtype_storage, fmt=fmt, n_bands=n_bands)
    if key in _CACHE:
        _STATS["hits"] += 1
        return _CACHE[key]
    _STATS["misses"] += 1

    feasible = sorted({min(c, n) for c in candidates if min(c, n) >= min_block})
    if not feasible:
        feasible = [max(n, min_block)]

    if backend == "tpu" and probe is not None:
        scored = [(_measure(probe(b)), b) for b in feasible]
    else:
        scored = [(modeled_words(n, b, words_per_row=words_per_row,
                                 resident_words=resident_words,
                                 step_words=step_words), b)
                  for b in feasible]
    # min score; ties resolved toward the LARGER block (fewer grid steps)
    best = min(scored, key=lambda sb: (sb[0], -sb[1]))[1]
    _CACHE[key] = best
    return best
