"""Bytes and operations of one PIPECG iteration, from shapes alone.

One single-sweep PIPECG iteration (Ghysels-Vanroose, Jacobi) on a DIA
operator with ``b`` bands reads the bands, diag^-1 and the ABFT column
checksum once, and reads and writes x, r, u, p once each:
``b + 2 + 4 + 4`` words per row (13 for the tridiagonal ex23 operator).
It is the same count whatever implements the sweep, and it leaves out
re-reads of tile halos.  Operations per row: two band products
(``s = A p``, ``w = A u``) at 2 per nonzero, the vector updates
``p = u + beta p``, ``x += alpha p``, ``r -= alpha s``,
``u -= alpha d^-1 s`` (9), and six dot products of two operands
(12) plus the checksum term ``w - c u`` (2).
"""
from __future__ import annotations


def pipecg_sweep(rows: int, bands: int, word_bytes: int = 4) -> dict:
    """``{"words", "bytes", "flops"}`` of one iteration over ``rows``."""
    words = (bands + 2 + 4 + 4) * rows
    return {"words": words, "bytes": words * word_bytes,
            "flops": (4 * bands + 9 + 12 + 2) * rows}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """Roofline least time of ``cost`` and which bound sets it."""
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = cost["flops"] / peaks["flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")
