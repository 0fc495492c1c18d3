"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

Reads the file with ``jax.profiler.ProfileData``.  Each TPU's plane is
``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per HLO
operation the chip ran, named by the instruction's HLO text
(``%pipecg_sweep_step.6 = (f32[...]) custom-call(...)``); ``op_name``
keeps the instruction's name.  The sweep kernel is
``pipecg_sweep_step.<k>`` on one chip and ``pipecg_spmv_halo_step.<k>``
inside the sharded driver; collectives are ``all-reduce.<k>`` and
``collective-permute-start.<k>`` / ``-done.<k>``.  The ``Async XLA
Ops`` line (DMA spans) is not read.  The host's Python thread is the
``python`` line of ``/host:CPU``.  Times are nanoseconds on one clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"^(all-reduce|collective-permute|all-gather|"
                        r"reduce-scatter|all-to-all)")

# control-flow ops whose events span the ops of their bodies
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")

Interval = Tuple[float, float]


class Trace:
    """Device op events per chip and the host's Python spans."""

    def __init__(self, ops: Dict[int, List[Tuple[str, float, float]]],
                 host: List[Tuple[str, float, float]], window_s: float):
        self.ops = ops            # chip -> [(name, start_ns, end_ns)]
        self.host = host          # [(name, start_ns, end_ns)]
        self.window_s = window_s  # host-clock length of the traced window

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def events(self, chip: int, pattern: Optional[re.Pattern] = None):
        """``(name, start, end)`` of ``chip``'s ops matching ``pattern``."""
        return [e for e in self.ops[chip]
                if pattern is None or pattern.match(e[0])]

    def busy_s(self, chip: int) -> float:
        """Seconds in which any op ran on ``chip`` (union of intervals)."""
        return measure(union([(s, e) for _, s, e in self.ops[chip]])) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(c) for c in self.chips) / len(self.chips)


def union(iv: List[Interval]) -> List[Interval]:
    """Sorted disjoint union of closed intervals."""
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(iv: List[Interval]) -> float:
    return float(sum(e - s for s, e in iv))


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_name(hlo: str) -> str:
    """``"%fusion.3 = f32[...] fusion(...)"`` -> ``"fusion.3"``."""
    return hlo[1:].split(" = ", 1)[0] if hlo.startswith("%") else hlo


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str, window_s: float) -> Optional[Trace]:
    """The device ops and host spans of ``path``; None without a TPU."""
    from jax.profiler import ProfileData

    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (op_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif plane.name == "/host:CPU" and line.name == "python":
                host.extend((ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events)
    ops = {c: sorted(v, key=lambda e: e[1]) for c, v in ops.items() if v}
    if not ops:
        return None
    return Trace(ops, host, window_s)


def exposed_collective_ns(trace: Trace, chip: int) -> float:
    """Collective op time on ``chip`` during which no other op runs
    (a loop's own event, which spans its body, does not count as one)."""
    coll = union([(s, e) for n, s, e in trace.ops[chip]
                  if COLLECTIVE.match(n)])
    comp = union([(s, e) for n, s, e in trace.ops[chip]
                  if not (COLLECTIVE.match(n) or CONTAINER.match(n))])
    return measure(coll) - measure(intersect(coll, comp))


def mean_call_us(trace: Trace, pattern: re.Pattern) -> Optional[float]:
    """Mean device time (us) of the ops matching ``pattern``, all chips."""
    ev = [e for c in trace.chips for e in trace.events(c, pattern)]
    if not ev:
        return None
    return sum(e - s for _, s, e in ev) / len(ev) * 1e-3


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` op names with the most device seconds (mean over chips),
    leaving out the loops that contain other ops."""
    tot: Dict[str, float] = {}
    for c in trace.chips:
        for n, s, e in trace.ops[c]:
            if CONTAINER.match(n):
                continue
            tot[n] = tot.get(n, 0.0) + (e - s) * 1e-9 / len(trace.chips)
    return [[n, v] for n, v in sorted(tot.items(), key=lambda t: -t[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps of the first chip, each named by the
    host span that overlaps it most (the shortest such span on a tie)."""
    busy = union([(s, e) for _, s, e in trace.ops[trace.chips[0]]])
    gaps = sorted(((busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)),
                  key=lambda g: g[0] - g[1])[:k]
    out = []
    for gs, ge in gaps:
        best, key = "(no host span)", None
        for n, s, e in trace.host:
            ov = min(e, ge) - max(s, gs)
            if ov > 0 and (key is None or (ov, s - e) > key):
                best, key = n, (ov, s - e)
        out.append([best, (ge - gs) * 1e-9])
    return out
