"""Optimized-HLO text analysis: collective wire bytes with while-loop
trip-count scaling.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but NOT collective
traffic, and (crucially) XLA's cost analysis does not multiply ops inside
``while`` bodies by their trip count.  This module parses the optimized HLO
text into computations, extracts per-computation collective bytes, detects
while-loop trip counts from the condition computation, and propagates
multipliers along the call graph so a collective inside the layer scan is
counted ``num_groups`` times.

Wire-byte convention (ring algorithms, per-chip traffic):
  all-reduce        2 x result bytes   (reduce-scatter + all-gather phases)
  all-gather        1 x result bytes
  reduce-scatter    1 x operand ~= result x shards  -> counted as result bytes
                    x (group-1)/group ~ result bytes (we use 1x result)
  all-to-all        1 x result bytes
  collective-permute 1 x result bytes
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, List, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls|body|condition|branch_computations)="
                      r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
# an integer scalar constant, with or without a layout (TPU: ``{:T(128)}``)
_CONST_RE = re.compile(r"[su](?:32|64)\[\](?:\{[^}]*\})?\s+constant\((\d+)\)")
_TRIP_RE = re.compile(r'known_trip_count"?:\{"?n"?:"?(\d+)"?\}')


def shape_bytes(type_str: str) -> int:
    """Sum of byte sizes of all array shapes appearing in a type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    entry = None
    for line in hlo.splitlines():
        s = line.strip()
        if cur is None:
            m = _COMP_HDR.match(s)
            if m and ("->" in s or s.endswith("{")):
                cur = m.group(1)
                comps[cur] = []
                if s.startswith("ENTRY"):
                    entry = cur
        else:
            if s == "}":
                cur = None
            else:
                comps[cur].append(s)
    if entry is not None:
        comps["__entry__"] = comps[entry]
    return comps


def _line_result_type(line: str) -> str:
    # '%x = (f32[8,4]{1,0}, f32[4]{0}) all-reduce(...)' -> type part
    m = re.match(r"%?[\w.\-]+\s*=\s*(.*?)\s+[\w\-]+\(", line)
    return m.group(1) if m else ""


def _call_graph(comps: Dict[str, List[str]]):
    """(trip counts, per-computation multipliers, fusion-body set)."""
    trip: Dict[str, int] = {}
    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    fusion_bodies = set()
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for ln in lines:
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                tm = _TRIP_RE.search(ln)  # XLA's own annotation, if present
                if tm:
                    trip[body] = int(tm.group(1))
                else:
                    consts = [int(c) for c in
                              _CONST_RE.findall("\n".join(comps.get(cond, [])))]
                    trip[body] = max(consts) if consts else 1
                edges[name].append((body, trip[body]))
                edges[name].append((cond, 1))
                continue
            is_fusion = re.search(r"\sfusion\(", ln) is not None
            for cm in _CALL_RE.finditer(ln):
                for callee in re.split(r",\s*", cm.group(1)):
                    callee = callee.lstrip("%")
                    if callee in comps:
                        edges[name].append((callee, 1))
                        if is_fusion:
                            fusion_bodies.add(callee)

    entry_name = None
    for name in comps:
        if name != "__entry__" and comps[name] is comps.get("__entry__"):
            entry_name = name
            break
    if entry_name is None:
        entry_name = next((n for n in comps if n != "__entry__"), None)
    mult: Dict[str, float] = defaultdict(float)
    stack = [(entry_name, 1.0)]
    guard = 0
    while stack and guard < 200000:
        guard += 1
        node, m = stack.pop()
        if node is None:
            break
        mult[node] += m
        for child, k in edges.get(node, []):
            stack.append((child, m * k))
    return trip, mult, fusion_bodies


def analyze_collectives(hlo: str) -> Dict[str, Dict]:
    """Returns {'per_op': {op: {'count','bytes','wire_bytes'}}, 'total_wire_bytes',
    'while_trip_counts': {...}} with trip-count multipliers applied."""
    comps = _split_computations(hlo)
    trip, mult, _ = _call_graph(comps)

    per_op = {c: {"count": 0.0, "bytes": 0.0, "wire_bytes": 0.0} for c in COLLECTIVES}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 1.0) or 1.0
        for ln in lines:
            for c in COLLECTIVES:
                # avoid matching 'all-reduce' inside 'all-reduce-scatter' etc.
                if re.search(rf"\s{c}(?:-start)?\(", ln):
                    ty = _line_result_type(ln)
                    b = shape_bytes(ty)
                    per_op[c]["count"] += m
                    per_op[c]["bytes"] += m * b
                    per_op[c]["wire_bytes"] += m * b * WIRE_FACTOR[c]
                    break

    total = sum(v["wire_bytes"] for v in per_op.values())
    return {"per_op": per_op, "total_wire_bytes": total,
            "while_trip_counts": trip}


# the result type may carry TPU tiled layouts ("{1,0:T(8,128)S(1)}"), so
# the opcode is the first token after whitespace that opens a paren
_DEF_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+([a-z][\w\-]*)\(")
_OPERANDS_RE = re.compile(r"\(((?:%[\w.\-]+(?:,\s*)?)+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIMS_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# ops whose operands/results represent real HBM traffic at fusion boundaries
_TRAFFIC_OPS = {
    "fusion", "dot", "convolution", "copy", "gather", "scatter",
    "dynamic-slice", "dynamic-update-slice", "transpose", "convert",
    "reduce", "broadcast", "iota", "concatenate", "slice", "reshape",
    "pad", "select-and-scatter", "sort", "bitcast-convert", "reverse",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
}
_NO_READ_OPS = {"iota", "broadcast", "constant", "parameter"}


def _first_shape_dims(type_str: str):
    m = _DIMS_RE.search(type_str)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",") if d]
    return dims


def full_cost(hlo: str) -> Dict[str, float]:
    """Trip-count-aware FLOPs + HBM-traffic estimate from optimized HLO.

    * flops: every ``dot`` (2 * numel(result) * prod(contracting dims)),
      counted in ALL computations (incl. fusion bodies), scaled by the call
      multiplier — this corrects XLA cost_analysis, which counts while
      bodies once.
    * bytes: at fusion boundaries only (top-level ops of non-fusion-body
      computations): result bytes (write) + operand bytes (read).
    """
    comps = _split_computations(hlo)
    trip, mult, fusion_bodies = _call_graph(comps)

    # symbol tables: per computation, op name -> (result type str, opcode)
    sym: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        table = {}
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if dm:
                table[dm.group(1)] = (dm.group(2).strip(), dm.group(3))
        sym[name] = table

    # fusion bodies that only move/convert data (no arithmetic): on TPU the
    # surrounding bf16 dot is native and these conversions don't exist —
    # their traffic is a CPU-backend artifact we report separately.
    _MOVE_OPS = {"convert", "copy", "bitcast", "bitcast-convert", "transpose",
                 "parameter", "tuple", "get-tuple-element", "reshape",
                 "broadcast", "constant", "multiply"}
    convert_bodies = set()
    for name in fusion_bodies:
        ops = {sym[name][k][1] for k in sym.get(name, {})}
        if ops and ops <= _MOVE_OPS and "convert" in ops:
            convert_bodies.add(name)

    flops = 0.0
    bytes_traffic = 0.0
    convert_traffic = 0.0
    dot_count = 0
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 1.0) or 1.0
        table = sym[name]
        in_fusion_body = name in fusion_bodies
        for ln in lines:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            res_type, opcode = dm.group(2).strip(), dm.group(3)

            if opcode == "dot":
                cm = _CONTRACT_RE.search(ln)
                om = re.search(r"dot\(([^)]*)\)", ln)
                k = 1
                if cm and om:
                    lhs_name = om.group(1).split(",")[0].strip().lstrip("%")
                    lhs_entry = table.get(lhs_name)
                    cdims = [int(d) for d in cm.group(1).split(",") if d]
                    if lhs_entry:
                        dims = _first_shape_dims(lhs_entry[0])
                        if dims:
                            for d in cdims:
                                if d < len(dims):
                                    k *= dims[d]
                res_elems = 0
                for dt, ds in _DIMS_RE.findall(res_type):
                    if dt in DTYPE_BYTES:
                        n = 1
                        for d in ds.split(","):
                            if d:
                                n *= int(d)
                        res_elems += n
                flops += m * 2.0 * res_elems * k
                dot_count += 1

            if in_fusion_body:
                continue  # bytes only at fusion boundaries
            if opcode not in _TRAFFIC_OPS:
                continue
            b = shape_bytes(res_type)  # write
            if opcode not in _NO_READ_OPS:
                om2 = _OPERANDS_RE.search(ln[ln.find(opcode + "("):])
                if om2:
                    for operand in om2.group(1).split(","):
                        operand = operand.strip().lstrip("%")
                        ent = table.get(operand)
                        if ent:
                            b += shape_bytes(ent[0])
            bytes_traffic += m * b
            if opcode == "fusion":
                cm = _CALL_RE.search(ln)
                if cm and cm.group(1).lstrip("%") in convert_bodies:
                    convert_traffic += m * b
            elif opcode in ("copy", "convert", "transpose"):
                convert_traffic += m * b

    return {"flops": flops, "bytes": bytes_traffic,
            "convert_bytes": convert_traffic,
            "dot_ops": float(dot_count),
            "max_trip": float(max(trip.values())) if trip else 1.0}


def split_phase_overlap(hlo: str, depth: int = 1) -> Dict:
    """Verify the split-phase reduction property on optimized HLO text.

    A pipelined distributed solve is genuinely split-phase when, inside
    each while-loop body, the inner-product ``all-reduce`` and the halo
    ``collective-permute``s are mutually independent in the dataflow
    graph: the all-reduce of iteration i is finished only by the scalar
    recurrence of iteration i+1, never by i+1's halo exchange or kernel
    operands — so XLA's latency-hiding scheduler may run the reduction
    concurrently with the next iteration's ppermute + SpMV launch
    (MPI_Iallreduce/MPI_Wait, rendered in XLA).

    Returns ``{"bodies": {body_name: {...}}, "overlap_ok": bool}`` where
    ``overlap_ok`` is True iff at least one while body contains both op
    kinds and in no body does a collective-permute (transitively) consume
    an all-reduce result.

    ``depth`` > 1 additionally certifies the depth-l amortized structure
    of ``sharded_pipecg_depth_solve``: one loop body = one ghost-basis
    block of ``depth`` iterations, whose l-deep reduction rows travel in
    a SINGLE fused Gram all-reduce (the l independent in-flight rows of
    the MPI rendering, fused into one payload because XLA collectives
    cannot span while-loop iterations).  The report then gains
    ``depth_ok`` — True iff every mixed body contains exactly ONE
    all-reduce (so the per-iteration reduction count is 1/depth) with
    the permutes still independent of it.
    """
    comps = _split_computations(hlo)
    bodies = set()
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for ln in lines:
            wm = _WHILE_RE.search(ln)
            if wm:
                bodies.add(wm.group(2))

    report: Dict[str, Dict] = {}
    for body in sorted(bodies & set(comps)):
        defs: Dict[str, Tuple[str, List[str]]] = {}
        for ln in comps[body]:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            name_, _, opcode = dm.group(1), dm.group(2), dm.group(3)
            om = re.search(re.escape(opcode) + r"\(([^)]*)\)", ln)
            operands = re.findall(r"%([\w.\-]+)", om.group(1)) if om else []
            defs[name_] = (opcode, operands)
        reduces = {nm for nm, (op, _) in defs.items()
                   if op.startswith("all-reduce")}
        permutes = {nm for nm, (op, _) in defs.items()
                    if op.startswith("collective-permute")}
        if not reduces or not permutes:
            continue
        tainted = set(reduces)   # transitive consumers of any all-reduce
        changed = True
        while changed:
            changed = False
            for nm, (_, operands) in defs.items():
                if nm not in tainted and any(o in tainted for o in operands):
                    tainted.add(nm)
                    changed = True
        report[body] = {
            "all_reduce": len(reduces),
            "collective_permute": len(permutes),
            "permute_depends_on_reduce": bool(permutes & tainted),
        }

    ok = bool(report) and not any(v["permute_depends_on_reduce"]
                                  for v in report.values())
    out = {"bodies": report, "overlap_ok": ok}
    if depth > 1:
        out["depth"] = depth
        out["depth_ok"] = ok and all(v["all_reduce"] == 1
                                     for v in report.values())
    return out


def scan_aware_cost(compiled, hlo: str) -> Dict[str, float]:
    """cost_analysis() FLOPs/bytes corrected for while-loop trip counts.

    XLA cost analysis counts a while body ONCE.  We approximate the true cost
    by scaling: for each while body we estimate its share of flops/bytes by
    re-running a regex-level dot/convolution size count is out of scope —
    instead we return both the raw numbers and the dominant trip count so the
    caller can combine with the analytic model (repro.roofline.flops).
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    out = {"flops_raw": float(ca.get("flops", -1.0)),
           "bytes_raw": float(ca.get("bytes accessed", -1.0))}
    comps = _split_computations(hlo)
    trips = analyze_collectives(hlo)["while_trip_counts"]
    out["max_trip_count"] = float(max(trips.values())) if trips else 1.0
    return out
