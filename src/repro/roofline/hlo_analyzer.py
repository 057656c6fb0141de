"""Trip-count-aware HLO analyzer.

XLA's ``compiled.cost_analysis()`` reports a single execution of each
computation — collectives *and* FLOPs inside ``while`` bodies (i.e. every
scan-over-layers / microbatch loop) are counted once instead of
trip_count times (verified empirically: a scan of 7 matmuls reports the
FLOPs of one).  For a framework whose every model is a scan over layers
that is off by 50-100x, so we analyze the HLO text ourselves:

  * flops:   2*M*N*K for every ``dot`` (operand shapes resolved through the
             instruction symbol table); convolutions counted analogously.
  * traffic: bytes written + bytes read per materialized instruction
             (fusions are single instructions = XLA's materialization
             boundaries; access-only ops — tuple/gte/parameter/bitcast —
             are skipped).  This is the HBM-traffic proxy for the memory
             roofline term.
  * wire:    per-kind collective bytes with ring / all-to-all formulas.

All three are multiplied through ``while`` trip counts (XLA annotates
``backend_config known_trip_count``) and ``call`` edges.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "s2": 1, "u2": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COMP_HEAD_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{")
# TPU layouts carry parentheses inside braces (``u8[4,256]{1,0:T(4,128)}``),
# so a brace group may hold anything but a closing brace, and a tuple type
# may nest one level of parentheses.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s+=\s+"
    r"((?:\((?:[^()]|\([^()]*\))*\))|(?:(?:\{[^}]*\}|[\w\[\],:*\s])+?))\s+"
    r"([\w\-]+)\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_SKIP_TRAFFIC = {
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "copy-start",
    "copy-done", "while", "call", "conditional",
}

# Pure elementwise / layout ops that a TPU backend fuses into their
# producers/consumers: counting their results as HBM traffic models the
# CPU backend's materialization choices, not the target's.  The memory
# roofline term assumes perfect elementwise fusion and charges traffic only
# at genuine materialization points (fusions, dots, reductions, data
# movement, collectives, RNG).
_ELEMENTWISE_FUSED = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "logistic", "rsqrt", "sqrt", "cbrt", "power", "negate", "abs",
    "compare", "select", "and", "or", "not", "xor", "convert", "broadcast",
    "reshape", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "clamp", "sign", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "reduce-precision", "sine", "cosine", "atan2",
    "is-finite", "remainder", "map", "slice", "rem", "real", "imag",
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _parse_dims(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _parse_dims(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    if "source_target_pairs={{" in line:
        # collective-permute carries pairs, not groups; any pair means the
        # payload crosses the wire (the formula charges full result bytes).
        return 2
    return 1


def _wire_bytes(kind: str, rbytes: int, g: int) -> int:
    if kind == "all-gather":
        return rbytes * (g - 1) // g
    if kind == "reduce-scatter":
        return rbytes * (g - 1)
    if kind == "all-reduce":
        return 2 * rbytes * (g - 1) // g
    if kind == "all-to-all":
        return rbytes * (g - 1) // g
    return rbytes  # collective-permute


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    line: str


@dataclasses.dataclass
class CompStats:
    flops: float = 0.0
    traffic: float = 0.0  # upper bound: every CPU-fusion boundary is HBM
    traffic_min: float = 0.0  # lower bound: perfect fusion (dots, reduces,
    # data movement, collectives, RNG only)
    wire: Optional[dict] = None
    counts: Optional[dict] = None
    # launch counts per "<kind>:<dtype>" (e.g. "all-gather:u8") — separates
    # quantized-payload launches from fp metadata/fallback launches, which
    # is how the coalesced-wire regression tests assert 1 launch per layer.
    counts_dt: Optional[dict] = None


def _split(hlo_text: str) -> tuple[dict[str, list[Instr]], Optional[str]]:
    comps: dict[str, list[Instr]] = {}
    entry = None
    cur: Optional[list[Instr]] = None
    for line in hlo_text.splitlines():
        h = _COMP_HEAD_RE.match(line)
        if h:
            cur = comps.setdefault(h.group(2), [])
            if h.group(1):
                entry = h.group(2)
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            cur.append(Instr(m.group(1), m.group(2).strip(), m.group(3), line))
    return comps, entry


def _dot_flops(instr: Instr, types: dict[str, str]) -> float:
    # result elems * 2 * contraction size
    res = _parse_dims(instr.type_str)
    if not res:
        return 0.0
    r_elems = 1
    for d in res[0][1]:
        r_elems *= d
    ops = _OPERAND_RE.findall(instr.line.split("(", 1)[1])
    lhs_type = types.get(ops[0]) if ops else None
    if lhs_type is None:
        return 0.0
    lhs_dims = _parse_dims(lhs_type)
    if not lhs_dims:
        return 0.0
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.line)
    k = 1
    if m:
        for idx in m.group(1).split(","):
            if idx:
                k *= lhs_dims[0][1][int(idx)]
    return 2.0 * r_elems * k


def _conv_flops(instr: Instr, types: dict[str, str]) -> float:
    res = _parse_dims(instr.type_str)
    if not res:
        return 0.0
    r_elems = 1
    for d in res[0][1]:
        r_elems *= d
    ops = _OPERAND_RE.findall(instr.line.split("(", 1)[1])
    if len(ops) < 2:
        return 0.0
    ker = _parse_dims(types.get(ops[1], ""))
    if not ker:
        return 0.0
    k_elems = 1
    for d in ker[0][1]:
        k_elems *= d
    # per output element: 2 * (kernel elems / output features)
    out_feat = res[0][1][-1] if res[0][1] else 1
    return 2.0 * r_elems * (k_elems / max(out_feat, 1))


def analyze_hlo(hlo_text: str) -> dict:
    comps, entry = _split(hlo_text)
    kinds = _COLLECTIVES
    memo: dict[str, CompStats] = {}

    def run(name: str, stack: frozenset) -> CompStats:
        if name in memo:
            return memo[name]
        st = CompStats(wire=dict.fromkeys(kinds, 0), counts=dict.fromkeys(kinds, 0),
                       counts_dt={})
        if name in stack or name not in comps:
            return st
        types = {i.name: i.type_str for i in comps[name]}
        for i in comps[name]:
            if i.op == "dot":
                st.flops += _dot_flops(i, types)
            elif i.op == "convolution":
                st.flops += _conv_flops(i, types)
            elif i.op == "fusion":
                # flops of fused dots live inside the called computation
                m = re.search(r"calls=%([\w.\-]+)", i.line)
                if m:
                    sub = run(m.group(1), stack | {name})
                    st.flops += sub.flops
            elif i.op == "while":
                m = re.search(r"body=%([\w.\-]+)", i.line)
                tm = _TRIP_RE.search(i.line)
                trips = int(tm.group(1)) if tm else 1
                if m:
                    sub = run(m.group(1), stack | {name})
                    st.flops += trips * sub.flops
                    st.traffic += trips * sub.traffic
                    st.traffic_min += trips * sub.traffic_min
                    for k in kinds:
                        st.wire[k] += trips * sub.wire[k]
                        st.counts[k] += trips * sub.counts[k]
                    for k2, v in sub.counts_dt.items():
                        st.counts_dt[k2] = st.counts_dt.get(k2, 0) + trips * v
            elif i.op == "call":
                m = re.search(r"to_apply=%([\w.\-]+)", i.line)
                if m:
                    sub = run(m.group(1), stack | {name})
                    st.flops += sub.flops
                    st.traffic += sub.traffic
                    st.traffic_min += sub.traffic_min
                    for k in kinds:
                        st.wire[k] += sub.wire[k]
                        st.counts[k] += sub.counts[k]
                    for k2, v in sub.counts_dt.items():
                        st.counts_dt[k2] = st.counts_dt.get(k2, 0) + v
            elif i.op == "conditional":
                for m in re.finditer(r"(?:true_computation|false_computation|branch_computations=\{)[^,)]*%([\w.\-]+)", i.line):
                    sub = run(m.group(1), stack | {name})
                    st.flops += sub.flops
                    st.traffic += sub.traffic
                    st.traffic_min += sub.traffic_min

            base = i.op.replace("-start", "")
            if base in kinds and not i.op.endswith("-done"):
                dims = _parse_dims(i.type_str)
                if i.op.endswith("-start") and i.type_str.startswith("("):
                    # async form: (operand, result[, ...]) tuple type — the
                    # RESULT buffer is the last shape (naive comma-splitting
                    # breaks on the commas inside shapes like u8[8,32])
                    dims = dims[-1:]
                rbytes = 0
                for dt, d in dims:
                    n_el = 1
                    for x in d:
                        n_el *= x
                    rbytes += n_el * _DTYPE_BYTES[dt]
                g = _group_size(i.line)
                if g > 1:
                    st.wire[base] += _wire_bytes(base, rbytes, g)
                    st.counts[base] += 1
                    dt = dims[0][0] if dims else "?"
                    k2 = f"{base}:{dt}"
                    st.counts_dt[k2] = st.counts_dt.get(k2, 0) + 1

            if (i.op not in _SKIP_TRAFFIC and i.op not in _ELEMENTWISE_FUSED
                    and not i.op.endswith("-done")):
                w = _type_bytes(i.type_str)
                tail = i.line.split("(", 1)[1]
                tail = tail.split("metadata=")[0]
                opnames = _OPERAND_RE.findall(tail)
                # essential ops contribute to the perfect-fusion lower bound.
                # A fusion counts as essential only if its body computes
                # (holds a dot/reduce) — pure elementwise kLoop fusions are
                # assumed to merge into their neighbours on TPU.
                essential = i.op in (
                    "dot", "convolution", "reduce", "reduce-window",
                    "dynamic-slice", "dynamic-update-slice", "gather",
                    "scatter", "concatenate", "pad", "copy", "sort",
                    "transpose", "rng", "rng-bit-generator",
                    "select-and-scatter",
                ) or i.op.replace("-start", "") in kinds
                dus_update_bytes = None
                slice_fusion = False
                if i.op == "fusion":
                    m = re.search(r"calls=%([\w.\-]+)", i.line)
                    sub = run(m.group(1), stack | {name}) if m else None
                    head = i.line.split("metadata=")[0]
                    essential = (sub is not None and sub.flops > 0) or any(
                        t in head for t in ("reduce", "dynamic", "scatter",
                                            "gather", "concat", "transpose"))
                    # in-place DUS fusions: XLA aliases the big buffer
                    # (input-output aliasing), so the physical traffic is
                    # the update slice, not the whole buffer.  Detect a
                    # fused computation whose root is a dynamic-update-slice
                    # of a parameter-sized buffer and charge update bytes.
                    if m and m.group(1) in comps:
                        body = comps[m.group(1)]
                        btypes = {j.name: j.type_str for j in body}
                        dus = [j for j in body if j.op == "dynamic-update-slice"]
                        if dus and _type_bytes(i.type_str) == max(
                                (_type_bytes(j.type_str) for j in body),
                                default=0):
                            ub = 0
                            for j in dus:
                                ops_j = _OPERAND_RE.findall(
                                    j.line.split("(", 1)[1].split("metadata=")[0])
                                if len(ops_j) > 1 and ops_j[1] in btypes:
                                    ub += _type_bytes(btypes[ops_j[1]])
                                else:
                                    ub = None
                                    break
                            if ub is not None and ub < _type_bytes(i.type_str):
                                dus_update_bytes = ub
                        # slice-consuming fusions: a fusion whose body
                        # dynamic-slices a much larger operand reads only
                        # the addressed slice on real hardware (the CPU
                        # backend sometimes hoists dtype converts over the
                        # whole buffer — a backend artifact, not traffic).
                        if dus_update_bytes is None:
                            has_ds = any(j.op == "dynamic-slice" for j in body)
                            tailf = i.line.split("(", 1)[1].split("metadata=")[0]
                            opsf = [_type_bytes(types[o]) for o in
                                    _OPERAND_RE.findall(tailf) if o in types]
                            if (has_ds and opsf
                                    and _type_bytes(i.type_str) <= max(opsf) // 4):
                                slice_fusion = True
                if dus_update_bytes is not None:
                    st.traffic += 2 * dus_update_bytes
                    st.traffic_min += 2 * dus_update_bytes
                elif slice_fusion:
                    w2 = _type_bytes(i.type_str)
                    small_ops = sum(
                        _type_bytes(types[o]) for o in opnames
                        if o in types and _type_bytes(types[o]) <= 4 * w2)
                    st.traffic += 2 * w2 + small_ops
                    if essential:
                        st.traffic_min += 2 * w2 + small_ops
                elif i.op in ("dynamic-slice", "gather"):
                    # reads only the addressed slice (~ result bytes)
                    st.traffic += 2 * w
                    if essential:
                        st.traffic_min += 2 * w
                elif i.op in ("dynamic-update-slice", "scatter"):
                    # in-place buffer update: reads+writes only the update
                    upd = types.get(opnames[1]) if len(opnames) > 1 else None
                    ub = _type_bytes(upd) if upd else w
                    st.traffic += 2 * min(ub, w)
                    if essential:
                        st.traffic_min += 2 * min(ub, w)
                else:
                    r = 0
                    for opname in opnames:
                        t = types.get(opname)
                        if t is not None:
                            r += _type_bytes(t)
                    st.traffic += w + r
                    if essential:
                        st.traffic_min += w + r
        memo[name] = st
        return st

    st = run(entry or "__missing__", frozenset())
    wire = dict(st.wire)
    wire["total"] = sum(st.wire.values())
    wire["counts"] = st.counts
    wire["counts_by_dtype"] = st.counts_dt
    return {
        "flops": st.flops,
        "traffic_bytes": st.traffic,
        "traffic_min_bytes": st.traffic_min,
        "collectives": wire,
    }
