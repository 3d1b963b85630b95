"""Loop-aware cost model over optimized HLO text.

``compiled.cost_analysis()`` counts while-loop bodies ONCE, so scanned layer
stacks / grad-accumulation loops / chunked attention are undercounted by
their trip counts.  XLA records ``known_trip_count`` on every scan-lowered
while loop, and every instruction is defined with its shape, so an exact
walker is possible from the HLO text alone:

  * build the computation call graph (while bodies/conds x trip counts,
    fusions, conditionals),
  * per computation: dot FLOPs (2 * prod(result) * K, K from
    lhs_contracting_dims via the local symbol table), collective wire bytes
    (ring model), fusion HBM bytes (operands + results; in-place
    dynamic-update-slice roots counted at update size),
  * totals = per-computation costs weighted by path multiplier from ENTRY.

Flops are dot/conv only (elementwise is noise next to MXU work at these
shapes - documented).  All numbers are PER DEVICE (the HLO is the SPMD
per-device program).
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "tf32": 4, "bf16": 2, "f16": 2,
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
    # sub-byte element types are storage-padded to one byte outside packed
    # custom calls; HBM accounting charges the padded width
    "s4": 1, "u4": 1, "s2": 1, "u2": 1, "s1": 1, "u1": 1,
    # opaque control/token values occupy no HBM
    "token": 0, "opaque": 0,
}

_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\][^\s]*\s+([a-z0-9\-]+)\("
)
_TUPLE_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*\(\s*.*\)\s+([a-z0-9\-]+)\("
)
_OPERANDS = re.compile(r"\(([^)]*)\)")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_WHILE_REFS = re.compile(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CDIMS = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_GROUPS = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_PARAM_DECL = re.compile(r"%?([\w.\-]+):\s*([a-z0-9]+)\[([0-9,]*)\]")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def collectives_in(text: str) -> List[str]:
    """The collective ops (sync or async ``-start`` form) an HLO text uses."""
    return [op for op in COLLECTIVES
            if re.search(rf"\b{op}(-start)?\(", text)]


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    width = _DTYPE_BYTES.get(dtype)
    if width is None:
        # a silent 4-byte default mis-prices every narrow-dtype buffer by
        # 4x (the int8 serving path hit exactly this) - fail loudly so new
        # HLO dtypes get an explicit entry instead of a wrong guess
        raise ValueError(
            f"unrecognized HLO element type {dtype!r} (dims=[{dims}]); add "
            f"its byte width to launch.hlo_cost._DTYPE_BYTES"
        )
    return n * width


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    mem_fusion: float = 0.0   # HBM traffic at fusion call sites
    mem_plain: float = 0.0    # HBM traffic of top-level (unfused) ops;
    #                           dropped when this comp is itself a fusion body
    wire_bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    # edges: (callee, multiplier)
    edges: List[Tuple[str, float]] = dataclasses.field(default_factory=list)
    fusion_callees: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class HloCost:
    flops: float
    mem_bytes: float
    wire_bytes: float
    coll_bytes: Dict[str, float]
    coll_counts: Dict[str, float]
    n_while_unknown: int

    def to_json(self):
        return dataclasses.asdict(self)


def _parse_computations(text: str):
    comps: Dict[str, List[str]] = {}
    headers: Dict[str, str] = {}
    entry = None
    name = None
    for line in text.splitlines():
        if line.endswith("{") and ("->" in line) and ("(" in line):
            m = _COMP_HEADER.match(line.strip())
            if m:
                name = m.group(1)
                comps[name] = []
                headers[name] = line
                if line.lstrip().startswith("ENTRY"):
                    entry = name
                continue
        if line.strip() == "}":
            name = None
            continue
        if name is not None:
            comps[name].append(line)
    return comps, headers, entry


def _symbols(header: str, lines: List[str]) -> Dict[str, Tuple[str, str]]:
    """name -> (dtype, dims) for params + instruction results."""
    syms: Dict[str, Tuple[str, str]] = {}
    for m in _PARAM_DECL.finditer(header):
        syms[m.group(1)] = (m.group(2), m.group(3))
    for line in lines:
        m = _INSTR.match(line)
        if m:
            syms[m.group(1)] = (m.group(2), m.group(3))
    return syms


def _operand_names(line: str) -> List[str]:
    m = _OPERANDS.search(line[line.index("(") :] if "(" in line else line)
    # find the operand list of the op call: first "(...)" after op name
    # robust approach: take text between the first '(' following '= ... op'
    try:
        start = line.index("(", line.index(" = ") if " = " in line else 0)
    except ValueError:
        return []
    depth = 0
    buf = []
    for ch in line[start:]:
        if ch == "(":
            depth += 1
            if depth == 1:
                continue
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        if depth >= 1:
            buf.append(ch)
    inner = "".join(buf)
    names = re.findall(r"%([\w.\-]+)", inner)
    return names


def _dus_aliased_param(comp_lines: List[str]) -> Optional[int]:
    """If the computation's ROOT is dynamic-update-slice, return the index of
    the fusion parameter that is updated in place (operand 0), else None."""
    for line in comp_lines:
        if "ROOT" in line and "dynamic-update-slice(" in line:
            ops = _operand_names(line)
            if ops:
                m = re.match(r"param_(\d+)", ops[0])
                if m:
                    return int(m.group(1))
    return None


_GTE_INDEX = re.compile(r"index=(\d+)")


def _compute_scoped(comps: Dict[str, List[str]], vmem_scopes: tuple) -> Dict[str, set]:
    """Per-computation sets of VMEM-scoped instruction names.

    Seeds: instructions whose op_name metadata carries a scope tag.
    Closure 1 (intra-comp): an op ALL of whose array operands are scoped is
    scoped (XLA re-wraps interior ops - reduce-window, copy - dropping
    metadata; anything computed purely from scoped values is interior).
    Closure 2 (across loop carries): if a while's init-tuple element is
    scoped in the parent, the body/cond get-tuple-elements at that index are
    scoped (online-softmax carries cross scan boundaries).
    Iterated to a global fixpoint.
    """
    if not vmem_scopes:
        return {}
    defs_by_comp: Dict[str, Dict[str, Tuple[List[str], bool, Optional[int]]]] = {}
    tuples: Dict[str, Dict[str, List[str]]] = {}
    while_calls: List[Tuple[str, str, str, str]] = []  # parent, body, cond, init
    for name, lines in comps.items():
        defs = {}
        tups = {}
        for line in lines:
            m = _INSTR.match(line)
            tm = None if m else _TUPLE_INSTR.match(line)
            if not m and not tm:
                continue
            iname = m.group(1) if m else tm.group(1)
            op = m.group(4) if m else tm.group(2)
            ops = _operand_names(line)
            tagged = any(s in line for s in vmem_scopes)
            gidx = None
            if op == "get-tuple-element":
                gm = _GTE_INDEX.search(line)
                gidx = int(gm.group(1)) if gm else None
            defs[iname] = (ops, tagged, gidx)
            if op == "tuple":
                tups[iname] = ops
            if op == "while":
                wm = _WHILE_REFS.search(line)
                if wm and ops:
                    while_calls.append((name, wm.group(2), wm.group(1), ops[0]))
        defs_by_comp[name] = defs
        tuples[name] = tups

    scoped: Dict[str, set] = {
        n: {i for i, (_, tag, _) in d.items() if tag} for n, d in defs_by_comp.items()
    }
    for _ in range(6):  # global fixpoint (nesting depth bound)
        changed = False
        # intra-computation closure (constants/iota are neutral operands)
        for name, defs in defs_by_comp.items():
            sc = scoped[name]
            neutral = {
                i for i, (ops_, _, _) in defs.items() if not ops_
            }  # constant(...), iota, parameter-like leaves have no operands
            local = True
            while local:
                local = False
                for iname, (ops, _, _) in defs.items():
                    if iname in sc:
                        continue
                    arr = [o for o in ops if o in defs and o not in neutral]
                    if arr and all(o in sc for o in arr):
                        sc.add(iname)
                        local = changed = True
        # loop-carry seeding
        for parent, body, cond, init in while_calls:
            init_ops = tuples.get(parent, {}).get(init)
            if not init_ops:
                continue
            scoped_pos = {
                i for i, o in enumerate(init_ops) if o in scoped.get(parent, set())
            }
            if not scoped_pos:
                continue
            for target in (body, cond):
                defs = defs_by_comp.get(target)
                if not defs:
                    continue
                sc = scoped[target]
                for iname, (_, _, gidx) in defs.items():
                    if gidx in scoped_pos and iname not in sc:
                        sc.add(iname)
                        changed = True
        if not changed:
            break
    return scoped


def analyze(text: str, default_group: int = 16,
            vmem_scopes: tuple = ()) -> HloCost:
    """``vmem_scopes``: op_name substrings whose instructions' HBM traffic is
    NOT counted (they model Pallas-kernel interiors that stay in VMEM on the
    TPU target; FLOPs and collectives are still counted)."""
    comps, headers, entry = _parse_computations(text)
    costs: Dict[str, CompCost] = {}
    unknown_trips = 0

    scoped_by_comp = _compute_scoped(comps, vmem_scopes)

    for name, lines in comps.items():
        syms = _symbols(headers[name], lines)
        scoped = scoped_by_comp.get(name, set())
        cc = CompCost()

        # --- CPU-lowering artifact correction -------------------------------
        # XLA CPU upcasts bf16 dot operands to f32 (no native bf16 matmul);
        # on the TPU target (MXU) those values stay bf16 and the converts do
        # not exist.  Track instructions that are f32 converts of bf16 values
        # so (a) their own traffic is skipped and (b) collectives/dots that
        # consume them are costed at bf16 width.
        upcast: set = set()
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            iname, dtype, op = m.group(1), m.group(2), m.group(4)
            if dtype != "f32":
                continue
            ops_ = _operand_names(line)
            if op == "convert" and ops_ and syms.get(ops_[0], ("",))[0] == "bf16":
                upcast.add(iname)
            elif op in ("copy", "bitcast", "reshape", "transpose", "all-gather",
                        "all-reduce", "broadcast") and ops_ and (
                ops_[0] in upcast
            ):
                upcast.add(iname)
            elif op == "fusion" and "convert" in line and ops_ and all(
                syms.get(o, ("",))[0] == "bf16" for o in ops_ if o in syms
            ):
                upcast.add(iname)

        def eff_bytes(dtype: str, dims: str, iname: Optional[str] = None) -> float:
            b = _shape_bytes(dtype, dims)
            if iname is not None and iname in upcast:
                return b / 2.0  # bf16 on the TPU target
            return b

        def operand_bytes(oname: str) -> float:
            s = syms.get(oname)
            if not s:
                return 0.0
            return eff_bytes(s[0], s[1], oname)

        def in_vmem_scope(line: str, _scoped=scoped) -> bool:
            if any(s in line for s in vmem_scopes):
                return True
            m = _INSTR.match(line)
            return bool(m and m.group(1) in _scoped)

        for line in lines:
            m = _INSTR.match(line)
            tuple_m = None if m else _TUPLE_INSTR.match(line)
            op = m.group(4) if m else (tuple_m.group(2) if tuple_m else None)
            if op is None:
                continue
            dtype, dims = (m.group(2), m.group(3)) if m else ("f32", "")

            if op == "while":
                wm = _WHILE_REFS.search(line)
                tm = _TRIP.search(line)
                trip = float(tm.group(1)) if tm else 1.0
                if not tm:
                    unknown_trips += 1
                if wm:
                    cc.edges.append((wm.group(2), trip))       # body x trip
                    cc.edges.append((wm.group(1), trip + 1.0))  # cond
                continue
            if op in ("fusion", "call", "custom-call"):
                fm = _CALLS.search(line)
                if fm:
                    cc.edges.append((fm.group(1), 1.0))
                    cc.fusion_callees.append(fm.group(1))
                # HBM traffic: operands + result (in-place DUS at update size)
                rb = _shape_bytes(dtype, dims) if m else 0.0
                onames = _operand_names(line)
                aliased = None
                if fm and fm.group(1) in comps:
                    aliased = _dus_aliased_param(comps[fm.group(1)])
                    if aliased is not None and fm.group(1) in comps:
                        # write = update size; use callee's operand-1 shape
                        upd = _update_bytes(comps[fm.group(1)])
                        if upd is not None:
                            rb = upd
                if not in_vmem_scope(line) and (m and m.group(1)) not in upcast:
                    for idx, on in enumerate(onames):
                        if aliased is not None and idx == aliased:
                            continue  # aliased buffer: not fully read/written
                        cc.mem_fusion += operand_bytes(on)
                    cc.mem_fusion += (
                        eff_bytes(dtype, dims, m.group(1)) if m and rb == _shape_bytes(dtype, dims) else rb
                    )
                continue
            if op == "conditional":
                bm = _BRANCHES.search(line)
                if bm:
                    for ref in re.findall(r"%?([\w.\-]+)", bm.group(1)):
                        cc.edges.append((ref, 1.0))
                continue
            if op in COLLECTIVES or any(
                op == c + sfx for c in COLLECTIVES for sfx in ("-start",)
            ):
                base = op.replace("-start", "")
                b = eff_bytes(dtype, dims, m.group(1)) if m else 0.0
                gm = _GROUPS.search(line)
                if gm:
                    k = int(gm.group(2))
                else:
                    gb = _GROUPS_BRACE.search(line)
                    k = len(gb.group(1).split(",")) if gb else default_group
                k = max(k, 2)
                if base == "all-reduce":
                    w = 2.0 * b * (k - 1) / k
                elif base == "all-gather":
                    w = b * (k - 1) / k
                elif base == "reduce-scatter":
                    w = b * (k - 1)
                elif base == "all-to-all":
                    w = b * (k - 1) / k
                else:
                    w = b
                cc.wire_bytes += w
                cc.coll_bytes[base] = cc.coll_bytes.get(base, 0.0) + b
                cc.coll_counts[base] = cc.coll_counts.get(base, 0) + 1
                continue
            if op in ("dot", "convolution"):
                res_elems = _shape_elems(dims)
                k = 1
                lhs = _operand_names(line)
                cd = _LHS_CDIMS.search(line)
                if lhs and cd:
                    s = syms.get(lhs[0])
                    if s:
                        ldims = [int(d) for d in s[1].split(",")] if s[1] else []
                        for ci in cd.group(1).split(","):
                            if ci != "" and int(ci) < len(ldims):
                                k *= ldims[int(ci)]
                cc.flops += 2.0 * res_elems * k
                # dot HBM traffic (only charged when this comp is unfused)
                if not in_vmem_scope(line):
                    for on in lhs[:2]:
                        cc.mem_plain += operand_bytes(on)
                    cc.mem_plain += eff_bytes(dtype, dims, m.group(1) if m else None)
                continue
            if op in ("copy", "transpose", "reshape", "broadcast",
                      "dynamic-slice", "dynamic-update-slice", "slice",
                      "concatenate", "reduce", "pad", "gather", "scatter",
                      "iota", "convert", "select", "compare", "add",
                      "multiply", "subtract", "divide", "exponential",
                      "tanh", "rsqrt", "log", "maximum", "minimum"):
                # unfused top-level op: result write + operand reads
                # (pure bf16->f32 upcasts do not exist on the TPU target)
                if m and not in_vmem_scope(line) and m.group(1) not in upcast:
                    cc.mem_plain += eff_bytes(dtype, dims, m.group(1))
                    for on in _operand_names(line)[:3]:
                        cc.mem_plain += operand_bytes(on)
                continue
        costs[name] = cc

    # propagate multipliers from ENTRY through the call graph
    mult: Dict[str, float] = {}

    def visit(name: str, m: float):
        if name not in costs or m <= 0:
            return
        mult[name] = mult.get(name, 0.0) + m
        for callee, em in costs[name].edges:
            visit(callee, m * em)

    if entry:
        visit(entry, 1.0)

    fused_comps = set()
    for cc in costs.values():
        fused_comps.update(cc.fusion_callees)

    total = HloCost(0.0, 0.0, 0.0, {}, {}, unknown_trips)
    for name, m in mult.items():
        cc = costs[name]
        total.flops += cc.flops * m
        total.mem_bytes += cc.mem_fusion * m
        if name not in fused_comps:
            total.mem_bytes += cc.mem_plain * m
        total.wire_bytes += cc.wire_bytes * m
        for k, v in cc.coll_bytes.items():
            total.coll_bytes[k] = total.coll_bytes.get(k, 0.0) + v * m
        for k, v in cc.coll_counts.items():
            total.coll_counts[k] = total.coll_counts.get(k, 0.0) + v * m
    return total


def _update_bytes(comp_lines: List[str]) -> Optional[float]:
    """Bytes of the DUS update operand (operand 1) in a fusion computation."""
    syms: Dict[str, Tuple[str, str]] = {}
    for line in comp_lines:
        m = _INSTR.match(line)
        if m:
            syms[m.group(1)] = (m.group(2), m.group(3))
    for line in comp_lines:
        if "ROOT" in line and "dynamic-update-slice(" in line:
            ops = _operand_names(line)
            if len(ops) >= 2 and ops[1] in syms:
                return _shape_bytes(*syms[ops[1]])
    return None
