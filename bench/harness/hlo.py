"""What the compiled step runs, read from its optimised HLO text.

The parser and the dot arithmetic are copied from the program's
``launch/hlo_cost.py``; this copy adds what a TPU program needs: on a
TPU the compiler turns matmuls into ``convolution`` ops, mostly inside
fusions, which that module does not count.

`op_table` walks the entry computation, multiplying through the known
trip counts of ``while`` loops, and returns for every top-level
instruction (the names the profiler gives device operations) its kind
and the matmul FLOPs of one execution:

* ``matmul``  — a dot or convolution, or a fusion containing one;
* ``kernel``  — a Mosaic custom call (``tpu_custom_call``: a Pallas
  kernel), with ``hbm_bytes``, the bytes of its operands and results
  that the compiler left in HBM (`hbm_bytes`);
* ``collective`` — all-gather, all-reduce, reduce-scatter, all-to-all,
  collective-permute;
* ``control`` — a ``while``, ``conditional`` or ``call``, whose device
  event spans the operations of the computations it runs;
* ``other``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")
_SPACE_RE = re.compile(r"S\((\d+)\)")
_ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_OPERAND = re.compile(r"%([\w.\-]+)")
_OPCALL = re.compile(r"([a-z][a-z0-9\-]*)\(")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TO_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LABELS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")
_WINDOW_RE = re.compile(r"window=\{size=([\dx]+)")
_LHS_DILATE_RE = re.compile(r"lhs_dilate=([\dx]+)")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclass
class Instr:
    name: str
    result_type: str
    op: str
    operands: list
    line: str


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    types: dict = field(default_factory=dict)


def _dims(type_str: str) -> list:
    m = _SHAPE_RE.search(type_str)
    return [int(d) for d in m.group(2).split(",") if d] if m else []


def parse_hlo(text: str) -> dict:
    comps, cur = {}, None
    for raw in text.splitlines():
        s = raw.strip()
        if cur is None:
            m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{", s)
            if m:
                cur = Computation(m.group(2))
                comps[cur.name] = cur
                if m.group(1):
                    comps["__entry__"] = cur
            continue
        if s.startswith("}"):
            cur = None
            continue
        if " = " not in s:
            continue
        lhs, rhs = s.split(" = ", 1)
        name = lhs.replace("ROOT", "").strip().lstrip("%")
        m = _OPCALL.search(rhs)
        if not m:
            continue
        rest = rhs[m.end():]
        depth, end = 1, len(rest)
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        ins = Instr(name, rhs[:m.start()].strip(), m.group(1),
                    _OPERAND.findall(rest[:end]), s)
        cur.instrs.append(ins)
        cur.types[name] = ins.result_type
    return comps


def hbm_bytes(type_str: str) -> int:
    """Bytes of the arrays of an HLO type (one array or a tuple) whose
    layout puts them in HBM: a layout that names a memory space
    ``S(n)`` with n > 0 puts the array in the core's own memory (VMEM on
    a TPU), which the HBM bandwidth does not bound."""
    total = 0
    for dt, dims, layout in _ARRAY_RE.findall(type_str):
        space = _SPACE_RE.search(layout or "")
        if dt not in _ITEM_BYTES or (space and int(space.group(1))):
            continue
        n = _ITEM_BYTES[dt]
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
    return total


def matmul_flops(ins: Instr, comp: Computation) -> float:
    """2 × output elements × contraction length of a dot or a
    convolution.

    A convolution's contraction is the kernel's input features times
    the window taps that meet a real input element.  TPU programs
    write batched matmuls as convolutions whose batch dimensions are
    windows over an input dilated by the window's own size
    (``window={size=4x25 ... lhs_dilate=4x25}``): each output element
    then meets one tap per such dimension, not the window's size, so
    the taps of a dimension are ceil(size / lhs_dilate)."""
    out = 1
    for d in _dims(ins.result_type):
        out *= d
    if ins.op == "dot":
        lhs = _dims(comp.types.get(ins.operands[0], ""))
        m = _CDIMS_RE.search(ins.line)
        k = 1
        for d in ([int(x) for x in m.group(1).split(",") if x] if m
                  else []):
            if d < len(lhs):
                k *= lhs[d]
        return 2.0 * out * k
    kernel = _dims(comp.types.get(ins.operands[1], "")) \
        if len(ins.operands) > 1 else []
    m = _LABELS_RE.search(ins.line)
    if not kernel or not m or "i" not in m.group(2):
        return 0.0
    k = kernel[m.group(2).index("i")]
    lhs = _dims(comp.types.get(ins.operands[0], ""))
    size = _WINDOW_RE.search(ins.line)
    dil = _LHS_DILATE_RE.search(ins.line)
    sizes = [int(x) for x in size.group(1).split("x")] if size else []
    dils = [int(x) for x in dil.group(1).split("x")] if dil else []
    for j, w in enumerate(sizes):
        taps = -(-w // (dils[j] if j < len(dils) else 1))
        pos = m.group(1).find(str(j))
        if 0 <= pos < len(lhs):
            taps = min(taps, lhs[pos])
        k *= taps
    return 2.0 * out * k


def _fusion_flops(comp: Computation, comps: dict, seen: dict) -> float:
    if comp.name in seen:
        return seen[comp.name]
    total = 0.0
    for ins in comp.instrs:
        if ins.op in ("dot", "convolution"):
            total += matmul_flops(ins, comp)
        elif ins.op == "fusion":
            m = _CALLS_RE.search(ins.line)
            if m and m.group(1) in comps:
                total += _fusion_flops(comps[m.group(1)], comps, seen)
    seen[comp.name] = total
    return total


def op_table(text: str) -> dict:
    """{instruction name: {"kind", "flops" (one execution), "count"
    (executions per program run)}} for every top-level instruction."""
    comps = parse_hlo(text)
    if "__entry__" not in comps:
        raise ValueError("no ENTRY computation in the HLO text")
    table, fseen = {}, {}

    def walk(comp: Computation, mult: int):
        for ins in comp.instrs:
            op = ins.op
            if op in ("while", "conditional", "call"):
                row = table.setdefault(ins.name, {"kind": "control",
                                                  "flops": 0.0, "count": 0})
                row["count"] += mult
            if op == "while":
                m, t = _BODY_RE.search(ins.line), _TRIP_RE.search(ins.line)
                if m and m.group(1) in comps:
                    walk(comps[m.group(1)], mult * (int(t.group(1))
                                                    if t else 1))
                continue
            if op == "conditional":
                m = _BRANCHES_RE.search(ins.line)
                for b in (_OPERAND.findall(m.group(1)) if m else []):
                    if b in comps:
                        walk(comps[b], mult)
                continue
            if op == "call":
                m = _TO_RE.search(ins.line) or _CALLS_RE.search(ins.line)
                if m and m.group(1) in comps:
                    walk(comps[m.group(1)], mult)
                continue
            flops = 0.0
            if op in ("dot", "convolution"):
                flops = matmul_flops(ins, comp)
            elif op == "fusion":
                m = _CALLS_RE.search(ins.line)
                if m and m.group(1) in comps:
                    flops = _fusion_flops(comps[m.group(1)], comps, fseen)
            if flops:
                kind = "matmul"
            elif op == "custom-call" and "tpu_custom_call" in ins.line:
                kind = "kernel"
            elif any(op == c or op == c + "-start" for c in COLLECTIVES):
                kind = "collective"
            else:
                kind = "other"
            row = table.setdefault(ins.name, {"kind": kind, "flops": flops,
                                              "count": 0})
            row["count"] += mult
            if kind == "kernel":
                row["hbm_bytes"] = hbm_bytes(ins.result_type) + sum(
                    hbm_bytes(comp.types.get(o, "")) for o in ins.operands)

    walk(comps["__entry__"], 1)
    return table
