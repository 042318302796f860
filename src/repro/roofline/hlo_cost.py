"""Loop-aware HLO cost model.

XLA's `compiled.cost_analysis()` counts while-loop (lax.scan) bodies
ONCE — for an 88-layer scanned transformer that under-reports FLOPs,
bytes, and collective traffic by ~88x. This module walks the optimized
HLO text, recovers each while loop's trip count from its condition
computation (scan emits `compare(counter, constant(N)), direction=LT`),
and accumulates:

  flops      — dot ops: 2 * prod(result dims) * prod(contracting dims);
               elementwise at fusion granularity: prod(result dims).
  bytes      — HBM traffic model: operand + result bytes at fusion /
               top-level-op boundaries (XLA materializes exactly these).
  collective — operand bytes per collective kind, x trip counts.

Validated against closed-form counts in tests/test_roofline.py.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-$]+)\s+\(.*\)\s*->.*\{\s*$")

_DATA_MOVERS = {
    "dynamic-slice", "dynamic-update-slice", "gather", "scatter",
    "slice", "pad", "concatenate", "reshape", "transpose", "broadcast",
    "reverse", "select-and-scatter",
}

_KNOWN_OPS = {
    "dot", "fusion", "while", "conditional", "call", "custom-call",
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "copy", "convolution", "iota", "async-start", "async-done",
} | _DATA_MOVERS | set(_COLLECTIVES) | \
    {c + "-start" for c in _COLLECTIVES} | \
    {c + "-done" for c in _COLLECTIVES}

_CALL_TOKEN_RE = re.compile(r"([a-z][\w\-]*)\(")


def _shape_dims(shape_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _shape_dims(shape_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _numel(shape_str: str) -> int:
    total = 0
    for _, dims in _shape_dims(shape_str):
        n = 1
        for d in dims:
            n *= d
        total += n
    return total


@dataclasses.dataclass
class Op:
    name: str
    shape: str
    opcode: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    shapes: Dict[str, str]  # symbol table: op name -> result shape
    by_name: Dict[str, Op] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in _COLLECTIVES})

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        for k in _COLLECTIVES:
            self.coll[k] += o.coll[k]
        return self

    def scaled(self, f: float) -> "Cost":
        return Cost(self.flops * f, self.bytes * f,
                    {k: v * f for k, v in self.coll.items()})

    @property
    def coll_total(self) -> float:
        return sum(self.coll.values())


def _parse_op(ls: str) -> Optional[Op]:
    """Parse '%name = <shape> opcode(...), attrs' robustly: the opcode is
    the first known-op token followed by '('; unknown ops are 'generic'
    (elementwise/data-movement — costed from the result shape alone)."""
    if " = " not in ls:
        return None
    lhs, rest = ls.split(" = ", 1)
    name = lhs.strip()
    if name.startswith("ROOT "):
        name = name[5:].strip()
    name = name.lstrip("%")
    opcode, shape = None, None
    for m in _CALL_TOKEN_RE.finditer(rest):
        tok = m.group(1)
        if tok in _KNOWN_OPS:
            opcode, shape = tok, rest[: m.start()].strip()
            break
    if opcode is None:
        opcode, shape = "generic", rest
    return Op(name, shape, opcode, ls)


def parse_computations(hlo: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = ""
    for raw in hlo.splitlines():
        ls = raw.strip()
        m = _COMP_RE.match(ls)
        if m:
            cur = Computation(m.group(1), [], {})
            comps[cur.name] = cur
            if ls.startswith("ENTRY"):
                entry = cur.name
            continue
        if ls.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        op = _parse_op(ls)
        if op is not None:
            cur.ops.append(op)
            cur.shapes[op.name] = op.shape
            cur.by_name[op.name] = op
    return comps, entry


def _bf16_legalized(operand: str, comp: Computation,
                    comps: Dict[str, Computation]) -> bool:
    """True if `operand` (an f32 tensor) is really a bf16 value that the
    CPU backend upcast (no native bf16): its producer is convert(bf16) or
    a fusion whose ROOT is convert(bf16). Collectives on such values run
    in bf16 on TPU — count half the bytes."""
    op = comp.by_name.get(operand)
    if op is None:
        return False
    if op.opcode == "generic" and " convert(" in op.line:
        src = _operands(op.line)
        return bool(src) and "bf16[" in comp.shapes.get(src[0], "")
    if op.opcode == "fusion":
        callee = _called(op.line, "calls")
        sub = comps.get(callee)
        if sub is None:
            return False
        for o in sub.ops:
            if "ROOT" in o.line and " convert(" in o.line:
                src = _operands(o.line)
                return bool(src) and "bf16[" in sub.shapes.get(src[0], "")
    return False


def _called(line: str, attr: str) -> Optional[str]:
    m = re.search(attr + r"=%?([\w.\-$]+)", line)
    return m.group(1) if m else None


def _operands(line: str) -> List[str]:
    paren = line.find("(", line.find("=") + 1)
    if paren < 0:
        return []
    depth, j = 0, paren
    for j in range(paren, len(line)):
        depth += line[j] == "("
        depth -= line[j] == ")"
        if depth == 0:
            break
    return re.findall(r"%([\w.\-$]+)", line[paren + 1: j])


def trip_count(cond: Computation) -> int:
    """Scan-style condition: compare(counter, constant(N)) LT -> N."""
    consts = [int(m.group(1))
              for op in cond.ops
              for m in [re.search(r"constant\((\d+)\)", op.line)]
              if m]
    return max(consts) if consts else 1


def _dot_flops(op: Op, comp: Computation) -> float:
    result_elems = _numel(op.shape)
    opnds = _operands(op.line)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    if not m or not opnds:
        return 2.0 * result_elems  # fallback
    cdims = [int(d) for d in m.group(1).split(",") if d]
    lhs_shape = comp.shapes.get(opnds[0], "")
    dims = _shape_dims(lhs_shape)
    if not dims:
        return 2.0 * result_elems
    lhs_dims = dims[0][1]
    k = 1
    for c in cdims:
        if c < len(lhs_dims):
            k *= lhs_dims[c]
    return 2.0 * result_elems * k


def _eff_bytes(operand: str, comp: Computation,
               comps: Dict[str, Computation]) -> float:
    """Operand bytes with the CPU bf16->f32 legalization halving."""
    shape = comp.shapes.get(operand, "")
    b = _shape_bytes(shape)
    if "f32[" in shape and _bf16_legalized(operand, comp, comps):
        return b * 0.5
    return b


_PARAM_RE = re.compile(r"parameter\((\d+)\)")


def _fusion_param_charges(callee: str, comps: Dict[str, Computation]
                          ) -> Dict[int, float]:
    """Per-parameter-index byte charge for a fused computation.

    A parameter consumed ONLY via dynamic-slice / gather touches just the
    sliced region — charging the full operand would bill a scan body for
    its entire stacked (L, ...) weights EVERY iteration (measured 100x
    inflation on the rwkv6 cell)."""
    sub = comps.get(callee)
    if sub is None:
        return {}
    pidx: Dict[str, int] = {}
    for o in sub.ops:
        if o.opcode == "parameter":
            m = _PARAM_RE.search(o.line)
            if m:
                pidx[o.name] = int(m.group(1))
    charge: Dict[int, object] = {}
    for o in sub.ops:
        if o.opcode == "parameter":
            continue
        srcs = _operands(o.line)
        for pos, src in enumerate(srcs):
            if src not in pidx:
                continue
            i = pidx[src]
            sliced = (o.opcode in ("dynamic-slice", "gather")
                      and pos == 0)
            if sliced and charge.get(i) != "full":
                charge[i] = charge.get(i, 0.0) + 2.0 * _shape_bytes(o.shape)
            else:
                charge[i] = "full"
    return {i: v for i, v in charge.items() if v != "full"}


def comp_cost(name: str, comps: Dict[str, Computation],
              memo: Dict[str, Cost], fused: bool = False) -> Cost:
    """Cost of one computation. `fused=True` -> inside a fusion: count
    dot flops but no boundary bytes (counted at the fusion op)."""
    key = (name, fused)
    if key in memo:
        return memo[key]
    comp = comps.get(name)
    total = Cost()
    if comp is None:
        memo[key] = total
        return total
    for op in comp.ops:
        oc = op.opcode
        if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "copy", "iota") or oc.endswith("-done"):
            continue
        if oc == "while":
            body = _called(op.line, "body")
            cond = _called(op.line, "condition")
            n = trip_count(comps[cond]) if cond in comps else 1
            total += comp_cost(body, comps, memo).scaled(n)
            continue
        if oc == "conditional":
            branches = re.findall(r"branch_computations=\{([^}]*)\}",
                                  op.line)
            names = (re.findall(r"%?([\w.\-$]+)", branches[0])
                     if branches else [])
            tc = _called(op.line, "true_computation")
            fc = _called(op.line, "false_computation")
            names += [x for x in (tc, fc) if x]
            if names:
                costs = [comp_cost(b, comps, memo) for b in names]
                total += max(costs, key=lambda c: c.flops + c.bytes)
            continue
        if oc == "fusion":
            callee = _called(op.line, "calls")
            inner = comp_cost(callee, comps, memo, fused=True)
            total.flops += inner.flops
            if not fused:
                nbytes = _shape_bytes(op.shape)
                if "f32[" in op.shape and _bf16_legalized(op.name, comp,
                                                          comps):
                    nbytes *= 0.5
                charges = _fusion_param_charges(callee, comps)
                for pos, o in enumerate(_operands(op.line)):
                    if pos in charges:
                        nbytes += charges[pos]
                    else:
                        nbytes += _eff_bytes(o, comp, comps)
                total.bytes += nbytes
            continue
        if oc in ("call", "async-start", "async-done", "custom-call"):
            callee = _called(op.line, "calls") or \
                _called(op.line, "called_computations=\\{")
            if callee:
                total += comp_cost(callee, comps, memo, fused=fused)
            if not fused and oc != "call":
                total.bytes += _shape_bytes(op.shape)
            continue
        if oc in _DATA_MOVERS:
            # data movement: traffic = touched bytes (read + write of the
            # RESULT region), NOT operand bytes — a dynamic-slice of the
            # stacked (L, d, e) scan params touches one layer's slice.
            # For dynamic-update-slice the touched region is the update
            # operand (2nd), read+written in place under aliasing.
            if fused:
                continue
            if oc == "dynamic-update-slice":
                opnds = _operands(op.line)
                upd = (comp.shapes.get(opnds[1], "")
                       if len(opnds) > 1 else op.shape)
                total.bytes += 2.0 * _shape_bytes(upd)
            else:
                total.bytes += 2.0 * _shape_bytes(op.shape)
            continue
        kind = next((c for c in _COLLECTIVES if oc.startswith(c)), None)
        if kind is not None:
            nbytes = 0.0
            for o in _operands(op.line):
                b = _shape_bytes(comp.shapes.get(o, ""))
                if "f32[" in comp.shapes.get(o, "") and \
                        _bf16_legalized(o, comp, comps):
                    b *= 0.5  # CPU-backend bf16->f32 legalization artifact
                nbytes += b
            if nbytes == 0:
                nbytes = _shape_bytes(op.shape)
            # ring all-reduce moves ~2x the payload of RS/AG per chip
            total.coll[kind] += nbytes * (2.0 if kind == "all-reduce"
                                          else 1.0)
            total.bytes += _shape_bytes(op.shape)
            continue
        if oc == "dot":
            total.flops += _dot_flops(op, comp)
            if not fused:
                nbytes = _shape_bytes(op.shape)
                for o in _operands(op.line):
                    nbytes += _eff_bytes(o, comp, comps)
                total.bytes += nbytes
            continue
        if oc == "convolution":
            total.flops += 2.0 * _numel(op.shape) * 128  # rough
            if not fused:
                total.bytes += _shape_bytes(op.shape)
            continue
        # generic elementwise / data movement
        total.flops += _numel(op.shape)
        if not fused:
            nbytes = _shape_bytes(op.shape)
            for o in _operands(op.line):
                nbytes += _eff_bytes(o, comp, comps)
            total.bytes += nbytes
    memo[key] = total
    return total


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """XLA's own `compiled.cost_analysis()` as a {metric: value} dict
    ({} when XLA reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def analyze(hlo_text: str) -> Dict[str, float]:
    comps, entry = parse_computations(hlo_text)
    cost = comp_cost(entry, comps, {})
    out = {"flops": cost.flops, "bytes": cost.bytes,
           "collective_bytes": cost.coll_total}
    out.update({f"coll_{k}": v for k, v in cost.coll.items()})
    return out
