"""Trip-count-aware HLO cost analyzer.

XLA's ``HloCostAnalysis`` (surfaced through ``compiled.cost_analysis()``)
visits each ``while`` body exactly once, so any program built around
``lax.scan`` — all our models scan over layers, microbatches, and loss
chunks — under-reports FLOPs, HBM bytes, and collective traffic by the loop
trip counts.  This analyzer parses the post-optimization HLO text and walks
the computation graph *multiplying loop bodies by their trip counts*:

* trip count: jax scans lower to ``while`` ops whose condition is
  ``compare(get-tuple-element(iter), constant(N)), direction=LT`` with the
  counter starting at 0 — N is the trip count.  Unrecognized conditions
  conservatively count the body once.
* FLOPs: ``dot`` ops contribute 2 x prod(result dims) x prod(contracting
  dims) (batch dims are already part of the result).  Elementwise ops are
  counted at 1 flop per result element.
* HBM bytes: for ``fusion`` ops, operands + result only (inner instructions
  stay in registers/VMEM — this is the fused kernel's true traffic).  For
  top-level non-fused ops, operands + result.
* Collectives: bytes per device using ring accounting (see hlo.py),
  multiplied by enclosing trip counts.

The result is the honest per-device (FLOPs, bytes, collective bytes) that
§Roofline needs.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_TRIP_CFG = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"\}")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+"
                    r"([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w\.\-]+)")
_BODY = re.compile(r"body=%?([\w\.\-]+)")
_COND = re.compile(r"condition=%?([\w\.\-]+)")
_CONST_INT = re.compile(r"=\s*[su]\d+\[\]\s+constant\((\d+)\)")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS = re.compile(r"replica_groups=\{\{([^}]*)\}")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _type_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _type_elems(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE.findall(type_str):
        if dt not in _DTYPE_BYTES or dt == "token":
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def _dims_of(type_str: str) -> list[int]:
    m = _SHAPE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str                       # args + attributes


@dataclass
class Computation:
    name: str
    instrs: list[Instr] = field(default_factory=list)
    types: dict[str, str] = field(default_factory=dict)


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0         # per-device interconnect traffic
    coll_by_kind: dict = field(default_factory=dict)

    def add(self, other: "Cost", times: float = 1.0):
        self.flops += other.flops * times
        self.bytes += other.bytes * times
        self.coll_bytes += other.coll_bytes * times
        for k, v in other.coll_by_kind.items():
            self.coll_by_kind[k] = self.coll_by_kind.get(k, 0.0) + v * times


_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute"}
_FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "copy-done")


class HloCostModel:
    def __init__(self, hlo_text: str, debug: bool = False):
        self.comps = self._parse(hlo_text)
        self.entry = self._find_entry(hlo_text)
        self._memo: dict[str, Cost] = {}
        self.debug = debug
        self.charges: dict[str, float] = {}     # instr label -> bytes

    def _charge(self, comp_name: str, ins: "Instr", b: float, mult: float):
        if self.debug and b * mult > 0:
            key = f"{ins.op}:{comp_name[:24]}:{ins.name[:40]}"
            self.charges[key] = self.charges.get(key, 0.0) + b * mult

    # -- parsing ---------------------------------------------------------------

    def _parse(self, text: str) -> dict[str, Computation]:
        comps: dict[str, Computation] = {}
        cur: Optional[Computation] = None
        for line in text.splitlines():
            if not line.startswith(" ") and "->" in line and "{" in line:
                m = _COMP_HDR.match(line.strip())
                if m:
                    cur = Computation(m.group(1))
                    comps[cur.name] = cur
                    continue
            if cur is None:
                continue
            m = _INSTR.match(line)
            if m:
                name, type_str, op, rest = m.groups()
                cur.instrs.append(Instr(name, type_str.strip(), op, rest))
                cur.types[name] = type_str.strip()
        return comps

    def _find_entry(self, text: str) -> str:
        for line in text.splitlines():
            if line.startswith("ENTRY"):
                m = _COMP_HDR.match(line.strip()[len("ENTRY"):].strip() if
                                    False else line.strip())
                if m:
                    return m.group(1)
                m2 = re.match(r"ENTRY\s+%?([\w\.\-]+)", line)
                if m2:
                    return m2.group(1)
        # fallback: computation named 'main*'
        for name in self.comps:
            if name.startswith("main"):
                return name
        return next(iter(self.comps))

    # -- trip counts -------------------------------------------------------------

    def _trip_count(self, cond_name: str) -> float:
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1.0
        bound = None
        has_lt = False
        for ins in comp.instrs:
            if ins.op == "constant" and ins.type_str.rstrip(
                    "{}0,") .endswith("[]"):
                mm = re.match(r"(\d+)\)", ins.rest)
                if mm:
                    bound = int(mm.group(1))
            if ins.op == "compare" and "direction=LT" in ins.rest:
                has_lt = True
        return float(bound) if (bound is not None and has_lt) else 1.0

    # -- per-instruction costs -------------------------------------------------------

    def _args(self, rest: str) -> list[str]:
        """Operand names from the call args (up to the closing paren).

        Commas inside shape brackets/layouts (``f32[64,64]{1,0}``) are part
        of one operand, not separators — splitting on them detaches the
        operand *name* from its position, which broke positional lookups
        (dot lhs type -> contracting dims, fusion param -> caller operand).
        """
        depth, i, out, cur = 1, 0, [], []
        nest = 0                        # []/{} nesting inside one operand
        while i < len(rest) and depth > 0:
            ch = rest[i]
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            elif ch in "[{":
                nest += 1
            elif ch in "]}":
                nest -= 1
            elif ch == "," and depth == 1 and nest == 0:
                out.append("".join(cur).strip())
                cur = []
                i += 1
                continue
            cur.append(ch)
            i += 1
        if cur:
            out.append("".join(cur).strip())
        names = []
        for a in out:
            a = a.strip()
            if a.startswith("%"):
                a = a[1:]
            names.append(a.split(" ")[-1].lstrip("%"))
        return names

    def _operand_bytes(self, comp: Computation, ins: Instr) -> float:
        total = 0.0
        for a in self._args(ins.rest):
            t = comp.types.get(a)
            if t:
                total += _type_bytes(t)
        return total

    def _fusion_bytes(self, comp: Computation, ins: Instr,
                      inner_name: str) -> float:
        """HBM traffic of a fused kernel: slice-aware reads + in-place
        writes.

        A fusion operand that is only consumed by (dynamic-)slice/gather ops
        inside the fused computation is read at the *slice* size, not the
        full buffer (scans fuse ``dynamic-slice(stacked_params, i)`` into
        consumers — charging the full stacked tensor per trip would
        over-count by the layer count).  A fusion whose root is
        dynamic-update-slice writes only the updated window (in-place
        aliasing), not the whole carried buffer.
        """
        inner = self.comps.get(inner_name)
        if inner is None:
            return self._operand_bytes(comp, ins) + _type_bytes(ins.type_str)
        args = self._args(ins.rest)
        params: list[tuple[str, int]] = []
        for iins in inner.instrs:
            if iins.op == "parameter":
                mm = re.match(r"(\d+)\)", iins.rest)
                if mm:
                    params.append((iins.name, int(mm.group(1))))
        pnames = {n for n, _ in params}
        # resolve free views (bitcast/reshape chains) back to parameters
        viewof: dict[str, str] = {}

        def _base(name: str) -> str:
            while name in viewof:
                name = viewof[name]
            return name

        sliced: dict[str, float] = {}
        nonslice: set[str] = set()
        aliased: set[str] = set()
        for iins in inner.instrs:
            if iins.op == "parameter":
                continue
            iargs = self._args(iins.rest)
            # convert counts as a view INSIDE a fusion: fused dtype changes
            # never touch HBM (XLA:CPU wraps bf16 loop buffers in converts
            # that a TPU compile does not emit — charging them would bill
            # phantom traffic against the TPU roofline)
            if iins.op in ("bitcast", "reshape", "convert") and iargs:
                viewof[iins.name] = iargs[0]
                continue
            for j, a in enumerate(iargs):
                a = _base(a)
                if a not in pnames:
                    continue
                if iins.op in ("dynamic-slice", "slice", "gather"):
                    sliced[a] = sliced.get(a, 0.0) + _type_bytes(iins.type_str)
                elif iins.op == "dynamic-update-slice" and j == 0:
                    aliased.add(a)       # in-place destination: no read
                else:
                    nonslice.add(a)
        read = 0.0
        for pname, idx in params:
            full = _type_bytes(inner.types.get(pname, ""))
            if idx < len(args):
                t = comp.types.get(args[idx])
                if t:
                    full = _type_bytes(t)
            if pname in nonslice:
                read += full
            elif pname in sliced:
                read += min(full, sliced[pname])
            elif pname in aliased:
                pass                     # write-only destination
            else:
                read += full
        # in-place write reduction: a dus producing (a view of) the fusion
        # result writes only its update window (element-count match — dtype
        # converts around the dus change bytes but not logical identity)
        write = _type_bytes(ins.type_str)
        res_elems = _type_elems(ins.type_str)
        for iins in inner.instrs:
            if iins.op != "dynamic-update-slice":
                continue
            if abs(_type_elems(iins.type_str) - res_elems) <= \
                    0.01 * max(res_elems, 1):
                upd = self._args(iins.rest)
                if len(upd) >= 2:
                    ub = _type_bytes(inner.types.get(_base(upd[1]), ""))
                    if ub:
                        write = 2 * ub      # read window + write window
                break
        return read + write

    def _dot_flops(self, comp: Computation, ins: Instr) -> float:
        result_elems = _type_elems(ins.type_str)
        args = self._args(ins.rest)
        lhs_t = comp.types.get(args[0]) if args else None
        m = _LHS_CONTRACT.search(ins.rest)
        contract = 1
        if lhs_t and m and m.group(1):
            dims = _dims_of(lhs_t)
            for ci in m.group(1).split(","):
                ci = int(ci)
                if ci < len(dims):
                    contract *= dims[ci]
        return 2.0 * result_elems * contract

    def _collective_cost(self, ins: Instr) -> tuple[str, float]:
        kind = ins.op.replace("-start", "")
        rb = _type_bytes(ins.type_str)
        m = _GROUPS_IOTA.search(ins.rest)
        if m:
            g = int(m.group(2))
        else:
            m = _GROUPS.search(ins.rest)
            g = (len([x for x in m.group(1).split(",") if x.strip()])
                 if m else 2)
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-gather":
            moved = rb * frac
        elif kind == "reduce-scatter":
            moved = rb * (g - 1)
        elif kind == "all-reduce":
            moved = 2 * rb * frac
        elif kind == "all-to-all":
            moved = rb * frac
        else:
            moved = rb
        return kind, moved

    # -- computation walk ----------------------------------------------------------------

    def _local_cost(self, comp: Computation, ins: Instr,
                    top_level: bool) -> Optional[Cost]:
        """Cost of one non-control-flow instruction (None = control flow,
        handled by the walker)."""
        op = ins.op
        base_op = op.replace("-start", "")
        c = Cost()
        if base_op in _COLLECTIVES:
            kind, moved = self._collective_cost(ins)
            c.coll_bytes += moved
            c.coll_by_kind[kind] = moved
            c.bytes += _type_bytes(ins.type_str)
            return c
        if op in ("while", "call", "conditional"):
            return None
        if op == "fusion":
            m = _CALLS.search(ins.rest)
            if m:
                inner = self.cost_of(m.group(1), False)
                c.flops += inner.flops
                c.coll_bytes += inner.coll_bytes
                for k, v in inner.coll_by_kind.items():
                    c.coll_by_kind[k] = c.coll_by_kind.get(k, 0.0) + v
                c.bytes += self._fusion_bytes(comp, ins, m.group(1))
            else:
                c.bytes += (self._operand_bytes(comp, ins)
                            + _type_bytes(ins.type_str))
            return c
        if op in ("dynamic-slice", "slice", "gather"):
            c.bytes += 2 * _type_bytes(ins.type_str)
            return c
        if op in ("dynamic-update-slice", "scatter"):
            upd = self._args(ins.rest)
            ub = (_type_bytes(comp.types.get(upd[1], ""))
                  if len(upd) >= 2 else 0.0)
            c.bytes += 2 * (ub or _type_bytes(ins.type_str))
            return c
        if op == "dot":
            c.flops += self._dot_flops(comp, ins)
            if top_level:
                c.bytes += (self._operand_bytes(comp, ins)
                            + _type_bytes(ins.type_str))
            return c
        if op in ("sort", "rng", "reduce-window", "convolution"):
            c.flops += _type_elems(ins.type_str) * 4
            if top_level:
                c.bytes += (self._operand_bytes(comp, ins)
                            + _type_bytes(ins.type_str))
            return c
        if op not in _FREE:
            c.flops += _type_elems(ins.type_str)
            if top_level:
                c.bytes += (self._operand_bytes(comp, ins)
                            + _type_bytes(ins.type_str))
        return c

    def _trips_of(self, ins: Instr) -> float:
        m = _TRIP_CFG.search(ins.rest)
        if m:
            return float(m.group(1))            # XLA's own loop analysis
        cond = _COND.search(ins.rest)
        return self._trip_count(cond.group(1)) if cond else 1.0

    def cost_of(self, comp_name: str, top_level: bool = True) -> Cost:
        key = (comp_name, top_level)
        if key in self._memo:
            return self._memo[key]
        comp = self.comps.get(comp_name)
        total = Cost()
        if comp is None:
            return total
        self._memo[key] = total      # guards recursion
        for ins in comp.instrs:
            local = self._local_cost(comp, ins, top_level)
            if local is not None:
                total.add(local)
                continue
            if ins.op == "while":
                body = _BODY.search(ins.rest)
                if body:
                    total.add(self.cost_of(body.group(1), True),
                              self._trips_of(ins))
            else:                    # call / conditional
                for callee in _CALLS.findall(ins.rest):
                    total.add(self.cost_of(callee, True), 1.0)
        return total

    def total(self) -> Cost:
        return self.cost_of(self.entry, True)

    def debug_walk(self, comp_name: Optional[str] = None, mult: float = 1.0):
        """Record per-instruction byte charges (trip-aware) in .charges."""
        self.debug = True
        comp_name = comp_name or self.entry
        comp = self.comps.get(comp_name)
        if comp is None:
            return
        for ins in comp.instrs:
            local = self._local_cost(comp, ins, True)
            if local is not None:
                self._charge(comp_name, ins, local.bytes, mult)
                continue
            if ins.op == "while":
                body = _BODY.search(ins.rest)
                if body:
                    self.debug_walk(body.group(1), mult * self._trips_of(ins))
            else:
                for callee in _CALLS.findall(ins.rest):
                    self.debug_walk(callee, mult)

    def top_charges(self, n: int = 15) -> list[tuple[str, float]]:
        if not self.charges:
            self.debug_walk()
        return sorted(self.charges.items(), key=lambda kv: -kv[1])[:n]


def analyze(hlo_text: str) -> Cost:
    return HloCostModel(hlo_text).total()

