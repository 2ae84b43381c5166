"""Trip-count-corrected cost analysis over compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts each while-loop body ONCE, so a
scan-over-layers model under-reports FLOPs by ~the layer count.  This
module re-derives roofline inputs from ``compiled.as_text()``:

  * FLOPs: every ``dot`` contributes 2·|result|·|contracted dims|,
    recursively through fusions/calls, and while bodies are multiplied
    by their trip count (parsed from the loop-condition constant).
  * HBM bytes: post-fusion traffic model — each *top-level* op in a
    computation contributes |operands| + |result| bytes (a fusion is one
    unit: exactly its HBM reads/writes), while bodies × trip count.
    Parameters / constants / tuple plumbing are free.
  * Collective bytes: result-shape bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, × trip count when
    inside loop bodies, with a ring wire factor (2 for all-reduce).

The HLO here is the per-device partitioned module, so all numbers are
per-chip.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"([\w\-]+)\(")
_TUPLE_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(")
_OPERANDS = re.compile(r"\(((?:%?[\w.\-]+(?:,\s*)?)+)\)")
_CALLS = re.compile(r"(?:calls=|to_apply=|body=|condition=)%?([\w.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONSTANT = re.compile(r"constant\((\d+)\)")

_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "after-all", "partition-id", "replica-id",
             "get-dimension-size", "iota"}

_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "all-gather-start", "all-reduce-start",
                "collective-permute-start"}
_WIRE_FACTOR = {"all-reduce": 2.0}


@dataclass
class Instr:
    name: str
    dtype: str
    dims: Tuple[int, ...]
    op: str
    line: str
    operands: List[str] = field(default_factory=list)

    @property
    def result_bytes(self) -> int:
        if self.dtype == "tuple":
            return 0
        b = _DTYPE_BYTES.get(self.dtype, 4)
        for d in self.dims:
            b *= d
        return b


@dataclass
class CostResult:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0          # payload (result-shape) bytes
    collective_wire_bytes: float = 0.0     # ring-model wire bytes
    per_collective: Dict[str, float] = field(default_factory=dict)

    def scaled(self, k: float) -> "CostResult":
        return CostResult(
            self.flops * k, self.bytes * k, self.collective_bytes * k,
            self.collective_wire_bytes * k,
            {kk: v * k for kk, v in self.per_collective.items()})

    def add(self, other: "CostResult") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        self.collective_bytes += other.collective_bytes
        self.collective_wire_bytes += other.collective_wire_bytes
        for k, v in other.per_collective.items():
            self.per_collective[k] = self.per_collective.get(k, 0.0) + v


class HloModule:
    def __init__(self, text: str):
        self.computations: Dict[str, List[Instr]] = {}
        self.shape_of: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        self._parse(text)
        self._memo: Dict[str, CostResult] = {}
        self._fusion_memo: Dict[str, float] = {}
        self._trip_memo: Dict[str, int] = {}
        self._slice_memo: Dict[str, bool] = {}
        self._dus_memo: Dict[str, Optional[Instr]] = {}

    # -------------------------------------------------------------- parse
    def _parse(self, text: str) -> None:
        current: Optional[str] = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line or line.startswith("HloModule"):
                continue
            if not line.startswith(" ") and line.endswith("{"):
                m = _COMP_HDR.match(line.strip())
                if m:
                    current = m.group(1)
                    self.computations[current] = []
                    continue
            if line.strip() == "}":
                continue
            m = _INSTR.match(line)
            if m and current is not None:
                name, dtype, dims_s, op = m.groups()
                dims = tuple(int(d) for d in dims_s.split(",") if d)
                ins = Instr(name, dtype, dims, op, line)
                self.computations[current].append(ins)
                self.shape_of[name] = (dtype, dims)
            elif _TUPLE_INSTR.match(line) and current is not None:
                # tuple-shaped result (while, all-reduce-start tuples...)
                tm = _TUPLE_INSTR.match(line)
                opm = re.search(r"\)\s+([\w\-]+)\(", line)
                op = opm.group(1) if opm else "tuple"
                ins = Instr(tm.group(1), "tuple", (), op, line)
                self.computations[current].append(ins)
                self.shape_of[tm.group(1)] = ("tuple", ())

    # -------------------------------------------------------------- sizes
    def _shape_bytes(self, name: str) -> int:
        dtype, dims = self.shape_of.get(name, ("tuple", ()))
        if dtype == "tuple":
            return 0
        b = _DTYPE_BYTES.get(dtype, 4)
        for d in dims:
            b *= d
        return b

    @staticmethod
    def _operand_names(line: str, op: Optional[str] = None) -> List[str]:
        # non-regex: take the parenthesized list right after "op(", with
        # depth counting.  Anchoring on the op name matters for
        # tuple-shaped results, where "= (f32[...], ...) all-reduce(...)"
        # would otherwise hand back the tuple TYPE list.
        eq = line.find("= ")
        if op is not None:
            anchor = line.find(op + "(", eq if eq >= 0 else 0)
            start = line.find("(", anchor) if anchor >= 0 else -1
        else:
            start = line.find("(", eq if eq >= 0 else 0)
        if start < 0:
            return []
        depth, i = 1, start + 1
        while i < len(line) and depth:
            c = line[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
            i += 1
        inner = line[start + 1:i - 1]
        out = []
        for t in inner.split(","):
            t = t.strip()
            if not t:
                continue
            # tokens may be "%name" or "f32[2,3]{1,0} %name"
            name = t.split()[-1].lstrip("%")
            if name and (name[0].isalpha() or name[0] in "._"):
                out.append(name)
        return out

    # -------------------------------------------------------- trip counts
    def _trip_count(self, cond_name: str) -> int:
        """Max integer constant inside the loop condition (covers
        wrapped-fusion compares); 1 if none found (conservative)."""
        if cond_name in self._trip_memo:
            return self._trip_memo[cond_name]
        best = 0
        stack = [cond_name]
        seen = set()
        while stack:
            c = stack.pop()
            if c in seen or c not in self.computations:
                continue
            seen.add(c)
            for ins in self.computations[c]:
                for m in _CONSTANT.finditer(ins.line):
                    best = max(best, int(m.group(1)))
                cm = _CALLS.findall(ins.line)
                stack.extend(cm)
        best = max(best, 1)
        self._trip_memo[cond_name] = best
        return best

    # ---------------------------------------------------------- op costs
    def _dot_flops(self, ins: Instr) -> float:
        ops = self._operand_names(ins.line, ins.op)
        if not ops:
            return 0.0
        lhs_dtype, lhs_dims = self.shape_of.get(ops[0], ("f32", ()))
        m = _CONTRACT.search(ins.line)
        contract = 1
        if m:
            for idx in m.group(1).split(","):
                if idx and int(idx) < len(lhs_dims):
                    contract *= lhs_dims[int(idx)]
        result = 1
        for d in ins.dims:
            result *= d
        return 2.0 * result * contract

    # ------------------------------------------------------- computation
    def computation_cost(self, name: str) -> CostResult:
        if name in self._memo:
            return self._memo[name]
        total = CostResult()
        for ins in self.computations.get(name, []):
            total.add(self._instr_cost(ins, top_level=True))
        self._memo[name] = total
        return total

    def _fusion_flops(self, name: str) -> float:
        """FLOPs inside a fusion/called computation (bytes NOT counted —
        the fusion is one HBM unit)."""
        if name in self._fusion_memo:
            return self._fusion_memo[name]
        self._fusion_memo[name] = 0.0      # cycle guard
        total = 0.0
        for ins in self.computations.get(name, []):
            if ins.op == "dot":
                total += self._dot_flops(ins)
            elif ins.op == "fusion" or ins.op == "call":
                for c in _CALLS.findall(ins.line):
                    total += self._fusion_flops(c)
        self._fusion_memo[name] = total
        return total

    def _instr_cost(self, ins: Instr, *, top_level: bool) -> CostResult:
        r = CostResult()
        if ins.op in _FREE_OPS:
            return r
        if ins.op == "while":
            calls = dict(re.findall(r"(body|condition)=%?([\w.\-]+)",
                                    ins.line))
            body = calls.get("body")
            cond = calls.get("condition")
            trips = self._trip_count(cond) if cond else 1
            if body:
                r.add(self.computation_cost(body).scaled(trips))
            return r
        if ins.op in ("conditional", "call", "async-start"):
            for c in _CALLS.findall(ins.line):
                r.add(self.computation_cost(c))
            r.bytes += self._io_bytes(ins)
            return r
        # collective?
        base = ins.op.replace("-start", "")
        if base in {"all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute"}:
            payload = ins.result_bytes
            if payload == 0:  # tuple-shaped: sum operand sizes
                payload = sum(self._shape_bytes(o)
                              for o in self._operand_names(ins.line, ins.op))
            r.collective_bytes += payload
            r.collective_wire_bytes += _WIRE_FACTOR.get(base, 1.0) * payload
            r.per_collective[base] = r.per_collective.get(base, 0.) + payload
            r.bytes += self._io_bytes(ins)
            return r
        if ins.op.endswith("-done"):
            return r
        # fusion: HBM unit + inner flops
        if ins.op == "fusion":
            r.bytes += self._fusion_io_bytes(ins)
            for c in _CALLS.findall(ins.line):
                r.flops += self._fusion_flops(c)
            return r
        if ins.op == "dot":
            r.flops += self._dot_flops(ins)
        r.bytes += self._io_bytes(ins)
        return r

    def _io_bytes(self, ins: Instr) -> float:
        ops = self._operand_names(ins.line, ins.op)
        if ins.op in ("dynamic-slice", "slice"):
            # a slice reads only result_bytes from the source buffer (plus
            # scalar indices) — charging the whole operand would bill a
            # 128-trip scan for reading its full input every iteration
            return float(2 * ins.result_bytes
                         + sum(min(self._shape_bytes(o), ins.result_bytes)
                               for o in ops[1:]))
        return float(sum(self._shape_bytes(o) for o in ops)
                     + ins.result_bytes)

    def _fusion_io_bytes(self, ins: Instr) -> float:
        """Fusion HBM traffic.  In-place dynamic-update-slice fusions
        alias their big input buffer: charge only the updated slice
        (read update + write slice + small operands), not the full
        buffer twice."""
        callees = _CALLS.findall(ins.line)
        dus = self._find_dus(callees[0]) if callees else None
        if dus is not None and dus.result_bytes == ins.result_bytes:
            # in-place slab write (scan-output stacking): the buffer
            # operand aliases the result; real traffic is the update
            # slab (read source + write slot) + the small operands.
            # The DUS may sit under a no-op root (convert/bitcast), so
            # this matches anywhere in the fusion, not just the root.
            dus_ops = self._operand_names(dus.line, dus.op)
            update_b = (self._shape_bytes(dus_ops[1])
                        if len(dus_ops) > 1 else 0)
            ops = self._operand_names(ins.line, ins.op)
            small = sum(b for b in (self._shape_bytes(o) for o in ops)
                        if b < ins.result_bytes)
            return float(2 * update_b + small)
        # a fusion reading big buffers but producing a small result is a
        # slice-read pattern (scan bodies consuming their per-trip slab):
        # each operand contributes at most what the fusion can consume —
        # bounded by result_bytes when the operand dwarfs it and the
        # fusion contains a dynamic-slice of it.
        ops = self._operand_names(ins.line, ins.op)
        if callees and self._fusion_has_slice(callees[0]):
            # only operands that dwarf the result (>=8x) are treated as
            # slice-reads; reduction-style full reads stay fully charged
            total = float(ins.result_bytes)
            for o in ops:
                b = self._shape_bytes(o)
                if b >= 8 * max(ins.result_bytes, 1):
                    total += ins.result_bytes
                else:
                    total += b
            return total
        return self._io_bytes(ins)

    def _find_dus(self, comp: str) -> Optional[Instr]:
        """First dynamic-update-slice inside a fusion computation."""
        if comp not in self._dus_memo:
            found = None
            for ins in self.computations.get(comp, []):
                if ins.op == "dynamic-update-slice":
                    found = ins
                    break
            self._dus_memo[comp] = found
        return self._dus_memo[comp]

    def _fusion_has_slice(self, comp: str) -> bool:
        if comp not in self._slice_memo:
            self._slice_memo[comp] = any(
                ins.op in ("dynamic-slice", "slice")
                for ins in self.computations.get(comp, []))
        return self._slice_memo[comp]

    # --------------------------------------------------------------- API
    def entry_cost(self) -> CostResult:
        entry = None
        for name in self.computations:
            if name.startswith("main") or entry is None:
                if name.startswith("main"):
                    entry = name
        if entry is None:
            entry = next(iter(self.computations))
        return self.computation_cost(entry)


def analyze(hlo_text: str) -> dict:
    mod = HloModule(hlo_text)
    cost = mod.entry_cost()
    return {
        "flops": cost.flops,
        "bytes": cost.bytes,
        "collective_bytes": cost.collective_bytes,
        "collective_wire_bytes": cost.collective_wire_bytes,
        "per_collective": cost.per_collective,
    }
