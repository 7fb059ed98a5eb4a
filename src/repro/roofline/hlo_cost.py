"""Trip-count-aware cost extraction from optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts while-loop (lax.scan) bodies
*once*, which understates FLOPs/bytes/collectives of scan-over-layers
models by ~L×.  This module re-derives totals by parsing the optimized
HLO module: per-computation instruction lists, a call graph (while /
fusion / call / conditional), and ``known_trip_count`` backend configs,
then accumulates

    flops             dot/cdot (2·M·N·K), elementwise/reduce (result size)
    bytes             operand + result bytes per non-fused instruction
                      (fusion internals are VMEM-resident: callsite only)
    collective bytes  operand shard bytes per collective × trip counts

Validated against cost_analysis on unrolled graphs (tests).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_LHS_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->\s*\S.*\{\s*$")


def _split_rhs(rhs: str):
    """'TYPE op(operands...)attrs' → (type_str, op, rest).  TYPE may be a
    tuple containing parens/comments, so split with paren counting."""
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        tstr, rest = rhs[:end + 1], rhs[end + 1:].lstrip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        tstr, rest = rhs[:sp], rhs[sp + 1:].lstrip()
    om = re.match(r"([\w\-]+)\((.*)$", rest)
    if not om:
        return None
    return tstr, om.group(1), om.group(2)

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "tanh", "rsqrt", "sqrt", "log", "power", "negate",
    "abs", "floor", "ceil", "round-nearest-even", "compare", "select",
    "and", "or", "xor", "clamp", "sign", "cosine", "sine", "logistic",
    "expm1", "log1p", "atan2", "remainder", "cbrt", "erf",
}
REDUCE_LIKE = {"reduce", "reduce-window", "cumsum"}
# pseudo-ops that move no HBM bytes themselves (aliases / tuple plumbing)
NO_BYTES_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                "constant", "iota", "while", "conditional", "call",
                "after-all", "partition-id", "replica-id", "custom-call",
                "opt-barrier", "domain", "rng-bit-generator"}
COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "all-reduce-start", "all-gather-start",
               "reduce-scatter-start", "collective-permute-start",
               "all-to-all-start"}


def _shape_elems_bytes(type_str: str) -> Tuple[int, int]:
    elems = 0
    byts = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        byts += n * _DTYPE_BYTES[dt]
    return elems, byts


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str                      # operands + attributes raw text
    operands: List[str]


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    collective_counts: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    cross_pod_bytes: float = 0.0

    def add(self, other: "Totals", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0) \
                + v * mult
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) \
                + v * mult
        self.cross_pod_bytes += other.cross_pod_bytes * mult


class HloModule:
    def __init__(self, text: str):
        self.computations: Dict[str, List[Instr]] = {}
        self.result_type: Dict[str, str] = {}
        self.entry: Optional[str] = None
        self._parse(text)

    def _parse(self, text: str) -> None:
        cur: Optional[str] = None
        for line in text.splitlines():
            cm = _COMP_RE.match(line)
            if cm and ("->" in line) and line.rstrip().endswith("{"):
                cur = cm.group(1)
                self.computations[cur] = []
                if line.lstrip().startswith("ENTRY"):
                    self.entry = cur
                continue
            if line.strip() == "}":
                cur = None
                continue
            lm = _LHS_RE.match(line)
            if lm and cur is not None:
                name, rhs = lm.groups()
                parts = _split_rhs(rhs)
                if parts is None:
                    continue
                tstr, op, rest = parts
                # operand refs live before the closing paren of the call
                depth = 1
                end = len(rest)
                for i, ch in enumerate(rest):
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            end = i
                            break
                operands = re.findall(r"%([\w\.\-]+)", rest[:end])
                inst = Instr(name, tstr, op, rest, operands)
                self.computations[cur].append(inst)
                self.result_type[name] = tstr

    # ------------------------------------------------------------- costs
    def _dot_flops(self, inst: Instr) -> float:
        elems, _ = _shape_elems_bytes(inst.type_str)
        k = 1
        m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", inst.rest)
        if m and inst.operands:
            lhs_t = self.result_type.get(inst.operands[0], "")
            sm = _SHAPE_RE.search(lhs_t)
            if sm and sm.group(2):
                dims = [int(d) for d in sm.group(2).split(",")]
                for ci in m.group(1).split(","):
                    if ci and int(ci) < len(dims):
                        k *= dims[int(ci)]
        return 2.0 * elems * k

    def _instr_totals(self, inst: Instr, in_fusion: bool,
                      pod_group_size: Optional[int]) -> Totals:
        t = Totals()
        elems, rbytes = _shape_elems_bytes(inst.type_str)
        op = inst.op
        base = op.replace("-start", "")
        if base in COLLECTIVES or op in COLLECTIVES:
            ob = 0
            for o in inst.operands:
                _, b = _shape_elems_bytes(self.result_type.get(o, ""))
                ob += b
            if ob == 0:
                ob = rbytes
            key = base
            t.collective_bytes[key] = t.collective_bytes.get(key, 0) + ob
            t.collective_counts[key] = t.collective_counts.get(key, 0) + 1
            gm = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", inst.rest)
            if pod_group_size and gm and int(gm.group(2)) == pod_group_size:
                t.cross_pod_bytes += ob
            t.bytes += rbytes + ob
            return t
        if op == "dot":
            t.flops += self._dot_flops(inst)
        elif op == "convolution":
            t.flops += 2.0 * elems  # lower bound; LM models don't use it
        elif op in ELEMENTWISE or op in REDUCE_LIKE:
            t.flops += elems
        if not in_fusion and op not in NO_BYTES_OPS:
            # slice-aware traffic: windowed reads/writes touch the window,
            # not the whole buffer (scan-stacked params/grad accumulators)
            if op in ("dynamic-slice", "slice", "gather"):
                t.bytes += 2.0 * rbytes
            elif op == "dynamic-update-slice":
                ub = 0
                if len(inst.operands) > 1:
                    _, ub = _shape_elems_bytes(
                        self.result_type.get(inst.operands[1], ""))
                t.bytes += 2.0 * (ub or rbytes)
            elif op == "scatter":
                upd = 0
                if len(inst.operands) > 2:
                    _, upd = _shape_elems_bytes(
                        self.result_type.get(inst.operands[2], ""))
                t.bytes += 2.0 * (upd or rbytes)
            else:
                ob = 0
                for o in inst.operands:
                    _, b = _shape_elems_bytes(self.result_type.get(o, ""))
                    ob += b
                t.bytes += rbytes + ob
        return t

    def totals_for(self, comp: str, pod_group_size: Optional[int] = None,
                   _depth: int = 0) -> Totals:
        t = Totals()
        if comp not in self.computations or _depth > 32:
            return t
        for inst in self.computations[comp]:
            if inst.op == "while":
                body = re.search(r"body=%?([\w\.\-]+)", inst.rest)
                trips = 1
                tm = re.search(
                    r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"(\d+)"',
                    inst.rest)
                if tm:
                    trips = int(tm.group(1))
                if body:
                    t.add(self.totals_for(body.group(1), pod_group_size,
                                          _depth + 1), trips)
                continue
            if inst.op == "fusion":
                called = re.search(r"calls=%?([\w\.\-]+)", inst.rest)
                if called:
                    sub = self._fusion_totals(called.group(1),
                                              pod_group_size, _depth + 1)
                    t.add(sub)
                    t.bytes += self._fusion_hbm_bytes(called.group(1), inst)
                continue
            if inst.op in ("call", "conditional", "async-start"):
                for target in re.findall(
                        r"(?:to_apply|calls|branch_computations=\{|"
                        r"true_computation|false_computation)=?%?"
                        r"([\w\.\-]+)", inst.rest):
                    t.add(self.totals_for(target, pod_group_size,
                                          _depth + 1))
                continue
            t.add(self._instr_totals(inst, in_fusion=False,
                                     pod_group_size=pod_group_size))
        return t

    def _fusion_hbm_bytes(self, comp: str, callsite: Instr) -> float:
        """HBM traffic of one fusion call: result write + per-parameter
        reads.  A parameter consumed only through dynamic-slice / slice /
        gather contributes just the sliced bytes (the scan-over-layers
        stacked-params pattern); otherwise the full operand is read."""
        _, rbytes = _shape_elems_bytes(callsite.type_str)
        instrs = self.computations.get(comp, [])
        by_name = {i.name: i for i in instrs}

        def chase_producer(inst):
            """Walk back through dtype converts/bitcasts (free on TPU —
            CPU XLA's float normalization materializes them)."""
            seen = 0
            while inst.op in ("convert", "bitcast", "copy") and \
                    inst.operands and inst.operands[0] in by_name and \
                    seen < 8:
                inst = by_name[inst.operands[0]]
                seen += 1
            return inst

        # in-place dynamic-update-slice root (possibly behind converts):
        # the write is the update slice, not the whole stacked buffer
        if instrs:
            root = chase_producer(instrs[-1])
            if root.op == "dynamic-update-slice" and len(root.operands) > 1:
                upd = by_name.get(root.operands[1])
                if upd is not None:
                    _, ub = _shape_elems_bytes(upd.type_str)
                    if ub:
                        rbytes = ub
        total = float(rbytes)
        # map param name -> param index
        param_idx: Dict[str, int] = {}
        for inst in instrs:
            if inst.op == "parameter":
                m = re.match(r"\s*(\d+)", inst.rest)
                if m:
                    param_idx[inst.name] = int(m.group(1))
        consumers: Dict[str, List[Instr]] = {}
        all_consumers: Dict[str, List[Instr]] = {}
        for inst in instrs:
            for o in inst.operands:
                all_consumers.setdefault(o, []).append(inst)

        def chase_consumer(inst):
            """Walk forward through single-consumer convert/bitcast/copy
            chains to the semantic consumer."""
            seen = 0
            while inst.op in ("convert", "bitcast", "copy") and seen < 8:
                nxt = all_consumers.get(inst.name, [])
                if len(nxt) != 1:
                    break
                inst = nxt[0]
                seen += 1
            return inst

        for inst in instrs:
            for o in inst.operands:
                if o in param_idx:
                    consumers.setdefault(o, []).append(
                        chase_consumer(inst))
        for pname, idx in param_idx.items():
            if idx >= len(callsite.operands):
                continue
            _, full = _shape_elems_bytes(
                self.result_type.get(callsite.operands[idx], ""))
            cons = consumers.get(pname, [])
            if cons and all(c.op in ("dynamic-slice", "slice", "gather")
                            for c in cons):
                sliced = 0
                for c in cons:
                    _, b = _shape_elems_bytes(c.type_str)
                    sliced += b
                total += min(sliced, full)
            elif cons and all(c.op == "dynamic-update-slice"
                              for c in cons):
                upd = 0
                for c in cons:
                    if len(c.operands) > 1:
                        _, b = _shape_elems_bytes(
                            self.result_type.get(c.operands[1], ""))
                        upd += b
                total += min(upd, full) if upd else full
            else:
                total += full
        return total

    def _fusion_totals(self, comp: str, pod_group_size, _depth) -> Totals:
        """FLOPs (not bytes) of a fused computation's instructions."""
        t = Totals()
        if comp not in self.computations or _depth > 32:
            return t
        for inst in self.computations[comp]:
            if inst.op == "fusion":
                called = re.search(r"calls=%?([\w\.\-]+)", inst.rest)
                if called:
                    t.add(self._fusion_totals(called.group(1),
                                              pod_group_size, _depth + 1))
                continue
            t.add(self._instr_totals(inst, in_fusion=True,
                                     pod_group_size=pod_group_size))
        return t


def analyze_hlo(text: str, pod_group_size: Optional[int] = None) -> Totals:
    mod = HloModule(text)
    if mod.entry is None:
        return Totals()
    return mod.totals_for(mod.entry, pod_group_size)
