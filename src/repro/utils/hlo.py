"""Structural analysis of compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts every ``while`` body ONCE, so for
scan-over-layers models it under-reports FLOPs/bytes/collectives by roughly
the layer count.  This module parses the module text into computations,
recovers each while loop's trip count from its condition, propagates
loop multipliers down the call graph, and then accumulates:

- ``flops``            exact MXU flops of every ``dot`` (2 * |out| * K)
- ``bytes``            operand+output bytes of top-level ops (fusion
                       boundaries = the HBM-traffic approximation XLA
                       itself uses), copies included, bitcast/GTE excluded
- ``collective_bytes`` output-shape bytes per collective kind

all scaled by the product of enclosing loop trip counts.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w[\w.]*)\[([\d,]*)\]")
# an op line:  %name = <type> opcode(...operands...), attrs
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

_NO_TRAFFIC = {"bitcast", "get-tuple-element", "parameter", "constant",
               "tuple", "after-all", "partition-id", "replica-id", "iota"}


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_dims(type_str: str) -> Optional[List[int]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    rest: str          # operands + attrs, raw


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    defs: Dict[str, str] = field(default_factory=dict)   # op name -> type str


def parse_computations(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        if cur is None:
            hdr = _COMP_HDR_RE.match(line)
            if hdr and line.rstrip().endswith("{"):
                cur = Computation(hdr.group(1))
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            op = Op(m.group(1), m.group(2), m.group(3), m.group(4))
            cur.ops.append(op)
            cur.defs[op.name] = op.type_str
    return comps


def _callee(rest: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-]+)", rest)
    return m.group(1) if m else None


def _trip_count(cond: Computation, comps: Dict[str, Computation]) -> int:
    """Max s32 constant in the condition (or its fusion callees) — the scan
    bound in every XLA-lowered lax.scan/while with a static trip count."""
    best = 1
    blocks = [cond]
    for op in cond.ops:
        if op.opcode == "fusion":
            callee = _callee(op.rest, "calls")
            if callee and callee in comps:
                blocks.append(comps[callee])
    for blk in blocks:
        for op in blk.ops:
            if op.opcode == "constant" and op.type_str.startswith("s32[]"):
                c = re.match(r"(\d+)\)", op.rest)
                if c:
                    best = max(best, int(c.group(1)))
    return best


def _compute_multipliers(comps: Dict[str, Computation], entry: str
                         ) -> Dict[str, float]:
    mult: Dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    frontier = [entry]
    visited_edges = set()
    while frontier:
        name = frontier.pop()
        if name not in comps:
            continue
        comp = comps[name]
        m = mult[name]
        for op in comp.ops:
            targets: List[Tuple[str, float]] = []
            if op.opcode == "while":
                cond = _callee(op.rest, "condition")
                body = _callee(op.rest, "body")
                trips = _trip_count(comps[cond], comps) if cond in comps else 1
                for t in (body, cond):
                    if t:
                        targets.append((t, m * trips))
            else:
                for key in ("calls", "to_apply", "condition", "body"):
                    t = _callee(op.rest, key)
                    if t and t in comps:
                        targets.append((t, m))
                for blist in re.findall(r"branch_computations=\{([^}]*)\}",
                                        op.rest):
                    for t in re.findall(r"%?([\w.\-]+)", blist):
                        if t in comps:
                            targets.append((t, m))
            for t, tm in targets:
                if tm > mult[t] or (name, t) not in visited_edges:
                    mult[t] = max(mult[t], tm)
                    visited_edges.add((name, t))
                    frontier.append(t)
    return dict(mult)


def _find_entry(text: str, comps: Dict[str, Computation]) -> str:
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M)
    if m and m.group(1) in comps:
        return m.group(1)
    return next(iter(comps))


def _dot_flops(op: Op, comp: Computation) -> float:
    out_dims = _shape_dims(op.type_str) or []
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    operands = _OPERAND_RE.findall(op.rest.split(")", 1)[0])
    k = 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    if m and operands:
        lhs_type = comp.defs.get(operands[0])
        dims = _shape_dims(lhs_type) if lhs_type else None
        if dims:
            for idx in m.group(1).split(","):
                if idx:
                    k *= dims[int(idx)]
    return 2.0 * out_elems * k


@dataclass
class HloAnalysis:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: Dict[str, float] = field(default_factory=dict)
    collective_counts: Dict[str, int] = field(default_factory=dict)
    while_trips: List[int] = field(default_factory=list)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())


def analyze(text: str) -> HloAnalysis:
    comps = parse_computations(text)
    entry = _find_entry(text, comps)
    mult = _compute_multipliers(comps, entry)
    fusion_callees = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "fusion":
                target = _callee(op.rest, "calls")
                if target:
                    fusion_callees.add(target)

    # fusions whose root is a dynamic-(update-)slice are in-place slab
    # updates / slab reads: traffic is the slice, not the full accumulator
    def _root_opcode(comp_name: str) -> str:
        c = comps.get(comp_name)
        return c.ops[-1].opcode if c and c.ops else ""

    out = HloAnalysis()
    cb = defaultdict(float)
    cc = defaultdict(int)
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m == 0.0:
            continue
        in_fusion = comp.name in fusion_callees
        for op in comp.ops:
            base = re.sub(r"-(start|done)$", "", op.opcode)
            if base in COLLECTIVE_KINDS:
                if op.opcode.endswith("-done"):
                    continue
                cb[base] += m * _shape_bytes(op.type_str)
                cc[base] += int(m)
                continue
            if op.opcode == "dot":
                out.flops += m * _dot_flops(op, comp)
            if in_fusion:
                continue  # fusion internals are not HBM traffic
            if op.opcode in _NO_TRAFFIC or op.opcode == "while":
                continue
            out_bytes = _shape_bytes(op.type_str)
            opnd_bytes = []
            for operand in _OPERAND_RE.findall(op.rest.split("),", 1)[0]):
                t = comp.defs.get(operand)
                if t:
                    opnd_bytes.append(_shape_bytes(t))
            root = op.opcode
            if op.opcode == "fusion":
                root = _root_opcode(_callee(op.rest, "calls") or "")
            if root == "dynamic-update-slice" or (op.opcode == "fusion" and
                                                  "update-slice" in op.name):
                # in-place accumulator: read the slice-sized operands, write
                # the slice; the full-buffer operand is aliased, not moved
                small = [b for b in opnd_bytes if b < out_bytes]
                nbytes = 2 * max(sum(small), 1)
            elif root == "dynamic-slice" or (op.opcode == "fusion" and
                                             "dynamic-slice" in op.name and
                                             "update" not in op.name):
                # slab read: only the slice leaves HBM
                nbytes = 2 * out_bytes
            else:
                nbytes = out_bytes + sum(opnd_bytes)
            out.bytes += m * nbytes
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "while":
                cond = _callee(op.rest, "condition")
                if cond and cond in comps:
                    out.while_trips.append(_trip_count(comps[cond], comps))
    out.collective_bytes = dict(cb)
    out.collective_counts = dict(cc)
    return out


# ---------------------------------------------------------------------------
# flat counters (no loop scaling) — fast path + tests
# ---------------------------------------------------------------------------

_FLAT_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w[\w.]*)\[([\d,]*)\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Flat (no loop-trip scaling) output bytes per collective kind."""
    out: Dict[str, float] = defaultdict(float)
    for m in _FLAT_OP_RE.finditer(hlo_text):
        tuple_body, dtype, dims, kind = m.groups()
        if "-done(" in m.group(0):
            continue
        if tuple_body is not None:
            total = _shape_bytes("(" + tuple_body + ")")
        else:
            total = _shape_bytes(f"{dtype}[{dims}]")
        out[kind] += total
    return dict(out)


def collective_counts(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = defaultdict(int)
    for m in _FLAT_OP_RE.finditer(hlo_text):
        if "-done(" in m.group(0):
            continue
        out[m.group(4)] += 1
    return dict(out)



def collective_ops(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """Collective instructions of a compiled module, by kind, in 3 groups.

    - ``"scalar"``: every array rank 0 (metric reductions);
    - ``"spmd"``: payload collectives whose recorded source is an op outside
      any ``shard_map``, i.e. ones XLA's SPMD partitioner inserted (in a
      data-parallel step: the gradient all-reduce of ``auto`` mode);
    - ``"exchange"``: every other payload collective, i.e. those of
      ``shard_map`` bodies, including ones the compiler rewrote and left
      without a source (a reduce-scatter lowered to an all-reduce).

    Async pairs count once.  A collective the compiler fused into several
    consumers counts once per distinct type, not once per copy.
    """
    comps = parse_computations(hlo_text)
    fused = {_callee(op.rest, "calls") for comp in comps.values()
             for op in comp.ops if op.opcode == "fusion"}
    out: Dict[str, Dict[str, int]] = {
        g: defaultdict(int) for g in ("exchange", "spmd", "scalar")}
    seen_fused = set()
    for comp in comps.values():
        for op in comp.ops:
            kind = re.sub(r"-start$", "", op.opcode)
            if kind not in COLLECTIVE_KINDS:
                continue
            if comp.name in fused:
                key = (kind, re.sub(r"\{[^}]*\}", "", op.type_str))
                if key in seen_fused:
                    continue
                seen_fused.add(key)
            source = re.search(r'op_name="([^"]*)"', op.rest)
            if not any(m.group(2) for m in _SHAPE_RE.finditer(op.type_str)):
                group = "scalar"
            elif source and "shard_map" not in source.group(1):
                group = "spmd"
            else:
                group = "exchange"
            out[group][kind] += 1
    return {g: dict(v) for g, v in out.items()}


# ---------------------------------------------------------------------------
# layers of the training step, from its named scopes
# ---------------------------------------------------------------------------

# The ``jax.named_scope`` names that ``launch/train.py`` and
# ``parallel/grad_sync.py`` put around the parts of the training step.  A
# bucket's scope carries its index: ``exchange.bucket3``.
SCOPES = (MODEL, PACK, BUCKET, UNPACK, OPTIMIZER) = (
    "model", "exchange.pack", "exchange.bucket", "exchange.unpack", "optimizer")
# what ``layers`` maps an instruction to
LAYERS = ("forward", "backward", PACK, BUCKET, UNPACK, OPTIMIZER, "mixed",
          "none")

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# fused instructions that compute nothing of their own: XLA shares and
# hoists them between scopes, under names such as ``shard_map/broadcast.167``
_PLACES_DATA = ("parameter", "constant", "broadcast", "bitcast")
_BUCKET_RE = re.compile(re.escape(BUCKET) + r"\d+")


def scope_layer(op_name: str) -> str:
    """The layer an ``op_name`` records: the outermost program scope in it.
    Under ``model``, an op that ``value_and_grad`` transposed is backward
    (a forward recomputed inside the backward included), any other forward.
    No program scope: ``none``."""
    for part in op_name.split("/"):
        name = part.rstrip(")").rsplit("(", 1)[-1]   # transpose(jvp(model)) -> model
        if name == MODEL:
            return "backward" if part.startswith("transpose(") else "forward"
        if name in (PACK, UNPACK, OPTIMIZER):
            return name
        if _BUCKET_RE.fullmatch(name):
            return BUCKET
    return "none"


def layers(text: str) -> Dict[str, str]:
    """Instruction name -> layer (one of ``LAYERS``) for every instruction of
    every computation of a compiled module's text, ``while`` bodies
    included.  An instruction takes the layer of its ``op_name`` metadata;
    a fusion takes the one layer that every instruction fused into it
    records, ``mixed`` where they disagree, and its own where none records
    any.  Fused instructions that only place data (``_PLACES_DATA``) do not
    count, nor does an ``op_name`` that records no traced operation, one
    that does not start ``jit(``: an argument's path (``params['w']``) or
    an instruction name that XLA made up (``broadcast.182``)."""
    comps = parse_computations(text)
    own = {}
    for comp in comps.values():
        for op in comp.ops:
            m = _OP_NAME_RE.search(op.rest)
            own[op.name] = (scope_layer(m.group(1))
                            if m and m.group(1).startswith("jit(") else None)
    fused: Dict[str, set] = {}

    def recorded(comp_name: str) -> set:
        """Layers recorded inside a fused computation, nested fusions too."""
        if comp_name not in fused:
            fused[comp_name] = set()
            for op in comps[comp_name].ops:
                callee = _callee(op.rest, "calls") if op.opcode == "fusion" else None
                if callee:
                    fused[comp_name] |= recorded(callee)
                elif op.opcode not in _PLACES_DATA:
                    fused[comp_name] |= {own[op.name]} - {None}
        return fused[comp_name]

    out: Dict[str, str] = {}
    for comp in comps.values():
        for op in comp.ops:
            callee = _callee(op.rest, "calls") if op.opcode == "fusion" else None
            inside = recorded(callee) if callee else set()
            out[op.name] = ("mixed" if len(inside) > 1 else
                            next(iter(inside)) if inside else
                            own[op.name] or "none")
    return out
