"""HLO-text cost analysis with while-loop trip-count multiplication.

XLA's built-in HloCostAnalysis visits each while body ONCE, so scan-heavy
programs (scan over layers x microbatches x kv chunks) under-count FLOPs and
bytes by orders of magnitude. This module re-derives per-device costs from the
compiled (post-GSPMD, post-fusion) HLO text:

  * FLOPs: every `dot` = 2 * prod(result dims) * prod(contracting dims),
    multiplied by the product of enclosing loop trip counts.
  * HBM bytes: fusion boundaries are the HBM round-trips in XLA's execution
    model, so we sum operand+result bytes of every *top-level* instruction in
    non-fused computations (fusions count as one I/O event; their interiors
    don't touch HBM).
  * Collective bytes: result bytes of all-reduce (x2 for ring RS+AG),
    all-gather, reduce-scatter, all-to-all, collective-permute, likewise
    loop-weighted.

All values are per-device: GSPMD emits the partitioned per-device module.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_ONE = re.compile(r"(\w+)\[([\d,]*)\]")
#: a tuple shape may nest one level of parens: TPU layouts read
#: ``{1,0:T(8,128)S(1)}``
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\((?:[^()]|\([^()]*\))*\)|\S+?)"
    r"\s+([\w\-]+)\((.*)$")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->")
_PARAM = re.compile(r"%([\w.\-]+)\s*=\s*(\S+?)\s+parameter\(")
_TRIP = re.compile(r'known_trip_count[^0-9]*(\d+)')

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SKIP_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "add-dependency",
    "partition-id", "replica-id",
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_ONE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> List[int]:
    m = _SHAPE_ONE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    op: str
    rest: str           # text after the opening paren (operands + attrs)

    def operands(self) -> List[str]:
        depth = 1
        out = []
        for i, ch in enumerate(self.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    inner = self.rest[:i]
                    out = re.findall(r"%([\w.\-]+)", inner)
                    break
        return out

    def attrs(self) -> str:
        depth = 1
        for i, ch in enumerate(self.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return self.rest[i + 1:]
        return ""


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    shapes: Dict[str, str]


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = None
    for line in text.splitlines():
        if line.startswith("%") or line.startswith("ENTRY"):
            m = _COMP_HDR.match(line)
            if m:
                cur = Computation(m.group(1), [], {})
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry = cur.name
                continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if m:
            name, shape, op, rest = m.groups()
            cur.instrs.append(Instr(name, shape, op, rest))
            cur.shapes[name] = shape
    return comps, entry


def _cond_trip(cond: Optional[Computation]) -> Optional[int]:
    """Trip count ``N`` of a counted loop whose condition is ``i < N`` — for
    while loops the compiler left without ``known_trip_count`` (the TPU
    backend's compiled HLO)."""
    if cond is None or not cond.instrs:
        return None
    root = cond.instrs[-1]
    if root.op != "compare" or "direction=LT" not in root.rest:
        return None
    consts = {i.name: i for i in cond.instrs if i.op == "constant"}
    for name in root.operands():
        m = re.match(r"(\d+)\)", consts[name].rest) if name in consts \
            else None
        if m:
            return int(m.group(1))
    return None


def _multipliers(comps: Dict[str, Computation],
                 entry: Optional[str]) -> Tuple[Dict[str, float], set]:
    """Per-computation execution multiplier + the set of fused computations."""
    mult: Dict[str, float] = defaultdict(float)
    fused: set = set()
    if entry is None:
        return mult, fused
    mult[entry] = 1.0
    # collect fusion targets first (their ops don't count for HBM traffic)
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.op == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", ins.attrs())
                if m:
                    fused.add(m.group(1))

    # propagate multipliers (iterate to fixed point over the call DAG)
    for _ in range(64):
        changed = False
        for comp in list(comps.values()):
            base = mult.get(comp.name, 0.0)
            if base == 0.0:
                continue
            for ins in comp.instrs:
                attrs = ins.attrs()
                targets = []
                if ins.op == "while":
                    tm = _TRIP.search(attrs)
                    cm = re.search(r"condition=%?([\w.\-]+)", attrs)
                    trip = int(tm.group(1)) if tm else (_cond_trip(
                        comps.get(cm.group(1)) if cm else None) or 1)
                    for key in ("body", "condition"):
                        m = re.search(key + r"=%?([\w.\-]+)", attrs)
                        if m:
                            targets.append((m.group(1), trip))
                else:
                    for key in ("calls", "to_apply", "body", "condition",
                                "true_computation", "false_computation"):
                        m = re.search(key + r"=%?([\w.\-]+)", attrs)
                        if m:
                            targets.append((m.group(1), 1))
                for tgt, trip in targets:
                    new = base * trip
                    if new > mult.get(tgt, 0.0):
                        mult[tgt] = new
                        changed = True
        if not changed:
            break
    return mult, fused


def _instr_hbm_bytes(ins: Instr, comp: Computation,
                     comps: Dict[str, Computation]) -> float:
    """HBM bytes touched by one top-level instruction.

    Slice-aware: XLA reads/writes only the touched region of dynamic-slice /
    dynamic-update-slice (DUS aliases its big operand in place), so counting
    full operand shapes would overcount scan-carried buffers by the trip
    count. For fusions, operands consumed exclusively through dynamic-slice
    inside the fused computation count at slice size, and a DUS root aliases
    its buffer (only the update region is written)."""
    ops = ins.operands()

    if ins.op == "dynamic-slice":
        return 2.0 * _shape_bytes(ins.shape)          # read slice + write out
    if ins.op == "dynamic-update-slice":
        upd = _shape_bytes(comp.shapes.get(ops[1], "")) if len(ops) > 1 else 0
        return 2.0 * upd                              # read update + write region
    if ins.op == "gather":
        return 2.0 * _shape_bytes(ins.shape)
    if ins.op == "scatter":
        upd = _shape_bytes(comp.shapes.get(ops[-1], "")) if ops else 0
        return 2.0 * upd

    if ins.op == "fusion":
        m = re.search(r"calls=%?([\w.\-]+)", ins.attrs())
        called = comps.get(m.group(1)) if m else None
        if called is not None:
            by_name = {i.name: i for i in called.instrs}

            def _resolve(name, _seen=None):
                """Trace through convert/bitcast/copy to the source instr.

                XLA:CPU canonicalizes bf16 DUS as convert->f32 DUS->convert;
                on the TPU target the DUS is native and in-place, so the
                converts are lowering artifacts we see through."""
                while name in by_name and by_name[name].op in (
                        "convert", "bitcast", "copy"):
                    ops2 = by_name[name].operands()
                    if not ops2:
                        break
                    name = ops2[0]
                return name

            # map fusion operands to the called computation's parameters
            def _pidx(i):
                m2 = re.match(r"(\d+)\)", i.rest)
                return int(m2.group(1)) if m2 else 0
            pnames = [i.name for i in sorted(
                (i for i in called.instrs if i.op == "parameter"),
                key=_pidx)]
            pshape = dict(zip(pnames, (comp.shapes.get(o, "") for o in ops)))

            root = called.instrs[-1] if called.instrs else None
            aliased_param = None
            total = 0.0
            root_src = by_name.get(_resolve(root.name)) if root else None
            if root_src is not None and root_src.op == "dynamic-update-slice":
                rops = root_src.operands()
                aliased_param = _resolve(rops[0]) if rops else None
                upd_p = _resolve(rops[1]) if len(rops) > 1 else None
                # count update traffic at the ORIGINAL operand dtype
                upd_shape = pshape.get(upd_p) or (
                    called.shapes.get(rops[1], "") if len(rops) > 1 else "")
                total += 2.0 * _shape_bytes(upd_shape)
                if upd_p in pnames:
                    pnames = [p for p in pnames if p != upd_p]
            else:
                total += _shape_bytes(ins.shape)      # fusion output write
            for opname, pname in zip(ops, pnames):
                if pname == aliased_param:
                    continue                          # aliased in-place buffer
                uses = [i for i in called.instrs
                        if pname in i.operands() and i.op != "parameter"]
                src_ops = {_resolve(u.name) for u in uses}
                if uses and all(
                        u.op in ("dynamic-slice", "convert", "bitcast", "copy")
                        for u in uses):
                    # consumed via slices (possibly through converts)
                    ds = [i for i in called.instrs
                          if i.op == "dynamic-slice"]
                    sliced = [d for d in ds
                              if _resolve(d.operands()[0]) == pname]
                    if sliced:
                        total += sum(_shape_bytes(d.shape) for d in sliced)
                        continue
                    if all(u.op == "dynamic-slice" for u in uses):
                        total += sum(_shape_bytes(u.shape) for u in uses)
                        continue
                    total += _shape_bytes(comp.shapes.get(opname, ""))
                else:
                    total += _shape_bytes(comp.shapes.get(opname, ""))
            return total

    nbytes = _shape_bytes(ins.shape)
    for opname in ops:
        nbytes += _shape_bytes(comp.shapes.get(opname, ""))
    return nbytes


@dataclasses.dataclass
class HloCost:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    collectives: Dict[str, dict]
    dot_count: float
    hbm_top: List[dict] = dataclasses.field(default_factory=list)

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze_hlo(text: str) -> HloCost:
    comps, entry = parse_hlo(text)
    mult, fused = _multipliers(comps, entry)

    flops = 0.0
    hbm = 0.0
    dot_count = 0.0
    colls: Dict[str, dict] = {}
    contributors: List[tuple] = []

    for comp in comps.values():
        w = mult.get(comp.name, 0.0)
        if w == 0.0:
            continue
        in_fusion = comp.name in fused
        for ins in comp.instrs:
            # ---- FLOPs (dots count whether fused or not) -----------------
            if ins.op == "dot":
                dims = _shape_dims(ins.shape)
                ops = ins.operands()
                csize = 1
                m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
                if m and ops:
                    lhs_shape = comp.shapes.get(ops[0], "")
                    lhs_dims = _shape_dims(lhs_shape)
                    for c in m.group(1).split(","):
                        if c and int(c) < len(lhs_dims):
                            csize *= lhs_dims[int(c)]
                n = 1
                for d in dims:
                    n *= d
                flops += w * 2.0 * n * csize
                dot_count += w
            elif ins.op == "convolution":
                # rough: 2 * prod(result) * prod(kernel dims) / out_features
                dims = _shape_dims(ins.shape)
                ops = ins.operands()
                ksz = 1
                if len(ops) > 1:
                    for d in _shape_dims(comp.shapes.get(ops[1], "")):
                        ksz *= d
                n = 1
                for d in dims:
                    n *= d
                if dims:
                    flops += w * 2.0 * n * ksz / max(dims[-1], 1)

            # ---- collectives ---------------------------------------------
            base_op = ins.op[:-6] if ins.op.endswith("-start") else ins.op
            if base_op in _COLLECTIVES:
                nbytes = _shape_bytes(ins.shape)
                weight = 2.0 if base_op == "all-reduce" else 1.0
                rec = colls.setdefault(base_op, dict(count=0.0, bytes=0.0))
                rec["count"] += w
                rec["bytes"] += w * weight * nbytes

            # ---- HBM traffic (top-level, non-fused computations) ---------
            if in_fusion or ins.op in _SKIP_BYTES or ins.op.endswith("-done"):
                continue
            b = w * _instr_hbm_bytes(ins, comp, comps)
            hbm += b
            if b > 0:
                contributors.append((b, ins.name, ins.op, ins.shape[:64], w))

    contributors.sort(reverse=True)
    top = [dict(bytes=b, name=n, op=o, shape=s, mult=m)
           for b, n, o, s, m in contributors[:20]]
    cbytes = sum(v["bytes"] for v in colls.values())
    return HloCost(flops=flops, hbm_bytes=hbm, collective_bytes=cbytes,
                   collectives=colls, dot_count=dot_count, hbm_top=top)
