"""Loop-aware HLO cost analysis.

XLA's built-in ``compiled.cost_analysis()`` counts a ``while`` body ONCE,
so any module that scans over layers/microbatches under-reports FLOPs,
bytes and collective traffic by the trip count (verified: a lax.scan of 8
matmuls reports 1/8th the flops of the unrolled version).  Rooflines built
on it would be fiction.  This module re-derives the three roofline inputs
from the optimized HLO text with while-loop bodies scaled by their trip
counts:

  * flops     — dot (2*M*N*K via contracting dims + symbol table),
                convolution, 1/elem for arithmetic elementwise, reduce;
  * hbm bytes — per materialized op: operand bytes + output bytes, where a
                fusion counts only its boundary (internals stay on-chip) —
                a structural post-fusion HBM-traffic model;
  * collective bytes — all-gather/all-reduce/reduce-scatter/all-to-all/
                collective-permute output bytes, ICI vs DCN by replica
                groups (pod boundary at device id // 256).

Compiled HLO does not annotate operand shapes at use sites, so each
computation builds a symbol table (params + op results) first.

Trip counts come from the canonical scan condition
(``compare(iv, constant(N)), direction=LT``); unrecognized loops fall back
to trip=1 and are flagged in ``Cost.unknown_trip``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from .hlo_stats import _DTYPE_BYTES, _crosses_pod

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_EW_ARITH = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "rsqrt", "sqrt", "power", "cosine", "sine",
    "select", "compare", "and", "or", "xor", "not", "clamp", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "remainder", "atan2",
    "cbrt", "erf", "sign",
}

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier", "iota",
}

_SHAPE_TOK = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[\w]+\[[\d,]*\]\S*)\s+"
    r"([\w\-]+)\((.*)$")
_HDR = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*\S.*\{")
_CALLED = re.compile(r"(?:body|condition|to|calls)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRUEFALSE = re.compile(r"(?:true|false)_computation=%?([\w.\-]+)")
_OPERAND_NAME = re.compile(r"%([\w.\-]+)")


def _shape_elems_bytes(shape_str: str):
    """(total elements, total bytes) across every typed shape in the str."""
    elems = byts = 0
    for dt, dims in _SHAPE_TOK.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        byts += n * _DTYPE_BYTES[dt]
    return elems, byts


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_ici: float = 0.0
    coll_dcn: float = 0.0
    coll_counts: dict = field(default_factory=dict)
    unknown_trip: int = 0

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.coll_ici += o.coll_ici
        self.coll_dcn += o.coll_dcn
        for k, v in o.coll_counts.items():
            self.coll_counts[k] = self.coll_counts.get(k, 0) + v
        self.unknown_trip += o.unknown_trip
        return self

    def scaled(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k, self.coll_ici * k,
                    self.coll_dcn * k,
                    {n: c * k for n, c in self.coll_counts.items()},
                    self.unknown_trip)


@dataclass
class _Op:
    name: str
    out: str
    kind: str
    rest: str


@dataclass
class _Comp:
    ops: list
    symtab: dict      # name -> shape string (params + results)


def _parse_params(params_str: str) -> dict:
    """'x.1: f32[256,256], ws: (f32[2], s32[])' -> {name: shape-str}."""
    out = {}
    # split on top-level commas
    depth = 0
    cur = ""
    parts = []
    for ch in params_str:
        if ch in "([":
            depth += 1
        elif ch in ")]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    if cur.strip():
        parts.append(cur)
    for p in parts:
        if ":" in p:
            nm, sh = p.split(":", 1)
            out[nm.strip().lstrip("%")] = sh.strip()
    return out


def _split_computations(text: str) -> dict[str, _Comp]:
    comps: dict[str, _Comp] = {}
    cur: _Comp | None = None
    for line in text.splitlines():
        s = line.rstrip()
        if not s:
            continue
        hm = _HDR.match(s)
        if hm and "=" not in s[: s.find("(")]:
            cur = _Comp([], _parse_params(hm.group(2)))
            comps[hm.group(1)] = cur
            continue
        if s.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        om = _OP_LINE.match(s)
        if om:
            op = _Op(om.group(1), om.group(2), om.group(3), om.group(4))
            cur.ops.append(op)
            cur.symtab[op.name] = op.out
    return comps


def _trip_count(cond: _Comp | None) -> int | None:
    """Fallback when known_trip_count is absent: the int constant feeding a
    direction=LT compare (possibly through a wrapped fusion)."""
    if cond is None:
        return None
    consts: dict[str, int] = {}
    for op in cond.ops:
        if op.kind == "constant":
            m = re.match(r"(\-?\d+)\)", op.rest)
            if m:
                consts[op.name] = int(m.group(1))
    for op in cond.ops:
        if (op.kind == "compare" and "direction=LT" in op.rest) or \
                (op.kind == "fusion" and "compare" in op.name):
            for nm, v in consts.items():
                if re.search(rf"%{re.escape(nm)}\b", op.rest):
                    return v
    if len(consts) == 1:
        return next(iter(consts.values()))
    return None


def _operand_names(rest: str) -> list[str]:
    seg = rest
    cut = seg.find(")")
    if cut != -1:
        seg = seg[:cut]
    return _OPERAND_NAME.findall(seg)


_PASS_THROUGH = {"bitcast", "reshape", "copy", "transpose", "convert"}
_SLICERS = {"dynamic-slice", "slice", "gather"}


class HloCostModel:
    def __init__(self, text: str):
        self.comps = _split_computations(text)
        self._memo: dict[str, Cost] = {}
        self._fb_memo: dict[tuple, float] = {}

    def _fusion_boundary_bytes(self, op: _Op, comp: _Comp,
                               fname: str) -> float:
        """HBM bytes at a fusion boundary, slice-aware:

        * an input that the fused computation only *slices* (a scan body
          dynamic-slicing one layer out of stacked weights) costs the slice
          bytes, not the whole operand;
        * a fusion rooted in dynamic-update-slice (in-place carry update)
          costs the updated region twice, not the whole carry.
        """
        fc = self.comps.get(fname)
        _, out_bytes_full = _shape_elems_bytes(op.out)
        if fc is None:
            in_b = sum(_shape_elems_bytes(s)[1]
                       for s in self._operand_shapes(op, comp))
            return float(in_b + out_bytes_full)

        key = (fname, op.out)
        if key in self._fb_memo:
            return self._fb_memo[key]

        # consumer map inside the fused computation
        consumers: dict[str, list[_Op]] = {}
        for o in fc.ops:
            for nm in _operand_names(o.rest):
                consumers.setdefault(nm, []).append(o)

        def slice_limited_bytes(pname: str) -> float | None:
            """If every (transitive through pass-through ops) consumer of
            the parameter is a slicer, return the summed slice bytes."""
            total = 0.0
            stack = [pname]
            seen = set()
            while stack:
                nm = stack.pop()
                if nm in seen:
                    continue
                seen.add(nm)
                for o in consumers.get(nm, []):
                    if o.kind in _SLICERS:
                        total += _shape_elems_bytes(o.out)[1]
                    elif o.kind in _PASS_THROUGH:
                        stack.append(o.name)
                    elif o.kind == "dynamic-update-slice":
                        # param used as the *operand being updated*: traffic
                        # is the update region (handled on the output side)
                        ops_in = _operand_names(o.rest)
                        if ops_in and ops_in[0] == nm:
                            continue
                        return None
                    else:
                        return None
            return total

        # inputs
        params = [o for o in fc.ops if o.kind == "parameter"]
        pnames = {o.name for o in params}
        in_bytes = 0.0
        opshapes = self._operand_shapes(op, comp)
        for i, o in enumerate(params):
            full = _shape_elems_bytes(o.out)[1]
            lim = slice_limited_bytes(o.name)
            in_bytes += min(full, lim) if lim is not None else full
        # output: DUS-rooted fusions move only the updated region.  The
        # root may be wrapped in pass-through ops (convert(DUS(...)) — an
        # XLA:CPU artifact; in-place on the TPU target), so walk back.
        root = fc.ops[-1] if fc.ops else None
        by_name = {o.name: o for o in fc.ops}
        hops = 0
        while root is not None and root.kind in _PASS_THROUGH and hops < 8:
            prev = _operand_names(root.rest)
            root = by_name.get(prev[0]) if prev else None
            hops += 1
        out_bytes = float(out_bytes_full)
        if root is not None and root.kind == "dynamic-update-slice":
            unames = _operand_names(root.rest)
            if len(unames) > 1:
                upd = _shape_elems_bytes(fc.symtab.get(unames[1], ""))[1]
                out_bytes = float(2 * upd)
        elif root is not None and root.kind == "tuple":
            parts = 0.0
            for nm in _operand_names(root.rest):
                o = by_name.get(nm)
                h = 0
                while o is not None and o.kind in _PASS_THROUGH and h < 8:
                    prev = _operand_names(o.rest)
                    o2 = by_name.get(prev[0]) if prev else None
                    if o2 is None:
                        break
                    o, h = o2, h + 1
                if o is not None and o.kind == "dynamic-update-slice":
                    un = _operand_names(o.rest)
                    upd = _shape_elems_bytes(fc.symtab.get(un[1], ""))[1] \
                        if len(un) > 1 else 0
                    parts += 2 * upd
                else:
                    parts += _shape_elems_bytes(
                        fc.symtab.get(nm, ""))[1] if o else 0
            if parts:
                out_bytes = float(parts)
        res = float(in_bytes + out_bytes)
        self._fb_memo[key] = res
        return res

    # -- per-op ------------------------------------------------------------
    def _operand_shapes(self, op: _Op, comp: _Comp) -> list[str]:
        return [comp.symtab.get(nm, "") for nm in _operand_names(op.rest)]

    def _op_cost(self, op: _Op, comp: _Comp) -> Cost:
        c = Cost()
        kind = op.kind
        if kind in _FREE_OPS:
            return c
        out_elems, out_bytes = _shape_elems_bytes(op.out)
        opshapes = self._operand_shapes(op, comp)
        # ---- flops
        if kind == "dot":
            k = 1
            m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
            lhs_dims = []
            if opshapes:
                sm = _SHAPE_TOK.search(opshapes[0])
                if sm:
                    lhs_dims = [int(d) for d in sm.group(2).split(",") if d]
            if m and lhs_dims:
                for idx in m.group(1).split(","):
                    if idx and int(idx) < len(lhs_dims):
                        k *= lhs_dims[int(idx)]
            c.flops = 2.0 * out_elems * k
        elif kind == "convolution":
            ksz = 1
            m = re.search(r"window=\{size=([\dx]+)", op.rest)
            if m:
                for d in m.group(1).split("x"):
                    ksz *= int(d)
            ci = 1
            if len(opshapes) >= 2:
                sm = _SHAPE_TOK.search(opshapes[1])
                if sm:
                    rdims = [int(d) for d in sm.group(2).split(",") if d]
                    if len(rdims) >= 2:
                        ci = rdims[-2]
            c.flops = 2.0 * out_elems * ksz * ci
        elif kind in _EW_ARITH:
            c.flops = float(out_elems)
        elif kind in ("reduce", "reduce-window"):
            in_elems = sum(_shape_elems_bytes(s)[0] for s in opshapes)
            c.flops = float(max(in_elems, out_elems))
        # ---- bytes (operands + output), with slicing ops costed by the
        # bytes they actually move, not the tensors they address:
        #   dynamic-slice/slice/gather read+write only the slice;
        #   dynamic-update-slice rewrites only the updated region (XLA
        #   performs it in place on the donated buffer).
        # Ops inside an exposed-library kernel body ("tapir_vmem_body"
        # scope) are VMEM-resident on the TPU target: only their HBM block
        # loads (slicers) cost traffic.
        if "tapir_vmem_body" in op.rest:
            c.bytes = float(out_bytes) if kind in _SLICERS else 0.0
            return c
        if kind in ("dynamic-slice", "slice", "gather"):
            c.bytes = float(2 * out_bytes)
        elif kind == "dynamic-update-slice":
            upd_bytes = (_shape_elems_bytes(opshapes[1])[1]
                         if len(opshapes) > 1 else out_bytes)
            c.bytes = float(2 * upd_bytes)
        elif kind == "scatter":
            upd = (_shape_elems_bytes(opshapes[2])[1]
                   if len(opshapes) > 2 else out_bytes)
            c.bytes = float(3 * upd)
        else:
            in_bytes = sum(_shape_elems_bytes(s)[1] for s in opshapes)
            c.bytes = float(in_bytes + out_bytes)
        # ---- collectives
        base = kind.replace("-start", "").replace("-done", "")
        if base in _COLLECTIVES and not kind.endswith("-done"):
            c.coll_counts[base] = 1
            line = f"= {op.out} {op.kind}({op.rest}"
            if _crosses_pod(line):
                c.coll_dcn = float(out_bytes)
            else:
                c.coll_ici = float(out_bytes)
        return c

    # -- per-computation -----------------------------------------------------
    def comp_cost(self, name: str) -> Cost:
        if name in self._memo:
            return self._memo[name]
        self._memo[name] = Cost()   # cycle guard
        comp = self.comps.get(name)
        total = Cost()
        if comp is None:
            return total
        for op in comp.ops:
            if op.kind == "while":
                bm = re.search(r"body=%?([\w.\-]+)", op.rest)
                cm = re.search(r"condition=%?([\w.\-]+)", op.rest)
                # XLA records the derived trip count on the op itself
                km = re.search(r'known_trip_count[^\d]*(\d+)', op.rest)
                trip = int(km.group(1)) if km else (
                    _trip_count(self.comps.get(cm.group(1))) if cm else None)
                sub = Cost()
                if bm:
                    sub += self.comp_cost(bm.group(1))
                if trip is None:
                    trip = 1
                    sub.unknown_trip += 1
                total += sub.scaled(trip)
            elif op.kind == "fusion":
                fm = re.search(r"calls=%?([\w.\-]+)", op.rest)
                if fm:
                    inner = self.comp_cost(fm.group(1))
                    if "tapir_vmem_body" in op.rest:
                        # kernel-body fusion: only HBM block loads count
                        fc = self.comps.get(fm.group(1))
                        fb = float(sum(
                            _shape_elems_bytes(o.out)[1]
                            for o in (fc.ops if fc else [])
                            if o.kind in _SLICERS))
                    else:
                        fb = self._fusion_boundary_bytes(op, comp,
                                                         fm.group(1))
                    total += Cost(flops=inner.flops, bytes=fb,
                                  coll_ici=inner.coll_ici,
                                  coll_dcn=inner.coll_dcn,
                                  coll_counts=dict(inner.coll_counts),
                                  unknown_trip=inner.unknown_trip)
                else:
                    total += self._op_cost(op, comp)
            elif op.kind == "call":
                tm = re.search(r"to=%?([\w.\-]+)", op.rest)
                if tm:
                    total += self.comp_cost(tm.group(1))
            elif op.kind == "conditional":
                branches = []
                bm = _BRANCHES.search(op.rest)
                if bm:
                    branches = [b.strip().lstrip("%")
                                for b in bm.group(1).split(",")]
                else:
                    branches = _TRUEFALSE.findall(op.rest)
                if branches:
                    costs = [self.comp_cost(b) for b in branches]
                    total += max(costs, key=lambda c: c.flops + c.bytes)
            else:
                total += self._op_cost(op, comp)
        self._memo[name] = total
        return total

    def entry_cost(self, entry: str | None = None) -> Cost:
        if entry is None:
            called = set()
            for name, comp in self.comps.items():
                for op in comp.ops:
                    for m in _CALLED.finditer(op.rest):
                        called.add(m.group(1))
                    bm = _BRANCHES.search(op.rest)
                    if bm:
                        called.update(b.strip().lstrip("%")
                                      for b in bm.group(1).split(","))
            roots = [n for n in self.comps if n not in called]
            entry = next((n for n in roots if "main" in n),
                         roots[0] if roots else next(iter(self.comps)))
        return self.comp_cost(entry)


def analyze(hlo_text: str) -> Cost:
    return HloCostModel(hlo_text).entry_cost()


def breakdown(hlo_text: str, top: int = 20) -> list[tuple[str, float, float]]:
    """Per-op-kind (kind, flops, bytes) totals with loop scaling — the
    debugging view behind the roofline numbers."""
    model = HloCostModel(hlo_text)
    totals: dict[str, list[float]] = {}

    def visit(name: str, mult: float, seen: tuple):
        if name in seen:
            return
        comp = model.comps.get(name)
        if comp is None:
            return
        for op in comp.ops:
            if op.kind == "while":
                bm = re.search(r"body=%?([\w.\-]+)", op.rest)
                km = re.search(r'known_trip_count[^\d]*(\d+)', op.rest)
                trip = int(km.group(1)) if km else 1
                if bm:
                    visit(bm.group(1), mult * trip, seen + (name,))
            elif op.kind == "fusion":
                fm = re.search(r"calls=%?([\w.\-]+)", op.rest)
                fb = (model._fusion_boundary_bytes(op, comp, fm.group(1))
                      if fm else model._op_cost(op, comp).bytes)
                t = totals.setdefault("fusion(boundary)", [0.0, 0.0])
                t[1] += fb * mult
                if fm:
                    inner = model.comp_cost(fm.group(1))
                    tf = totals.setdefault("fusion(flops)", [0.0, 0.0])
                    tf[0] += inner.flops * mult
            elif op.kind == "call":
                tm = re.search(r"to=%?([\w.\-]+)", op.rest)
                if tm:
                    visit(tm.group(1), mult, seen + (name,))
            else:
                c = model._op_cost(op, comp)
                t = totals.setdefault(op.kind, [0.0, 0.0])
                t[0] += c.flops * mult
                t[1] += c.bytes * mult

    entry = model.entry_cost() and None
    # find entry name the same way entry_cost does
    called = set()
    for nm, comp in model.comps.items():
        for op in comp.ops:
            for m in _CALLED.finditer(op.rest):
                called.add(m.group(1))
    roots = [n for n in model.comps if n not in called]
    entry_name = next((n for n in roots if "main" in n),
                      roots[0] if roots else next(iter(model.comps)))
    visit(entry_name, 1.0, ())
    rows = [(k, v[0], v[1]) for k, v in totals.items()]
    rows.sort(key=lambda r: -r[2])
    return rows[:top]
