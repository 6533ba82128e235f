"""Call-graph-aware HLO cost model for the dry-run roofline.

Why not ``compiled.cost_analysis()``?  Two measured facts (see
EXPERIMENTS.md §Dry-run methodology): XLA's cost analysis (a) reports
*per-partition* numbers for SPMD modules — which is what we want — but (b)
counts every computation **once**, ignoring while-loop trip counts, so a
``lax.scan`` over 96 layers under-reports FLOPs by 96x.

This analyzer parses the post-optimization HLO text and walks the call
graph, multiplying each computation's costs by its execution count:

  * while body/condition — trip count recovered from the loop condition's
    compare constant (scan emits ``compare(iter, constant(N))``),
  * fusion / call / conditional / to_apply — caller's multiplier.

Costs per computation:
  * FLOPs            — 2 * prod(result dims) * prod(contracting dims) per
                       ``dot`` (matmuls dominate every assigned arch; the
                       elementwise remainder is < 2%),
  * HBM bytes        — for *scheduled* instructions (i.e. not inside fusion
                       bodies, which never touch HBM): operand bytes read +
                       result bytes written; parameter/tuple/gte/bitcast/
                       constant are aliasing ops and excluded,
  * collective bytes — max(result, operand) bytes per all-gather /
                       all-reduce / reduce-scatter / all-to-all /
                       collective-permute.

All quantities are per-device (the HLO module is one SPMD partition).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

# dtype widths + shape parsing shared with roofline.py (hlo_types is the
# single copy; private aliases keep this module's call sites stable)
from repro.launch.hlo_types import SHAPE_RE as _SHAPE_RE
from repro.launch.hlo_types import shape_bytes as _type_bytes

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# scheduled-op exclusions: no real HBM traffic of their own
_ALIAS_OPS = {"parameter", "tuple", "get-tuple-element", "bitcast",
              "constant", "after-all", "add-dependency", "iota"}

# "Heavy" ops always materialize their result in HBM on the TPU target.
_HEAVY_OPS = {"dot", "convolution", "sort", "scatter", "gather",
              "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
              "custom-call", "copy", "select-and-scatter", "reduce-window",
              "triangular-solve", "cholesky", "fft", "rng",
              "rng-bit-generator", "while", "conditional",
              "call"} | set(COLLECTIVES)

# Everything that is neither heavy nor aliasing is *fusable*: the CPU
# backend schedules elementwise/layout ops individually (often as
# single-op kLoop fusions), but the TPU compiler fuses such chains into one
# kernel — so HBM traffic is charged at *fusion-cluster boundaries*, not per
# op.  Validated on glm4-9b train_4k: the per-op model over-reports ~100x
# vs a first-principles params+activations estimate; the cluster model is
# within ~2x.

# non-greedy args: operand lists contain no parens in post-opt HLO; the
# attribute tail (condition=, calls=, backend_config=...) follows the ")".
# The "%" sigil on instruction/computation names is optional: older XLA
# prints "%dot.3 = ...", newer prints "dot.3 = ...".
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+"
                       r"([\w\-]+)\((.*?)\)(.*)$")
_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->\s*.+\s*\{")


def _dims(type_str: str) -> Optional[List[int]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class Instr:
    name: str
    type_str: str
    op: str
    args: str
    tail: str


@dataclass
class Computation:
    name: str
    is_entry: bool = False
    root_op: str = ""
    instrs: List[Instr] = field(default_factory=list)
    symtab: Dict[str, str] = field(default_factory=dict)
    fusion_callees: Set[str] = field(default_factory=set)
    plain_callees: Set[str] = field(default_factory=set)  # call/cond/to_apply
    # (cond, body, trip_count_or_None)
    while_edges: List[Tuple[str, str, Optional[int]]] = field(
        default_factory=list)


def parse_hlo(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for raw in hlo.splitlines():
        hdr = _HDR_RE.match(raw.strip())
        if hdr:
            current = Computation(hdr.group(2), is_entry=bool(hdr.group(1)))
            comps[current.name] = current
            continue
        if raw.strip() == "}":
            current = None
            continue
        if current is None:
            continue
        m = _INSTR_RE.match(raw)
        if not m:
            continue
        instr = Instr(m.group(1), m.group(2), m.group(3), m.group(4),
                      m.group(5))
        current.instrs.append(instr)
        current.symtab[instr.name] = instr.type_str
        if raw.lstrip().startswith("ROOT"):
            current.root_op = instr.op
        tail = instr.tail
        if instr.op == "fusion":
            c = re.search(r"calls=%?([\w\.\-]+)", tail)
            if c:
                current.fusion_callees.add(c.group(1))
        elif instr.op == "while":
            c = re.search(r"condition=%?([\w\.\-]+)", tail)
            b = re.search(r"body=%?([\w\.\-]+)", tail)
            t = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', tail)
            if c and b:
                current.while_edges.append(
                    (c.group(1), b.group(1),
                     int(t.group(1)) if t else None))
        else:
            for key in ("to_apply", "called_computations"):
                for c in re.finditer(key + r"=%?([\w\.\-]+)", tail):
                    current.plain_callees.add(c.group(1))
            if instr.op == "conditional":
                for c in re.finditer(r"(?:branch_computations=\{([^}]*)\}|"
                                     r"(?:true|false)_computation=%?([\w\.\-]+))",
                                     tail):
                    blob = c.group(1) or c.group(2) or ""
                    for name in re.findall(r"%?([\w\.\-]+)", blob):
                        current.plain_callees.add(name)
    return comps


def _trip_count(cond: Computation, comps: Dict[str, Computation]) -> int:
    """Max integer constant in the loop condition (scan: iter < N), its
    fused callees included (a condition ``iter < n & iter < N`` with a
    traced ``n`` compiles to one fusion holding N: its static bound)."""
    best = 1
    for ins in cond.instrs:
        if ins.op == "constant":
            m = re.search(r"constant\((\d+)\)", ins.op + "(" + ins.args + ")")
            if m:
                best = max(best, int(m.group(1)))
        for m in re.finditer(r"constant\((\d+)\)", ins.args):
            best = max(best, int(m.group(1)))
    for callee in cond.fusion_callees:
        if callee in comps:
            best = max(best, _trip_count(comps[callee], comps))
    return best


def _multipliers(comps: Dict[str, Computation]) -> Dict[str, float]:
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        for c in comps.values():
            mult[c.name] = 1.0
        return mult

    import collections
    stack = [(entry.name, 1.0)]
    guard = collections.Counter()
    while stack:
        name, m = stack.pop()
        comp = comps.get(name)
        if comp is None:
            continue
        guard[name] += 1
        if guard[name] > 10_000:   # cycle guard (HLO is acyclic in practice)
            continue
        mult[name] = mult.get(name, 0.0) + m
        for callee in comp.fusion_callees | comp.plain_callees:
            stack.append((callee, m))
        for cond_name, body_name, trips in comp.while_edges:
            if trips is None:
                trips = (_trip_count(comps[cond_name], comps)
                         if cond_name in comps else 1)
            stack.append((cond_name, m * (trips + 1)))
            stack.append((body_name, m * trips))
    return mult


def _split_top(args: str) -> List[str]:
    """Split an operand list on top-level commas only (shape dims
    ``f32[256,512]``, layouts ``{1,0}``, and literal tuples nest commas)."""
    parts: List[str] = []
    depth = 0
    cur: List[str] = []
    for ch in args:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def _dot_flops(comp: Computation, ins: Instr) -> float:
    out_dims = _dims(ins.type_str) or []
    out_prod = 1
    for d in out_dims:
        out_prod *= d
    # contracting dims from the lhs operand's type.  The operand may be
    # "f32[256,512]{1,0} %a", "f32[256,512] a", "%a", or "a" depending on
    # the XLA printer version — take the last token of the first top-level
    # operand, and fall back to its inline type when the symtab misses.
    parts = _split_top(ins.args)
    lhs = parts[0] if parts else ""
    lhs_name = lhs.split()[-1].lstrip("%") if lhs.split() else ""
    lhs_type = comp.symtab.get(lhs_name)
    if lhs_type is None and _SHAPE_RE.search(lhs):
        lhs_type = lhs
    lhs_dims = _dims(lhs_type) if lhs_type else None
    cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                      ins.args + " " + ins.tail)
    k = 1
    if lhs_dims and cdims:
        for idx in cdims.group(1).split(","):
            if idx:
                k *= lhs_dims[int(idx)]
    return 2.0 * out_prod * k


@dataclass
class HLOCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_by_op: Dict[str, float] = field(default_factory=dict)
    collective_counts: Dict[str, float] = field(default_factory=dict)
    dot_count: float = 0.0
    while_trips: Dict[str, int] = field(default_factory=dict)
    # bytes moved by collectives whose replica groups cross the pod boundary
    # (the slow inter-pod DCN link in the multi-pod mesh)
    dcn_bytes: float = 0.0


def _replica_groups_cross_pod(tail: str, pod_size: int) -> Optional[bool]:
    """Parse iota replica_groups ('[G,S]<=[dims]T(perm)') and report whether
    any group spans devices from different pods (id // pod_size differs)."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", tail)
    if not m:
        m2 = re.search(r"replica_groups=\{\{([^}]*)\}", tail)
        if m2:  # explicit list format: check the first group
            ids = [int(x) for x in m2.group(1).split(",") if x.strip()]
            return len({i // pod_size for i in ids}) > 1
        return None
    import numpy as _np
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(x) for x in m.group(3).split(",")]
    perm = ([int(x) for x in m.group(4).split(",")]
            if m.group(4) else list(range(len(dims))))
    devices = _np.arange(int(_np.prod(dims))).reshape(dims)
    devices = devices.transpose(perm).reshape(g, s)
    pods = devices // pod_size
    return bool((pods != pods[:, :1]).any())


class _UnionFind:
    def __init__(self):
        self.parent: Dict[str, str] = {}

    def find(self, x: str) -> str:
        p = self.parent.setdefault(x, x)
        while p != self.parent.setdefault(p, p):
            self.parent[x] = self.parent[p]
            x, p = p, self.parent[p]
        return p

    def union(self, a: str, b: str) -> None:
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[ra] = rb


def _operands(ins: Instr) -> List[str]:
    """Operand names for both printer styles: sigil ("%a") and bare ("a"),
    with or without inline operand types.  Non-operand parenthesized args
    (parameter indices, constant literals) yield tokens that never resolve
    in the symtab and are filtered by every caller."""
    if "%" in ins.args:
        return [m.group(1) for m in re.finditer(r"%([\w\.\-]+)", ins.args)]
    names = []
    for part in _split_top(ins.args):
        toks = part.split()
        if toks:
            names.append(toks[-1])
    return names


def _comp_hbm_bytes(comp: Computation, fusion_root: Dict[str, str],
                    comps: Optional[Dict[str, Computation]] = None) -> float:
    """Fusion-cluster HBM traffic model for one scheduled computation.

    Fusable ops (fusion + standalone elementwise/layout) connected by
    def-use edges form clusters that execute as one TPU kernel: traffic is
    the cluster's external reads + its materialized outputs.  Heavy ops
    read their operands and write their result.  Alias ops are free.

    In-place update patterns (dynamic-update-slice, incl. DUS-rooted
    fusions — scan carries, KV-cache writes, stacked-grad accumulation)
    touch only the updated slice, not the whole buffer; dynamic-slice /
    gather read only their result extent."""
    kind: Dict[str, str] = {}
    instrs: Dict[str, Instr] = {}
    for ins in comp.instrs:
        instrs[ins.name] = ins
        if ins.op in _ALIAS_OPS:
            kind[ins.name] = "alias"
        elif ins.op in _HEAVY_OPS:
            kind[ins.name] = "heavy"
        else:
            kind[ins.name] = "fusable"

    def is_inplace(ins: Instr) -> bool:
        if ins.op == "dynamic-update-slice":
            return True
        if ins.op == "fusion":
            c = re.search(r"calls=%?([\w\.\-]+)", ins.tail)
            return bool(c) and fusion_root.get(c.group(1)) == \
                "dynamic-update-slice"
        return False

    def is_slice_read(ins: Instr) -> bool:
        return ins.op in ("dynamic-slice", "gather")

    def fusion_operand_bytes(ins: Instr, op_index: int,
                             full_bytes: float) -> float:
        """A fusion that only dynamic-slices a (stacked) operand internally
        reads the slice extent, not the full buffer — e.g. the bwd scan
        reading layer i's saved activation from the (L, ...) stack."""
        c = re.search(r"calls=%?([\w\.\-]+)", ins.tail)
        callee = (comps or {}).get(c.group(1)) if c else None
        if callee is None:
            return full_bytes
        pname = None
        for ci in callee.instrs:
            if ci.op == "parameter" and ci.args.strip() == str(op_index):
                pname = ci.name
                break
        if pname is None:
            return full_bytes
        # transitive: fused elementwise chains evaluate lazily, so the param
        # is read slice-sized iff every use-path hits a dynamic-slice whose
        # extent bounds the demanded elements
        consumers: Dict[str, List[Instr]] = {}
        root_name = callee.instrs[-1].name if callee.instrs else None
        for ci in callee.instrs:
            for o in _operands(ci):
                consumers.setdefault(o, []).append(ci)
        sizes: List[float] = []
        stack = [pname]
        seen: Set[str] = set()
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            for ci in consumers.get(n, []):
                if ci.op == "dynamic-slice":
                    sizes.append(_type_bytes(ci.type_str))
                elif ci.op == "slice":
                    sizes.append(_type_bytes(ci.type_str))
                elif ci.name == root_name:
                    return full_bytes       # reaches the root unsliced
                else:
                    stack.append(ci.name)
        return min(sum(sizes), full_bytes) if sizes else full_bytes

    uf = _UnionFind()
    for ins in comp.instrs:
        if kind[ins.name] != "fusable" or is_inplace(ins):
            continue
        for op in _operands(ins):
            if kind.get(op) == "fusable" and not is_inplace(instrs[op]):
                uf.union(ins.name, op)

    def cluster(name: str) -> Optional[str]:
        return uf.find(name) if kind.get(name) == "fusable" else None

    traffic = 0.0
    consumed: Dict[str, Set[Optional[str]]] = {}
    for ins in comp.instrs:
        if kind[ins.name] == "alias":
            continue
        if is_inplace(ins):
            sizes = [_type_bytes(instrs[o].type_str) for o in _operands(ins)
                     if o in instrs]
            if sizes:
                # read update+indices, write the slice region (2x non-buffer)
                traffic += 2.0 * (sum(sizes) - max(sizes))
            continue
        if is_slice_read(ins):
            traffic += 2.0 * _type_bytes(ins.type_str)   # read + write slice
            continue
        my_cluster = cluster(ins.name)
        for idx, op in enumerate(_operands(ins)):
            if op not in instrs:
                continue
            k = kind.get(op)
            b = _type_bytes(instrs[op].type_str)
            if ins.op == "fusion":
                b = fusion_operand_bytes(ins, idx, b)
            if k == "alias":
                # read through the alias (e.g. gte of the loop carry)
                traffic += b
                continue
            if k == "fusable" and not is_inplace(instrs[op]) \
                    and cluster(op) == my_cluster:
                continue  # VMEM-internal edge
            traffic += b                                     # HBM read
            consumed.setdefault(op, set()).add(my_cluster)
    # writes: every heavy op + every fusable op whose value escapes its
    # cluster
    for ins in comp.instrs:
        k = kind[ins.name]
        if is_inplace(ins) or is_slice_read(ins):
            continue
        if k == "heavy":
            traffic += _type_bytes(ins.type_str)
        elif k == "fusable" and ins.name in consumed:
            traffic += _type_bytes(ins.type_str)
    return traffic


def dot_breakdown(hlo: str, top: int = 20) -> List[Tuple[str, float, float]]:
    """Top dot ops by total FLOPs: (metadata op_name | shape, flops, mult).
    The hillclimb's 'profiler': shows where compiled compute actually goes."""
    comps = parse_hlo(hlo)
    mult = _multipliers(comps)
    rows: List[Tuple[str, float, float]] = []
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m <= 0:
            continue
        for ins in comp.instrs:
            if ins.op != "dot":
                continue
            fl = _dot_flops(comp, ins)
            meta = re.search(r'op_name="([^"]+)"', ins.tail)
            label = (meta.group(1) if meta else ins.name)
            shape = _SHAPE_RE.search(ins.type_str)
            label += f" -> {shape.group(0) if shape else ins.type_str[:30]}"
            rows.append((label, m * fl, m))
    rows.sort(key=lambda r: -r[1])
    # merge identical labels
    merged: Dict[str, Tuple[float, float]] = {}
    for label, fl, m in rows:
        f0, m0 = merged.get(label, (0.0, 0.0))
        merged[label] = (f0 + fl, m0 + m)
    out = sorted(((k, v[0], v[1]) for k, v in merged.items()),
                 key=lambda r: -r[1])
    return out[:top]


def analyze_hlo(hlo: str, pod_size: Optional[int] = None) -> HLOCost:
    comps = parse_hlo(hlo)
    mult = _multipliers(comps)
    # fusion bodies have no scheduled HBM traffic of their own
    fusion_bodies: Set[str] = set()
    for c in comps.values():
        fusion_bodies |= c.fusion_callees

    cost = HLOCost()
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m <= 0:
            continue
        scheduled = comp.name not in fusion_bodies
        for ins in comp.instrs:
            if ins.op == "dot":
                cost.flops += m * _dot_flops(comp, ins)
                cost.dot_count += m
            if not scheduled:
                continue
            if ins.op in COLLECTIVES:
                res_b = _type_bytes(ins.type_str)
                opnd_b = sum(
                    _type_bytes(comp.symtab[o]) for o in _operands(ins)
                    if o in comp.symtab)
                # per-chip received-bytes convention:
                #   all-reduce      ~ 2x data (ring reduce-scatter+gather)
                #   all-gather      = result - own shard
                #   reduce-scatter  = operand - own result
                #   all-to-all      ~ full buffer ((n-1)/n ~ 1)
                #   permute         = result
                if ins.op == "all-reduce":
                    moved = 2.0 * opnd_b
                elif ins.op == "all-gather":
                    moved = max(res_b - opnd_b, 0.0)
                elif ins.op == "reduce-scatter":
                    moved = max(opnd_b - res_b, 0.0)
                elif ins.op == "collective-permute":
                    moved = res_b
                else:
                    moved = max(res_b, opnd_b)
                cost.collective_bytes += m * moved
                cost.collective_by_op[ins.op] = (
                    cost.collective_by_op.get(ins.op, 0.0) + m * moved)
                cost.collective_counts[ins.op] = (
                    cost.collective_counts.get(ins.op, 0.0) + m)
                if pod_size:
                    crosses = _replica_groups_cross_pod(
                        ins.args + " " + ins.tail, pod_size)
                    if crosses:
                        cost.dcn_bytes += m * moved
        if scheduled:
            fusion_root = {name: c.root_op for name, c in comps.items()}
            cost.hbm_bytes += m * _comp_hbm_bytes(comp, fusion_root, comps)
    for c in comps.values():
        for cond, body, trips in c.while_edges:
            if trips is None and cond in comps:
                trips = _trip_count(comps[cond], comps)
            cost.while_trips[body] = trips or 1
    return cost
