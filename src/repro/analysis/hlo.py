"""Optimized-HLO text parser: FLOPs / HBM traffic / collective bytes.

Why this exists: ``compiled.cost_analysis()`` counts a ``while`` (scan) body
ONCE, regardless of trip count (verified empirically — a 4-step scan of a
256³ matmul reports 1× matmul flops). Since every model here scans over
layers, we parse the per-device optimized HLO ourselves and multiply
computation costs through the call graph, detecting scan trip counts from
the loop-condition constants.

Counting conventions:
  * FLOPs       — 2·numel(out)·K for every ``dot`` (K = contracted extent);
                  elementwise/reduce ops are counted at 1 flop/output element.
  * HBM bytes   — every non-fused op boundary is a materialization point:
                  operands + outputs of top-level ops (fusion internals are
                  free, which is exactly XLA's fusion-boundary cost model).
  * collective  — operand bytes summed over all-reduce / all-gather /
                  reduce-scatter / all-to-all / collective-permute (and their
                  async -start forms), as the brief specifies.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "token": 0,
    "opaque": 0, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([a-z][\w\-]*)\((.*)\)",
)
_CALL_ATTR_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")

COLLECTIVE_OPS = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "all-reduce-scatter", "ragged-all-to-all",
    "collective-broadcast",
}
_SKIP_TRAFFIC = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
    "get-dimension-size", "opt-barrier", "optimization-barrier",
}


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_numel(type_str: str) -> int:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    n = 1
    if m.group(2):
        for d in m.group(2).split(","):
            n *= int(d)
    return n


@dataclasses.dataclass
class OpInfo:
    name: str
    opcode: str
    result_type: str
    operands_str: str
    attrs_str: str
    line: str
    operand_types: List[str] = dataclasses.field(default_factory=list)
    operand_names: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[OpInfo]
    symtab: Dict[str, str] = dataclasses.field(default_factory=dict)

    def resolve_operands(self) -> None:
        """Modern HLO dumps omit operand types; resolve via local names."""
        for op in self.ops:
            types: List[str] = []
            names: List[str] = []
            depth = 0
            token = ""
            parts: List[str] = []
            for ch in op.operands_str:
                if ch == ")" and depth == 0:
                    break   # the operand list closes; attributes follow
                if ch == "," and depth == 0:
                    parts.append(token)
                    token = ""
                    continue
                if ch in "([{":
                    depth += 1
                elif ch in ")]}":
                    depth -= 1
                token += ch
            if token.strip():
                parts.append(token)
            for part in parts:
                part = part.strip()
                if _SHAPE_RE.search(part):
                    types.append(part)  # inline type present (older dumps)
                    m = re.search(r"%([\w.\-]+)", part)
                    names.append(m.group(1) if m else "")
                    continue
                nm = part.lstrip("%")
                types.append(self.symtab.get(nm, ""))
                names.append(nm)
            op.operand_types = types
            op.operand_names = names


# Transcendental/special-function opcodes: far costlier than 1 flop/elem on
# every backend, so the cost-model feature vector tracks them separately
# (exactly what HloCostAnalysis's transcendental_count does).
TRANSCENDENTAL_OPS = {
    "exponential", "exponential-minus-one", "log", "log1p", "tanh",
    "rsqrt", "sqrt", "cbrt", "power", "logistic", "atan2", "expm1",
    "sin", "cos", "tan",
}

# The per-plan cost-model feature schema (shared with
# ``repro.core.calibrate.FEATURES`` — a test pins the correspondence).
# ``nnz`` is a plan-level notion with no HLO counterpart, so the HLO
# extractor emits 0.0 for it.
FEATURE_NAMES = ("dot_flops", "ew_flops", "bytes", "transcendentals",
                 "comm_bytes", "nnz", "ops")


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0
    collective_breakdown: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    dot_flops: float = 0.0
    transcendentals: float = 0.0      # elements through transcendental ops
    op_count: float = 0.0             # executed top-level ops (launches)
    while_trip_counts: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    warnings: List[str] = dataclasses.field(default_factory=list)

    def merged_scaled(self, other: "HloStats", k: float) -> None:
        self.flops += other.flops * k
        self.bytes_accessed += other.bytes_accessed * k
        self.collective_bytes += other.collective_bytes * k
        self.dot_flops += other.dot_flops * k
        self.transcendentals += other.transcendentals * k
        self.op_count += other.op_count * k
        for op, b in other.collective_breakdown.items():
            self.collective_breakdown[op] = \
                self.collective_breakdown.get(op, 0.0) + b * k

    def feature_vector(self) -> Dict[str, float]:
        """This module's stats as the cost-model feature schema
        (``FEATURE_NAMES``): dot vs elementwise flops split, HBM traffic,
        transcendental elements, collective bytes and launch count."""
        return {
            "dot_flops": self.dot_flops,
            "ew_flops": max(self.flops - self.dot_flops, 0.0),
            "bytes": self.bytes_accessed,
            "transcendentals": self.transcendentals,
            "comm_bytes": self.collective_bytes,
            "nnz": 0.0,
            "ops": self.op_count,
        }


def _split_computations(text: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    entry_name = ""
    cur: Optional[Computation] = None
    header_re = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(\([^)]*\))?.*\{\s*$")
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = header_re.match(line.strip())
            if m and "->" in line or (m and line.strip().endswith("{")):
                if m:
                    cur = Computation(m.group(2), [])
                    if m.group(1):
                        entry_name = m.group(2)
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        om = _OP_RE.match(line)
        if om:
            name, rtype, opcode, rest = om.groups()
            # split operands from attrs: attrs follow the closing paren —
            # rest may contain nested parens from types; use the raw line
            attr_idx = line.find("), ")
            attrs = line[attr_idx + 3:] if attr_idx >= 0 else ""
            cur.ops.append(OpInfo(name, opcode, rtype, rest, attrs, line))
            cur.symtab[name] = rtype
    for comp in comps.values():
        comp.resolve_operands()
    return comps, entry_name


def _op_in_bytes(op: OpInfo) -> int:
    return sum(_shape_bytes(t) for t in op.operand_types)


# ---------------------------------------------------------------------------
# Fusion-aware HBM traffic model.
#
# CPU-lowered HLO is barely fused, so charging operands+outputs of every op
# wildly overestimates what XLA:TPU would move through HBM. We simulate the
# standard greedy producer fusion: a cheap (elementwise-ish) op with exactly
# one consumer joins its consumer's group; HBM traffic is charged only at
# group boundaries (deduped external inputs + externally-consumed outputs).
# Dynamic-slice/gather charge the slice, not the sliced buffer;
# dynamic-update-slice charges 2× the update (read-modify-write of the
# aliased region).
# ---------------------------------------------------------------------------

_FUSABLE = {
    "add", "multiply", "subtract", "divide", "maximum", "minimum", "power",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs", "sign",
    "floor", "ceil", "clamp", "rem", "and", "or", "xor", "not",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "is-finite", "atan2", "expm1", "log1p", "logistic", "cbrt", "cos",
    "sin", "tan", "round-nearest-afz", "round-nearest-even", "compare",
    "select", "convert", "broadcast", "iota", "reshape", "bitcast",
    "transpose", "pad", "slice", "reduce", "concatenate", "reverse", "map",
    "reduce-precision", "stochastic-convert", "exponential-minus-one",
    "copy",
}
_GROUP_BLOCKERS = {"while", "fusion", "call", "conditional", "custom-call",
                   "async-start"} | COLLECTIVE_OPS
_NO_DEF_TRAFFIC = {"parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast", "after-all", "partition-id", "replica-id",
                   "iota", "opt-barrier", "optimization-barrier",
                   "get-dimension-size"}


def _edge_price(consumer: OpInfo, operand_idx: int, operand_type: str,
                comps: Optional[Dict[str, "Computation"]] = None) -> int:
    """Bytes read for one external tensor → op edge.

    dynamic-slice/gather read only the slice; dynamic-update-slice touches
    only the updated region; a FUSION that consumes the operand exclusively
    through dynamic-slice/gather on its matching parameter is priced at the
    slice size too (critical inside scan bodies, where per-layer weight and
    per-step activation slices are read from stacked arrays — charging the
    full stacked array once per iteration would overcount by the trip
    count)."""
    if consumer.opcode in ("dynamic-slice", "gather") and operand_idx == 0:
        return _shape_bytes(consumer.result_type)
    if consumer.opcode == "dynamic-update-slice" and operand_idx == 0:
        # aliased in-place update: read+write only the updated region
        upd = consumer.operand_types[1] if len(consumer.operand_types) > 1 \
            else consumer.result_type
        return _shape_bytes(upd)
    if consumer.opcode == "scatter" and operand_idx == 0:
        # in-place scatter: touched region ≈ updates (operand 2)
        upd = consumer.operand_types[2] if len(consumer.operand_types) > 2 \
            else consumer.result_type
        return _shape_bytes(upd)
    if consumer.opcode == "fusion" and comps is not None:
        m = _CALL_ATTR_RE.search(consumer.line)
        called = comps.get(m.group(1)) if m else None
        if called is not None:
            price = _fusion_param_price(called, operand_idx)
            if price is not None:
                return price
    return _shape_bytes(operand_type)


def _fusion_param_price(called: "Computation", idx: int) -> Optional[int]:
    """If parameter ``idx`` of a fused computation is consumed only via
    dynamic-slice / gather / DUS(op0), return the sliced byte count."""
    pname = None
    for op in called.ops:
        if op.opcode == "parameter" and f"parameter({idx})" in op.line:
            pname = op.name
            break
    if pname is None:
        return None
    total = 0
    seen = False
    for op in called.ops:
        if pname not in op.operand_names:
            continue
        seen = True
        oidx = op.operand_names.index(pname)
        if op.opcode in ("dynamic-slice", "gather") and oidx == 0:
            total += _shape_bytes(op.result_type)
        elif op.opcode == "dynamic-update-slice" and oidx == 0:
            upd = op.operand_types[1] if len(op.operand_types) > 1 \
                else op.result_type
            total += _shape_bytes(upd)
        else:
            return None  # consumed wholesale somewhere: full price
    return total if seen else 0


def _traffic(comp: Computation,
             comps: Optional[Dict[str, Computation]] = None
             ) -> float:
    name2op = {op.name: op for op in comp.ops}
    consumers: Dict[str, List[OpInfo]] = {}
    for op in comp.ops:
        for nm in op.operand_names:
            if nm in name2op:
                consumers.setdefault(nm, []).append(op)

    parent: Dict[str, str] = {op.name: op.name for op in comp.ops}

    def find(x: str) -> str:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for op in comp.ops:
        cons = consumers.get(op.name, [])
        if (op.opcode in _FUSABLE and len(cons) == 1
                and cons[0].opcode not in _GROUP_BLOCKERS):
            parent[find(op.name)] = find(cons[0].name)

    # fixpoint: a fusable op whose consumers all landed in ONE group joins it
    # (XLA fusions allow multi-use internal values — e.g. the flash-softmax
    # pattern where the logits tensor feeds both the running max and the exp)
    for _ in range(8):
        changed = False
        for op in comp.ops:
            cons = consumers.get(op.name, [])
            if op.opcode not in _FUSABLE or len(cons) < 2:
                continue
            if any(c.opcode in _GROUP_BLOCKERS for c in cons):
                continue
            tgt = {find(c.name) for c in cons}
            if len(tgt) == 1 and find(op.name) not in tgt:
                parent[find(op.name)] = tgt.pop()
                changed = True
        if not changed:
            break

    groups: Dict[str, List[OpInfo]] = {}
    for op in comp.ops:
        groups.setdefault(find(op.name), []).append(op)

    total = 0.0
    root_name = comp.ops[-1].name if comp.ops else None
    zero_charge = {"while", "call", "conditional", "async-start"}
    for gid, members in groups.items():
        mset = {m.name for m in members}
        if all(m.opcode in _NO_DEF_TRAFFIC | zero_charge for m in members):
            continue
        ext_in: Dict[str, int] = {}
        for m in members:
            if m.opcode in zero_charge:
                continue  # internals charged via recursion, not boundary
            for idx, (nm, ty) in enumerate(zip(m.operand_names,
                                               m.operand_types)):
                if nm in mset or not ty:
                    continue
                src = name2op.get(nm)
                if src is not None and src.opcode == "constant" \
                        and _shape_numel(src.result_type) <= 256:
                    continue  # small constants live in registers/immediate
                price = _edge_price(m, idx, ty, comps)
                ext_in[nm] = max(ext_in.get(nm, 0), price)
        out_bytes = 0
        for m in members:
            if m.opcode in _NO_DEF_TRAFFIC or m.opcode in zero_charge:
                continue
            ext_cons = [c for c in consumers.get(m.name, [])
                        if c.name not in mset]
            if ext_cons or m.name == root_name \
                    or not consumers.get(m.name):
                if m.opcode == "dynamic-update-slice":
                    upd = m.operand_types[1] if len(m.operand_types) > 1 \
                        else m.result_type
                    out_bytes += _shape_bytes(upd)
                elif m.opcode == "scatter":
                    upd = m.operand_types[2] if len(m.operand_types) > 2 \
                        else m.result_type
                    out_bytes += _shape_bytes(upd)
                else:
                    out_bytes += _shape_bytes(m.result_type)
        total += sum(ext_in.values()) + out_bytes
    return total


def _dot_flops(op: OpInfo) -> float:
    out_numel = _shape_numel(op.result_type)
    lhs_m = _SHAPE_RE.search(op.operand_types[0]) if op.operand_types else None
    if lhs_m is None:
        return 0.0
    lhs_dims = [int(d) for d in lhs_m.group(2).split(",")] \
        if lhs_m.group(2) else []
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    k = 1
    if cm and cm.group(1):
        for d in cm.group(1).split(","):
            di = int(d)
            if di < len(lhs_dims):
                k *= lhs_dims[di]
    return 2.0 * out_numel * k


def _conv_flops(op: OpInfo) -> float:
    # rough: 2 * out_numel * (kernel spatial * in_channels); estimated from
    # the rhs (kernel) operand numel divided by output feature dim if found
    out_numel = _shape_numel(op.result_type)
    if len(op.operand_types) < 2:
        return 2.0 * out_numel
    m = _SHAPE_RE.search(op.operand_types[1])
    rhs_dims = [int(d) for d in m.group(2).split(",")] if m and m.group(2) else []
    if not rhs_dims:
        return 2.0 * out_numel
    k = 1
    for d in rhs_dims[:-1]:
        k *= d
    return 2.0 * out_numel * k


def _trip_count(cond: Computation) -> Optional[int]:
    """JAX scans lower to while(cond: iter < C). Return the compare bound."""
    consts: Dict[str, int] = {}
    for op in cond.ops:
        if op.opcode == "constant" and op.result_type.strip().startswith("s"):
            m = re.search(r"constant\((-?\d+)\)", op.line)
            if m:
                consts[op.name] = int(m.group(1))
    bounds = []
    for op in cond.ops:
        if op.opcode == "compare":
            names = re.findall(r"%([\w.\-]+)", op.operands_str)
            for nm in names:
                if nm in consts:
                    bounds.append(consts[nm])
    if bounds:
        return max(bounds)
    if consts:
        return max(consts.values())
    return None


def _analyze(comp: Computation, comps: Dict[str, Computation],
             memo: Dict[str, HloStats]) -> HloStats:
    if comp.name in memo:
        return memo[comp.name]
    stats = HloStats()
    memo[comp.name] = stats  # placed first to break accidental cycles
    # HBM traffic: fusion-aware group model over this computation's ops
    # (control-flow/called computations contribute via recursion below)
    stats.bytes_accessed += _traffic(comp, comps)
    for op in comp.ops:
        out_bytes = _shape_bytes(op.result_type)
        in_bytes = _op_in_bytes(op)
        if op.opcode not in _SKIP_TRAFFIC:
            # every executed op is (roughly) one kernel launch; a fusion is
            # one launch regardless of its internals, while/call bodies add
            # theirs via merged_scaled below
            stats.op_count += 1.0
        if op.opcode in TRANSCENDENTAL_OPS:
            stats.transcendentals += _shape_numel(op.result_type)
        if op.opcode == "dot":
            f = _dot_flops(op)
            stats.flops += f
            stats.dot_flops += f
        elif op.opcode == "convolution":
            stats.flops += _conv_flops(op)
        elif op.opcode in COLLECTIVE_OPS:
            b = in_bytes
            stats.collective_bytes += b
            key = op.opcode.replace("-start", "")
            stats.collective_breakdown[key] = \
                stats.collective_breakdown.get(key, 0.0) + b
        elif op.opcode == "fusion":
            # the fusion op is a single group: boundary traffic is charged by
            # _traffic at the call site; internals add flops/collectives/
            # transcendentals only (op_count stays 1 — one launch)
            m = _CALL_ATTR_RE.search(op.line)
            if m and m.group(1) in comps:
                inner = _analyze(comps[m.group(1)], comps, memo)
                stats.flops += inner.flops
                stats.dot_flops += inner.dot_flops
                stats.transcendentals += inner.transcendentals
                stats.collective_bytes += inner.collective_bytes
                for k2, v in inner.collective_breakdown.items():
                    stats.collective_breakdown[k2] = \
                        stats.collective_breakdown.get(k2, 0.0) + v
        elif op.opcode == "while":
            body_name = cond_name = None
            bm = re.search(r"body=%?([\w.\-]+)", op.line)
            cm = re.search(r"condition=%?([\w.\-]+)", op.line)
            if bm:
                body_name = bm.group(1)
            if cm:
                cond_name = cm.group(1)
            trips = None
            if cond_name and cond_name in comps:
                trips = _trip_count(comps[cond_name])
            if trips is None:
                trips = 1
                stats.warnings.append(
                    f"while {op.name}: trip count unknown, assuming 1")
            stats.while_trip_counts[op.name] = trips
            if body_name and body_name in comps:
                inner = _analyze(comps[body_name], comps, memo)
                stats.merged_scaled(inner, trips)
                for wn, tc in inner.while_trip_counts.items():
                    stats.while_trip_counts[f"{op.name}/{wn}"] = tc
        elif op.opcode in ("call", "async-start", "custom-call"):
            m = _CALL_ATTR_RE.search(op.line)
            if m and m.group(1) in comps:
                inner = _analyze(comps[m.group(1)], comps, memo)
                stats.merged_scaled(inner, 1.0)
        elif op.opcode == "conditional":
            bm = _BRANCH_RE.search(op.line)
            if bm:
                branches = re.findall(r"%?([\w.\-]+)", bm.group(1))
                if branches:
                    # charge the most expensive branch (worst case)
                    inners = [_analyze(comps[b], comps, memo)
                              for b in branches if b in comps]
                    if inners:
                        worst = max(inners, key=lambda s: s.flops)
                        stats.merged_scaled(worst, 1.0)
        elif op.opcode in _SKIP_TRAFFIC:
            pass
        elif op.opcode in ("reduce", "reduce-window", "scatter", "gather",
                           "sort", "copy", "transpose", "reshape",
                           "broadcast", "concatenate", "slice",
                           "dynamic-slice", "dynamic-update-slice", "pad",
                           "convert", "select", "compare", "add", "multiply",
                           "subtract", "divide", "exponential", "log",
                           "tanh", "rsqrt", "sqrt", "maximum", "minimum",
                           "negate", "abs", "power", "rng", "rng-bit-generator",
                           "cbrt", "logistic", "sign", "floor", "ceil",
                           "clamp", "rem", "and", "or", "xor", "not",
                           "shift-left", "shift-right-logical",
                           "shift-right-arithmetic", "is-finite", "atan2",
                           "expm1", "log1p", "round-nearest-afz",
                           "round-nearest-even", "stochastic-convert",
                           "reverse", "map", "reduce-precision", "cos",
                           "sin", "tan", "real", "imag", "complex"):
            stats.flops += _shape_numel(op.result_type)
        else:
            pass  # unknown op: traffic handled by the group model
    return stats


def parse_hlo_module(text: str) -> HloStats:
    comps, entry = _split_computations(text)
    if not comps:
        raise ValueError("no computations parsed from HLO text")
    if not entry:
        # fall back: the computation that is not referenced by any other
        referenced = set()
        for c in comps.values():
            for op in c.ops:
                for m in _CALL_ATTR_RE.finditer(op.line):
                    referenced.add(m.group(1))
        candidates = [n for n in comps if n not in referenced]
        entry = candidates[-1] if candidates else list(comps)[-1]
    memo: Dict[str, HloStats] = {}
    top = _analyze(comps[entry], comps, memo)
    out = HloStats()
    out.merged_scaled(top, 1.0)
    out.while_trip_counts = dict(top.while_trip_counts)
    out.warnings = list(top.warnings)
    return out
