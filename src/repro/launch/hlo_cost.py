"""HLO-text cost model with while-loop trip-count accounting.

XLA's ``compiled.cost_analysis()`` counts each while-loop *body once* —
useless for scan-over-layers models (a 60-layer stack reports 1/60th of its
FLOPs).  This module re-derives the three roofline inputs directly from the
post-SPMD HLO text:

* **FLOPs** — every ``dot`` (2 x prod(result dims) x contraction size),
  including dots inside fusion computations, multiplied through the while
  trip counts (nested loops multiply).
* **HBM bytes** — per top-level instruction: operand + result bytes
  (producer+consumer counting, like XLA's own 'bytes accessed'), with two
  corrections: bookkeeping ops (tuple/GTE/parameter/bitcast/constant) are
  free, and dynamic-update-slice fusions count only the update traffic (XLA
  aliases the big buffer in place).
* **Collective link bytes** — ring-weighted per-op traffic:
      all-gather (g-1)/g x out, all-reduce 2(g-1)/g x buf,
      reduce-scatter (g-1) x out (out is the post-scatter shard),
      all-to-all (g-1)/g x buf, collective-permute 1 x buf.

Trip counts come from the loop-condition computation's compare constant
(scan lowers to ``i < N`` with a literal N).  Shapes in the partitioned
module are per-device, so all results are per-chip.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["HloCost", "analyze_hlo", "while_costs"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COMP_HDR = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
# type is either a (possibly /*index=N*/-commented) tuple "(...)" — HLO tuple
# types have no nested parens — or a single shape token.
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+)\s*=\s*(\([^)]*\)|\S+?)\s+([\w\-]+)\(")
_SHAPE = re.compile(r"(\w[\w\d]*)\[([\d,]*)\]")
_OPERANDS = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COND_BODY = re.compile(r"condition=%([\w.\-]+),\s*body=%([\w.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BATCH = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CONST_INT = re.compile(r"constant\((\d+)\)")

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "reshape",
    # Fusion-optimistic TPU model: standalone elementwise/convert/broadcast
    # ops at CPU-HLO top level would be fused into neighboring matmuls or
    # fusions by the TPU backend — counting their IO would bill the same
    # activation tensor 3-5x.  Real HBM traffic is captured by dot / fusion /
    # reduce / slice / collective IO below.
    "convert", "broadcast", "add", "subtract", "multiply", "divide",
    "maximum", "minimum", "clamp", "compare", "select", "tanh", "exponential",
    "rsqrt", "sqrt", "negate", "abs", "and", "or", "not", "xor", "sign",
    "floor", "ceil", "log", "log-plus-one", "exponential-minus-one", "power",
}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_dims(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",")] if dims else []))
    return out


def _type_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _shape_dims(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class _Instr:
    name: str
    type_str: str
    op: str
    line: str


@dataclass
class _Comp:
    name: str
    instrs: list = field(default_factory=list)
    types: dict = field(default_factory=dict)  # %name -> type string


@dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    link_bytes: float = 0.0
    coll_bytes: dict = field(default_factory=dict)
    coll_count: dict = field(default_factory=dict)

    def scaled(self, k: float) -> "HloCost":
        return HloCost(
            self.flops * k,
            self.bytes * k,
            self.link_bytes * k,
            {a: b * k for a, b in self.coll_bytes.items()},
            {a: b * k for a, b in self.coll_count.items()},
        )

    def add(self, other: "HloCost"):
        self.flops += other.flops
        self.bytes += other.bytes
        self.link_bytes += other.link_bytes
        for k, v in other.coll_bytes.items():
            self.coll_bytes[k] = self.coll_bytes.get(k, 0.0) + v
        for k, v in other.coll_count.items():
            self.coll_count[k] = self.coll_count.get(k, 0) + v

    def as_dict(self):
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "link_bytes": self.link_bytes,
            "per_op_bytes": self.coll_bytes,
            "per_op_count": self.coll_count,
        }


def _parse_computations(text: str) -> dict[str, _Comp]:
    comps: dict[str, _Comp] = {}
    cur: _Comp | None = None
    entry_name = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR.match(line.strip())
            if m and "{" in line:
                cur = _Comp(m.group(1))
                if line.strip().startswith("ENTRY"):
                    entry_name = m.group(1)
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            name, type_str, op = m.group(1), m.group(2), m.group(3)
            cur.instrs.append(_Instr(name, type_str, op, line))
            cur.types[name] = type_str
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def _trip_count(cond: _Comp) -> int:
    """Trip count of a scan-lowered while: the literal the induction counter
    is compared against.

    We resolve the ROOT instruction's *operands* and take constants among
    them (the compare may be wrapped in a fusion, but the constant still
    appears as an operand by name on the root/fusion line).  Falling back to
    the max constant in the computation is wrong whenever the cond carries
    unrelated literals (observed: shape bounds leaking in and inflating
    costs 1000x), so the fallback is only used when no operand resolves.
    """
    consts: dict[str, int] = {}
    for ins in cond.instrs:
        if ins.op == "constant":
            m = _CONST_INT.search(ins.line)
            if m:
                consts[ins.name] = int(m.group(1))
    root = None
    for ins in cond.instrs:
        if "ROOT" in ins.line:
            root = ins
    root = root or (cond.instrs[-1] if cond.instrs else None)
    if root is not None:
        call_part = root.line.split(root.op + "(", 1)
        if len(call_part) == 2:
            cands = [
                consts[name]
                for name in _OPERANDS.findall(call_part[1].split(")")[0])
                if name in consts
            ]
            if cands:
                return max(max(cands), 1)
    best = 1
    for ins in cond.instrs:
        for m in _CONST_INT.finditer(ins.line):
            best = max(best, int(m.group(1)))
    return best


def _dot_flops(ins: _Instr, comp: _Comp) -> float:
    result_elems = 1
    for _, dims in _shape_dims(ins.type_str):
        for d in dims:
            result_elems *= d
    # contraction size from lhs operand shape
    mc = _CONTRACT.search(ins.line)
    if not mc:
        return 0.0
    cdims = [int(x) for x in mc.group(1).split(",")] if mc.group(1) else []
    # first operand after the op name
    call_part = ins.line.split(ins.op + "(", 1)[1]
    ops = _OPERANDS.findall(call_part)
    if not ops:
        return 0.0
    lhs_type = comp.types.get(ops[0])
    if lhs_type is None:
        return 2.0 * result_elems  # unknown operand: assume contraction 1
    shapes = _shape_dims(lhs_type)
    if not shapes:
        return 0.0
    dims = shapes[0][1]
    csize = 1
    for cd in cdims:
        if cd < len(dims):
            csize *= dims[cd]
    return 2.0 * result_elems * csize


def _operand_bytes(ins: _Instr, comp: _Comp) -> tuple[float, float]:
    """(total operand bytes, biggest single operand bytes)."""
    call_part = ins.line.split(ins.op + "(", 1)
    if len(call_part) < 2:
        return 0.0, 0.0
    total = biggest = 0.0
    for op_name in _OPERANDS.findall(call_part[1].split(")")[0]):
        t = comp.types.get(op_name)
        if t:
            b = _type_bytes(t)
            total += b
            biggest = max(biggest, b)
    return total, biggest


def _fusion_param_kinds(callee: _Comp):
    """Classify how each fusion parameter is consumed inside the callee.

    Returns "convert_only" when the fusion is a pure dtype-cast chain, else
    {param_index: slice_bytes} for parameters read via dynamic-slice (only
    the slice hits memory), other params read fully.
    """
    param_index: dict[str, int] = {}
    ops_seen = set()
    via: dict[str, str] = {}  # alias (bitcast/copy) -> source name
    sliced: dict[int, float] = {}
    for ins in callee.instrs:
        ops_seen.add(ins.op)
        if ins.op == "parameter":
            m = re.search(r"parameter\((\d+)\)", ins.line)
            if m:
                param_index[ins.name] = int(m.group(1))
        elif ins.op in ("bitcast", "copy", "reshape"):
            srcs = _OPERANDS.findall(ins.line.split(ins.op + "(", 1)[1])
            if srcs:
                via[ins.name] = srcs[0]
        elif ins.op in ("dynamic-slice", "gather"):
            # Both address only the selected rows of their big operand:
            # charge the result bytes, not the whole table (a prefix-table
            # gather reads k rows, not the (k, 4, cap) table it indexes).
            srcs = _OPERANDS.findall(ins.line.split(ins.op + "(", 1)[1])
            if srcs:
                src = srcs[0]
                for _ in range(4):
                    src = via.get(src, src)
                if src in param_index:
                    sliced[param_index[src]] = _type_bytes(ins.type_str)
    body_ops = ops_seen - {"parameter", "constant", "bitcast", "reshape", "copy"}
    if body_ops <= {"convert"}:
        return "convert_only"
    return sliced


def _fusion_operand_bytes(ins: _Instr, comp: _Comp, sliced: dict) -> float:
    call_part = ins.line.split(ins.op + "(", 1)
    if len(call_part) < 2:
        return 0.0
    total = 0.0
    for i, op_name in enumerate(_OPERANDS.findall(call_part[1].split(")")[0])):
        if i in sliced:
            total += sliced[i]
            continue
        t = comp.types.get(op_name)
        if t:
            total += _type_bytes(t)
    return total


def _collective(ins: _Instr, n_devices: int):
    nbytes = _type_bytes(ins.type_str)
    m = _GROUPS_IOTA.search(ins.line)
    if m:
        g = int(m.group(2))
    else:
        m = _GROUPS_LIST.search(ins.line)
        g = len(m.group(1).split(",")) if m else n_devices
    g = max(g, 1)
    kind = next(k for k in _COLLECTIVES if ins.op.startswith(k))
    if kind == "all-reduce":
        w = 2.0 * (g - 1) / g
    elif kind == "reduce-scatter":
        w = float(g - 1)          # result is the post-scatter shard
    elif kind == "collective-permute":
        w = 1.0
    else:
        w = (g - 1) / g
    return kind, nbytes, nbytes * w


def _eval_comp(
    comp: _Comp, comps: dict, n_devices: int, memo: dict, flops_only_fusion=False
) -> HloCost:
    if comp.name in memo:
        return memo[comp.name]
    total = HloCost()
    for ins in comp.instrs:
        op = ins.op
        if op == "while":
            mcb = _COND_BODY.search(ins.line)
            if mcb:
                cond = comps.get(mcb.group(1))
                body = comps.get(mcb.group(2))
                trips = _trip_count(cond) if cond else 1
                if body:
                    total.add(
                        _eval_comp(body, comps, n_devices, memo).scaled(trips)
                    )
            continue
        if op == "fusion":
            mcalls = _CALLS.search(ins.line)
            callee = comps.get(mcalls.group(1)) if mcalls else None
            if callee is not None:
                sub = _eval_comp(
                    callee, comps, n_devices, memo, flops_only_fusion=True
                )
                total.flops += sub.flops            # dots inside fusions count
                total.link_bytes += sub.link_bytes  # (collectives never fuse)
            rb = _type_bytes(ins.type_str)
            if "dynamic_update_slice" in ins.line or "dynamic-update-slice" in ins.line:
                # DUS fusions alias the big buffer in place:
                # traffic = read update + write slice ~= 2 x update bytes.
                ob, biggest = _operand_bytes(ins, comp)
                total.bytes += 2.0 * max(ob - biggest, 0.0)
            elif callee is not None:
                # Per-operand accounting: params consumed via dynamic-slice
                # inside the fusion read only the slice (e.g. one layer of a
                # scanned weight stack), not the whole operand; pure-convert
                # fusions are CPU bf16->f32 staging the TPU backend never
                # emits -> free.
                kinds = _fusion_param_kinds(callee)
                if kinds == "convert_only":
                    pass
                else:
                    total.bytes += rb + _fusion_operand_bytes(ins, comp, kinds)
            else:
                ob, _ = _operand_bytes(ins, comp)
                total.bytes += ob + rb
            continue
        if op in ("call", "conditional"):
            mcalls = _CALLS.search(ins.line) or _COND_BODY.search(ins.line)
            for name in _OPERANDS.findall(ins.line.split("(", 1)[1]):
                if name in comps:
                    total.add(_eval_comp(comps[name], comps, n_devices, memo))
            continue
        if any(op.startswith(c) for c in _COLLECTIVES):
            if op.endswith("-done"):
                continue
            kind, nbytes, link = _collective(ins, n_devices)
            total.coll_bytes[kind] = total.coll_bytes.get(kind, 0.0) + nbytes
            total.coll_count[kind] = total.coll_count.get(kind, 0) + 1
            total.link_bytes += link
            total.bytes += 2 * nbytes  # collectives also touch HBM
            continue
        if op == "dot":
            total.flops += _dot_flops(ins, comp)
            if not flops_only_fusion:
                ob, _ = _operand_bytes(ins, comp)
                total.bytes += ob + _type_bytes(ins.type_str)
            continue
        if op in _FREE_OPS:
            continue
        if flops_only_fusion:
            continue  # inside fusions, non-dot ops stay in registers
        if op == "dynamic-update-slice":
            # in-place: traffic = read update + write slice = 2 x update
            ob, biggest = _operand_bytes(ins, comp)
            total.bytes += 2.0 * max(ob - biggest, 0.0)
            continue
        if op == "gather":
            # addressed traffic only: read the gathered rows + the index
            # operand, write the result — NOT the whole indexed table
            # (billing it would claim a (k, 4, cap) prefix-table read per
            # O(1) AFC lookup).  The table is specifically operand 0 of
            # gather(operand, indices) — not "the biggest operand", which
            # would mischarge whenever the index tensor outgrows the table.
            call_part = ins.line.split(op + "(", 1)
            table_bytes = 0.0
            if len(call_part) == 2:
                srcs = _OPERANDS.findall(call_part[1].split(")")[0])
                if srcs:
                    t = comp.types.get(srcs[0])
                    table_bytes = _type_bytes(t) if t else 0.0
            ob, _ = _operand_bytes(ins, comp)
            rb = _type_bytes(ins.type_str)
            total.bytes += max(ob - table_bytes, 0.0) + 2.0 * rb
            continue
        # generic top-level op: producer+consumer traffic
        ob, _ = _operand_bytes(ins, comp)
        total.bytes += ob + _type_bytes(ins.type_str)
    memo[comp.name] = total
    return total


def analyze_hlo(text: str, n_devices: int) -> HloCost:
    comps = _parse_computations(text)
    entry = comps.get("__entry__")
    if entry is None:
        return HloCost()
    memo: dict = {}
    return _eval_comp(entry, comps, n_devices, memo)


def while_costs(text: str, n_devices: int = 1) -> list[dict]:
    """Per-while-loop body costs of a compiled module.

    Returns one entry per ``while`` instruction found anywhere in the
    module: ``{"body": name, "trips": estimated trip count, "cost": HloCost
    of ONE body execution}`` (nested whiles inside the body are multiplied
    through as usual).  This is the per-iteration cost probe the
    incremental-AFC regression test uses: the fused executor's planner loop
    body must cost the same regardless of the (k, cap) buffer size, while
    the whole-program cost may scale with cap (the once-per-request
    precompute is allowed to).  Callers pick their loop of interest — the
    planner while is the one with the largest body cost (the inner Beta
    rejection loops are tiny).
    """
    comps = _parse_computations(text)
    out = []
    seen: set[str] = set()
    memo: dict = {}
    for cname, comp in comps.items():
        if cname == "__entry__" or comp.name in seen:
            continue
        seen.add(comp.name)
        for ins in comp.instrs:
            if ins.op != "while":
                continue
            mcb = _COND_BODY.search(ins.line)
            if not mcb:
                continue
            cond = comps.get(mcb.group(1))
            body = comps.get(mcb.group(2))
            if body is None:
                continue
            out.append(
                {
                    "body": body.name,
                    "trips": _trip_count(cond) if cond else 1,
                    "cost": _eval_comp(body, comps, n_devices, memo),
                }
            )
    return out
