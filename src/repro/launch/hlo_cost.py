"""HLO-text cost model: FLOPs / HBM bytes / collective bytes with
while-loop trip-count scaling.

XLA's built-in ``compiled.cost_analysis()`` visits each while body
ONCE — for scan-over-layers/time models (everything here) that
under-counts by the trip count, so the roofline would be fiction. This
walks the optimized post-SPMD HLO text instead:

- builds the computation call graph (while/fusion/call/conditional),
- multiplies while bodies by ``backend_config known_trip_count``,
- dot FLOPs = 2 * prod(result dims) * prod(contracting dims),
- ~1 FLOP/element for arithmetic ops (transcendentals included),
- HBM bytes = operands + results of *top-level* ops per computation
  (fusion interiors don't round-trip HBM — XLA's own model),
- collectives recorded per-op with replica-group size and scaled by
  the enclosing trip multiplier; link traffic uses ring factors.

Shapes in post-SPMD HLO are per-device shards, so every number is
per-chip — divide by per-chip peaks for roofline terms.

Robustness: HLO text evolves across XLA releases (dynamic ``<=N``
bounded dims, new narrow dtypes, opcode syntax we have never seen).
Instructions this parser cannot price degrade to a counted
``unparsed_ops`` field on :class:`CostSummary` instead of raising
mid-parse, so the dry run keeps working on newer jax HLO text —
consumers decide how much unparsed mass they tolerate.
"""

from __future__ import annotations

import dataclasses
import math
import re

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1,
    "u4": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "f16": 2,
    "bf16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "c64": 8,
    "c128": 16,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "f8e4m3b11fnuz": 1,
    "f8e4m3": 1,
    "f8e5m2fnuz": 1,
    "token": 0,
    "opaque": 0,
}

_NO_BYTES = {
    "parameter",
    "constant",
    "tuple",
    "get-tuple-element",
    "bitcast",
    "after-all",
    "partition-id",
    "replica-id",
}
_NO_FLOPS = _NO_BYTES | {
    "copy",
    "reshape",
    "broadcast",
    "transpose",
    "slice",
    "dynamic-slice",
    "dynamic-update-slice",
    "concatenate",
    "gather",
    "iota",
    "convert",
    "reverse",
    "pad",
    "reduce",
    "while",
    "fusion",
    "call",
    "conditional",
    "custom-call",
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "select",
    "compare",
    "rng-bit-generator",
    "dot",
    "scatter",
    "sort",
    "optimization-barrier",
    "convolution",
    "copy-start",
    "copy-done",
    "send",
    "recv",
    "send-done",
    "recv-done",
    "infeed",
    "outfeed",
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# every bracketed shape token, including dims this parser cannot price
# (dynamic "<=128", "?", ...) — the delta vs _SHAPE_RE is what degrades
# to unparsed_ops instead of raising.
_ANY_SHAPE_RE = re.compile(r"(\w+)\[([^\]]*)\]")
_DIMS_OK_RE = re.compile(r"^[\d,]*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")


def shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += _DTYPE_BYTES[dtype] * n
    return total


def shape_elems(type_str: str) -> int:
    total = 0
    for _, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def shape_unparsed(type_str: str) -> int:
    """Count array tokens in ``type_str`` this parser cannot price:
    non-literal dims (``f32[<=128]``) or dtypes missing from the byte
    table (``u2[64]``). Zero for every shape the cost model fully
    understands."""
    bad = 0
    for dtype, dims in _ANY_SHAPE_RE.findall(type_str):
        if not _DIMS_OK_RE.match(dims):
            bad += 1
        elif dtype not in _DTYPE_BYTES and not dtype.isdigit():
            # pure-digit "tokens" are layout minor-to-major annotations
            # ({1,0:T(8,128)} fragments), not dtypes
            bad += 1
    return bad


def _first_array_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    line: str


@dataclasses.dataclass
class CostSummary:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0  # raw payload bytes
    link_bytes: float = 0.0  # ring-model link traffic
    unparsed_ops: float = 0.0  # instructions priced best-effort (or not at all)
    collectives: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "CostSummary", mult: float = 1.0):
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        self.collective_bytes += mult * other.collective_bytes
        self.link_bytes += mult * other.link_bytes
        self.unparsed_ops += mult * other.unparsed_ops
        for k, v in other.collectives.items():
            cur = self.collectives.get(k, {"count": 0.0, "bytes": 0.0, "link_bytes": 0.0})
            # tolerate partially-populated entries (older trace JSON,
            # hand-built summaries): missing keys count as zero
            self.collectives[k] = {
                "count": cur.get("count", 0.0) + mult * v.get("count", 0.0),
                "bytes": cur.get("bytes", 0.0) + mult * v.get("bytes", 0.0),
                "link_bytes": cur.get("link_bytes", 0.0) + mult * v.get("link_bytes", 0.0),
            }


class HloCostModel:
    def __init__(self, hlo_text: str):
        self.computations: dict[str, list[Instr]] = {}
        self._parse(hlo_text)
        self._memo: dict[str, CostSummary] = {}
        self._dus_memo: dict[str, tuple] = {}

    def _parse(self, text: str):
        cur = None
        for line in text.splitlines():
            stripped = re.sub(r"/\*.*?\*/", "", line.rstrip())
            if not stripped:
                continue
            if stripped.endswith("{") and "->" in stripped:
                # "=" before "->" means an instruction, not a header —
                # but ignore "=" inside shape brackets (dynamic "<=N"
                # bounded dims appear in newer XLA signatures)
                head = re.sub(r"\[[^\]]*\]", "", stripped.split("->")[0])
                if "=" not in head:
                    mc = _COMP_RE.match(stripped)
                    if mc:
                        cur = mc.group(1)
                        self.computations[cur] = []
                        continue
            if stripped.startswith("}"):
                cur = None
                continue
            if cur is None:
                continue
            mi = _INSTR_RE.match(stripped)
            if mi:
                self.computations[cur].append(
                    Instr(mi.group(1), mi.group(2), mi.group(3), stripped)
                )

    # ---------------------------------------------------------- helpers

    def _symbols(self, instrs):
        return {i.name: i.type_str for i in instrs}

    def _operands(self, instr: Instr, symbols):
        # operand names are %refs inside the (...) after the opcode
        m = re.search(re.escape(instr.opcode) + r"\((.*)$", instr.line)
        if not m:
            return []
        args = m.group(1)
        names = re.findall(r"%([\w.\-]+)", args.split("), ")[0] if ")," in args else args)
        return [symbols[n] for n in names if n in symbols]

    def _dot_flops(self, instr: Instr, symbols) -> float:
        result_elems = shape_elems(instr.type_str)
        ops = self._operands(instr, symbols)
        if not ops:
            return 0.0
        lhs_dims = _first_array_dims(ops[0])
        mdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.line)
        contract = 1
        if mdims and mdims.group(1):
            for d in mdims.group(1).split(","):
                contract *= lhs_dims[int(d)] if int(d) < len(lhs_dims) else 1
        return 2.0 * result_elems * contract

    def _conv_flops(self, instr: Instr, symbols) -> float:
        # flops = 2 * out_elems * (in_channels/feature_group * prod(kernel spatial))
        ops = self._operands(instr, symbols)
        if len(ops) < 2:
            return 0.0
        rhs = _first_array_dims(ops[1])
        out_elems = shape_elems(instr.type_str)
        k = math.prod(rhs[:-1]) if rhs else 1  # rough: kernel elems / out_features
        return 2.0 * out_elems * k

    def _trip_count(self, instr: Instr) -> float:
        m = re.search(r"known_trip_count[^\d]*(\d+)", instr.line)
        if m:
            return float(m.group(1))
        return 1.0

    def _called(self, instr: Instr, attr: str):
        m = re.search(attr + r"=%?([\w.\-]+)", instr.line)
        return m.group(1) if m else None

    def _branches(self, instr: Instr):
        m = re.search(r"branch_computations=\{([^}]*)\}", instr.line)
        if m:
            return re.findall(r"%?([\w.\-]+)", m.group(1))
        out = []
        for attr in ("true_computation", "false_computation"):
            c = self._called(instr, attr)
            if c:
                out.append(c)
        return out

    def _dus_signature(self, comp_name: str):
        """For a fusion computation: byte sizes of buffers updated
        in place by interior dynamic-update-slices (counted with
        multiplicity: {full_buffer_bytes: count}) and the total bytes
        of their slice updates."""
        if comp_name in self._dus_memo:
            return self._dus_memo[comp_name]
        bufs: dict[int, int] = {}
        upd_total = 0
        instrs = self.computations.get(comp_name, [])
        symbols = self._symbols(instrs)
        for ins in instrs:
            if ins.opcode == "dynamic-update-slice":
                ops_ = self._operands(ins, symbols)
                if ops_:
                    b = shape_bytes(ops_[0])
                    bufs[b] = bufs.get(b, 0) + 1
                if len(ops_) > 1:
                    upd_total += shape_bytes(ops_[1])
        # also count the fusion result matching each updated buffer
        bufs = {k: v * 2 for k, v in bufs.items()}  # operand + result slot
        self._dus_memo[comp_name] = (bufs, upd_total)
        return bufs, upd_total

    def _group_size(self, instr: Instr) -> int:
        # replica_groups=[8,64]<=[512] -> groups of 64 / {{0,1},...}
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]", instr.line)
        if m:
            return int(m.group(2))
        m = re.search(r"replica_groups=\{\{([\d,]+)\}", instr.line)
        if m:
            return len(m.group(1).split(","))
        return 2

    def _collective_traffic(self, instr: Instr, symbols):
        """(payload_bytes, link_bytes) per-device ring estimates."""
        out_b = shape_bytes(instr.type_str)
        ops = self._operands(instr, symbols)
        in_b = sum(shape_bytes(o) for o in ops) if ops else out_b
        n = max(self._group_size(instr), 2)
        ring = (n - 1) / n
        if instr.opcode == "all-reduce":
            return out_b, 2.0 * ring * out_b
        if instr.opcode == "all-gather":
            return out_b, ring * out_b
        if instr.opcode == "reduce-scatter":
            return in_b, ring * in_b
        if instr.opcode == "all-to-all":
            return out_b, ring * out_b
        return out_b, float(out_b)  # collective-permute

    # ---------------------------------------------------------- cost

    def _accumulate(self, ins: Instr, symbols: dict, total: CostSummary) -> None:
        """Price one instruction into ``total``. May raise on HLO text
        this parser has never seen — cost() catches and counts it."""
        op = ins.opcode
        if op == "while":
            trips = self._trip_count(ins)
            body = self._called(ins, "body")
            cond = self._called(ins, "condition")
            if body:
                total.add(self.cost(body), trips)
            if cond:
                total.add(self.cost(cond), trips)
            return
        if op == "fusion":
            called = self._called(ins, "calls")
            dus_bufs, dus_updates = {}, 0
            if called:
                sub = self.cost(called)
                total.flops += sub.flops  # interior flops only
                total.unparsed_ops += sub.unparsed_ops
                dus_bufs, dus_updates = self._dus_signature(called)
            # HBM traffic: operands + result of the fusion itself —
            # EXCEPT buffers updated in place by an interior
            # dynamic-update-slice: those cost the slice, not the
            # full buffer (scan carries would otherwise be charged
            # thousands of times their real traffic).
            io = [shape_bytes(ins.type_str)]
            io += [shape_bytes(o) for o in self._operands(ins, symbols)]
            remaining = dict(dus_bufs)
            for b in io:
                if remaining.get(b, 0) > 0:
                    remaining[b] -= 1
                else:
                    total.bytes += b
            total.bytes += 2 * dus_updates  # slice read-modify-write
            return
        if op == "dynamic-update-slice":
            ops_ = self._operands(ins, symbols)
            upd = shape_bytes(ops_[1]) if len(ops_) > 1 else 0
            total.bytes += 2 * upd
            return
        if op == "call":
            called = self._called(ins, "to_apply")
            if called:
                total.add(self.cost(called))
            return
        if op == "conditional":
            branches = [self.cost(b) for b in self._branches(ins)]
            if branches:
                worst = max(branches, key=lambda c: c.flops + c.bytes)
                total.add(worst)
            return
        base_op = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done") and base_op[:-5] in COLLECTIVES:
            return
        if base_op in COLLECTIVES:
            payload, link = self._collective_traffic(ins, symbols)
            total.collective_bytes += payload
            total.link_bytes += link
            key = base_op
            cur = total.collectives.get(key, {"count": 0.0, "bytes": 0.0, "link_bytes": 0.0})
            total.collectives[key] = {
                "count": cur["count"] + 1,
                "bytes": cur["bytes"] + payload,
                "link_bytes": cur["link_bytes"] + link,
            }
            total.bytes += shape_bytes(ins.type_str)
            return
        # plain op
        if op not in _NO_BYTES:
            total.bytes += shape_bytes(ins.type_str)
            total.bytes += sum(shape_bytes(o) for o in self._operands(ins, symbols))
        if op == "dot":
            total.flops += self._dot_flops(ins, symbols)
        elif op == "convolution":
            total.flops += self._conv_flops(ins, symbols)
        elif op in ("reduce", "scatter", "select"):
            total.flops += shape_elems(ins.type_str)
        elif op not in _NO_FLOPS:
            total.flops += shape_elems(ins.type_str)

    def cost(self, comp_name: str) -> CostSummary:
        if comp_name in self._memo:
            return self._memo[comp_name]
        total = CostSummary()
        instrs = self.computations.get(comp_name, [])
        symbols = self._symbols(instrs)
        for ins in instrs:
            try:
                self._accumulate(ins, symbols, total)
                if shape_unparsed(ins.type_str):
                    # priced best-effort: the parsable fraction of the
                    # result shape is in the totals, the rest is not
                    total.unparsed_ops += 1.0
            except Exception:
                total.unparsed_ops += 1.0
        self._memo[comp_name] = total
        return total

    def entry_cost(self) -> CostSummary:
        # entry computation = the one not called by anyone; parse order:
        # ENTRY is usually last, and _COMP_RE tagged it; find by name "main"
        # or fall back to the computation with max cost reachability.
        names = list(self.computations)
        if not names:
            return CostSummary()
        called = set()
        for comp, instrs in self.computations.items():
            for ins in instrs:
                for attr in ("body", "condition", "calls", "to_apply"):
                    c = self._called(ins, attr)
                    if c:
                        called.add(c)
                for b in self._branches(ins):
                    called.add(b)
        roots = [n for n in names if n not in called]
        if not roots:
            roots = names[-1:]
        best = None
        for r in roots:
            c = self.cost(r)
            if best is None or c.flops > best[1].flops:
                best = (r, c)
        return best[1]


def analyze(hlo_text: str) -> dict:
    model = HloCostModel(hlo_text)
    c = model.entry_cost()
    return {
        "flops": c.flops,
        "bytes": c.bytes,
        "collective_bytes": c.collective_bytes,
        "link_bytes": c.link_bytes,
        "unparsed_ops": c.unparsed_ops,
        "collectives": c.collectives,
    }
