"""Post-compile HLO analysis: trip-count-aware flops/bytes/collective costs.

XLA's ``compiled.cost_analysis()`` counts every computation ONCE — including
``while`` (lax.scan) bodies — so a scanned-L-layer model under-reports
flops/bytes/collectives by ~L x. This module parses the optimized HLO text,
reconstructs the call graph (while/fusion/call/conditional), extracts loop
trip counts from the loop-condition constants, and accumulates per-
instruction costs weighted by execution multiplicity:

  flops             dot ops: 2 * |out| * |contracting| (plus elementwise)
  bytes accessed    sum(operand bytes + output bytes) per executed op
  collective bytes  operand bytes of all-gather / all-reduce /
                    reduce-scatter / all-to-all / collective-permute

Validated against cost_analysis() on loop-free programs and against manual
math on scanned programs (tests/test_hlo_analysis.py).

Contract: `program_costs(hlo_text)` is pure text analysis — it never
executes the program, tolerates unknown ops (counted as zero-cost), and
weights every instruction by the product of the trip counts of the while
loops enclosing it. Hardware model (TPU v5e target): 197 TFLOP/s bf16
per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e5m2": 1, "f8e4m3fn": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# elementwise-ish ops counted as 1 flop per output element
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "log", "tanh", "rsqrt", "sqrt", "negate", "abs",
    "compare", "select", "and", "or", "xor", "floor", "ceil",
    "exponential-minus-one", "log-plus-one", "cosine", "sine", "atan2",
}

# zero-cost meta ops: no HBM traffic (aliases/views/plumbing). XLA's
# bytes-accessed ignores these too; counting them would charge the whole
# loop-carried state tuple once per get-tuple-element line.
_NO_BYTES = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "after-all", "while", "conditional", "call", "custom-call",
    "opt-barrier", "domain", "partition-id", "replica-id", "iota",
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# a shape's layout, e.g. f32[1,512]{1,0:T(1,128)S(1)} on the TPU: its tile
# parentheses would break the instruction pattern, and it carries no size
_LAYOUT_RE = re.compile(r"(\w+\[[\d,]*\])\{[^{}]*\}")
# tuple types contain /*index=N*/ comments (with '=') but never nested
# parens, so the tuple branch is "anything but parens"
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*((?:\([^()]*\))|(?:[\w\[\],\s{}]+?))\s+"
    r"([\w\-]+)\((.*)$"
)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^\n]*\)\s*->")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|condition|body|to_apply)=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _shape_dims(type_str: str) -> list[tuple[str, tuple[int, ...]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",")) if dims else ()
        out.append((dt, shape))
    return out


def _nelems(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _shape_bytes(type_str: str) -> int:
    return sum(
        _nelems(s) * _DTYPE_BYTES[dt] for dt, s in _shape_dims(type_str)
    )


@dataclasses.dataclass
class _CompCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: dict = dataclasses.field(default_factory=dict)
    coll_count: dict = dataclasses.field(default_factory=dict)
    calls: list = dataclasses.field(default_factory=list)  # (name, kind)
    trip_const: int = 1  # max int const (trip count when used as a cond)


def _parse_computations(text: str) -> dict[str, _CompCost]:
    comps: dict[str, _CompCost] = {}
    cur: _CompCost | None = None
    shapes: dict[str, str] = {}
    for line in text.splitlines():
        line = _LAYOUT_RE.sub(r"\1", line)
        hdr = _COMP_HDR_RE.match(line)
        if hdr and ("{" in line or line.rstrip().endswith("->") or "->" in line):
            cur = _CompCost()
            comps[hdr.group(1)] = cur
            shapes = {}
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op, rest = m.groups()
        shapes[name] = type_str
        out_bytes = _shape_bytes(type_str)
        out_dims = _shape_dims(type_str)
        out_elems = sum(_nelems(s) for _, s in out_dims)

        # integer constants (trip-count fallback for loop conditions)
        if op == "constant" and type_str.strip().rstrip("{}0,: ") in (
            "s32[]", "s64[]", "u32[]", "u64[]"
        ):
            c = re.search(r"constant\((\d+)\)", line)
            if c:
                cur.trip_const = max(cur.trip_const, int(c.group(1)))

        # operand bytes: resolve names defined earlier in this computation
        call_part = rest.split(")", 1)[0]
        operand_names = _OPERAND_RE.findall(call_part)
        in_bytes = sum(
            _shape_bytes(shapes.get(nm, "")) for nm in operand_names
        )
        if op not in _NO_BYTES:
            cur.bytes += out_bytes + in_bytes

        if op == "dot":
            cm = _CONTRACT_RE.search(line)
            contract = 1
            if cm and operand_names:
                lhs_shape = None
                for dt, s in _shape_dims(shapes.get(operand_names[0], "")):
                    lhs_shape = s
                    break
                if lhs_shape and cm.group(1):
                    for di in cm.group(1).split(","):
                        if int(di) < len(lhs_shape):
                            contract *= lhs_shape[int(di)]
            cur.flops += 2.0 * out_elems * contract
        elif op == "convolution":
            # dominated elsewhere; approximate via output x window if present
            cur.flops += 2.0 * out_elems
        elif op in _ELEMENTWISE:
            cur.flops += float(out_elems)

        base = op[:-6] if op.endswith("-start") else op
        if base in COLLECTIVE_OPS and not op.endswith("-done"):
            cur.coll_bytes[base] = cur.coll_bytes.get(base, 0) + in_bytes
            cur.coll_count[base] = cur.coll_count.get(base, 0) + 1

        # authoritative trip count: XLA annotates the while instruction
        ktc = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', line)
        trip_hint = int(ktc.group(1)) if ktc else None
        for cm in _CALLS_RE.finditer(line):
            kind = "body" if "body=" in cm.group(0) else (
                "cond" if "condition=" in cm.group(0) else "call"
            )
            cur.calls.append((cm.group(1), kind, op, trip_hint))
        bm = _BRANCHES_RE.search(line)
        if bm:
            for nm in _OPERAND_RE.findall(bm.group(1)):
                cur.calls.append((nm, "call", op, None))
    return comps


@dataclasses.dataclass
class ProgramCosts:
    flops: float
    bytes: float
    coll_bytes_by_op: dict
    coll_count_by_op: dict

    @property
    def collective_bytes(self) -> float:
        return float(sum(self.coll_bytes_by_op.values()))


def program_costs(text: str, entry: str | None = None) -> ProgramCosts:
    """Walk the call graph from ENTRY accumulating multiplicity-weighted costs."""
    comps = _parse_computations(text)
    em = re.search(r"^ENTRY\s+%?([\w\.\-]+)", text, re.M)
    entry = entry or (em.group(1) if em else next(iter(comps)))

    total = ProgramCosts(0.0, 0.0, {}, {})

    def _sibling_cond(comp: _CompCost, body_name: str) -> str | None:
        # a while instruction contributes both a 'cond' and a 'body' call;
        # pair them by order of appearance
        conds = [n for n, k, *_ in comp.calls if k == "cond"]
        bodies = [n for n, k, *_ in comp.calls if k == "body"]
        if body_name in bodies and len(conds) > bodies.index(body_name):
            return conds[bodies.index(body_name)]
        return conds[0] if conds else None

    def visit(name: str, mult: float, stack: frozenset, count_bytes: bool):
        if name not in comps or name in stack:
            return
        c = comps[name]
        total.flops += mult * c.flops
        if count_bytes:
            # bytes are only HBM-level: instructions INSIDE fusion bodies
            # are registers/VMEM, already accounted at the fusion call site
            total.bytes += mult * c.bytes
        for k, v in c.coll_bytes.items():
            total.coll_bytes_by_op[k] = total.coll_bytes_by_op.get(k, 0) + mult * v
        for k, v in c.coll_count.items():
            total.coll_count_by_op[k] = total.coll_count_by_op.get(k, 0) + mult * v
        stack = stack | {name}
        for callee, kind, op, trip_hint in c.calls:
            child_bytes = count_bytes and op != "fusion"
            if kind in ("body", "cond"):
                trip = trip_hint
                if trip is None:
                    # fallback: constants in the loop-condition computation
                    cond_name = (
                        callee if kind == "cond" else _sibling_cond(c, callee)
                    )
                    trip = (
                        comps[cond_name].trip_const
                        if cond_name in comps else 1
                    )
                visit(callee, mult * max(trip, 1), stack, child_bytes)
            else:
                visit(callee, mult, stack, child_bytes)

    visit(entry, 1.0, frozenset(), True)
    return total


# ---------------------------------------------------------------------------
# legacy simple interface (kept for callers that want raw per-text stats)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: dict[str, float]
    count_by_op: dict[str, float]

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_op.values()))


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Multiplicity-aware collective stats for the whole program."""
    pc = program_costs(hlo_text)
    return CollectiveStats(dict(pc.coll_bytes_by_op), dict(pc.coll_count_by_op))


def compiled_collective_costs(compiled, iterations: int = 1) -> dict:
    """Per-iteration collective traffic MEASURED from a compiled executable.

    Parses the optimized (post-SPMD-partitioning) HLO of ``compiled`` —
    e.g. a ``jit(shard_map(...)).lower(...).compile()`` of one sharded
    solver chunk — and divides the trip-count-weighted collective bytes by
    ``iterations`` (the scan length the program executes). All figures are
    PER DEVICE: a ``collective-permute`` is charged its operand bytes on
    each sender, matching the per-node accounting convention of the
    modeled ``doubles_received`` columns.

    Returns ``{"bytes_per_iter", "count_per_iter", "bytes_by_op",
    "count_by_op"}`` (the by-op dicts are also per iteration).
    """
    stats = collective_stats(compiled.as_text())
    it = max(int(iterations), 1)
    return {
        "bytes_per_iter": stats.total_bytes / it,
        "count_per_iter": float(sum(stats.count_by_op.values())) / it,
        "bytes_by_op": {k: v / it for k, v in stats.bytes_by_op.items()},
        "count_by_op": {k: v / it for k, v in stats.count_by_op.items()},
    }


@dataclasses.dataclass
class Roofline:
    """All terms are SECONDS for one step of the lowered program."""

    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float  # per device
    hlo_bytes: float  # per device
    collective_bytes: float  # per device
    model_flops: float  # global useful flops (6ND / 2ND)
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flop_ratio(self) -> float:
        tot = self.hlo_flops * self.chips
        return self.model_flops / tot if tot else float("nan")

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound time — the MFU analogue derivable
        without wall clocks: (model_flops/chips/peak) / max(terms)."""
        ideal = self.model_flops / self.chips / PEAK_FLOPS
        return ideal / self.bound_s if self.bound_s else float("nan")


def roofline_terms(
    cost: dict, colls: CollectiveStats, chips: int, model_flops: float,
    links_per_chip: float = 1.0,
) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    cb = float(colls.total_bytes)
    return Roofline(
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=cb / (ICI_BW * links_per_chip),
        hlo_flops=flops,
        hlo_bytes=byts,
        collective_bytes=cb,
        model_flops=model_flops,
        chips=chips,
    )


def model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    """Useful FLOPs: 6*N*D train, 2*N*D inference (+ attention terms)."""
    n_active = cfg.active_param_count()
    L = cfg.n_layers
    H, hd = cfg.n_heads, cfg.head_dim
    if kind == "train":
        tokens = batch * seq
        # causal attn fwd ~ 2 * S^2/2 * H*hd * 2(qk+av); x3 with backward
        attn = 2.0 * 3.0 * L * batch * seq * seq * H * hd
        return 6.0 * n_active * tokens + attn
    if kind == "prefill":
        tokens = batch * seq
        attn = 2.0 * L * batch * seq * seq * H * hd
        return 2.0 * n_active * tokens + attn
    # decode: one token, attends over `seq` cache entries
    attn = 4.0 * L * batch * seq * H * hd
    return 2.0 * n_active * batch + attn
