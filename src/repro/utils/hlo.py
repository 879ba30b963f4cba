"""HLO analysis: trip-count-aware FLOP/byte/collective accounting + roofline.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified in
tests/test_hlo.py), so a scanned-layers program under-reports by ~L x M.
``analyze_hlo`` instead walks the optimized HLO structurally:

  * computations are parsed into instruction tables,
  * the call graph (while / fusion / call / conditional / to_apply) is
    expanded with multipliers — ``while`` trip counts come from the
    ``backend_config={"known_trip_count":{"n":...}}`` annotation,
  * FLOPs  = Σ mult·2·|out|·K over every ``dot`` (MXU ops dominate; the
    elementwise tail is ignored, stated in EXPERIMENTS.md),
  * HBM bytes = Σ mult·(out + operands) over materializing instructions at
    computation level (fusion internals live in registers/VMEM),
  * wire bytes = Σ mult·bytes·wire_mult over collective instructions.

Per-op wire multipliers (ring algorithms, n -> inf):

    all-reduce          2x   (reduce-scatter + all-gather)
    all-gather          1x   (each device receives the full output once)
    reduce-scatter      1x
    all-to-all          1x
    collective-permute  1x

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (assignment-specified).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "HW_V5E",
    "op_names",
    "CollectiveStats",
    "collective_bytes",
    "analyze_hlo",
    "HloCosts",
    "Roofline",
    "roofline",
]


@dataclass(frozen=True)
class Hardware:
    peak_flops: float     # per chip, bf16
    hbm_bw: float         # bytes/s per chip
    ici_bw: float         # bytes/s per link


HW_V5E = Hardware(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_WIRE_MULT = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
    "ragged-all-to-all": 1.0,
}

# "%name = TYPE op(" where TYPE may be a tuple of shapes; async variants
# appear as op-start (count) + op-done (skip).
_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9_,\[\]{}:#()\s]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"ragged-all-to-all)"
    r"(-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: dict[str, float] = field(default_factory=dict)
    count_by_op: dict[str, int] = field(default_factory=dict)

    @property
    def wire_bytes(self) -> float:
        return sum(self.bytes_by_op.values())

    @property
    def count(self) -> int:
        return sum(self.count_by_op.values())


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum output bytes x wire multiplier of every collective instruction
    in (optimized) HLO text.  ``-done`` ops are skipped (their ``-start``
    twin carries the shape)."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-done" in line and "(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_text, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_text) * _WIRE_MULT[op]
        stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0.0) + b
        stats.count_by_op[op] = stats.count_by_op.get(op, 0) + 1
    return stats


# --------------------------------------------------------------------------
# structural HLO walk (trip-count aware)
# --------------------------------------------------------------------------
# header args may nest parens/tuples: match loosely on "(name (...) -> ... {"
_COMP_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?[^=]+?\)?)\s*([\w\-]+)\((.*?)\)(.*)$"
)
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CDIMS = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_SKIP_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "while", "conditional",
}


@dataclass
class HloCosts:
    flops: float = 0.0            # dot FLOPs, trip-count expanded
    hbm_bytes: float = 0.0        # materializing-instruction traffic
    stats: CollectiveStats = field(default_factory=CollectiveStats)

    @property
    def wire_bytes(self) -> float:
        return self.stats.wire_bytes


_COMMENT = re.compile(r"/\*.*?\*/")


def _parse_computations(text: str) -> dict[str, list[tuple]]:
    comps: dict[str, list[tuple]] = {}
    cur: list[tuple] | None = None
    for raw in text.splitlines():
        # long tuple shapes carry /*index=N*/ comments whose '=' breaks the
        # instruction regex — strip comments before matching
        line = _COMMENT.sub("", raw).rstrip()
        if cur is None:
            m = _COMP_HEAD.match(line.strip())
            if m:
                comps[m.group(2)] = cur = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            name, shape_text, op, operands, attrs = m.groups()
            # operand tokens may carry inline types ("f32[8]{0} %x") on newer
            # XLA text dumps or be bare ("%x") on older ones — take the names
            ops = re.findall(r"%([\w.\-]+)", operands)
            cur.append((name, shape_text.strip(), op, ops, attrs))
    return comps


_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INLINED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def op_names(text: str) -> dict[str, str]:
    """Instruction name -> its ``op_name`` metadata ("" where it carries
    none) for every instruction of one optimized HLO module that runs as
    an operation of its own: fusion bodies and reducers are left out, as a
    fusion runs as one operation under the fusion's name and with its
    root's ``op_name``.  ``jax.named_scope``s show as path segments of
    the ``op_name`` (``jit(f)/coded_head/dot_general``)."""
    comps: dict[str, dict[str, str]] = {}
    inlined: set[str] = set()
    cur: dict[str, str] | None = None
    for raw in text.splitlines():
        line = _COMMENT.sub("", raw).rstrip()
        if cur is None:
            m = _COMP_HEAD.match(line.strip())
            if m:
                cur = comps.setdefault(m.group(2), {})
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _NAME.match(line)
        if m:
            meta = _OP_NAME.search(line)
            cur[m.group(1)] = meta.group(1) if meta else ""
            inlined.update(_INLINED.findall(line))
    return {name: op for comp, instrs in comps.items() if comp not in inlined
            for name, op in instrs.items()}


def analyze_hlo(text: str) -> HloCosts:
    """Trip-count-expanded FLOPs / HBM bytes / collective bytes of one
    optimized per-device HLO module."""
    comps = _parse_computations(text)
    # find the entry computation (re-scan text for 'ENTRY')
    entry = None
    for line in text.splitlines():
        m = _COMP_HEAD.match(line.strip())
        if m and m.group(1):
            entry = m.group(2)
            break
    if entry is None:  # pragma: no cover
        raise ValueError("no ENTRY computation found")

    costs = HloCosts()
    fusion_called: set[str] = set()
    for instrs in comps.values():
        for name, shape_text, op, ops, attrs in instrs:
            if op == "fusion":
                m = _CALLS.search(attrs)
                if m:
                    fusion_called.add(m.group(1))

    def shape_table(comp: str) -> dict[str, str]:
        return {name: st for name, st, *_ in comps.get(comp, [])}

    def walk(comp: str, mult: float, in_fusion: bool, seen: tuple = ()):
        if comp not in comps or comp in seen:
            return
        table = shape_table(comp)
        for name, shape_text, op, ops, attrs in comps[comp]:
            # ---- recurse into called computations -----------------------
            trip = 1.0
            if op == "while":
                m = _TRIP.search(attrs)
                trip = float(m.group(1)) if m else 1.0
            called = _CALLS.findall(attrs)
            mb = _BRANCHES.search(attrs)
            if mb:
                called += [c.strip().lstrip("%") for c in mb.group(1).split(",")]
            child_fusion = in_fusion or op == "fusion"
            for c in called:
                walk(c, mult * trip, child_fusion, seen + (comp,))
            # ---- dot FLOPs ----------------------------------------------
            if op == "dot":
                out_elems = 1
                sm = _SHAPE_RE.search(shape_text)
                if sm:
                    dims = [int(d) for d in sm.group(2).split(",") if d]
                    for d in dims:
                        out_elems *= d
                k = 1
                cm = _LHS_CDIMS.search(attrs)
                if cm and ops:
                    lhs_shape = table.get(ops[0], "")
                    lm = _SHAPE_RE.search(lhs_shape)
                    if lm:
                        ldims = [int(d) for d in lm.group(2).split(",") if d]
                        for ci in cm.group(1).split(","):
                            if ci and int(ci) < len(ldims):
                                k *= ldims[int(ci)]
                costs.flops += mult * 2.0 * out_elems * k
            # ---- collectives ---------------------------------------------
            base_op = op[:-6] if op.endswith("-start") else op
            if base_op in _WIRE_MULT and not op.endswith("-done"):
                b = _shape_bytes(shape_text)
                if op.endswith("-start"):
                    b /= 2.0  # start tuples carry (input, output) buffers
                wb = b * _WIRE_MULT[base_op] * mult
                costs.stats.bytes_by_op[base_op] = (
                    costs.stats.bytes_by_op.get(base_op, 0.0) + wb
                )
                costs.stats.count_by_op[base_op] = (
                    costs.stats.count_by_op.get(base_op, 0) + int(mult)
                )
            # ---- HBM traffic ---------------------------------------------
            if not in_fusion and op not in _SKIP_BYTES:
                b = _shape_bytes(shape_text)
                for o in ops:
                    b += _shape_bytes(table.get(o, ""))
                costs.hbm_bytes += mult * b

    # walk entry; fusion-called computations are traversed from their call
    # sites with in_fusion=True, so only visit non-fusion roots here
    walk(entry, 1.0, False)
    return costs


# --------------------------------------------------------------------------
@dataclass
class Roofline:
    """Three-term roofline for one compiled (per-device) program."""

    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    hbm_bytes: float
    wire_bytes: float
    model_flops: float = 0.0   # analytic 6·N·D / 2·N·D useful FLOPs (per device)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def mfu_bound(self) -> float:
        """Upper bound on MFU: useful FLOPs / (peak x bound-time)."""
        if self.bound_s <= 0:
            return 0.0
        return self.model_flops / (HW_V5E.peak_flops * self.bound_s)

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "wire_bytes": self.wire_bytes,
            "model_flops": self.model_flops,
            "useful_fraction": self.useful_fraction,
            "mfu_bound": self.mfu_bound,
        }


def roofline(
    flops: float,
    hbm_bytes: float,
    wire_bytes: float,
    model_flops: float = 0.0,
    hw: Hardware = HW_V5E,
) -> Roofline:
    """All inputs are PER-DEVICE quantities of one step."""
    return Roofline(
        compute_s=flops / hw.peak_flops,
        memory_s=hbm_bytes / hw.hbm_bw,
        collective_s=wire_bytes / hw.ici_bw,
        flops=flops,
        hbm_bytes=hbm_bytes,
        wire_bytes=wire_bytes,
        model_flops=model_flops,
    )
