"""Compiled-artifact analysis: HLO cost model, roofline, dense-free proofs.

This module absorbs the former ``repro.launch.hlo_cost`` (trip-count-aware
cost model over post-SPMD HLO text) and ``repro.launch.hlo_analysis``
(roofline-term extraction); both old import paths remain as thin shims.

On top of those it adds the piece that makes the analyzers a CI *gate*
rather than a per-PR ritual: :func:`dense_free` statically proves that a
registered pack kernel never materializes a d-sized dense buffer outside
its tile-granular VMEM working set.  The proof traces the kernel wrapper to
a jaxpr (no lowering, no TPU needed) and checks

  1. the wrapper stages exactly into a ``pallas_call`` -- no top-level eqn
     creates a new >= d buffer around it (a stray ``astype`` or mask there
     would be a dense HBM pass the fusion docs promised away), and
  2. every value inside the kernel jaxpr (including fori_loop bodies) is
     bounded by the tile size, which itself is a strict fraction of d.

Together these say: the dense compressed delta exists only one tile at a
time, in VMEM -- the EF-BV payload path is O(payload), not O(d), in HBM.

-- cost model rationale (unchanged from the former module) -----------------
On the CPU backend, ``compiled.cost_analysis()`` counts a while-loop body
ONCE -- a lax.scan over 40 layers contributes 1/40th of its real cost,
which breaks the roofline for every scan-based model here.  ``hlo_cost``
re-derives the three roofline numerators directly from the compiled HLO:

  flops       -- 2*M*N*K per dot (descending into fusion computations and
                 multiplying nested while bodies by their trip counts),
  hbm bytes   -- sum of operand+result bytes of *top-level* instructions per
                 computation (XLA's fusion boundaries are exactly the HBM
                 materialization points), trip-count weighted,
  wire bytes  -- per collective kind, with all-reduce counted as 2x payload
                 (ring reduce-scatter + all-gather).

All numbers are per-device (the HLO is the partitioned module).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Callable, Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\}?\s*([a-z][\w\-]*)\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    rhs: str
    opcode: str
    result_type: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    types: Dict[str, str]  # value name -> type string (params + results)


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    entry_name = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if current is None:
            if line.endswith("{"):
                m = _COMP_HDR.match(line.strip())
                if m:
                    current = Computation(m.group(2), [], {})
                    if m.group(1):
                        entry_name = m.group(2)
                    # parameter types from the header signature
                    for pm in re.finditer(r"([\w\.\-]+)\s*:\s*((?:\([^)]*\))|[\w\[\],]+)",
                                          m.group(3)):
                        current.types[pm.group(1)] = pm.group(2)
            continue
        if line.strip() == "}":
            comps[current.name] = current
            current = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            name, rhs = m.group(1), m.group(2)
            om = _OPCODE_RE.search(rhs)
            opcode = om.group(1) if om else ""
            idx = rhs.find(opcode + "(") if opcode else -1
            rtype = rhs[:idx].strip() if idx > 0 else rhs
            ins = Instr(name, rhs, opcode, rtype)
            current.instrs.append(ins)
            current.types[name] = rtype
    if comps and entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


def _operand_names(ins: Instr) -> List[str]:
    """Operand names of an instruction, robust to both operand syntaxes:
    bare (``dot(%a, %b)``) and inline-typed (``dot(f32[32,64]{1,0} %a, ...)``
    -- older XLA text).  Commas inside ``[]``/``{}`` (shape dims, layouts)
    are not operand separators."""
    idx = ins.rhs.find(ins.opcode + "(")
    if idx < 0:
        return []
    depth, bracket, args, cur = 0, 0, [], ""
    for ch in ins.rhs[idx + len(ins.opcode):]:
        if ch == "(":
            depth += 1
            if depth == 1:
                continue
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        if depth < 1:
            continue
        if ch in "[{":
            bracket += 1
        elif ch in "]}":
            bracket -= 1
        if ch == "," and depth == 1 and bracket == 0:
            args.append(cur)
            cur = ""
        else:
            cur += ch
    if cur.strip():
        args.append(cur)
    out = []
    for a in args:
        a = a.strip()
        named = re.findall(r"%([\w\.\-]+)", a)
        if named:
            out.append(named[-1])
            continue
        toks = a.split()
        if toks and re.fullmatch(r"[\w\.\-]+", toks[-1]):
            out.append(toks[-1])
    return out


def _called(ins: Instr) -> List[str]:
    out = []
    for key in ("calls=", "body=", "to_apply=", "condition="):
        for m in re.finditer(re.escape(key) + r"%?([\w\.\-]+)", ins.rhs):
            out.append(m.group(1))
    m = re.search(r"branch_computations=\{([^}]*)\}", ins.rhs)
    if m:
        out.extend(x.strip().lstrip("%") for x in m.group(1).split(","))
    return out


def trip_count(cond: Computation) -> int:
    consts: Dict[str, int] = {}
    best = None
    for ins in cond.instrs:
        m = re.search(r"constant\((\d+)\)", ins.rhs)
        if m:
            consts[ins.name] = int(m.group(1))
    for ins in cond.instrs:
        if "compare(" in ins.rhs:
            for op in _operand_names(ins):
                if op in consts:
                    best = consts[op]
    if best is None:
        best = max(consts.values(), default=1)
    return max(best, 1)


def dot_flops(ins: Instr, types: Dict[str, str]) -> float:
    res = _first_shape_dims(ins.result_type)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rhs)
    ops = _operand_names(ins)
    k = 1
    if m and ops:
        lhs_dims = _first_shape_dims(types.get(ops[0], ""))
        for c in (int(d) for d in m.group(1).split(",") if d):
            if c < len(lhs_dims):
                k *= lhs_dims[c]
    return 2.0 * float(math.prod(res) if res else 0) * float(k)


def _io_bytes(ins: Instr, types: Dict[str, str]) -> float:
    """HBM traffic of one materialized op: result bytes + operand bytes.

    Slicing/update ops only *touch* the slice, not the whole operand -- a
    dynamic-slice of one layer's weights from the (L, ...) scan stack reads
    the slice, not L x it.  Counting full operands there inflated the memory
    term ~100x on deep models (hypothesis->measure cycle recorded in
    EXPERIMENTS §Perf methodology)."""
    op = ins.opcode
    res = _shape_bytes(ins.result_type)
    ops = _operand_names(ins)
    if op in ("dynamic-slice", "slice"):
        return float(2 * res)  # read slice + write result
    if op == "gather":
        idx = _shape_bytes(types.get(ops[1], "")) if len(ops) > 1 else 0
        return float(2 * res + idx)
    if op == "dynamic-update-slice":
        upd = _shape_bytes(types.get(ops[1], "")) if len(ops) > 1 else 0
        return float(2 * upd)  # in-place: read+write the update region
    if op == "scatter":
        upd = _shape_bytes(types.get(ops[2], "")) if len(ops) > 2 else res
        idx = _shape_bytes(types.get(ops[1], "")) if len(ops) > 1 else 0
        return float(3 * upd + idx)  # read-modify-write of touched region
    total = res
    for name in ops:
        total += _shape_bytes(types.get(name, ""))
    return float(total)


_SLICING = ("dynamic-slice", "slice", "gather")


def _param_names_of(comp: "Computation") -> Dict[int, str]:
    out: Dict[int, str] = {}
    for b_ins in comp.instrs:
        m = re.search(r"parameter\((\d+)\)", b_ins.rhs)
        if m:
            out[int(m.group(1))] = b_ins.name
    return out


def _sliced_only_bytes(body: "Computation", pname: str,
                       comps: Dict[str, "Computation"], seen) -> Optional[float]:
    """Bytes actually read from parameter ``pname`` of ``body`` when its
    every use is a slicing op -- descending through nested fusion/call
    wrappers (older XLA wraps the scan-stack dynamic-slice in a parallel
    call computation).  None if any consumer reads the full operand."""
    key = (body.name, pname)
    if key in seen:
        return None
    seen = seen | {key}
    consumers = [b for b in body.instrs if pname in _operand_names(b)]
    if not consumers:
        return None  # conservatively charge the full operand
    total = 0.0
    for c in consumers:
        if c.opcode in _SLICING:
            total += _shape_bytes(c.result_type)
        elif c.opcode in ("fusion", "call"):
            called = [comps[x] for x in _called(c) if x in comps]
            if not called:
                return None
            inner = called[0]
            inner_params = _param_names_of(inner)
            # the operand may be passed at several positions; every one must
            # be slice-only inside the callee
            positions = [i for i, o in enumerate(_operand_names(c))
                         if o == pname]
            for pos in positions:
                inner_pname = inner_params.get(pos)
                if inner_pname is None:
                    return None
                sub = _sliced_only_bytes(inner, inner_pname, comps, seen)
                if sub is None:
                    return None
                total += sub
        else:
            return None
    return total


def _fusion_io_bytes(ins: Instr, types: Dict[str, str],
                     body: Optional["Computation"],
                     comps: Optional[Dict[str, "Computation"]] = None) -> float:
    """Fusion boundary traffic with slice-awareness: when a fusion *parameter*
    is only consumed by slicing ops inside the body (the scan-stack weight
    lookup pattern), charge the slice sizes, not the full stacked operand."""
    ops = _operand_names(ins)
    # in-place accumulation pattern: fusion rooted in dynamic-update-slice
    # aliases its big buffer operand -- traffic is the update region, not the
    # whole (L, ...) stack (and the result is the aliased buffer, also not
    # re-written in full).
    root = body.instrs[-1] if (body and body.instrs) else None
    if root is not None and root.opcode == "dynamic-update-slice":
        upd_ops = _operand_names(root)
        upd = _shape_bytes(body.types.get(upd_ops[1], "")) if len(upd_ops) > 1 \
            else 0
        small = 0
        res_b = _shape_bytes(ins.result_type)
        for name in ops:
            b = _shape_bytes(types.get(name, ""))
            if b != res_b:  # skip the aliased buffer itself
                small += min(b, res_b)
        return float(2 * upd + small)

    total = _shape_bytes(ins.result_type)
    if body is None:
        for name in ops:
            total += _shape_bytes(types.get(name, ""))
        return float(total)
    # map parameter index -> param instr name inside the body
    param_names = _param_names_of(body)
    for i, name in enumerate(ops):
        full = _shape_bytes(types.get(name, ""))
        pname = param_names.get(i)
        if pname is None:
            total += full
            continue
        sliced = _sliced_only_bytes(body, pname, comps or {}, frozenset())
        total += full if sliced is None else sliced
    return float(total)


_COLL_WEIGHT = {
    "all-reduce": 2.0,        # ring RS + AG
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "",
}


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_breakdown: Dict[str, float] = dataclasses.field(default_factory=dict)

    def __iadd__(self, other: "Cost"):
        self.flops += other.flops
        self.hbm_bytes += other.hbm_bytes
        self.coll_bytes += other.coll_bytes
        for k, v in other.coll_breakdown.items():
            self.coll_breakdown[k] = self.coll_breakdown.get(k, 0.0) + v
        return self

    def scaled(self, f: float) -> "Cost":
        return Cost(self.flops * f, self.hbm_bytes * f, self.coll_bytes * f,
                    {k: v * f for k, v in self.coll_breakdown.items()})


def _fusion_flops(comp: Computation, comps, memo) -> float:
    if comp.name in memo:
        return memo[comp.name]
    memo[comp.name] = 0.0
    total = 0.0
    for ins in comp.instrs:
        if ins.opcode == "dot":
            total += dot_flops(ins, comp.types)
        elif ins.opcode == "convolution":
            total += 2.0 * float(math.prod(_first_shape_dims(ins.result_type)) or 0)
        elif ins.opcode in ("fusion", "call"):
            for c in _called(ins):
                if c in comps:
                    total += _fusion_flops(comps[c], comps, memo)
    memo[comp.name] = total
    return total


def computation_cost(comp: Computation, comps: Dict[str, Computation],
                     memo: Dict[str, Cost],
                     flop_memo: Dict[str, float]) -> Cost:
    if comp.name in memo:
        return memo[comp.name]
    memo[comp.name] = Cost()  # cycle guard
    total = Cost()
    for ins in comp.instrs:
        op = ins.opcode
        if op == "while":
            bm = re.search(r"body=%?([\w\.\-]+)", ins.rhs)
            cm = re.search(r"condition=%?([\w\.\-]+)", ins.rhs)
            trips = trip_count(comps[cm.group(1)]) if (cm and cm.group(1) in comps) else 1
            if bm and bm.group(1) in comps:
                total += computation_cost(comps[bm.group(1)], comps, memo,
                                          flop_memo).scaled(trips)
            continue
        if op == "conditional":
            for c in _called(ins):
                if c in comps:
                    total += computation_cost(comps[c], comps, memo, flop_memo)
            continue
        if op in ("fusion", "call"):
            called = [comps[c] for c in _called(ins) if c in comps]
            for c in called:
                total.flops += _fusion_flops(c, comps, flop_memo)
            total.hbm_bytes += _fusion_io_bytes(
                ins, comp.types, called[0] if called else None, comps)
            continue
        if op == "dot":
            total.flops += dot_flops(ins, comp.types)
            total.hbm_bytes += _io_bytes(ins, comp.types)
            continue
        if op == "convolution":
            total.flops += 2.0 * float(math.prod(_first_shape_dims(ins.result_type)) or 0)
            total.hbm_bytes += _io_bytes(ins, comp.types)
            continue
        base = op.replace("-start", "")
        if base in _COLL_WEIGHT and not op.endswith("-done"):
            payload = _shape_bytes(ins.result_type)
            w = _COLL_WEIGHT[base]
            total.coll_bytes += payload * w
            total.coll_breakdown[base] = total.coll_breakdown.get(base, 0.0) \
                + payload * w
            total.hbm_bytes += _io_bytes(ins, comp.types)
            continue
        if op in _SKIP_OPS or op.endswith("-done"):
            continue
        total.hbm_bytes += _io_bytes(ins, comp.types)
    memo[comp.name] = total
    return total


def hlo_cost(hlo_text: str) -> Cost:
    comps = parse_computations(hlo_text)
    entry = comps.get("__entry__")
    if entry is None:
        if not comps:
            return Cost()
        entry = max(comps.values(), key=lambda c: len(c.instrs))
    return computation_cost(entry, comps, {}, {})


# ---------------------------------------------------------------------------
# roofline-term extraction (former repro.launch.hlo_analysis)
# ---------------------------------------------------------------------------

# v5e hardware constants (assignment)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[\d,]*\]))\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"all-gather-start|all-reduce-start|collective-permute-start)\(")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes (per device)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str = m.group(1) or m.group(2)
        kind = m.group(3).replace("-start", "")
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    """The three roofline terms (seconds) + raw numerators."""

    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int]
    n_chips: int
    xla_flops: float = 0.0  # raw cost_analysis (undercounts scan bodies)
    xla_bytes: float = 0.0

    @property
    def t_compute(self) -> float:
        # cost_analysis flops are whole-program per-device after SPMD
        return self.hlo_flops / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    def as_dict(self) -> Dict:
        return {
            "hlo_flops_per_device": self.hlo_flops,
            "hlo_bytes_per_device": self.hlo_bytes,
            "coll_bytes_per_device": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "n_chips": self.n_chips,
            "xla_cost_analysis_flops": self.xla_flops,
            "xla_cost_analysis_bytes": self.xla_bytes,
        }


def analyze(compiled, n_chips: int, hlo_text: Optional[str] = None) -> Roofline:
    """Roofline terms from the compiled artifact.

    Primary source: the trip-count-aware HLO cost model above -- XLA-CPU's
    cost_analysis() counts while-loop (lax.scan) bodies once instead of
    x trip-count, which under-reports every scan-over-layers model here by
    ~n_layers.  The raw cost_analysis numbers are retained in ``xla_flops``
    / ``xla_bytes`` for reference.
    """
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):  # older API returned [dict]
        cost = cost[0] if cost else {}
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    c = hlo_cost(txt)
    r = Roofline(
        hlo_flops=c.flops,
        hlo_bytes=c.hbm_bytes,
        coll_bytes=c.coll_bytes,
        coll_breakdown={k: int(v) for k, v in c.coll_breakdown.items()},
        n_chips=n_chips,
    )
    r.xla_flops = float(cost.get("flops", 0.0))
    r.xla_bytes = float(cost.get("bytes accessed", 0.0))
    return r


def memory_stats(compiled) -> Optional[Dict[str, float]]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes"]
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    if not out and isinstance(ma, dict):
        out = {k: float(v) for k, v in ma.items()}
    return out or None


# ---------------------------------------------------------------------------
# dense-free proofs over the registered pack kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DenseFreeReport:
    """The evidence behind one dense-free verdict (``as_dict`` goes to CI)."""

    kernel: str
    d: int                    #: dense element count of the full problem
    tile: int                 #: largest kernel-visible ref (elements)
    max_inner: int            #: largest value inside the kernel jaxpr
    n_pallas_calls: int
    violations: List[str]

    @property
    def ok(self) -> bool:
        return not self.violations

    def as_dict(self) -> dict:
        return {"kernel": self.kernel, "d": self.d, "tile": self.tile,
                "max_inner": self.max_inner, "ok": self.ok,
                "violations": list(self.violations)}


def _aval_size(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(math.prod(shape)) if shape else 1


def _inner_jaxprs(params: dict):
    """Every jaxpr-valued entry of an eqn's params (scan/while bodies,
    pallas kernels, custom_* wrappers), across jax versions."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                yield getattr(v, "jaxpr", v)


def _walk_sizes(jaxpr, out: List[int]) -> None:
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.append(_aval_size(v))
        for sub in _inner_jaxprs(eqn.params):
            _walk_sizes(sub, out)


def dense_free(name: str) -> DenseFreeReport:
    """Statically prove the registered pack kernel ``name`` materializes no
    d-sized dense buffer: trace to a jaxpr (no lowering; runs on CPU) and
    bound every intermediate by the tile size.

    The dense inputs (g, h) and the dense state output h_new are exempt by
    construction -- they are the algorithm's state, written one tile per
    grid step; what must never exist is a NEW dense buffer holding the
    compressed delta d = C(g - h)."""
    import jax

    fn, example_args, d = PACK_KERNELS[name]()
    jaxpr = jax.make_jaxpr(fn)(*example_args).jaxpr
    violations: List[str] = []

    pallas_eqns = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    if not pallas_eqns:
        violations.append("no pallas_call primitive in the traced jaxpr")
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.outvars:
            if _aval_size(v) >= d:
                violations.append(
                    f"top-level {eqn.primitive.name} materializes a "
                    f"{_aval_size(v)}-element buffer (d = {d}) outside "
                    "the kernel")

    tile = 0
    max_inner = 0
    for eqn in pallas_eqns:
        inners = list(_inner_jaxprs(eqn.params))
        if not inners:
            violations.append("pallas_call carries no inner jaxpr to check")
            continue
        kernel_jaxpr = inners[0]
        tile = max(tile, max((_aval_size(v) for v in kernel_jaxpr.invars),
                             default=0))
        sizes: List[int] = []
        _walk_sizes(kernel_jaxpr, sizes)
        max_inner = max([max_inner] + sizes)
    if pallas_eqns and not violations:
        if tile >= d:
            violations.append(
                f"tile covers the whole problem (tile = {tile} >= d = {d}); "
                "grid must split d so only a fraction is live at once")
        if max_inner > tile:
            violations.append(
                f"kernel-internal value of {max_inner} elements exceeds the "
                f"tile ({tile}) -- the kernel builds something denser than "
                "its VMEM working set")

    return DenseFreeReport(kernel=name, d=d, tile=tile, max_inner=max_inner,
                           n_pallas_calls=len(pallas_eqns),
                           violations=violations)


def _block_topk_case():
    import jax.numpy as jnp
    from repro.kernels import pack

    # 384 blocks: pack_tile takes 256 rows a step, so the grid has two
    # steps (the second ragged) and no step holds the whole leaf
    nb, block, kb = 384, 128, 4
    g = jnp.zeros((nb, block), jnp.float32)
    h = jnp.zeros((nb, block), jnp.float32)
    fn = lambda g, h: pack.pack_update_pallas(g, h, 0.5, kb)
    return fn, (g, h), nb * block


def _randk_case():
    import jax.numpy as jnp
    from repro.kernels import pack

    nr, cols, k = 32, 128, 16
    g = jnp.zeros((nr, cols), jnp.float32)
    h = jnp.zeros((nr, cols), jnp.float32)
    idx = jnp.zeros((k,), jnp.int32)
    fn = lambda g, h, idx: pack.randk_update_pallas(g, h, idx, 2.0, 0.5)
    return fn, (g, h, idx), nr * cols


def _qsgd_case():
    import jax.numpy as jnp
    from repro.kernels import pack

    nr, cols, s = 64, 128, 16
    g = jnp.zeros((nr, cols), jnp.float32)
    h = jnp.zeros((nr, cols), jnp.float32)
    u = jnp.zeros((nr, cols), jnp.float32)
    norm = jnp.ones((1, 1), jnp.float32)
    fn = lambda g, h, u, norm: pack.qsgd_pack_update_pallas(g, h, u, norm,
                                                            s, 0.5)
    return fn, (g, h, u, norm), nr * cols


#: name -> zero-arg builder returning (traceable fn, example args, d).
#: Every fused pack kernel MUST be registered here: the CI lint job runs
#: ``python -m repro.analysis --hlo-gate`` which proves each one dense-free.
PACK_KERNELS: Dict[str, Callable[[], Tuple[Callable, tuple, int]]] = {
    "block_topk_pack": _block_topk_case,
    "randk_update": _randk_case,
    "qsgd_pack": _qsgd_case,
}


def gate(names: Optional[List[str]] = None) -> List[DenseFreeReport]:
    """Run the dense-free proof over (a subset of) the registry."""
    return [dense_free(n) for n in (names or sorted(PACK_KERNELS))]
