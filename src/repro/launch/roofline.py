"""Roofline-term derivation from compiled XLA artifacts (deliverable g).

Hardware model: the published peaks of one chip, looked up by jax
``device_kind`` in :data:`CHIP_PEAKS` (the repo's one peak table).

  compute term    = HLO_FLOPs   / (chips * peak FLOP/s)
  memory term     = HLO_bytes   / (chips * HBM bandwidth)
  collective term = collective_bytes / (chips * link bandwidth)

XLA's compiled.cost_analysis() counts while bodies once, so a lax.scan
over 95 layers would be undercounted ~95x.  We therefore parse the
optimized (SPMD-partitioned, per-device) HLO text ourselves:

  - computations are split into blocks; while-loop trip counts come from
    XLA's ``known_trip_count`` backend_config (authoritative) with the
    loop-condition comparison constant as fallback; multiplicities
    propagate through nested loops from ENTRY;
  - a per-module symbol table (instruction -> shape) resolves operand
    shapes, since operands are referenced by name in this dialect;
  - dot FLOPs = 2 * out_elems * contracted_elems, scaled by multiplicity;
  - bytes = output + operand bytes of every materializing instruction at
    post-fusion granularity (a tensor is written once where defined and
    read once per consumer - the HBM-traffic model for fused XLA code);
  - collective bytes sum *operand* sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, scaled by
    multiplicity.

All quantities are per-device (the HLO is the per-device program), so the
roofline terms divide by per-chip peaks only; `chips` enters when
converting whole-job numbers.
"""

from __future__ import annotations

import dataclasses
import re


#: Published peaks of one chip, keyed by jax ``device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
#: at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (200 GB/s over
#: four ICI links, so 50 GB/s per link).
V5E = "TPU v5 lite"
CHIP_PEAKS = {
    V5E: {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_link_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a kind not in the table is
    an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[\d_a-z]*)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"([a-z][\w\-]*)\(")

_SKIP_BYTES_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast", "after-all", "iota", "while", "conditional",
                   "custom-call"}


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclasses.dataclass
class _Instr:
    name: str
    op: str
    out_shapes: list        # [(dtype, dims)]
    operands: list          # operand instruction names
    line: str


def _parse_computations(hlo: str) -> dict:
    """computation name -> list[_Instr]; "__entry__" is the ENTRY block."""
    comps = {}
    cur = None
    for line in hlo.splitlines():
        ls = line.strip()
        if ls.endswith("{") and "->" in ls:
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", ls)
            if m:
                cur = "__entry__" if ls.startswith("ENTRY") else m.group(1)
                comps[cur] = []
                continue
        if ls == "}":
            cur = None
            continue
        if cur is None or not ls:
            continue
        mi = _INSTR_RE.match(ls)
        if not mi:
            continue
        name, rhs = mi.group(1), mi.group(2)
        mo = _OP_RE.search(rhs)
        if not mo:
            continue
        op = mo.group(1)
        out_shapes = _SHAPE_RE.findall(rhs[:mo.start()])
        # operand names: inside the op's balanced parens
        depth = 0
        end = mo.end() - 1
        for i in range(mo.end() - 1, len(rhs)):
            if rhs[i] == "(":
                depth += 1
            elif rhs[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = re.findall(r"%([\w\.\-]+)", rhs[mo.end() - 1:end + 1])
        comps[cur].append(_Instr(name, op, out_shapes, operands, ls))
    return comps


def _symbol_table(comps: dict) -> dict:
    sym = {}
    for instrs in comps.values():
        for ins in instrs:
            sym[ins.name] = ins.out_shapes
    return sym


def _computation_multiplicities(comps: dict) -> dict:
    """computation name -> execution count (nested while trip products)."""
    cond_consts = {}
    for name, instrs in comps.items():
        consts = {}
        for ins in instrs:
            m = re.search(r"s32\[\]\s*constant\((\d+)\)", ins.line)
            if m:
                consts[ins.name] = int(m.group(1))
        for ins in instrs:
            if "compare" in ins.line:
                for cname, val in consts.items():
                    if cname in ins.operands:
                        cond_consts[name] = max(
                            cond_consts.get(name, 0), val)

    edges = {}
    for name, instrs in comps.items():
        for ins in instrs:
            if ins.op != "while":
                continue
            mb = re.search(r"body=%?([\w\.\-]+)", ins.line)
            if not mb:
                continue
            mt = re.search(r"known_trip_count[^\d]+(\d+)", ins.line)
            if mt:
                trips = int(mt.group(1))
            else:
                mc = re.search(r"condition=%?([\w\.\-]+)", ins.line)
                trips = cond_consts.get(mc.group(1), 1) if mc else 1
            edges.setdefault(name, []).append((mb.group(1), max(trips, 1)))

    mult = {"__entry__": 1}
    frontier = ["__entry__"]
    seen = set()
    while frontier:
        c = frontier.pop()
        if c in seen:
            continue
        seen.add(c)
        for body, trips in edges.get(c, []):
            mult[body] = mult.get(body, 0) + mult.get(c, 1) * trips
            frontier.append(body)
    return mult


@dataclasses.dataclass
class CollectiveStats:
    total_bytes: float
    by_kind: dict
    count: int


def collective_bytes(hlo_text: str) -> CollectiveStats:
    comps = _parse_computations(hlo_text)
    sym = _symbol_table(comps)
    mults = _computation_multiplicities(comps)

    by_kind = {k: 0.0 for k in _COLLECTIVES}
    count = 0
    for name, instrs in comps.items():
        mult = mults.get(name)
        if mult is None:
            continue  # fusion bodies etc.: accounted at their call sites
        for ins in instrs:
            kind = None
            for k in _COLLECTIVES:
                if ins.op == k or ins.op == k + "-start":
                    kind = k
                    break
            if kind is None:
                continue
            count += 1
            b = 0
            for o in ins.operands:
                for d, s in sym.get(o, ()):
                    b += _shape_bytes(d, s)
            if b == 0:  # fall back to result size
                b = sum(_shape_bytes(d, s) for d, s in ins.out_shapes)
            by_kind[kind] += b * mult
    total = sum(by_kind.values())
    return CollectiveStats(total_bytes=total, by_kind=by_kind, count=count)


def hlo_cost(hlo_text: str) -> dict:
    """Trip-count-aware per-device FLOPs/bytes from optimized HLO text."""
    comps = _parse_computations(hlo_text)
    sym = _symbol_table(comps)
    mult = _computation_multiplicities(comps)

    dot_flops = 0.0
    total_bytes = 0.0
    n_dots = 0
    for name, m in mult.items():
        for ins in comps.get(name, ()):
            if ins.op in _SKIP_BYTES_OPS:
                continue
            out_b = sum(_shape_bytes(d, s) for d, s in ins.out_shapes)
            if "dynamic-update-slice" in ins.name or \
                    "dynamic-update-slice" in ins.line[:120]:
                # in-place DUS inside a loop: across all m iterations the
                # loop writes the aliased buffer once and reads each big
                # sliced operand once.  Charge output + operands one time
                # (minus the aliased buffer operand) instead of per-trip.
                op_b = sum(_shape_bytes(d, s)
                           for o in ins.operands
                           for d, s in sym.get(o, ()))
                buf_b = max((sum(_shape_bytes(d, s)
                                 for d, s in sym.get(o, ()))
                             for o in ins.operands), default=0)
                total_bytes += out_b + max(op_b - buf_b, 0)
                continue
            # write once + read once per consumer ~= 2x output traffic
            total_bytes += m * 2 * out_b
            if ins.op == "dot":
                n_dots += 1
                out_elems = sum(_shape_elems(s) for _, s in ins.out_shapes)
                lhs_shapes = sym.get(ins.operands[0], ()) if ins.operands \
                    else ()
                lhs_dims = lhs_shapes[0][1].split(",") if lhs_shapes and \
                    lhs_shapes[0][1] else []
                mc = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                               ins.line)
                contr = 1
                if mc and mc.group(1) and lhs_dims:
                    for ix in mc.group(1).split(","):
                        i = int(ix)
                        if i < len(lhs_dims):
                            contr *= int(lhs_dims[i])
                dot_flops += m * 2.0 * out_elems * contr
    return {"dot_flops": dot_flops, "bytes": total_bytes,
            "n_dot_sites": n_dots,
            "multiplicities": {k: v for k, v in mult.items() if v > 1}}


def roofline_terms(flops: float, bytes_accessed: float,
                   coll_bytes: float, chips: int,
                   per_device: bool = True, *, device_kind: str) -> dict:
    """Terms in seconds on chips of ``device_kind``. When per_device=True
    the inputs are per-chip (SPMD HLO) and `chips` is ignored for
    compute/memory."""
    peaks = chip_peaks(device_kind)
    div = 1 if per_device else chips
    compute_s = flops / (div * peaks["flops_bf16"])
    memory_s = bytes_accessed / (div * peaks["hbm_bw"])
    collective_s = coll_bytes / (div * peaks["ici_link_bw"])
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    terms["dominant"] = dominant
    terms["step_lower_bound_s"] = bound
    terms["roofline_fraction"] = (compute_s / bound) if bound > 0 else 0.0
    return terms


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE); decode uses one
    token per sequence.  Whole-job quantity (divide by chips for
    per-device)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one new token per sequence
    return 2.0 * n * tokens
