"""TPU "bring your own hardware" backend (paper §5.3, DESIGN.md §3).

Generates memory traces for the framework's *own* models: the jaxpr of a
jitted step function is walked op by op; each op advances a cycle cursor by
its roofline time on one TPU v5e chip (peaks from
``repro.launch.roofline.CHIP_PEAKS``), and
each intermediate buffer contributes

  - a *write* burst when its producer op completes (HBM -> VMEM fill /
    VMEM materialization), and
  - a *read* burst at each consumer op,

at VMEM-tile granularity (one block = one 4 KiB VMEM tile).  The resulting
trace is scratchpad-mode (Def 4.2): VMEM is software-managed, exactly like
the systolic-array buffers of §5.2.

This ties GainSight to the real compiled workloads: the same model configs
that the launcher trains/serves are profiled here, and the frontend answers
"how much of this model's VMEM could be GCRAM?".
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np

from repro.core.api import ProfileResult, register_backend
from repro.core.trace import Trace, chunk_trace
from repro.launch.roofline import V5E, chip_peaks

_PEAKS = chip_peaks(V5E)    # the chip this backend models, not the host
BLOCK_BYTES = 4096
_HASH = np.uint64(11400714819323198485)


@dataclasses.dataclass(frozen=True)
class OpCost:
    name: str
    flops: float
    bytes_touched: float
    start_cycle: int
    cycles: int


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _eqn_flops(eqn) -> float:
    prim = eqn.primitive.name
    out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    if prim == "dot_general":
        dnums = eqn.params["dimension_numbers"]
        (lc, rc), (lb, rb) = dnums
        lhs = eqn.invars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        batch = int(np.prod([lhs[i] for i in lb])) if lb else 1
        k = int(np.prod([lhs[i] for i in lc])) if lc else 1
        m = int(np.prod([d for i, d in enumerate(lhs)
                         if i not in lc and i not in lb]))
        n = int(np.prod([d for i, d in enumerate(rhs)
                         if i not in rc and i not in rb]))
        return 2.0 * batch * m * n * k
    if prim in ("conv_general_dilated",):
        return 2.0 * out_b  # rough: bytes-proportional
    # elementwise / reduce / reshape: ~1 flop per output element
    return out_b / 2.0


def trace_jaxpr(
    fn,
    *example_args,
    clock_hz: float = 940e6,   # v5e core clock
    sample: int = 1,
    max_blocks_per_buffer: int = 64,
    scan_unroll_cap: int = 4,
) -> tuple[Trace, list[OpCost]]:
    """Walk fn's jaxpr on ShapeDtypeStruct args; emit a VMEM trace."""
    jaxpr = jax.make_jaxpr(fn)(*example_args).jaxpr

    times, addrs, writes = [], [], []
    base_block = [0]
    var_block: dict = {}       # var -> (base_block, n_blocks)
    cursor = [0]
    ops: list[OpCost] = []

    def blocks_of(var):
        key = id(var)
        if key not in var_block:
            nb = max(1, math.ceil(_aval_bytes(var.aval) / BLOCK_BYTES))
            nb = min(nb, max_blocks_per_buffer)
            var_block[key] = (base_block[0], nb)
            base_block[0] += nb
        return var_block[key]

    def emit(var, t0, t1, is_write):
        b0, nb = blocks_of(var)
        lines = np.arange(b0, b0 + nb, dtype=np.int64)
        if sample > 1:
            h = (lines.astype(np.uint64) * _HASH) >> np.uint64(33)
            lines = lines[(h % np.uint64(sample)) == 0]
        n = len(lines)
        if n == 0:
            return
        ts = t0 + (np.arange(n, dtype=np.int64) * max(t1 - t0, 1)) // n
        times.append(ts)
        addrs.append(lines)
        writes.append(np.full(n, is_write, bool))

    def walk(jx, mult: float = 1.0):
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in ("pjit", "custom_jvp_call", "custom_vjp_call",
                        "remat", "checkpoint", "custom_vjp_call_jaxpr",
                        "closed_call"):
                inner = eqn.params.get("jaxpr") or eqn.params.get(
                    "call_jaxpr")
                if inner is not None:
                    walk(inner.jaxpr if hasattr(inner, "jaxpr") else inner,
                         mult)
                    continue
            if prim == "scan":
                inner = eqn.params["jaxpr"]
                length = eqn.params.get("length", 1)
                reps = min(length, scan_unroll_cap)
                for _ in range(reps):
                    walk(inner.jaxpr, mult * length / reps)
                continue
            flops = _eqn_flops(eqn) * mult
            in_b = sum(_aval_bytes(v.aval) for v in eqn.invars
                       if hasattr(v, "aval"))
            out_b = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            total_b = (in_b + out_b) * mult
            dur = max(1, int(max(flops / _PEAKS["flops_bf16"],
                                 total_b / _PEAKS["hbm_bw"]) * clock_hz))
            t0 = cursor[0]
            for v in eqn.invars:
                if hasattr(v, "aval") and hasattr(v, "count"):
                    emit(v, t0, t0 + dur // 2, False)
            for v in eqn.outvars:
                emit(v, t0 + dur - 1, t0 + dur, True)
            ops.append(OpCost(prim, flops, total_b, t0, dur))
            cursor[0] += dur

    # model inputs/weights land in VMEM at t=0
    for v in jaxpr.invars:
        emit(v, 0, 1, True)
    walk(jaxpr)

    if not times:
        z = np.zeros(0, np.int64)
        tr = Trace(z, z, np.zeros(0, bool), np.zeros(0, bool),
                   np.zeros(0, np.int32), clock_hz, BLOCK_BYTES * 8,
                   ("VMEM",))
        return tr, ops
    t = np.concatenate(times)
    a = np.concatenate(addrs)
    w = np.concatenate(writes)
    order = np.argsort(t, kind="stable")
    tr = Trace(
        time_cycles=t[order], addr=a[order], is_write=w[order],
        hit=np.ones(len(t), bool),
        subpartition=np.zeros(len(t), np.int32),
        clock_hz=clock_hz, block_bits=BLOCK_BYTES * 8, names=("VMEM",))
    return tr, ops


@register_backend("tpu_graph", aliases=("tpu",))
class TpuGraphBackend:
    """Registry adapter for the jaxpr-walking TPU backend (alias: "tpu").

    Workload: a traceable function, or a ``(fn, *example_args)`` tuple
    whose args are ShapeDtypeStructs/arrays.  Config kwargs go straight to
    :func:`trace_jaxpr` (``clock_hz``, ``sample``, ``max_blocks_per_buffer``,
    ``scan_unroll_cap``).
    """
    name = "tpu_graph"
    mode = "scratchpad"

    def run(self, workload, *, chunk_events: int | None = None,
            **cfg) -> ProfileResult:
        if isinstance(workload, (tuple, list)) and workload \
                and callable(workload[0]):
            fn, *args = workload
        elif callable(workload):
            fn, args = workload, ()
        else:
            raise TypeError("tpu_graph workload must be a callable or a "
                            "(fn, *example_args) tuple")
        trace, ops = trace_jaxpr(fn, *args, **cfg)
        kernels = [dataclasses.asdict(o) for o in ops]
        if chunk_events:
            return ProfileResult(chunks=chunk_trace(trace, chunk_events),
                                 kernels=kernels, mode=self.mode,
                                 meta={"n_ops": len(ops)})
        return ProfileResult(trace=trace, kernels=kernels, mode=self.mode,
                             meta={"n_ops": len(ops)})
