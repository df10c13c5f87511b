"""jit'd public wrapper: sort + pad + dispatch the lifetime-scan kernel.

Padding protocol: events are lexsorted by (addr, time), then padded to a
block multiple (plus at least one full pad slot) with *write* events at a
sentinel address.  The first pad event closes the final real segment; every
closed pad segment is a zero-read orphan at the sentinel address, so the
wrapper subtracts the known pad contribution from the orphan count.  The
still-open final pad segment is never counted.

int64 time protocol: cycle stamps are rebased to the trace minimum on the
host (lifetimes are differences, so rebasing is exact), then split into
two int32 limbs (hi = t >> 30, lo = t & (2**30 - 1)) that ride through
the jitted lexsort, the padding, and the kernel's segment scan — so
traces past 2**31 (and well past 2**40) run on the kernel path instead
of raising.  The only remaining :class:`KernelRangeError` contracts are
the dense int32 address window (addresses must fit [0, SENTINEL)) and
the astronomically-large rebased time span limit of 2**61 - 2 cycles
(~73 years at 1 GHz), which the limbs cannot exceed.

Histogram edges are computed in float64 and converted to *integer*
thresholds (ceil) on the host: for integer lifetimes ``lt >= e`` iff
``lt >= ceil(e)`` and ``lt < e`` iff ``lt < ceil(e)``, so the kernel's
limb-pair binning is exact at any magnitude — no f32 misbinning past
2**24 cycles.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.lifetime_scan.kernel import (LO_BITS, LO_MOD,
                                                lifetime_scan_sorted)

SENTINEL = 2 ** 31 - 10
# max rebased (t - t.min()) span the two int32 limbs can carry; the edge
# cap 2**61 - 1 must stay strictly above any representable lifetime
SPAN_LIMIT = 2 ** 61 - 1


class KernelRangeError(OverflowError):
    """An input field exceeds the kernel's carrying capacity.

    Subclasses ``OverflowError`` so existing ``except OverflowError``
    fallbacks keep working, but carries the offending field and bounds
    so callers (and logs) can say *which* value broke the contract and
    what to do about it instead of parsing a message.

    Attributes:
      field:   "time_cycles" or "addr" — the offending input
      lo, hi:  observed min/max of that field
      limit:   half-open valid range ``(lo_ok, hi_ok)`` for the field
      remediation: one-line fix, always naming the int64 numpy/jnp
        fallback (``repro.core.lifetime``)
    """

    def __init__(self, field: str, lo: int, hi: int,
                 limit: tuple, remediation: str):
        self.field = field
        self.lo = lo
        self.hi = hi
        self.limit = limit
        self.remediation = remediation
        super().__init__(
            f"lifetime_scan kernel range: {field} range "
            f"[{lo}, {hi}] exceeds the valid half-open range "
            f"[{limit[0]}, {limit[1]}) (offending extreme: "
            f"{hi if hi >= limit[1] else lo}); {remediation}")


def default_edges(n_bins: int = 64, lo_cycles: float = 1.0,
                  hi_cycles: float = 1e8) -> np.ndarray:
    """Log-spaced lifetime bins (cycles); final edge is +inf.

    float64: f32 edges misbin integer cycle counts past 2**24 (f32 has a
    24-bit significand, so distinct edges collapse and boundary lifetimes
    land one bin off); the kernel boundary converts to exact integer
    thresholds, never back to f32.
    """
    e = np.logspace(np.log10(lo_cycles), np.log10(hi_cycles), n_bins)
    return np.concatenate([[0.0], e[:-1], [np.inf]]).astype(np.float64)


def _integer_edges(edges) -> tuple:
    """float64 edges -> exact int64 ceil thresholds, limb-split int32.

    +inf (and anything past the span limit) caps at 2**61 - 1, strictly
    above every representable lifetime, so the open top bin still
    catches everything.
    """
    e = np.asarray(edges, np.float64)
    ie_f = np.where(np.isfinite(e), np.ceil(e), 2.0 ** 61)
    ie_f = np.clip(ie_f, -(2.0 ** 61), 2.0 ** 61)
    ie = np.clip(ie_f.astype(np.int64), -SPAN_LIMIT, SPAN_LIMIT)
    # arithmetic shift keeps hi*2**30 + lo == ie for negative edges too
    return ((ie >> LO_BITS).astype(np.int32),
            (ie & (LO_MOD - 1)).astype(np.int32))


@partial(jax.jit, static_argnames=("block",))
def _run(t_hi, t_lo, addr, w, edges_hi, edges_lo, block):
    n = t_hi.shape[0]
    order = jnp.lexsort((t_lo, t_hi, addr))
    th, tl, as_, ws = t_hi[order], t_lo[order], addr[order], w[order]
    n_pad = block - (n % block) if n % block else block
    th = jnp.concatenate([th, jnp.full((n_pad,), th[-1], th.dtype)])
    tl = jnp.concatenate([tl, jnp.full((n_pad,), tl[-1], tl.dtype)])
    as_ = jnp.concatenate(
        [as_, SENTINEL + jnp.arange(n_pad, dtype=as_.dtype)])
    ws = jnp.concatenate([ws, jnp.ones((n_pad,), ws.dtype)])
    hist, stats = lifetime_scan_sorted(
        th, tl, as_, ws, edges_hi, edges_lo, block=block,
        n_bins=edges_hi.shape[0] - 1, interpret=interpret_mode())
    # remove pad bookkeeping: n_pad-1 closed orphan pad segments, n_pad
    # pad writes
    stats = stats.at[1].add(-(n_pad - 1)).at[5].add(-n_pad)
    return hist, stats


def lifetime_histogram(time_cycles, addr, is_write, edges=None,
                       block: int = 256):
    """Aggregate lifetime histogram + stats over an (unsorted) event list.

    Returns (hist [NB] f32, stats [8] f32); see kernel docstring for the
    stats layout.  Cycle stamps are int64-capable (rebase + split int32
    limbs); addresses must fit the dense int32 [0, SENTINEL) window.
    """
    if edges is None:
        edges = default_edges()
    t_np = np.asarray(time_cycles, np.int64)
    a_np = np.asarray(addr)
    if t_np.size:
        # The TPU kernel carries addresses in int32 SMEM/VMEM; unlike the
        # int64 jnp frontend (repro.core.lifetime) it cannot widen them,
        # so out-of-window addresses fail loudly instead of wrapping.
        if int(a_np.min()) < 0 or int(a_np.max()) >= SENTINEL:
            raise KernelRangeError(
                "addr", int(a_np.min()), int(a_np.max()),
                (0, SENTINEL),
                remediation="remap addresses into the dense [0, "
                            f"{SENTINEL}) window or use the int64 "
                            "numpy/jnp fallback "
                            "repro.core.lifetime.lifetime_histogram")
        t_min = int(t_np.min())
        t_max = int(t_np.max())
        # unreachable for physical traces (~73 years at 1 GHz): the two
        # int32 limbs carry rebased spans up to 2**61 - 2 exactly
        if t_max - t_min >= SPAN_LIMIT:
            raise KernelRangeError(
                "time_cycles", t_min, t_max,
                (t_min, t_min + SPAN_LIMIT),
                remediation="the rebased time span exceeds the split "
                            "int32 limb capacity; use the int64 "
                            "numpy/jnp fallback "
                            "repro.core.lifetime.lifetime_histogram")
    else:
        t_min = 0
    # rebase (lifetimes are differences: exact) and split into limbs
    t_r = t_np - t_min
    t_hi = jnp.asarray((t_r >> LO_BITS).astype(np.int32))
    t_lo = jnp.asarray((t_r & (LO_MOD - 1)).astype(np.int32))
    a = jnp.asarray(a_np, jnp.int32)
    w = jnp.asarray(is_write, jnp.int32)
    if t_np.size == 0:
        return (jnp.zeros(len(edges) - 1, jnp.float32),
                jnp.zeros(8, jnp.float32))
    eh, el = _integer_edges(edges)
    return _run(t_hi, t_lo, a, w, jnp.asarray(eh), jnp.asarray(el), block)
