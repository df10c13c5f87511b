"""Data-lifetime extraction (paper §4, Definitions 4.1-4.3).

A *lifetime* of a value at an address is the interval between its first
write (store / fetch / cache miss, depending on the memory kind) and the
last read of that value before it is overwritten or invalidated.

The extraction is a segmented reduction over the event stream sorted by
(address, time): a new segment ("lifetime") begins whenever the address
changes or a *boundary* event occurs.  Boundary rules per Definition:

  Def 4.1/4.2 (scratchpad):  boundary = is_write
  Def 4.3    (data cache):   boundary = is_write | miss
      under no-allocate-on-write, write misses do not allocate: the write
      terminates the previous lifetime but does not begin a new one, so a
      segment started by a write-miss is dropped.

Implemented as pure-jnp segment ops so it jits and shards; a Pallas TPU
kernel covering the same computation lives in ``repro.kernels.lifetime_scan``
(this module is its oracle for the sorted-segment phase).

Outputs are *per-segment* arrays padded to ``n_events`` (a trace of N events
has at most N lifetimes):
  lifetime_cycles  i64   last-read - first-write (0 for orphans)
  n_reads          i32   reads observed within the lifetime
  start_cycles     i64   cycle stamp of the initiating event
  addr             i64   block address hosting the lifetime
  valid            bool  segment exists (non-padding)
  orphan           bool  lifetime with zero reads (fetched/written, never
                         reused) - paper §7.1.6 "orphaned accesses"

Cycle stamps and addresses are carried as **int64 end-to-end** (the trace
schema stores them as int64): cycle counts past 2**31 (~2.1 s at 1 GHz,
i.e. any multi-step streamed workload) and line addresses >= 2**31 are
exact, not silently wrapped.  The extraction runs its jitted segment ops
under a scoped ``repro.compat.enable_x64`` so the 64-bit arithmetic
survives jax's default 32-bit mode without flipping the global flag.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import enable_x64
from repro.core.trace import Trace
from repro.runtime import obs

# "no read yet" sentinel: below any real int64 cycle stamp, with headroom
# so segment arithmetic cannot overflow (repro.core.accumulate mirrors it).
NO_READ_SENTINEL = -(2 ** 62)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LifetimeStats:
    lifetime_cycles: jnp.ndarray
    n_reads: jnp.ndarray
    start_cycles: jnp.ndarray
    addr: jnp.ndarray
    valid: jnp.ndarray
    orphan: jnp.ndarray
    seg_id_per_event: jnp.ndarray  # maps events -> their lifetime segment

    def lifetimes_s(self, clock_hz: float) -> np.ndarray:
        """Valid lifetimes in seconds (host-side convenience)."""
        lt = np.asarray(self.lifetime_cycles)
        v = np.asarray(self.valid)
        return lt[v] / clock_hz


def extract_lifetimes(
    time_cycles,
    addr,
    is_write,
    hit,
    mode: str = "scratchpad",
    write_allocate: bool = True,
) -> LifetimeStats:
    """Segmented lifetime extraction. All inputs are 1-D, equal length.

    mode: "scratchpad" (Def 4.2) or "cache" (Def 4.3).
    write_allocate: cache write-allocation policy ablation (§7.1.6).

    Cycle stamps and addresses are promoted to int64 inside a scoped
    x64 region, so values past 2**31 are exact (see module docstring).
    """
    if mode not in ("scratchpad", "cache"):
        raise ValueError(f"unknown mode {mode!r}")
    with enable_x64():
        args = (jnp.asarray(np.asarray(time_cycles), jnp.int64),
                jnp.asarray(np.asarray(addr), jnp.int64),
                jnp.asarray(np.asarray(is_write), bool),
                jnp.asarray(np.asarray(hit), bool))
        obs.count("h2d_bytes", sum(a.nbytes for a in args))
        return _extract_lifetimes(*args, mode=mode,
                                  write_allocate=write_allocate)


@partial(jax.jit, static_argnames=("mode", "write_allocate"))
def _extract_lifetimes(
    time_cycles: jnp.ndarray,
    addr: jnp.ndarray,
    is_write: jnp.ndarray,
    hit: jnp.ndarray,
    mode: str = "scratchpad",
    write_allocate: bool = True,
) -> LifetimeStats:
    n = time_cycles.shape[0]
    t = time_cycles.astype(jnp.int64)  # exact cycle arithmetic
    a = addr.astype(jnp.int64)
    w = is_write.astype(bool)
    h = hit.astype(bool)

    # Sort events by (addr, time); stable so same-cycle order is preserved.
    order = jnp.lexsort((t, a))
    t, a, w, h = t[order], a[order], w[order], h[order]

    new_addr = jnp.concatenate(
        [jnp.ones((1,), bool), a[1:] != a[:-1]]) if n > 0 else jnp.zeros((0,), bool)
    if mode == "scratchpad":
        boundary = new_addr | w
        read_ok = ~w
        dead_start = jnp.zeros_like(w)  # every segment is a real lifetime
    elif mode == "cache":
        miss = ~h
        boundary = new_addr | w | miss
        # a read only extends a lifetime if it hits in the cache
        read_ok = (~w) & h
        if write_allocate:
            dead_start = jnp.zeros_like(w)
        else:
            # write misses do not allocate a line: segments they start are
            # not lifetimes in the cache (the data never lived on-chip).
            dead_start = w & miss
    else:
        raise ValueError(f"unknown mode {mode!r}")

    seg_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg_id = jnp.maximum(seg_id, 0)

    neg = jnp.asarray(NO_READ_SENTINEL, t.dtype)
    start = jax.ops.segment_min(t, seg_id, num_segments=n)
    last_read = jax.ops.segment_max(
        jnp.where(read_ok, t, neg), seg_id, num_segments=n)
    n_reads = jax.ops.segment_sum(
        read_ok.astype(jnp.int32), seg_id, num_segments=n)
    n_events_seg = jax.ops.segment_sum(
        jnp.ones_like(seg_id), seg_id, num_segments=n)
    seg_addr = jax.ops.segment_max(a, seg_id, num_segments=n)
    seg_dead = jax.ops.segment_max(
        dead_start.astype(jnp.int32) * boundary.astype(jnp.int32),
        seg_id, num_segments=n).astype(bool)

    valid = (n_events_seg > 0) & (~seg_dead)
    has_read = n_reads > 0
    lifetime = jnp.where(valid & has_read, last_read - start, 0)
    orphan = valid & (~has_read)

    return LifetimeStats(
        lifetime_cycles=lifetime,
        n_reads=n_reads,
        start_cycles=jnp.where(valid, start, 0),
        addr=jnp.where(valid, seg_addr, -1),
        valid=valid,
        orphan=orphan,
        seg_id_per_event=seg_id,
    )


def lifetimes_of_trace(
    trace: Trace,
    mode: str = "scratchpad",
    write_allocate: bool = True,
) -> LifetimeStats:
    return extract_lifetimes(
        trace.time_cycles,
        trace.addr,
        trace.is_write,
        trace.hit,
        mode=mode,
        write_allocate=write_allocate,
    )


def short_lived_fraction(
    stats: LifetimeStats, clock_hz: float, retention_s: float,
    weight_by_accesses: bool = True,
) -> float:
    """Fraction of accesses (or lifetimes) at or under a device retention.

    The paper's headline numbers ("64% of L1 accesses are short-lived")
    weight by *accesses*: every event belonging to a lifetime that fits the
    retention counts.
    """
    lt_s = np.asarray(stats.lifetime_cycles) / clock_hz
    valid = np.asarray(stats.valid)
    fits = (lt_s <= retention_s) & valid
    if weight_by_accesses:
        seg_events = np.asarray(
            jax.ops.segment_sum(
                jnp.ones_like(stats.seg_id_per_event),
                stats.seg_id_per_event,
                num_segments=stats.lifetime_cycles.shape[0]))
        tot = seg_events[valid].sum()
        return float(seg_events[fits].sum() / max(tot, 1))
    nv = valid.sum()
    return float(fits.sum() / max(nv, 1))


def lifetime_histogram(
    stats: LifetimeStats, clock_hz: float,
    bins_s: np.ndarray,
) -> np.ndarray:
    """Histogram of valid lifetimes (seconds) over given bin edges."""
    lt = stats.lifetimes_s(clock_hz)
    hist, _ = np.histogram(lt, bins=np.asarray(bins_s))
    return hist
