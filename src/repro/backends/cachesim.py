"""Set-associative L1/L2 data-cache simulator (paper §5.1).

Replaces the Accel-Sim GPU backend: address streams (from
``repro.backends.opstream`` or any other source) are replayed through a
two-level write-back cache hierarchy modeled after an H100 SM slice:
configurable size / associativity / line size, LRU replacement, and the
write-allocation policy ablation of §5.1.2 / §7.1.6.

Two jitted implementations of the per-level replay exist:

  ``set_parallel`` (default)
      Accesses to different cache sets are independent in a set-associative
      cache, so the stream is partitioned by set index on the host, all
      sets are simulated concurrently by one batched ``lax.scan`` whose
      carry is just each set's ``ways``-wide state, and per-access outputs
      are scattered back into stream order.  The scan length drops from
      ``n_events`` to ``max`` events-per-set (~``n_events / n_sets`` for
      realistic streams), which is where the >=10x large-trace speedup
      comes from (``benchmarks/cachesim_bench.py`` tracks it).

  ``scalar``
      The original one-access-per-step ``lax.scan`` over the whole
      ``(n_sets, ways)`` tag array.  Kept as the differential oracle: the
      set-parallel simulator is bit-for-bit identical to it (randomized
      differential tests in ``tests/test_cachesim_parallel.py``).

Select via ``HierarchyConfig(simulator="scalar")`` (or the ``simulator=``
kwarg through ``ProfileSession("gpu")`` / ``CacheHierarchyBackend.run``).

Cycle stamps, line addresses, and the LRU clock are carried as **int64**
(under a scoped ``repro.compat.enable_x64``): line addresses >= 2**31
and multi-billion-cycle streams are exact, matching the int64 trace
contract of ``repro.core.trace``.

L2 stream composition (write-back hierarchy):
  - L1 read misses and (under write-allocate) L1 write misses fetch the
    line from L2  -> L2 *read* access;
  - dirty L1 evictions write back           -> L2 *write* access;
  - under no-write-allocate, L1 write misses bypass to L2 -> L2 *write*.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import enable_x64
from repro.core.api import ProfileResult, register_backend
from repro.core.trace import Trace, chunk_trace
from repro.runtime import obs

L1, L2 = 0, 1
SUB_NAMES = ("L1", "L2")

SIMULATORS = ("set_parallel", "scalar")


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    size_kb: int = 128
    ways: int = 8
    line_bytes: int = 128

    @property
    def n_sets(self) -> int:
        return max(1, (self.size_kb * 1024) // (self.line_bytes * self.ways))


@dataclasses.dataclass(frozen=True)
class HierarchyConfig:
    l1: CacheConfig = CacheConfig(size_kb=128, ways=8)
    l2: CacheConfig = CacheConfig(size_kb=4096, ways=16)
    write_allocate: bool = True
    clock_hz: float = 1.0e9
    l2_latency: int = 30  # cycles added to L2 access stamps
    simulator: str = "set_parallel"  # or "scalar" (differential oracle)


def _simulate_cache(line_addr, is_write, n_sets, ways, write_allocate):
    """Scalar oracle: scan one access per step over one cache level.

    Host entry point: inputs are promoted to int64 inside a scoped x64
    region, so streams with addresses past 2**31 are exact for *any*
    caller (a bare jitted entry would let jax's default 32-bit mode
    silently demote int64 inputs at conversion).  Returns numpy
    (hit, fill, evict_addr, evict_dirty):
    fill:        line was allocated (miss that fetched from next level)
    evict_addr:  address of a line evicted by the fill (-1 if none/invalid)
    evict_dirty: evicted line was dirty (needs write-back)
    """
    lines = np.asarray(line_addr, np.int64)
    w = np.asarray(is_write, bool)
    obs.count("h2d_bytes", lines.nbytes + w.nbytes)
    with enable_x64():
        outs = _simulate_cache_scan(jnp.asarray(lines, jnp.int64),
                                    jnp.asarray(w, bool),
                                    n_sets, ways, write_allocate)
    outs = tuple(np.asarray(x) for x in outs)
    obs.count("d2h_bytes", sum(x.nbytes for x in outs))
    return outs


@partial(jax.jit, static_argnames=("n_sets", "ways", "write_allocate"))
def _simulate_cache_scan(line_addr, is_write, n_sets, ways, write_allocate):
    addrs = jnp.asarray(line_addr, jnp.int64)
    dt = addrs.dtype
    tags0 = jnp.full((n_sets, ways), -1, dt)
    dirty0 = jnp.zeros((n_sets, ways), bool)
    stamp0 = jnp.zeros((n_sets, ways), dt)

    def step(state, inp):
        tags, dirty, stamp, clock = state
        addr, w = inp
        s = addr % n_sets
        row = tags[s]
        match = row == addr
        hit = match.any()
        way_hit = jnp.argmax(match)

        allocate = (~hit) & (write_allocate | (~w))
        victim = jnp.argmin(stamp[s])
        evict_addr = jnp.where(allocate, tags[s, victim], -1)
        evict_dirty = jnp.where(allocate, dirty[s, victim], False)

        way = jnp.where(hit, way_hit, victim)
        touched = hit | allocate
        new_tag = jnp.where(allocate, addr, tags[s, way])
        new_dirty = jnp.where(
            touched, jnp.where(w, True, dirty[s, way] & hit), dirty[s, way])
        tags = tags.at[s, way].set(jnp.where(touched, new_tag, tags[s, way]))
        dirty = dirty.at[s, way].set(new_dirty)
        stamp = stamp.at[s, way].set(
            jnp.where(touched, clock, stamp[s, way]))

        out = (hit, allocate, evict_addr, evict_dirty & (evict_addr >= 0))
        return (tags, dirty, stamp, clock + 1), out

    (_, _, _, _), outs = jax.lax.scan(
        step, (tags0, dirty0, stamp0, jnp.asarray(1, dt)),
        (addrs, is_write.astype(bool)))
    return outs


@partial(jax.jit, static_argnames=("ways", "write_allocate"))
def _simulate_cache_sets(packed, counts, ways, write_allocate):
    """Batched scan over (n_sets, L) set-partitioned padded streams.

    Step j processes slot j of *every* set at once; the carry is each
    set's ways-wide state.  Padding lanes (slot >= that set's count)
    leave the state untouched and emit don't-care outputs.

    Layout (built by :func:`_simulate_cache_set_parallel`):
      packed    (n_sets, L) int64  ``line_addr * 2 + is_write`` per slot
      counts    (n_sets,)   int32  events per set (defines valid lanes)

    Only the (n_sets, L) padded shape reaches the jit, and L is quantized
    to a power of two by the caller, so workload sweeps over many streams
    reuse the XLA compile cache (the stream-order gather happens on the
    host).

    The step body is trimmed for XLA's per-op while-loop overhead (the
    per-step tensors are tiny, so op count — not FLOPs — is the cost):

      - tag and dirty bit live in one packed int64 carry
        (``tag * 2 + dirty``, -2 = invalid way), so one masked-sum gather
        serves tag compare, eviction address, and dirty bookkeeping;
      - LRU uses a *unique* recency key ``clock * ways + way`` instead of
        a raw clock, so the victim one-hot is a plain ``== min`` (no
        argmin/cumsum): within a set the keys order touches exactly like
        the scalar oracle's strictly-increasing clock, and the
        untouched-way init keys 0..ways-1 reproduce argmin's
        lowest-index tie-break.  Keys are int64, so the 2**31-access
        wraparound of the old int32 LRU clock cannot occur;
      - all four per-access outputs ride in one int64
        (``(evict_addr + 1) << 3 | dirty_evict << 2 | fill << 1 | hit``),
        returned in the (L, n_sets) slot layout.
    """
    n_sets, L = packed.shape
    addr_p = packed >> 1
    w_p = (packed & 1).astype(bool)
    valid_p = (jax.lax.broadcasted_iota(jnp.int32, (n_sets, L), 1)
               < counts[:, None])
    alloc_ok_p = valid_p if write_allocate else (valid_p & ~w_p)

    T0 = jnp.full((n_sets, ways), -2, jnp.int64)
    key0 = jnp.broadcast_to(jnp.arange(ways, dtype=jnp.int64),
                            (n_sets, ways))
    way_iota = jnp.arange(ways, dtype=jnp.int64)

    def step(state, inp):
        T, key, clockw = state
        addr, w, alloc_ok, valid = inp            # each (n_sets,)
        match = (T >> 1) == addr[:, None]
        raw_hit = match.any(1)
        hit = raw_hit & valid
        victim_oh = key == key.min(1, keepdims=True)
        allocate = alloc_ok & (~raw_hit)

        woh = jnp.where(raw_hit[:, None], match, victim_oh)
        touched = hit | allocate
        upd = woh & touched[:, None]
        selv = (T * woh).sum(1)          # selected way's packed tag|dirty
        cur_dirty = (selv & 1).astype(bool)
        evict_addr = jnp.where(allocate & (selv >= 0), selv >> 1, -1)
        evict_dirty = allocate & cur_dirty & (selv >= 0)
        new_dirty = w | (cur_dirty & hit)
        T = jnp.where(upd, (addr * 2 + new_dirty)[:, None], T)
        key = jnp.where(upd, clockw + way_iota[None, :], key)

        out = (((evict_addr + 1) << 3)
               | (evict_dirty.astype(jnp.int64) << 2)
               | (allocate.astype(jnp.int64) << 1)
               | hit.astype(jnp.int64))
        return (T, key, clockw + ways), out

    init = (T0, key0, jnp.asarray(ways, jnp.int64))
    _, out_p = jax.lax.scan(
        step, init, (addr_p.T, w_p.T, alloc_ok_p.T, valid_p.T), unroll=2)
    return out_p  # (L, n_sets)


# Fall back to the scalar scan when the dense (n_sets, L) padded layout
# would mostly hold padding: L is the *max* events per set, so a heavily
# skewed stream (e.g. a stride that is a multiple of n_sets lines, landing
# every access in one set) would cost O(n_sets * n) memory and a length-n
# scan at width n_sets - strictly worse than the O(n) scalar oracle.  The
# two are bit-for-bit identical, so the fallback is behaviorally invisible.
_MAX_PAD_RATIO = 8


def _simulate_cache_set_parallel(line_addr, is_write, n_sets, ways,
                                 write_allocate, *, level="L1"):
    """Set-parallel replay of one cache level; host in/out in stream order.

    Partitions the stream by set index (stable, so each set keeps its
    access order), simulates all sets concurrently, and gathers the
    per-access outputs back.  Returns numpy (hit, fill, evict_addr,
    evict_dirty) bit-for-bit identical to the scalar oracle's.  Streams
    skewed enough that the set-partitioned layout is mostly padding run
    through the scalar scan instead (same results, better complexity).
    The three steps are the spans ``cachesim.partition``,
    ``cachesim.scan`` and ``cachesim.gather``, each with attribute
    ``level``.
    """
    lines = np.asarray(line_addr, np.int64)
    w = np.asarray(is_write, bool)
    n = lines.shape[0]
    if n == 0:
        return (np.zeros(0, bool), np.zeros(0, bool),
                np.zeros(0, np.int64), np.zeros(0, bool))
    if int(lines.min()) < 0 or int(lines.max()) >= 2 ** 59:
        raise OverflowError(
            "cachesim line addresses must lie in [0, 2^59) "
            f"(got [{int(lines.min())}, {int(lines.max())}]); that is "
            "byte addresses below 2^66 at 128-byte lines")

    with obs.span("cachesim.partition", level=level):
        set_dt = np.uint8 if n_sets <= 256 else np.uint32
        set_idx = (lines % n_sets).astype(set_dt)
        counts64 = np.bincount(set_idx, minlength=n_sets)
        L = int(counts64.max())
        skewed = n_sets * L > max(_MAX_PAD_RATIO * n, 4096)
        if not skewed:
            # Round the padded width up to a power of two: the jitted
            # scan is shape-specialized, so quantizing L makes workload
            # sweeps reuse the XLA compile cache instead of recompiling
            # per stream (the counts mask already neutralizes padding
            # lanes, so results are unchanged).
            L = 1 << (L - 1).bit_length()

            order = np.argsort(set_idx, kind="stable")
            counts = counts64.astype(np.int32)
            starts = np.zeros(n_sets, np.int64)
            starts[1:] = np.cumsum(counts64)[:-1]
            rows = set_idx[order].astype(np.int64)
            slots = np.arange(n, dtype=np.int64) - starts[rows]

            packed = np.zeros((n_sets, L), np.int64)
            packed[rows, slots] = lines[order] * 2 + w[order]
            flat_pos = np.empty(n, np.int64)
            flat_pos[order] = slots * n_sets + rows   # (L, n_sets) row-major

    with obs.span("cachesim.scan", level=level):
        if skewed:
            return _simulate_cache(lines, w, n_sets, ways, write_allocate)
        obs.count("h2d_bytes", packed.nbytes + counts.nbytes)
        with enable_x64():
            out_p = np.asarray(_simulate_cache_sets(
                jnp.asarray(packed), jnp.asarray(counts),
                ways, write_allocate))
        obs.count("d2h_bytes", out_p.nbytes)

    with obs.span("cachesim.gather", level=level):
        out = out_p.reshape(-1)[flat_pos]         # back to stream order
        return ((out & 1).astype(bool), ((out >> 1) & 1).astype(bool),
                (out >> 3) - 1, ((out >> 2) & 1).astype(bool))


def _simulate_level(lines, w, level: CacheConfig, write_allocate: bool,
                    simulator: str, name: str = "L1"):
    """Dispatch one cache level (``name``, the span attribute) to the
    selected simulator (host arrays)."""
    if simulator == "set_parallel":
        return _simulate_cache_set_parallel(
            lines, w, level.n_sets, level.ways, write_allocate, level=name)
    if simulator == "scalar":
        return _simulate_cache(lines, w, level.n_sets, level.ways,
                               write_allocate)
    raise ValueError(
        f"unknown simulator {simulator!r}; available: {SIMULATORS}")


def simulate_hierarchy(
    time_cycles: np.ndarray,
    byte_addr: np.ndarray,
    is_write: np.ndarray,
    cfg: HierarchyConfig = HierarchyConfig(),
) -> Trace:
    """Replay a byte-address stream through L1 -> L2; emit a two-subpartition
    trace in the canonical format (line-granular addresses)."""
    t = np.asarray(time_cycles, np.int64)
    lines = (np.asarray(byte_addr, np.int64) // cfg.l1.line_bytes)
    w = np.asarray(is_write, bool)

    hit1, fill1, ev_addr, ev_dirty = _simulate_level(
        lines, w, cfg.l1, cfg.write_allocate, cfg.simulator, SUB_NAMES[L1])

    # --- compose the L2 access stream, preserving time order -------------
    with obs.span("cachesim.l2_stream"):
        l2_t, l2_a, l2_w = [], [], []
        # fills: L1 fetched the line from L2 (read)
        l2_t.append(t[fill1] + cfg.l2_latency)
        l2_a.append(lines[fill1])
        l2_w.append(np.zeros(int(fill1.sum()), bool))
        # dirty evictions: write-back to L2
        m = ev_dirty & (ev_addr >= 0)
        l2_t.append(t[m] + cfg.l2_latency)
        l2_a.append(ev_addr[m].astype(np.int64))
        l2_w.append(np.ones(int(m.sum()), bool))
        # no-write-allocate: write misses bypass to L2
        if not cfg.write_allocate:
            m = w & ~hit1
            l2_t.append(t[m] + cfg.l2_latency)
            l2_a.append(lines[m])
            l2_w.append(np.ones(int(m.sum()), bool))
        l2_t = np.concatenate(l2_t)
        l2_a = np.concatenate(l2_a)
        l2_w = np.concatenate(l2_w)
        order = np.argsort(l2_t, kind="stable")
        l2_t, l2_a, l2_w = l2_t[order], l2_a[order], l2_w[order]

    hit2 = _simulate_level(
        l2_a, l2_w, cfg.l2, cfg.write_allocate, cfg.simulator,
        SUB_NAMES[L2])[0]

    with obs.span("cachesim.merge"):
        times = np.concatenate([t, l2_t])
        addrs = np.concatenate([lines, l2_a])
        writes = np.concatenate([w, l2_w])
        hits = np.concatenate([np.asarray(hit1), np.asarray(hit2)])
        subs = np.concatenate([np.zeros(len(t), np.int32),
                               np.ones(len(l2_t), np.int32)])
        order = np.argsort(times, kind="stable")
    return Trace(
        time_cycles=times[order], addr=addrs[order], is_write=writes[order],
        hit=hits[order], subpartition=subs[order],
        clock_hz=cfg.clock_hz, block_bits=cfg.l1.line_bytes * 8,
        names=SUB_NAMES)


@register_backend("cachesim", aliases=("gpu",))
class CacheHierarchyBackend:
    """Registry adapter for the L1/L2 cache hierarchy (alias: "gpu").

    Workload forms:
      - ``(time_cycles, byte_addr, is_write)`` arrays to replay directly,
      - a filled ``opstream.StreamBuilder`` (anything with ``.finish()``),
      - a callable op program ``fn(sb)`` lowered onto a fresh builder
        (``sample=`` controls its line sampling).

    Config kwargs are the :class:`HierarchyConfig` fields (or pass
    ``config=HierarchyConfig(...)``); ``simulator="set_parallel"``
    (default) or ``"scalar"`` picks the per-level replay implementation.
    ``chunk_events=N`` streams the hit-annotated trace to the frontend in
    N-event chunks.
    """
    name = "cachesim"
    mode = "cache"

    def run(self, workload, *, config: HierarchyConfig | None = None,
            sample: int = 1, chunk_events: int | None = None,
            **cfg) -> ProfileResult:
        kernels = []
        if hasattr(workload, "finish"):
            t, a, w = workload.finish()
            kernels = [k.__dict__ for k in workload.kernels]
        elif callable(workload):
            from repro.backends.opstream import StreamBuilder
            sb = StreamBuilder(sample=sample)
            workload(sb)
            t, a, w = sb.finish()
            kernels = [k.__dict__ for k in sb.kernels]
        else:
            t, a, w = workload
        if config is not None and cfg:
            raise ValueError(
                "pass either config=HierarchyConfig(...) or field kwargs "
                f"({sorted(cfg)}), not both - the kwargs would be "
                "silently ignored")
        hcfg = config if config is not None else HierarchyConfig(**cfg)
        if hcfg.simulator not in SIMULATORS:
            raise ValueError(
                f"unknown simulator {hcfg.simulator!r}; "
                f"available: {SIMULATORS}")
        trace = simulate_hierarchy(t, a, w, hcfg)
        if chunk_events:
            return ProfileResult(chunks=chunk_trace(trace, chunk_events),
                                 kernels=kernels, mode=self.mode)
        return ProfileResult(trace=trace, kernels=kernels, mode=self.mode)
