"""Fused sweep executor: device-resident trace state + bucketed jits.

The PR-9 jax engine (:mod:`repro.compose.jax_engine`) accelerates one
candidate chunk at a time: every chunk re-uploads the [L]/[A] trace
arrays, re-does the host address sort, and jit-compiles per chunk
shape.  This module removes all three costs for ``engine="jax"``:

* **Device residency** — the lifetime/reads/bits arrays, the address
  segment ids, and the value-sorted lifetime prefix sums are uploaded
  once per subpartition (memoized on the identity of the host-side
  :func:`repro.compose.engine.sorted_trace_view`, itself memoized per
  ``(stats, raw)`` pair) and reused across every candidate batch,
  policy, and geometry.

* **Fused candidate batches** — one jit per policy family evaluates the
  whole ``[C, D, L]`` batch through ``vmap``; the host chunk loop is
  gone.  The refresh-free kernel is reformulated as interval arithmetic
  over the value-sorted lifetimes: first-fit assignment of lifetime
  ``t`` to device ``d`` is exactly ``t ∈ (chi_{d-1}, chi_d]`` with
  ``chi = cummax(retention)`` over the cheapest-first device axis, so
  per-device totals are ``searchsorted`` positions into precomputed
  prefix sums — O(C·D·log L) instead of O(C·D·L), and the per-device
  capacity *counts* are position differences (exact integers, so
  capacity fractions stay bit-identical to the NumPy oracle).  The
  prefix sums are accumulated on the host in ``np.longdouble`` and
  rounded once to float64, so energy differences stay ~1e-16 relative —
  far inside the 1e-9 engine contract.

* **Scatter-free per-address sums** — the grouped refresh-aware kernel
  picks each address's device from per-address sums of integers (bits,
  reads·bits, refresh count·bits).  They are int64 prefix sums along
  the address-sorted lifetimes, built from uint32 scans and read at
  each address's last lifetime: exact in any order, so the picks are
  those of a float64 ``segment_sum`` wherever that was exact.  A trace
  with non-integer values, or sums that could pass 2**62, is refused
  with ``ValueError`` (:meth:`_TraceResidence.check_int64`).

* **Shape buckets** — inputs are padded to a small pow2 bucket grammar
  (``L`` to ≥2048, ``A`` to ≥256, ``D`` to ≥2, candidates to ≥8; the
  refresh-aware batch is dispatched in fixed-size pow2 candidate slabs
  sized from the same 256 MB broadcast budget as the NumPy engine), so
  an entire ``FamilyGrid`` sweep — and distinct workloads of a campaign
  that land in the same buckets — compile O(buckets) times instead of
  O(chunks).  The real extents travel as *traced* scalars, never as
  static shapes, so two workloads inside one bucket share a compile.
  Padding is masked everywhere it could leak: padded lifetimes carry
  ``lt = reads = bits = 0`` (exact-zero contributions), padded
  addresses are excluded from pick counts, padded device slots keep the
  engine's ``-inf`` retention / ``+inf`` energy sentinels with their
  coefficients zeroed before any ``0 * inf`` could produce NaN, and
  padded candidates are sliced off on the host.

* **Compile telemetry** — :func:`compile_stats` exposes jit-entry
  counts, the persistent-cache hit/miss counters of
  :mod:`repro.runtime.compile_cache` and the device the kernels ran on,
  for the campaign report.

Thread safety: dispatch is serialized on
:data:`repro.compose.jax_engine._DISPATCH_LOCK` (shared with the
per-chunk path), which also guards the residence memo.

Knife-edge reductions (capacity count division, bits-weighted
fractions) finish on the host exactly as the PR-9 engine does, keeping
capacity fractions — and therefore bank quantization — bit-identical
across engines.

Import contract: like ``jax_engine``, this module imports jax at module
level and is exempt from the ``repro.compose`` import-purity contract
(``repro check``); it must only be imported lazily, from
:func:`repro.compose.engine.evaluate` / ``compile_stats``.
"""

from __future__ import annotations

import contextlib
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import enable_x64
from repro.compose.jax_engine import (_DISPATCH_LOCK, _base_policy,
                                      _host_weighted_fracs, supports)
from repro.compose.policies import RefreshFreePolicy
from repro.runtime import obs

_F64 = np.float64

# Bucket grammar: every axis is padded up to a power of two, floored at
# these minimums, so distinct workload shapes collapse onto a handful
# of compiled signatures (docs/API.md "Fused sweep execution").
_L_MIN = 2048       # lifetimes
_A_MIN = 256        # addresses
_D_MIN = 2          # device slots
_C_MIN = 8          # candidates (refresh-free batch / refresh-aware slab)

# The refresh-aware [slab, D, L] broadcast budget — same cap as the
# NumPy engine's chunking (engine._MAX_BROADCAST_BYTES) at the policy's
# broadcast itemsize.
_SLAB_BYTES = 256 * 1024 * 1024

# A refresh count is ceil(T / t_ret) - 1.  Where T / t_ret lies within a
# few ulps of an integer - a lifetime that is an exact multiple of a
# retention, as one of k microseconds on a 1-us gain cell - a division
# that is not correctly rounded (a TPU emulates float64) can round the
# quotient across the integer, and ceil bills one refresh more or less
# than the float64 oracle.  The host finds those lifetimes (quotients
# within _NEAR_INT of an integer, far wider than the device's error)
# and hands the kernels the oracle's count for each, in fixed-width
# lists of at least _FIX_MIN entries per candidate device.
_NEAR_INT = 1e-11
_FIX_MIN = 64

# Exact int64 prefix sums from uint32 scans (:func:`_prefix_sums`): a
# TPU emulates int64, and its compiler refuses some int64 scans at a few
# million lifetimes (out of scoped vector memory), while uint32 scans
# are native.  A block's running sums of 25-bit limbs stay below 2**32.
_SCAN_BLOCK = 128
_LIMB_BITS = 25


def _next_pow2(n: int, lo: int) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def _slab_size(d_pad: int, l_pad: int, n_cands: int, itemsize: int) -> int:
    """Fixed pow2 candidate-slab width for the refresh-aware dispatch
    loop: the largest pow2 keeping ``slab * D * L * itemsize`` under the
    broadcast budget, floored at ``_C_MIN`` and capped at the batch's
    own bucket (no point compiling wider than the grid)."""
    budget = _SLAB_BYTES // max(1, d_pad * l_pad * itemsize)
    slab = 1 << max(0, budget.bit_length() - 1)
    return min(max(_C_MIN, slab), _next_pow2(n_cands, _C_MIN))


# ---------------------------------------------------------------------------
# compile telemetry
# ---------------------------------------------------------------------------

def compile_stats() -> dict:
    """Compile telemetry for campaign job rows: total jit cache entries
    across the fused and per-chunk kernels (deltas across a job count
    its new compiles), persistent-cache hit/miss counters, and the
    platform and ``device_kind`` the kernels run on."""
    from repro.compose import jax_engine
    from repro.runtime.compile_cache import counters
    kernels = (_rf_fused, _ra_grouped, _ra_ungrouped,
               jax_engine._refresh_free_kernel,
               jax_engine._refresh_aware_kernel,
               jax_engine._refresh_free_ungrouped,
               jax_engine._refresh_aware_ungrouped)
    entries = 0
    for fn in kernels:
        try:
            entries += fn._cache_size()
        except Exception:       # noqa: BLE001 - telemetry must not raise
            pass
    dev = jax.devices()[0]
    return {"jit_entries": entries, **counters(),
            "platform": dev.platform, "device_kind": dev.device_kind}


# ---------------------------------------------------------------------------
# host <-> device, counted (repro.runtime.obs)
# ---------------------------------------------------------------------------

def _put(a: np.ndarray):
    obs.count("h2d_bytes", a.nbytes)
    return jnp.asarray(a)


def _pull(x) -> np.ndarray:
    obs.count("d2h_bytes", x.nbytes)
    return np.asarray(x)


# ---------------------------------------------------------------------------
# device-resident trace state
# ---------------------------------------------------------------------------

class _TraceResidence:
    """Device-resident, bucket-padded twins of one subpartition's
    ``sorted_trace_view`` arrays, built lazily per policy family and
    reused across every candidate batch, policy, and geometry.  Each
    set's build (host padding and upload) is the span
    ``executor.residence``, attribute ``arrays``."""

    def __init__(self, view):
        self.n_lt = int(view.n_lt)
        self.n_addr = int(view.n_addr)
        self.L_pad = _next_pow2(self.n_lt, _L_MIN)
        self.A_pad = _next_pow2(max(1, self.n_addr), _A_MIN)
        self._value = None      # (lt_sorted, prefix_bits, prefix_rb, maxlt)
        self._addr = None       # (lt, reads, bits, seg) addr-sorted
        self._orig = None       # (lt, reads, bits) original order
        self._int_max = None    # (max bits, max reads), once checked

    def value_sorted(self, view):
        """+inf-padded value-sorted lifetimes, their [L+1] prefix sums
        (padded positions repeat the final total, so clamped positions
        past the real extent read exact totals), and the +inf-padded
        sorted per-address max lifetimes."""
        if self._value is None:
            with obs.span("executor.residence", arrays="value"):
                lt = np.full(self.L_pad, np.inf)
                lt[:self.n_lt] = view.lt_sorted
                pb = np.empty(self.L_pad + 1)
                pb[:self.n_lt + 1] = view.prefix_bits
                pb[self.n_lt + 1:] = view.prefix_bits[-1]
                prb = np.empty(self.L_pad + 1)
                prb[:self.n_lt + 1] = view.prefix_read_bits
                prb[self.n_lt + 1:] = view.prefix_read_bits[-1]
                ml = np.full(self.A_pad, np.inf)
                if view.maxlt_sorted is not None:
                    ml[:self.n_addr] = view.maxlt_sorted
                self._value = tuple(map(_put, (lt, pb, prb, ml)))
        return self._value

    def addr_sorted(self, view):
        """Zero-padded address-sorted lifetime arrays + segment ids —
        padding lands in segment 0 and contributes exact zeros."""
        if self._addr is None:
            with obs.span("executor.residence", arrays="addr"):
                seg = np.zeros(self.L_pad, np.int32)
                seg[:self.n_lt] = view.seg
                self._addr = tuple(map(_put, (
                    self._zpad(view.lt_addr), self._zpad(view.reads_addr),
                    self._zpad(view.bits_addr), seg)))
        return self._addr

    def check_int64(self, view, ret, pad):
        """Raise ``ValueError`` unless ``_ra_grouped``'s per-address sums
        are exact in int64 for the candidates ``ret``/``pad`` (``[C, D]``):
        bits and reads are nonnegative integers (checked once per
        residence), no live retention is zero or NaN, and no sum of bits
        times a read or a refresh count reaches 2**62 (O(C·D))."""
        if self._int_max is None:
            b, r = view.bits_addr, view.reads_addr
            if not all(np.isfinite(a).all() and (a >= 0).all()
                       and (a == np.floor(a)).all() for a in (b, r)):
                raise ValueError("refresh-aware per-address sums need "
                                 "nonnegative integer bits and reads")
            self._int_max = (float(b.max(initial=0)),
                             float(r.max(initial=0)))
        live = ret[~pad]
        if np.isnan(live).any() or (live == 0).any():
            raise ValueError("refresh-aware retention is zero or NaN")
        max_bits, max_reads = self._int_max
        max_lt = float(view.lt_sorted[-1]) if self.n_lt else 0.0
        r_min = live[live > 0].min(initial=np.inf)
        limit = 2.0 ** 62 / max(1.0, self.n_lt * max_bits)
        if not (max_lt / r_min + 1.0 < limit and max_reads < limit):
            raise ValueError("refresh-aware per-address sums could pass "
                             "2**62 (int64)")

    def original(self, lt, reads, bits):
        """Zero-padded original-order arrays (ungrouped refresh-aware:
        the per-lifetime picks must come back in oracle element order)."""
        if self._orig is None:
            with obs.span("executor.residence", arrays="original"):
                self._orig = tuple(map(_put, (
                    self._zpad(lt), self._zpad(reads), self._zpad(bits))))
        return self._orig

    def _zpad(self, a):
        out = np.zeros(self.L_pad)
        out[:self.n_lt] = a
        return out


# id(view) -> (weakref(view), residence); the weakref guards id reuse
# and evicts device buffers when the host view (and with it the
# originating stats/raw pair) is collected.
_residence_memo: dict = {}


def _residence_for(view) -> _TraceResidence:
    key = id(view)
    hit = _residence_memo.get(key)
    if hit is not None and hit[0]() is view:
        return hit[1]
    res = _TraceResidence(view)
    try:
        ref = weakref.ref(
            view, lambda _, k=key: _residence_memo.pop(k, None))
        _residence_memo[key] = (ref, res)
    except TypeError:
        pass                    # view not weakref-able: skip the memo
    return res


def _near_integer_ranks(lt_sorted: np.ndarray, r: float) -> np.ndarray:
    """Value-sorted ranks of the lifetimes whose quotient by retention
    ``r`` lies within ``_NEAR_INT`` (relative) of an integer >= 1."""
    if not (np.isfinite(r) and r > 0) or lt_sorted.size == 0:
        return np.zeros(0, np.int64)
    k_max = int(lt_sorted[-1] / r * (1.0 + _NEAR_INT))
    if k_max > lt_sorted.size:          # tiny retention: test each lifetime
        q = lt_sorted / r
        k = np.round(q)
        return np.flatnonzero((k >= 1) & (np.abs(q - k) <= _NEAR_INT * q))
    kr = np.arange(1, k_max + 1) * r
    lo = np.searchsorted(lt_sorted, kr * (1.0 - _NEAR_INT))
    n = np.searchsorted(lt_sorted, kr * (1.0 + _NEAR_INT), side="right") - lo
    return np.repeat(lo - np.cumsum(n) + n, n) + np.arange(int(n.sum()))


def _refresh_fixes(view, ret: np.ndarray, rows: int, d_pad: int,
                   grouped: bool):
    """``(idx, cnt)``, each ``[rows, d_pad, N]``: for every candidate
    device of ``ret`` (``[C, D]``, C <= rows), the lifetimes whose
    refresh count a device division may round wrongly
    (:func:`_near_integer_ranks`), as positions in the kernel's lifetime
    order (address-sorted when ``grouped``, else original), and their
    oracle counts ``max(ceil(T / t_ret) - 1, 0)`` in float64.  Unused
    entries point past the lifetimes (dropped by the kernel's scatter).
    ``N`` is a power of two, at least ``_FIX_MIN``."""
    found = {}
    for (c, d), r in np.ndenumerate(ret):
        ranks = _near_integer_ranks(view.lt_sorted, r)
        if ranks.size:
            found[c, d] = ranks
    n = _next_pow2(max((len(v) for v in found.values()), default=0),
                   _FIX_MIN)
    l_pad = _next_pow2(view.n_lt, _L_MIN)
    idx = np.full((rows, d_pad, n), l_pad, np.int64)
    cnt = np.zeros((rows, d_pad, n))
    for (c, d), ranks in found.items():
        pos = view.order[ranks]
        idx[c, d, :len(ranks)] = view.addr_pos[pos] if grouped else pos
        cnt[c, d, :len(ranks)] = np.maximum(
            np.ceil(view.lt_sorted[ranks] / ret[c, d]) - 1.0, 0.0)
    return idx, cnt


def _refresh_counts(lt, ret_r, fix_idx, fix_cnt):
    """``[D, L]`` refresh counts ``max(ceil(lt / ret) - 1, 0)`` of every
    lifetime on every device, with the host's exact counts at
    ``fix_idx`` (:func:`_refresh_fixes`)."""
    n = jnp.maximum(jnp.ceil(lt[None, :] / ret_r[:, None]) - 1.0, 0.0)
    dev = jnp.arange(ret_r.shape[0])[:, None]
    return n.at[dev, fix_idx].set(fix_cnt, mode="drop")


# ---------------------------------------------------------------------------
# fused kernels
# ---------------------------------------------------------------------------

@jax.jit
def _rf_fused(ret, read_fj, write_fj, pad, fallback,
              lt_sorted, pbits, prbits, maxlt_sorted, n_lt, n_addr):
    """Refresh-free, whole batch in one vmapped jit.

    First-fit device of lifetime ``t`` is the first ``d`` with
    ``t <= chi_d`` (``chi = cummax(retention)``, nondecreasing): the
    interval ``(chi_{d-1}, chi_d]`` is nonempty only when
    ``chi_d = ret_d``, so interval membership coincides exactly with the
    seed's argmax-of-fits pick, ties included — no float arithmetic,
    only comparisons, which is why capacity counts are bit-identical.
    ``searchsorted`` positions are clamped to the *traced* real extents
    so +inf padding (and SRAM's infinite retention) never counts pad
    entries, and real-extent changes inside a bucket never recompile.
    Padded device slots get their energy coefficients zeroed (their
    position intervals are empty by construction) instead of keeping
    the +inf sentinels, so ``inf * 0`` NaNs cannot appear."""
    def one(ret_r, rf_r, wf_r, pad_r, fb_r):
        chi = jax.lax.cummax(ret_r)
        pos = jnp.minimum(
            jnp.searchsorted(lt_sorted, chi, side="right"), n_lt)
        prev = jnp.concatenate([jnp.zeros(1, pos.dtype), pos[:-1]])
        wf0 = jnp.where(pad_r, 0.0, wf_r)
        rf0 = jnp.where(pad_r, 0.0, rf_r)
        e = wf0 * (pbits[pos] - pbits[prev]) \
            + rf0 * (prbits[pos] - prbits[prev])
        # lifetimes beyond every retention bill the fallback device
        tail = (wf0[fb_r] * (pbits[-1] - pbits[pos[-1]])
                + rf0[fb_r] * (prbits[-1] - prbits[pos[-1]]))
        energy = (e.sum() + tail) * 1e-15
        apos = jnp.minimum(
            jnp.searchsorted(maxlt_sorted, chi, side="right"), n_addr)
        aprev = jnp.concatenate([jnp.zeros(1, apos.dtype), apos[:-1]])
        counts = (apos - aprev).astype(jnp.float64)
        counts = counts + jnp.where(
            jnp.arange(ret_r.shape[0]) == fb_r, n_addr - apos[-1], 0)
        return energy, counts
    return jax.vmap(one)(ret, read_fj, write_fj, pad, fallback)


def _segment_ends(seg, n_lt, n_seg: int):
    """``[n_seg]`` index of each address's last lifetime in the
    address-sorted order, by a binary search of the segment ids with the
    padded lifetimes lifted past every address (so the padded addresses
    all end at the last real lifetime).  An address with no lifetime
    ends where the one before it does: -1 before the first lifetime."""
    key = jnp.where(jnp.arange(seg.shape[0]) < n_lt, seg, n_seg)
    return jnp.searchsorted(key, jnp.arange(n_seg, dtype=seg.dtype),
                            side="right") - 1


def _prefix_sums(x):
    """Inclusive prefix sums along the last axis of the nonnegative int64
    ``x`` (a power-of-two length), exact while the total stays below
    2**63.  Built from uint32 scans alone: each block of
    ``_SCAN_BLOCK`` values is cut into limbs of ``_LIMB_BITS`` bits,
    whose running sums inside the block stay below 2**32, and the
    blocks' totals are summed the same way one level up."""
    n = x.shape[-1]
    b = min(n, _SCAN_BLOCK)
    blocks = x.reshape(x.shape[:-1] + (n // b, b))
    inner = jnp.zeros_like(blocks)
    for k in range(0, 63, _LIMB_BITS):
        limb = ((blocks >> k) & ((1 << _LIMB_BITS) - 1)).astype(jnp.uint32)
        inner += jax.lax.cumsum(limb, axis=blocks.ndim - 1).astype(
            jnp.int64) << k
    if n == b:
        return inner.reshape(x.shape)
    tot = inner[..., -1]
    return (inner + (_prefix_sums(tot) - tot)[..., None]).reshape(x.shape)


def _segment_totals(x, ends):
    """Each address's sum of the nonnegative int64 ``x`` (``[..., L]``,
    address-sorted): the prefix sums read at the addresses' last
    lifetimes (zero at an end of -1), less the previous address's.
    Exact in any order, with one gather of ``[..., A]`` and no
    scatter."""
    p = jnp.where(ends >= 0, _prefix_sums(x)[..., jnp.maximum(ends, 0)], 0)
    return p - jnp.concatenate([jnp.zeros_like(p[..., :1]), p[..., :-1]],
                               axis=-1)


@functools.partial(jax.jit, static_argnames=("n_seg",))
def _ra_grouped(ret, read_fj, write_fj, pad, lt, reads, bits, seg, n_lt,
                n_addr, n_real, fix_idx, fix_cnt, *, n_seg):
    """Refresh-aware, one fixed-width candidate slab against the
    resident addr-sorted arrays.  Same decomposition as the PR-9 kernel
    (separable base terms + one refresh sum per address) so argmin ties
    resolve identically; the candidate-independent base sums are
    hoisted out of the candidate loop.  Padded addresses are masked out
    of the pick counts; padded lifetimes contribute exact zeros.

    The per-address sums — bits, reads·bits and each device's refresh
    count·bits — are sums of integers: int64 prefix sums along the
    address-sorted lifetimes, differenced at each address's last
    lifetime (:func:`_segment_totals`), exact in any order and with no
    scatter.  The caller has checked that they fit
    (:meth:`_TraceResidence.check_int64`).

    Every per-candidate array keeps the lifetime or address axis last
    ([D, L], [D, A]): on a TPU an array whose last axis is the device
    (or candidate) axis is padded to 128 lanes, which at a few million
    lifetimes does not fit in HBM.  Candidates run one after another
    (``lax.map``) for the same reason.

    Only the first ``n_real`` rows (a traced scalar, so the slab's
    shape and its executable stay the same) are candidates: each row
    takes a ``lax.cond`` on its index, and padded rows take the branch
    that returns zeros without computing anything.  Refresh counts
    near an integer quotient come from ``fix_idx``/``fix_cnt``
    (:func:`_refresh_counts`)."""
    rb = reads * bits
    ends = _segment_ends(seg, n_lt, n_seg)
    bits_i = bits.astype(jnp.int64)
    sb, srb = _segment_totals(
        jnp.stack([bits_i, reads.astype(jnp.int64) * bits_i]),
        ends).astype(jnp.float64)                           # [A] each
    amask = jnp.arange(n_seg) < n_addr
    dev_ids = jnp.arange(ret.shape[1])

    def one(args):
        ret_r, rf_r, wf_r, pad_r, fi_r, fc_r = args
        n = _refresh_counts(lt, ret_r, fi_r, fc_r)          # [D, L]
        refresh_e = n * bits[None, :]
        rw = rf_r + wf_r
        e = (wf_r[:, None] * bits[None, :]
             + rf_r[:, None] * rb[None, :]
             + rw[:, None] * refresh_e)
        e = jnp.where(pad_r[:, None], jnp.inf, e)
        energy = e.min(axis=0).sum() * 1e-15
        per_addr = (wf_r[:, None] * sb[None, :]
                    + rf_r[:, None] * srb[None, :]
                    + rw[:, None] * _segment_totals(
                        n.astype(jnp.int64) * bits_i[None, :],
                        ends).astype(jnp.float64))          # [D, A]
        per_addr = jnp.where(pad_r[:, None], jnp.inf, per_addr)
        ad = jnp.argmin(per_addr, axis=0)
        counts = ((ad[None, :] == dev_ids[:, None])
                  & amask[None, :]).sum(axis=1)
        return energy, counts.astype(jnp.float64)

    def skip(_):
        return jnp.zeros((), jnp.float64), jnp.zeros(ret.shape[1],
                                                     jnp.float64)

    def row(args):
        i, cand = args
        return jax.lax.cond(i < n_real, one, skip, cand)

    rows = jnp.arange(ret.shape[0])
    return jax.lax.map(row, (rows, (ret, read_fj, write_fj, pad, fix_idx,
                                    fix_cnt)))


@jax.jit
def _ra_ungrouped(ret, read_fj, write_fj, pad, lt, reads, bits, fix_idx,
                  fix_cnt):
    """Refresh-aware without address groups: per-lifetime argmin picks
    (original element order) for the host's exact weighted fractions."""
    def one(ret_r, rf_r, wf_r, pad_r, fi_r, fc_r):
        refresh = _refresh_counts(lt, ret_r, fi_r, fc_r)
        rw = rf_r[:, None] + wf_r[:, None]
        e = bits[None, :] * (wf_r[:, None]
                             + reads[None, :] * rf_r[:, None]
                             + refresh * rw)
        e = jnp.where(pad_r[:, None], jnp.inf, e)
        ff = jnp.argmin(e, axis=0)
        e_sel = jnp.take_along_axis(e, ff[None, :], axis=0)[0]
        return e_sel.sum() * 1e-15, ff
    return jax.vmap(one)(ret, read_fj, write_fj, pad, fix_idx, fix_cnt)


# ---------------------------------------------------------------------------
# the batch executor
# ---------------------------------------------------------------------------

def _pad_cd(a: np.ndarray, c_pad: int, d_pad: int, fill) -> np.ndarray:
    """[C, D] device matrix -> [c_pad, d_pad] with sentinel fill; padded
    candidate rows are all-pad device rows (harmless by masking)."""
    out = np.full((c_pad, d_pad), fill, dtype=a.dtype)
    out[:a.shape[0], :a.shape[1]] = a
    return out


@contextlib.contextmanager
def _slab(kernel: str, rows: int, real_rows: int):
    """The span ``executor.slab`` of one kernel dispatch: inputs up,
    the kernel, outputs down, so the slab's device work lies inside it;
    ``slab_rows`` counts its candidate rows and ``slab_real_rows`` those
    that are not padding."""
    with obs.span("executor.slab", kernel=kernel):
        obs.count("slab_rows", rows)
        obs.count("slab_real_rows", real_rows)
        yield


def _slab_inputs(batch, lo: int, hi: int, rows: int, d_pad: int):
    """Candidates ``lo:hi`` of the batch's device matrices, padded to
    ``[rows, d_pad]`` and uploaded."""
    return (_put(_pad_cd(batch.ret_s[lo:hi], rows, d_pad, -np.inf)),
            _put(_pad_cd(batch.read_fj[lo:hi], rows, d_pad, np.inf)),
            _put(_pad_cd(batch.write_fj[lo:hi], rows, d_pad, np.inf)),
            _put(_pad_cd(batch.pad[lo:hi], rows, d_pad, True)))


def _rf_ungrouped_host_fracs(batch, d_max: int) -> np.ndarray:
    """raw=None capacity: reconstruct the per-lifetime first-fit picks
    on the host (``searchsorted`` into each candidate's retention
    cummax — the same interval identity as the kernel, exact integer
    picks) and reduce with the oracle's masked weighted sums."""
    chi = np.maximum.accumulate(batch.ret_s, axis=1)
    lt = np.asarray(batch.lt_s)
    ff = np.empty((chi.shape[0], lt.size), np.int64)
    for c in range(chi.shape[0]):
        ff[c] = np.searchsorted(chi[c], lt, side="left")
    np.minimum(ff, np.asarray(batch.fallback), out=ff)  # no fit -> fallback
    return _host_weighted_fracs(ff, np.asarray(batch.bits, _F64), d_max)


def run_batch(pol, batch, view):
    """Evaluate the *whole* candidate batch; returns ``(energy_j [C],
    capacity_fractions [C, D])`` as NumPy arrays (D = padded width; the
    engine slices each candidate's real device count).

    ``batch`` is the engine's full-grid :class:`PolicyBatch`; ``view``
    the memoized :func:`repro.compose.engine.sorted_trace_view` of the
    same ``(stats, raw)`` pair.  Capacity fractions are bit-identical to
    the NumPy oracle (integer counts / exact host sums); energy agrees
    to ~1e-9 relative (measured ~1e-16)."""
    base = _base_policy(pol)
    if not supports(pol):
        raise ValueError(
            f"engine='jax' has no fused kernel for policy "
            f"{base.name!r}; use engine='numpy'")
    C, d_max = batch.ret_s.shape
    grouped = batch.groups is not None and view.n_addr > 0
    with _DISPATCH_LOCK, enable_x64():
        res = _residence_for(view)
        d_pad = _next_pow2(d_max, _D_MIN)
        n_lt = _put(np.int64(res.n_lt))
        n_addr = _put(np.int64(res.n_addr))
        if isinstance(base, RefreshFreePolicy):
            c_pad = _next_pow2(C, _C_MIN)
            lt_s, pbits, prbits, ml = res.value_sorted(view)
            fb = np.zeros(c_pad, np.int64)
            fb[:C] = np.asarray(batch.fallback)[:, 0]
            with _slab("rf_fused", c_pad, C):
                ret, rfj, wfj, padm = _slab_inputs(batch, 0, C, c_pad,
                                                   d_pad)
                e, cnt = _rf_fused(ret, rfj, wfj, padm, _put(fb),
                                   lt_s, pbits, prbits, ml, n_lt, n_addr)
                e, cnt = _pull(e), _pull(cnt)
            energy = e[:C]
            if grouped:
                # integer counts / A on the host: correctly rounded,
                # bit-identical to the oracle's bincount / A
                frac = cnt[:C, :d_max] / view.n_addr
            else:
                frac = _rf_ungrouped_host_fracs(batch, d_max)
            return energy, frac

        # refresh-aware: fixed-width pow2 slabs against the resident
        # arrays — one compiled shape per (slab, D, L, A) bucket
        slab = _slab_size(d_pad, res.L_pad, C, base.broadcast_itemsize)
        energy = np.empty(C)
        frac = np.empty((C, d_max))
        if grouped:
            res.check_int64(view, batch.ret_s, batch.pad)
            lt_a, reads_a, bits_a, seg = res.addr_sorted(view)
        else:
            lt_o, reads_o, bits_o = res.original(
                batch.lt_s, batch.reads, batch.bits)
            bits_host = np.asarray(batch.bits, _F64)
        kernel = "ra_grouped" if grouped else "ra_ungrouped"
        for lo in range(0, C, slab):
            hi = min(lo + slab, C)
            with _slab(kernel, slab, hi - lo):
                ret, rfj, wfj, padm = _slab_inputs(batch, lo, hi, slab,
                                                   d_pad)
                fix_idx, fix_cnt = map(_put, _refresh_fixes(
                    view, batch.ret_s[lo:hi], slab, d_pad, grouped))
                # out: pick counts [slab, D] when grouped, else each
                # lifetime's pick [slab, L]
                if grouped:
                    # padded rows the kernel skips
                    obs.count("slab_rows_skipped", slab - (hi - lo))
                    e, out = _ra_grouped(ret, rfj, wfj, padm, lt_a,
                                         reads_a, bits_a, seg, n_lt, n_addr,
                                         _put(np.int64(hi - lo)), fix_idx,
                                         fix_cnt, n_seg=res.A_pad)
                else:
                    e, out = _ra_ungrouped(ret, rfj, wfj, padm, lt_o,
                                           reads_o, bits_o, fix_idx,
                                           fix_cnt)
                e, out = _pull(e), _pull(out)
            energy[lo:hi] = e[:hi - lo]
            if grouped:
                frac[lo:hi] = out[:hi - lo, :d_max] / view.n_addr
            else:
                frac[lo:hi] = _host_weighted_fracs(
                    out[:hi - lo, :res.n_lt], bits_host, d_max)
        return energy, frac
