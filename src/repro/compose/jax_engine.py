"""Jitted JAX executor for the batched composition engine.

One fused kernel per policy family does everything the NumPy policy
kernels plus the engine's per-candidate Python loop do — the [C, D, L]
fit/argmin broadcast, Algorithm-1 refresh billing, the per-address
``segment_sum`` grouping, and the per-device energy/capacity
reductions — in a single jitted graph, so a whole candidate chunk
reduces to ``(energy_j [C], capacity_fraction [C, D])`` without ever
materializing per-candidate masks (``ff == i``) or capacity counts
(``np.mean(ad == i)``) in Python.

Selected as ``evaluate(..., engine="jax")`` (threaded through
``ProfileSession``, ``SweepRunner``, ``CampaignRunner`` and the
profile/sweep/campaign CLIs); the NumPy path stays the default and
keeps the bit-for-bit seed guarantee.

Numerical contract: everything runs in float64 under a scoped
``repro.compat.enable_x64`` (as ``repro.core.lifetime`` does for
int64), computing the *same* reductions as the NumPy kernels — only
the float summation order differs, so the two engines agree within
~1e-9 relative energy (``tests/test_jax_engine.py`` locks this
differentially across all policies and random grids).  Capacity
fractions (and hence bank quantization) ARE bit-identical across
engines: the knife-edge reductions (pick counts, bits-weighted sums)
are finished on the host with the oracle's exact arithmetic.  Energy
on ``engine="jax"`` is tolerance-equal, not bit-for-bit; use
``engine="numpy"`` (the differential oracle) where exact seed equality
matters.

Buffer protocol: the per-chunk [C, D] retention matrix is donated to
the jit (it is freshly built per chunk, never reused, and aliases the
same-shaped fraction output); the per-subpartition [L]/[A] arrays
(lifetimes, reads, bits, grouping) are shared across chunks.  Because
donation invalidates the input buffer the moment the call is traced,
dispatch is serialized on :data:`_DISPATCH_LOCK` — two
``SweepRunner(workers>1)`` threads racing into the same jit must not
interleave donate/execute (``tests/test_executor.py`` locks 4-thread
vs serial bit-for-bit).  First call per (C, D, L, A) shape pays jit
compilation; steady-state sweep shapes hit the trace cache (see the
jit-warmup note in docs/API.md).

This per-chunk path is kept as the differential yardstick (and for
callers holding a single ``PolicyBatch``); ``evaluate(...,
engine="jax")`` itself now routes whole batches through the fused
bucketed executor in :mod:`repro.compose.executor`, which reuses this
module's host-side reductions and the same dispatch lock.

Import contract: this module imports jax at module level and is
deliberately OUTSIDE every stdlib-only / jax-free import surface
(``repro check`` import-purity); it must only ever be imported lazily,
from inside :func:`repro.compose.engine.evaluate`.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import enable_x64
from repro.compose.policies import (BankQuantizedPolicy, PolicyBatch,
                                    RefreshAwarePolicy, RefreshFreePolicy)

_F64 = np.float64

# Serializes every jax dispatch (per-chunk and fused executor alike):
# the grouped kernels donate their [C, D] input buffer, and a racing
# thread re-dispatching into the same jit while another call is in
# flight could observe the donated (already invalidated) buffer.  The
# lock also guards the executor's device-residence memo.  NumPy-engine
# sweeps are unaffected — they never enter this module.
_DISPATCH_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# fused policy kernels
# ---------------------------------------------------------------------------
# Shapes: C candidates x D device slots x L lifetimes (x A addresses).
# Padded device slots carry ret = -inf / read = write = +inf exactly as
# the NumPy PolicyBatch does, so fits are never satisfied and energy
# argmins never pick them.

def _capacity_counts(ad: jnp.ndarray, n_dev: int) -> jnp.ndarray:
    """[C, A] per-address device picks -> [C, D] integer pick counts.

    Counts only — the ``count / A`` division happens on the host in
    :func:`run_chunk` so it is correctly rounded and bit-identical to
    the NumPy path's ``bincount / A`` (XLA strength-reduces an
    in-graph divide-by-constant into a reciprocal multiply, which is
    off by an ulp).
    """
    onehot = ad[:, :, None] == jnp.arange(n_dev)[None, None, :]
    return onehot.sum(axis=1).astype(jnp.float64)


@functools.partial(jax.jit, donate_argnums=(0,))
def _refresh_free_kernel(ret, read_fj, write_fj, fallback, pad,
                         lt, reads, bits, max_lt):
    """Seed fit semantics: first (cheapest) device whose retention
    covers the datum; capacity from each address's max lifetime."""
    fits = lt[None, None, :] <= ret[:, :, None]                 # [C, D, L]
    ff = jnp.where(fits.any(axis=1), jnp.argmax(fits, axis=1), fallback)
    rf = jnp.take_along_axis(read_fj, ff, axis=1)               # [C, L]
    wf = jnp.take_along_axis(write_fj, ff, axis=1)
    energy = (bits[None, :] * (wf + reads[None, :] * rf)).sum(axis=1)
    afits = max_lt[None, None, :] <= ret[:, :, None]            # [C, D, A]
    ad = jnp.where(afits.any(axis=1), jnp.argmax(afits, axis=1), fallback)
    _ = pad   # refresh-free never evaluates energy on padded slots
    return energy * 1e-15, _capacity_counts(ad, ret.shape[1])


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("n_addr",))
def _refresh_aware_kernel(ret, read_fj, write_fj, pad,
                          lt, reads, bits, seg, *, n_addr):
    """Algorithm-1 total-energy min with refresh billed as
    ``(ceil(T / t_ret) - 1) * (E_r + E_w) * B``; per-address capacity
    from the argmin of the address's summed lifetime energies.

    ``lt``/``reads``/``bits`` arrive pre-sorted by address (the host
    gathers through ``groups.order`` once per chunk), so the segment
    reduction runs straight off ``seg`` with no in-graph gather.  The
    per-address energy is decomposed into separable base terms
    (``write_fj * sum(bits)`` + ``read_fj * sum(reads * bits)``, two
    [L]-sized segment sums shared across devices) plus one [L, C, D]
    segment sum of the refresh term, the only part that is not
    separable in the device axis; total energy never materializes the
    [C, D, L] matrix at all — XLA fuses it into the min/sum reduce.
    """
    rb = reads * bits
    rw = read_fj + write_fj
    # lt / inf -> 0 refreshes; lt / -inf (pad) -> clamped 0, and the
    # resulting 0 * inf NaN is forced to +inf below, as in NumPy.
    refresh_e = (jnp.maximum(
        jnp.ceil(lt[None, None, :] / ret[:, :, None]) - 1.0, 0.0)
        * bits[None, None, :])                                  # [C, D, L]
    e = (write_fj[:, :, None] * bits[None, None, :]
         + read_fj[:, :, None] * rb[None, None, :]
         + rw[:, :, None] * refresh_e)
    e = jnp.where(pad[:, :, None], jnp.inf, e)
    # the energy billed per lifetime is the device minimum — argmin +
    # gather spelled as a min, so no [C, L] pick matrix is needed
    energy = e.min(axis=1).sum(axis=1) * 1e-15                  # [C]
    refresh_b = (jnp.maximum(
        jnp.ceil(lt[:, None, None] / ret[None]) - 1.0, 0.0)
        * bits[:, None, None])                                  # [L, C, D]
    ss = functools.partial(jax.ops.segment_sum, segment_ids=seg,
                           num_segments=n_addr,
                           indices_are_sorted=True)
    per_addr = (write_fj[None] * ss(bits)[:, None, None]
                + read_fj[None] * ss(rb)[:, None, None]
                + rw[None] * ss(refresh_b))                     # [A, C, D]
    per_addr = jnp.where(pad[None], jnp.inf, per_addr)
    ad = jnp.argmin(per_addr, axis=2).T                         # [C, A]
    return energy, _capacity_counts(ad, ret.shape[1])


@jax.jit
def _refresh_free_ungrouped(ret, read_fj, write_fj, fallback, pad,
                            lt, reads, bits):
    """raw=None fallback: returns the per-lifetime picks ``ff`` so the
    host can reduce them to bits-weighted capacity fractions with the
    oracle's exact masked sums (see :func:`_host_weighted_fracs`)."""
    fits = lt[None, None, :] <= ret[:, :, None]
    ff = jnp.where(fits.any(axis=1), jnp.argmax(fits, axis=1), fallback)
    rf = jnp.take_along_axis(read_fj, ff, axis=1)
    wf = jnp.take_along_axis(write_fj, ff, axis=1)
    energy = (bits[None, :] * (wf + reads[None, :] * rf)).sum(axis=1)
    _ = pad
    return energy * 1e-15, ff


@jax.jit
def _refresh_aware_ungrouped(ret, read_fj, write_fj, pad,
                             lt, reads, bits):
    retc = ret[:, :, None]
    refresh = jnp.maximum(jnp.ceil(lt[None, None, :] / retc) - 1.0, 0.0)
    rw = read_fj[:, :, None] + write_fj[:, :, None]
    e = bits[None, None, :] * (write_fj[:, :, None]
                               + reads[None, None, :] * read_fj[:, :, None]
                               + refresh * rw)
    e = jnp.where(pad[:, :, None], jnp.inf, e)
    ff = jnp.argmin(e, axis=1)
    e_sel = jnp.take_along_axis(e, ff[:, None, :], axis=1)[:, 0, :]
    energy = e_sel.sum(axis=1) * 1e-15
    return energy, ff


def _host_weighted_fracs(ff: np.ndarray, bits: np.ndarray,
                         d_max: int) -> np.ndarray:
    """Bits-weighted capacity fractions from per-lifetime picks, on the
    host — the same masked ``w[ff == i].sum()`` (same element order,
    same pairwise summation) as the NumPy oracle, so capacity stays
    bit-identical across engines.  An in-graph weighted reduce can land
    an ulp past 1.0 and flip a ``ceil`` bank count at quantization
    boundaries; energy is where the jax engine earns its keep, not this
    [C, D]-sized epilogue."""
    w = bits / bits.sum()
    frac = np.zeros((ff.shape[0], d_max))
    for c in range(ff.shape[0]):
        for i in range(d_max):
            frac[c, i] = w[ff[c] == i].sum()
    return frac


# ---------------------------------------------------------------------------
# the chunk executor (the engine's jax twin of its NumPy loop)
# ---------------------------------------------------------------------------

def _base_policy(pol):
    return pol.base if isinstance(pol, BankQuantizedPolicy) else pol


def supports(pol) -> bool:
    """Whether the jax engine has a fused kernel for this policy (the
    bank-quantized capacity post-pass runs on the host either way)."""
    return isinstance(_base_policy(pol),
                      (RefreshFreePolicy, RefreshAwarePolicy))


def _segment_ids(starts: np.ndarray, n: int) -> np.ndarray:
    """Segment id per sorted-lifetime position from segment starts."""
    seg = np.zeros(n, np.int32)
    seg[starts[1:]] = 1           # starts[0] == 0 stays segment 0
    return np.cumsum(seg, dtype=np.int32)


def run_chunk(pol, batch: PolicyBatch):
    """Evaluate one candidate chunk; returns ``(energy_j [C],
    capacity_fractions [C, D])`` as NumPy arrays (D = padded width;
    the engine slices each candidate's real device count)."""
    base = _base_policy(pol)
    if not supports(pol):
        raise ValueError(
            f"engine='jax' has no fused kernel for policy "
            f"{base.name!r}; use engine='numpy'")
    with _DISPATCH_LOCK, enable_x64():
        ret = jnp.asarray(batch.ret_s, _F64)
        read_fj = jnp.asarray(batch.read_fj, _F64)
        write_fj = jnp.asarray(batch.write_fj, _F64)
        pad = jnp.asarray(batch.pad)
        lt = jnp.asarray(batch.lt_s, _F64)
        reads = jnp.asarray(batch.reads, _F64)
        bits = jnp.asarray(batch.bits, _F64)
        n_addr = (len(batch.groups.max_lt_s)
                  if batch.groups is not None else 0)
        counts = False   # did the kernel return counts (vs fractions)?
        if isinstance(base, RefreshFreePolicy):
            fallback = jnp.asarray(batch.fallback)
            if batch.groups is not None:
                e, f = _refresh_free_kernel(
                    ret, read_fj, write_fj, fallback, pad, lt, reads,
                    bits, jnp.asarray(batch.groups.max_lt_s, _F64))
                counts = True
            else:
                e, f = _refresh_free_ungrouped(
                    ret, read_fj, write_fj, fallback, pad, lt, reads,
                    bits)
        else:
            if batch.groups is not None and len(batch.groups.starts):
                # pre-sort the lifetime axis by address on the host so
                # the kernel's segment reduction needs no in-graph
                # gather (the sort permutes, it never re-rounds)
                starts = np.asarray(batch.groups.starts)
                order = np.asarray(batch.groups.order)
                seg = jnp.asarray(
                    _segment_ids(starts, len(batch.lt_s)))
                lt_srt = jnp.asarray(
                    np.asarray(batch.lt_s)[order], _F64)
                reads_srt = jnp.asarray(
                    np.asarray(batch.reads)[order], _F64)
                bits_srt = jnp.asarray(
                    np.asarray(batch.bits)[order], _F64)
                e, f = _refresh_aware_kernel(
                    ret, read_fj, write_fj, pad, lt_srt, reads_srt,
                    bits_srt, seg, n_addr=n_addr)
                counts = True
            else:
                e, f = _refresh_aware_ungrouped(
                    ret, read_fj, write_fj, pad, lt, reads, bits)
        e, f = np.asarray(e), np.asarray(f)
        if counts:
            # grouped kernels return integer pick counts; the host
            # division is correctly rounded (bit-identical to the
            # NumPy oracle's bincount / A), unlike XLA's in-graph
            # divide-by-constant
            f = f / n_addr
        else:
            # ungrouped kernels return per-lifetime picks; the
            # weighted fractions are reduced on the host to match the
            # oracle bit-for-bit
            f = _host_weighted_fracs(f, np.asarray(batch.bits, _F64),
                                     batch.ret_s.shape[1])
        return e, f
