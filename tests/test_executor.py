"""Tests for the fused sweep executor (``repro.compose.executor``).

The locked contracts:
  - the fused bucketed batch path matches the NumPy oracle exactly on
    capacity fractions and to <=1e-9 relative on energy, for every
    policy x grouped/ungrouped combination — including trace/address/
    device/candidate sizes straddling the pow2 bucket boundaries, so
    masked padding provably never leaks into results;
  - a second workload whose padded shapes land in the same buckets
    triggers zero new jit compiles (``compile_stats`` telemetry), and
    so does a refresh-aware batch whose real rows fill less or more of
    the slab;
  - the refresh-aware grouped slab computes only its real rows: padded
    rows come back as the skip branch's zeros, counted as
    ``slab_rows_skipped``;
  - the device-resident trace view is built once per (stats, raw)
    pair and reused across evaluate() calls;
  - a 4-thread ``SweepRunner`` on the jax engine is bit-for-bit equal
    to the serial run (dispatch lock);
  - a process-scheduler campaign with a shared persistent compile
    cache reports warm compiles in fresh worker processes.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.compat import enable_x64
from repro.compose import compile_stats
from repro.compose import engine as compose_engine
from repro.compose import executor
from repro.compose.engine import evaluate
from repro.core.frontend import SubpartitionStats
from repro.runtime import obs
from repro.sweep import (SRAM_ONLY_ID, DeviceGrid, FamilyGrid, SweepRunner,
                         pareto_frontier)

jax = pytest.importorskip("jax")

CLOCK = 1.0e9


@dataclasses.dataclass
class _Raw:
    lifetime_cycles: np.ndarray
    addr: np.ndarray
    valid: np.ndarray


def _synth(n=4000, n_addr=311, seed=0, bits=256):
    rng = np.random.RandomState(seed)
    lt_cycles = np.maximum(
        rng.lognormal(mean=6.5, sigma=2.0, size=n), 1.0).astype(np.int64)
    addr = rng.randint(0, n_addr, n).astype(np.int64)
    reads = rng.poisson(3.0, n).astype(np.float64)
    dur = float(lt_cycles.max()) / CLOCK
    st = SubpartitionStats(
        name="syn", n_reads=int(reads.sum()), n_writes=n,
        n_unique_addrs=len(np.unique(addr)), duration_s=dur,
        write_freq_hz=n / dur, read_freq_hz=float(reads.sum()) / dur,
        lifetimes_s=lt_cycles / CLOCK,
        lifetime_bits=np.full(n, bits, np.float64),
        accesses_per_lifetime=reads + 1.0, orphan_fraction=0.0,
        block_bits=bits)
    return st, _Raw(lt_cycles, addr, np.ones(n, bool))


def _asym_devices():
    from repro.devices import get_device_family
    return (get_device_family("sram-gaincell-default").build()
            + get_device_family("sot-mram").build()[1:])


POLICIES = ("refresh-free", "refresh-aware",
            "bank-quantized:refresh-free@8")


def _assert_matches_oracle(cands, st, raw, policy):
    ref = evaluate(cands, st, raw=raw, clock_hz=CLOCK, policy=policy)
    got = evaluate(cands, st, raw=raw, clock_hz=CLOCK, policy=policy,
                   engine="jax")
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        assert np.array_equal(a.capacity_fractions, b.capacity_fractions)
        if a.energy_j > 0:
            assert abs(a.energy_j - b.energy_j) <= 1e-9 * a.energy_j
        else:
            assert b.energy_j == a.energy_j


# ---------------------------------------------------------------------------
# equivalence: every policy x grouped/ungrouped path
# ---------------------------------------------------------------------------

def test_fused_batch_matches_numpy_oracle_all_paths():
    st, raw = _synth(n=3000, n_addr=300)
    grid = DeviceGrid(mixes=(0.0, 0.5, 1.0),
                      retention_scales=(0.5, 1.0, 2.0), per_mix=True)
    cands = [c.devices for c in grid.candidates()]
    for policy in POLICIES:
        for use_raw in (raw, None):
            _assert_matches_oracle(cands, st, raw=use_raw, policy=policy)


def _ra_grid_cands():
    """18 two-device candidates (the grid's SRAM-only anchor left out)."""
    grid = DeviceGrid(mixes=(0.0, 0.5, 1.0),
                      retention_scales=(0.5, 1.0, 2.0),
                      energy_scales=(0.7, 1.4), per_mix=True)
    return [c.devices for c in grid.candidates()][1:]


# one partial slab, one full, one full plus a row, two full plus a row
@pytest.mark.parametrize("n_cands", [1, 3, 8, 9, 17])
@pytest.mark.parametrize("policy",
                         ["refresh-aware", "bank-quantized:refresh-aware@8"])
def test_refresh_aware_slab_rows_match_numpy_oracle(monkeypatch, policy,
                                                    n_cands):
    # a budget that holds no more than the floor: 8-row slabs, as at the
    # chip's trace sizes, so a batch's last slab is partly padding
    monkeypatch.setattr(executor, "_SLAB_BYTES", 1)
    st, raw = _synth(n=3000, n_addr=300, seed=n_cands)
    _assert_matches_oracle(_ra_grid_cands()[:n_cands], st, raw=raw,
                           policy=policy)


def _ra_slab_inputs(rng, rows=8, d=2, n=2048, n_seg=256):
    ret = rng.uniform(1e2, 1e4, (rows, d))
    ret[:, 0] = np.inf                                  # SRAM
    read_fj = rng.uniform(1.0, 5.0, (rows, d))
    write_fj = rng.uniform(1.0, 5.0, (rows, d))
    pad = np.zeros((rows, d), bool)
    lt = rng.uniform(1.0, 1e4, n)
    reads = rng.poisson(3.0, n).astype(np.float64)
    bits = np.full(n, 256.0)
    seg = np.sort(rng.randint(0, n_seg - 6, n)).astype(np.int32)
    return ret, read_fj, write_fj, pad, lt, reads, bits, seg


def _multiples_of_a_microsecond(seed=3):
    """A trace whose every fourth lifetime lasts a whole number of
    microseconds: on the paper's 1-us Si gain cell its quotient T /
    t_ret is an integer, or a float64 ulp off one."""
    st, raw = _synth(n=3000, n_addr=300, seed=seed)
    lt = raw.lifetime_cycles.copy()
    lt[::4] = 1000 * np.random.RandomState(seed).randint(1, 40, lt[::4].size)
    return (dataclasses.replace(st, lifetimes_s=lt / CLOCK),
            dataclasses.replace(raw, lifetime_cycles=lt))


def _inexact_device_division(monkeypatch, rel):
    """The kernels' refresh counts from a division off by ``rel``
    (relative), as an emulated float64 division on a TPU may be; the
    host's fix lists still apply."""
    import jax.numpy as jnp

    def counts(lt, ret_r, fix_idx, fix_cnt):
        n = jnp.maximum(jnp.ceil(lt[None, :] / ret_r[:, None] * (1.0 + rel))
                        - 1.0, 0.0)
        dev = jnp.arange(ret_r.shape[0])[:, None]
        return n.at[dev, fix_idx].set(fix_cnt, mode="drop")
    monkeypatch.setattr(executor, "_refresh_counts", counts)
    jax.clear_caches()          # retrace the kernels with it


@pytest.mark.parametrize("use_raw", [True, False])
@pytest.mark.parametrize("policy",
                         ["refresh-aware", "bank-quantized:refresh-aware@8"])
def test_refresh_counts_exact_under_an_inexact_device_division(
        monkeypatch, use_raw, policy):
    from repro.core.devices import DEFAULT_DEVICES
    st, raw = _multiples_of_a_microsecond()
    raw = raw if use_raw else None
    cands = [tuple(DEFAULT_DEVICES)]
    _inexact_device_division(monkeypatch, 1e-13)
    try:
        _assert_matches_oracle(cands, st, raw=raw, policy=policy)
        # without the host's fix lists that division bills other counts
        monkeypatch.setattr(executor, "_near_integer_ranks",
                            lambda lt, r: np.zeros(0, np.int64))
        (ref,) = evaluate(cands, st, raw=raw, clock_hz=CLOCK, policy=policy)
        (got,) = evaluate(cands, st, raw=raw, clock_hz=CLOCK, policy=policy,
                          engine="jax")
        assert abs(got.energy_j - ref.energy_j) > 1e-6 * ref.energy_j
    finally:
        jax.clear_caches()


def test_near_integer_ranks():
    lt = np.sort(np.array([0.0, 0.5e-6, 1e-6, 1.5e-6, 2e-6,
                           np.nextafter(3e-6, 1.0), 3.01e-6, 7e-6]))
    assert executor._near_integer_ranks(lt, 1e-6).tolist() == [2, 4, 5, 7]
    assert executor._near_integer_ranks(lt, np.inf).size == 0
    assert executor._near_integer_ranks(lt, -np.inf).size == 0
    # a retention far below the lifetimes, each lifetime tested directly:
    # every one but the zero is a whole number of nanoseconds
    assert executor._near_integer_ranks(lt, 1e-9).tolist() == \
        [1, 2, 3, 4, 5, 6, 7]
    assert executor._near_integer_ranks(lt, 0.7e-6).tolist() == [7]


def _no_fixes(n, rows=8, d=2):
    """Empty refresh-count fix lists: every entry points past the
    lifetimes."""
    return (np.full((rows, d, executor._FIX_MIN), n, np.int64),
            np.zeros((rows, d, executor._FIX_MIN)))


def test_ra_grouped_skips_rows_past_the_real_count():
    args = _ra_slab_inputs(np.random.RandomState(5))
    n_seg = 256
    with enable_x64():
        def call(n_real):
            e, cnt = executor._ra_grouped(
                *args, np.int64(len(args[4])), np.int64(n_seg - 6),
                np.int64(n_real), *_no_fixes(len(args[4])), n_seg=n_seg)
            return np.asarray(e), np.asarray(cnt)
        e1, c1 = call(1)
        e8, c8 = call(8)
    # computed, every row has energy and picks; skipped, it is all zeros
    assert (e8 > 0).all() and (c8.sum(axis=1) == n_seg - 6).all()
    assert (e1[1:] == 0).all() and (c1[1:] == 0).all()
    assert e1[0] == e8[0]
    assert np.array_equal(c1[0], c8[0])


# ---------------------------------------------------------------------------
# per-address sums: int64 prefix scans against the segment_sum rows
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_seg",))
def _segment_sum_rows(ret, read_fj, write_fj, pad, lt, reads, bits, seg,
                      n_addr, n_real, fix_idx, fix_cnt, *, n_seg):
    """The refresh-aware slab kernel as it was before the int64 scans,
    frozen: float64 ``segment_sum`` per-address sums, one device row at
    a time, padded addresses masked out of the counts."""
    import jax.numpy as jnp
    rb = reads * bits
    ss = functools.partial(jax.ops.segment_sum, segment_ids=seg,
                           num_segments=n_seg, indices_are_sorted=True)
    ssb = ss(bits)
    ssrb = ss(rb)
    amask = jnp.arange(n_seg) < n_addr
    dev_ids = jnp.arange(ret.shape[1])

    def one(args):
        ret_r, rf_r, wf_r, pad_r, fi_r, fc_r = args
        refresh_e = (executor._refresh_counts(lt, ret_r, fi_r, fc_r)
                     * bits[None, :])
        rw = rf_r + wf_r
        e = (wf_r[:, None] * bits[None, :]
             + rf_r[:, None] * rb[None, :]
             + rw[:, None] * refresh_e)
        e = jnp.where(pad_r[:, None], jnp.inf, e)
        energy = e.min(axis=0).sum() * 1e-15
        per_addr = jnp.stack([
            wf_r[d] * ssb + rf_r[d] * ssrb + rw[d] * ss(refresh_e[d])
            for d in range(ret_r.shape[0])])
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


def _grouped_slab(seed, n, n_addr, l_pad, a_pad, d, pad_slots, gaps,
                  rows=8):
    """One refresh-aware slab's kernel inputs: ``n`` real lifetimes over
    ``n_addr`` address segments, zero-padded to ``l_pad`` — dense (the
    first and the last address one lifetime each) or, with ``gaps``,
    drawn at random with the first address empty; an SRAM slot (+inf
    retention) first, ``pad_slots`` padded device slots last; and
    non-empty fix lists with counts the division would not give."""
    rng = np.random.RandomState(seed)
    if gaps:
        seg = np.sort(rng.randint(1, n_addr, n)).astype(np.int32)
    else:
        seg = np.sort(np.concatenate([np.arange(n_addr), rng.randint(
            1, n_addr - 1, n - n_addr)])).astype(np.int32)
    lt = rng.lognormal(0.0, 2.0, n)
    lt[::5] = rng.randint(1, 50, lt[::5].size) * 0.25   # whole quotients
    reads = rng.poisson(3.0, n).astype(np.float64)
    bits = rng.choice([64.0, 256.0, 1024.0], n)
    ret = rng.choice([0.25, 0.5, 1.0, 3.0], (rows, d)) \
        * rng.uniform(0.5, 2.0, (rows, d))
    ret[:, 0] = np.inf
    read_fj = rng.uniform(1.0, 5.0, (rows, d))
    write_fj = rng.uniform(1.0, 5.0, (rows, d))
    pad = np.zeros((rows, d), bool)
    if pad_slots:
        pad[:, -pad_slots:] = True
        ret[pad], read_fj[pad], write_fj[pad] = -np.inf, np.inf, np.inf
    fix_idx = np.full((rows, d, executor._FIX_MIN), l_pad, np.int64)
    fix_cnt = np.zeros((rows, d, executor._FIX_MIN))
    k = 9
    fix_idx[:, :, :k] = rng.randint(0, n, (rows, d, k))
    fix_cnt[:, :, :k] = rng.randint(0, 40, (rows, d, k))
    z = np.zeros(l_pad)
    lt_p, reads_p, bits_p = (np.concatenate([a, z[n:]])
                             for a in (lt, reads, bits))
    seg_p = np.concatenate([seg, np.zeros(l_pad - n, np.int32)])
    return (ret, read_fj, write_fj, pad, lt_p, reads_p, bits_p, seg_p,
            np.int64(n), np.int64(n_addr), fix_idx, fix_cnt, a_pad)


# (n, n_addr, l_pad, a_pad, d, pad_slots, gaps, n_real)
_SLABS = {
    "padded": (1500, 200, 2048, 256, 4, 1, False, 8),
    "unpadded": (2048, 256, 2048, 256, 2, 0, False, 8),
    "few_real_rows": (3000, 700, 4096, 1024, 4, 2, False, 3),
    "empty_segments": (1500, 400, 2048, 512, 4, 1, True, 8),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", sorted(_SLABS))
def test_ra_grouped_scans_match_the_segment_sum_rows(shape, seed):
    n, n_addr, l_pad, a_pad, d, pad_slots, gaps, n_real = _SLABS[shape]
    (ret, rf, wf, pad, lt, reads, bits, seg, n_lt, n_a, fi, fc,
     a_pad) = _grouped_slab(seed, n, n_addr, l_pad, a_pad, d, pad_slots,
                            gaps)
    assert not gaps or (seg[:n].min() > 0
                        and len(np.unique(seg[:n])) < n_addr - 1)
    with enable_x64():
        e, cnt = executor._ra_grouped(
            ret, rf, wf, pad, lt, reads, bits, seg, n_lt, n_a,
            np.int64(n_real), fi, fc, n_seg=a_pad)
        e0, cnt0 = _segment_sum_rows(
            ret, rf, wf, pad, lt, reads, bits, seg, n_a, np.int64(n_real),
            fi, fc, n_seg=a_pad)
        e, cnt, e0, cnt0 = map(np.asarray, (e, cnt, e0, cnt0))
    assert np.array_equal(e, e0)
    assert np.array_equal(cnt, cnt0)
    # every real row picks a device for every address, none a padded one
    assert (cnt[:n_real].sum(axis=1) == n_addr).all()
    assert (cnt[:n_real, d - pad_slots:] == 0).all()
    assert (cnt[n_real:] == 0).all()


@pytest.mark.parametrize("n", [16, 2048, 1 << 15])
def test_prefix_sums_exact_to_the_int64_limit(n):
    # values of every limb's width, the total just under 2**63; 2**15
    # values take two levels of blocks
    rng = np.random.RandomState(n)
    x = rng.randint(1 << 61, 1 << 62, (2, n), dtype=np.int64) // n * 2
    x[:, ::7] = 0
    x[:, 1::5] = (1 << 25) - 1
    with enable_x64():
        got = np.asarray(jax.jit(executor._prefix_sums)(x))
    assert np.array_equal(got, np.cumsum(x, axis=1))
    assert got[:, -1].min() > 1 << 61


def test_check_int64():
    st, raw = _synth(n=2000, n_addr=150, seed=4)
    view = compose_engine.sorted_trace_view(st, raw, CLOCK)
    res = executor._TraceResidence(view)
    ret = np.array([[np.inf, 1e-6, -np.inf]])
    pad = np.array([[False, False, True]])
    res.check_int64(view, ret, pad)
    # a padded slot's retention is never read
    res.check_int64(view, np.array([[np.inf, 1e-6, 0.0]]), pad)
    # refresh counts past 2**62 / (lifetimes x bits); a zero retention
    for bad in (1e-20, 0.0, np.nan):
        with pytest.raises(ValueError):
            res.check_int64(view, np.array([[np.inf, bad, 1.0]]), pad)
    frac = dataclasses.replace(st, lifetime_bits=st.lifetime_bits + 0.5)
    view = compose_engine.sorted_trace_view(frac, raw, CLOCK)
    with pytest.raises(ValueError, match="integer"):
        executor._TraceResidence(view).check_int64(view, ret, pad)


def test_non_integer_bits_are_refused():
    st, raw = _synth(n=2500, n_addr=200, seed=9)
    st = dataclasses.replace(st, lifetime_bits=np.random.RandomState(
        9).uniform(100.0, 300.0, len(st.lifetime_bits)))
    cands = _ra_grid_cands()[:9]
    # the oracle takes them; the grouped kernel's int64 sums cannot
    assert evaluate(cands, st, raw=raw, clock_hz=CLOCK,
                    policy="refresh-aware")
    with pytest.raises(ValueError, match="integer"):
        evaluate(cands, st, raw=raw, clock_hz=CLOCK,
                 policy="refresh-aware", engine="jax")


def test_slab_rows_skipped_counts_the_padded_rows(monkeypatch):
    monkeypatch.setattr(executor, "_SLAB_BYTES", 1)     # 8-row slabs
    st, raw = _synth(n=2500, n_addr=200, seed=8)
    with obs.span("mark"):
        pass
    mark = obs.snapshot()["spans"][-1]["id"]
    for policy in ("refresh-free", "refresh-aware"):
        evaluate(_ra_grid_cands()[:17], st, raw=raw, clock_hz=CLOCK,
                 policy=policy, engine="jax")
    slabs = [s for s in obs.snapshot()["spans"]
             if s["id"] > mark and s["name"] == "executor.slab"]
    assert [s["attrs"]["kernel"] for s in slabs] == \
        ["rf_fused"] + ["ra_grouped"] * 3
    assert "slab_rows_skipped" not in slabs[0]["counts"]
    ra = [s["counts"] for s in slabs[1:]]
    for c in ra:
        assert c["slab_rows_skipped"] == c["slab_rows"] - c["slab_real_rows"]
    assert [c["slab_rows_skipped"] for c in ra] == [0, 0, 7]


# ---------------------------------------------------------------------------
# shape buckets: second workload in the same bucket -> zero new compiles
# ---------------------------------------------------------------------------

def test_same_bucket_workload_triggers_zero_new_compiles(monkeypatch):
    # the refresh-aware slab held at its 8-row floor, as the broadcast
    # budget holds it at the chip's trace sizes
    monkeypatch.setattr(executor, "_SLAB_BYTES", 1)
    # workload A: n=3000 -> L bucket 4096, n_addr=300 -> A bucket 512
    st_a, raw_a = _synth(n=3000, n_addr=300, seed=0)
    grid_a = DeviceGrid(mixes=(0.0, 0.5, 1.0),
                        retention_scales=(0.5, 2.0), per_mix=True)
    cands_a = [c.devices for c in grid_a.candidates()]  # 7 -> c_pad 8
    for policy in POLICIES:
        for use_raw in (raw_a, None):
            evaluate(cands_a, st_a, raw=use_raw, clock_hz=CLOCK,
                     policy=policy, engine="jax")
    entries = compile_stats()["jit_entries"]
    assert entries > 0

    # workload B: different trace (n=3500 -> 4096, n_addr=280 -> 512),
    # different candidate count (5 -> c_pad 8) and a 1-device anchor
    # (d_pad still 2) — every padded shape lands in workload A's bucket
    st_b, raw_b = _synth(n=3500, n_addr=280, seed=7)
    grid_b = DeviceGrid(mixes=(0.25, 0.75),
                        retention_scales=(0.7, 1.3), per_mix=True)
    cands_b = [c.devices for c in grid_b.candidates()]
    assert len(cands_b) != len(cands_a)
    for policy in POLICIES:
        for use_raw in (raw_b, None):
            evaluate(cands_b, st_b, raw=use_raw, clock_hz=CLOCK,
                     policy=policy, engine="jax")
    assert compile_stats()["jit_entries"] == entries

    # one real row of an 8-row slab, then 16 rows in two full slabs:
    # the real-row count is traced, so both run workload A's executable
    widest = [c for c in cands_a if len(c) == 2]
    for cands in (widest[:1], (widest * 3)[:16]):
        evaluate(cands, st_b, raw=raw_b, clock_hz=CLOCK,
                 policy="refresh-aware", engine="jax")
    assert compile_stats()["jit_entries"] == entries


# ---------------------------------------------------------------------------
# device-resident trace view: one build + one host sort per (stats, raw)
# ---------------------------------------------------------------------------

def test_trace_view_built_once_per_stats_raw_pair(monkeypatch):
    st, raw = _synth(n=2500, n_addr=200, seed=3)
    calls = {"n": 0}
    real = compose_engine._build_trace_view

    def spy(stats, raw_, clock_hz):
        calls["n"] += 1
        return real(stats, raw_, clock_hz)

    monkeypatch.setattr(compose_engine, "_build_trace_view", spy)
    grid = DeviceGrid(mixes=(0.0, 1.0), retention_scales=(1.0,),
                      per_mix=False)
    cands = [c.devices for c in grid.candidates()]
    # two policies, two grids, one (stats, raw) pair -> one view build
    evaluate(cands, st, raw=raw, clock_hz=CLOCK,
             policy="refresh-free", engine="jax")
    evaluate(cands[:2], st, raw=raw, clock_hz=CLOCK,
             policy="refresh-aware", engine="jax")
    assert calls["n"] == 1
    # a different trace is a different residence
    st2, raw2 = _synth(n=2500, n_addr=200, seed=4)
    evaluate(cands, st2, raw=raw2, clock_hz=CLOCK,
             policy="refresh-free", engine="jax")
    assert calls["n"] == 2


# ---------------------------------------------------------------------------
# thread-safety: 4-thread sweep == serial, bit for bit
# ---------------------------------------------------------------------------

class _FakeSession:
    """Duck-types the slice of ProfileSession that run_session uses."""

    def __init__(self, parts):
        self._stats = parts
        self._clock_hz = CLOCK

    def _require_analyzed(self):
        return None


def test_threaded_jax_sweep_is_bit_identical_to_serial():
    parts = {}
    for i, (n, n_addr) in enumerate(
            [(2000, 150), (2600, 220), (1800, 90), (3100, 310)]):
        st, raw = _synth(n=n, n_addr=n_addr, seed=10 + i)
        parts[f"sub{i}"] = (st, raw)
    grid = DeviceGrid(mixes=(0.0, 1.0), retention_scales=(0.5, 2.0),
                      per_mix=True)
    serial = SweepRunner(grid, workers=1, engine="jax").run_session(
        _FakeSession(parts))
    threaded = SweepRunner(grid, workers=4, engine="jax").run_session(
        _FakeSession(parts))
    assert len(serial) == len(threaded) == len(grid) * 4
    for ps, pt in zip(serial.points, threaded.points):
        assert (ps.candidate, ps.subpartition) == (pt.candidate,
                                                   pt.subpartition)
        assert ps.composition.energy_j == pt.composition.energy_j
        assert np.array_equal(ps.composition.capacity_fractions,
                              pt.composition.capacity_fractions)


# ---------------------------------------------------------------------------
# padding property: bucket boundaries, masked tails, asymmetric devices
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_padding_never_leaks_across_bucket_boundaries():
    asym = _asym_devices()
    sram = asym[0]
    # candidate lists straddling the c_pad=8 boundary (7 / 9 entries)
    base_cands = [tuple(asym), tuple(asym[:2]), (sram,),
                  tuple(asym[:3]), tuple(reversed(asym)),
                  tuple(asym[1:]) + (sram,), tuple(asym[:2][::-1])]
    nine_cands = base_cands + [tuple(asym[2:]) + (sram,), (sram, asym[1])]
    # trace/address sizes just below / at / above the pow2 buckets,
    # plus a tiny trace that is almost entirely masked tail
    shapes = [(2047, 255), (2049, 257), (17, 3)]
    for (n, n_addr), cands in zip(shapes,
                                  [base_cands, nine_cands, base_cands]):
        st, raw = _synth(n=n, n_addr=n_addr, seed=n)
        for policy in ("refresh-free", "refresh-aware"):
            for use_raw in (raw, None):
                _assert_matches_oracle(cands, st, raw=use_raw,
                                       policy=policy)


@pytest.mark.slow
def test_pareto_anchor_survives_padded_family_batch():
    st, raw = _synth(n=2300, n_addr=180, seed=21)
    grid = FamilyGrid("sot-mram", axes={"delta": (40.0, 55.0, 70.0)})
    frontiers = []
    for eng in ("numpy", "jax"):
        pts = SweepRunner(grid, engine=eng).run_stats(
            st, raw, clock_hz=CLOCK)
        fr = pareto_frontier(pts)
        assert fr.anchor is not None
        assert fr.anchor.candidate == SRAM_ONLY_ID
        assert fr.anchor.composition.area_vs_sram == 1.0
        frontiers.append(fr)
    ref, got = frontiers
    assert [p.candidate for p in got.points] == [p.candidate
                                                 for p in ref.points]
    for a, b in zip(ref.points, got.points):
        assert np.array_equal(a.composition.capacity_fractions,
                              b.composition.capacity_fractions)


# ---------------------------------------------------------------------------
# campaign: shared persistent cache -> warm compiles in fresh workers
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_process_campaign_workers_share_persistent_cache(tmp_path):
    from repro.launch.campaign import CampaignRunner

    def campaign(store):
        return CampaignRunner(
            "polybench-2mm", ("systolic",), jobs=1,
            cache_dir=str(tmp_path / store),
            params={"polybench-2mm": {"ni": 24, "nj": 20, "nk": 16,
                                      "nl": 28}},
            backend_cfg={"systolic": {"rows": 16, "cols": 16}},
            sweep_axes={"mixes": (0.0, 1.0), "retention_scales": (1.0,),
                        "per_mix": False},
            engine="jax", scheduler="process", lease_ttl_s=30.0,
            compile_cache=str(tmp_path / "jax-cache")).run()

    cold = campaign("store-a")
    assert cold.executed == 1 and cold.failed == 0
    (row,) = cold.aggregate["jobs"]
    tele = row["compile_telemetry"]
    assert tele["new_compiles"] > 0
    assert tele["persistent_cache_misses"] > 0
    assert tele["cache_dir"] == str(tmp_path / "jax-cache")
    dev = jax.devices()[0]      # the worker ran on this host's backend
    assert (tele["platform"], tele["device_kind"]) == (dev.platform,
                                                       dev.device_kind)

    # a second campaign at a fresh artifact store re-executes the job
    # in a brand-new worker process; every compile must come out of the
    # shared persistent cache
    warm = campaign("store-b")
    assert warm.executed == 1 and warm.failed == 0
    (row,) = warm.aggregate["jobs"]
    tele = row["compile_telemetry"]
    assert tele["persistent_cache_hits"] > 0
    assert tele["persistent_cache_misses"] == 0
