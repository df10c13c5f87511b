#!/usr/bin/env python3
"""Smoke run of the profiler's device path on one TPU chip.

    python3 chip_smoke.py

Drives the main path once, in one process, through the entry points a
user calls, on the registered ``chatglm3_6b`` workload at its default
spec (published widths: d_model 4096, 32 heads, 2 KV heads, d_ff 13696;
seq 128, 2 layers, line sample 8):

1. device   - refuses to run unless jax's devices are TPUs;
2. profile  - ``ProfileSession("gpu")``, the call
              ``python -m repro profile --arch chatglm3_6b --backend gpu``
              makes (set-parallel cache simulator scan);
3. analyze  - lifetime extraction and subpartition stats, L1 and L2;
4. compose  - ``engine="jax"`` under three policies, each held to the
              NumPy engine (capacity fractions bit-identical, energy
              within 1e-9 relative);
5. sweep    - the 257-candidate ``sot-mram`` ``FamilyGrid``, jax engine
              against the NumPy engine, Pareto frontiers included;
6. oracles  - the set-parallel cache simulator against the scalar scan
              on a stream prefix that evicts in every L1 set, and the
              lifetime extraction against the NumPy streaming fold
              (``TraceAccumulator``) on the same trace fed in chunks;
7. placement - the arrays the jitted stages returned live on the TPU.

At the end it prints, per program span name, the spans' count and
their total and self seconds (``repro.runtime.obs``; compiles fall
inside the spans that trigger them), and every counter's total.  Any
failure raises, and the exit code is non-zero.  The last line of
standard output is a JSON object naming the device, printed only when
every phase passed.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import compile_cache, obs  # noqa: E402

ARCH = "chatglm3_6b"
SEQ = 128                       # the workload's default spec
POLICIES = ("refresh-free", "refresh-aware",
            "bank-quantized:refresh-aware@8")
ENERGY_RTOL = 1e-9              # docs/API.md "Accelerated engine"
ORACLE_PREFIX = 1 << 17         # cachesim oracle: events of the L1 stream
CHUNK_EVENTS = 1 << 20          # lifetime oracle: streaming chunk size


class Spy:
    """Wraps a jitted stage to record the devices its outputs live on."""

    def __init__(self, module, attr, required=True):
        self.fn = getattr(module, attr)
        self.name = f"{module.__name__}.{attr}"
        self.required = required
        self.calls = 0
        self.platforms = set()
        setattr(module, attr, self)

    def __call__(self, *args, **kwargs):
        out = self.fn(*args, **kwargs)
        self.calls += 1
        for leaf in jax.tree.leaves(out):
            self.platforms |= {d.platform for d in leaf.devices()}
        return out

    def __getattr__(self, attr):        # _cache_size() etc.
        return getattr(self.fn, attr)


def device_phase():
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax found platform "
            f"{d.platform!r} ({d.device_kind}, {len(devs)} device(s))")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def profile_phase():
    from repro.core import ProfileSession
    from repro.launch.profile import build_workload
    workload, cfg = build_workload(ARCH, "gpu", seq=SEQ, smoke=True)
    session = ProfileSession("gpu")
    session.profile(workload, **cfg)
    tr = session.trace
    jax.block_until_ready(tr.time_cycles)
    sub = np.asarray(tr.subpartition)
    print(f"profile: {ARCH} events={len(sub)} "
          f"(L1 {int((sub == 0).sum())}, L2 {int((sub == 1).sum())}), "
          f"sample={cfg.get('sample')}", flush=True)
    return session, workload, cfg


def analyze_phase(session):
    session.analyze()
    counts = {}
    for name in ("L1", "L2"):
        st, raw = session.subpartition_stats(name)
        jax.block_until_ready(raw.lifetime_cycles)
        counts[name] = len(st.lifetimes_s)
    print(f"analyze: lifetimes {counts}", flush=True)
    return counts


def check(ok, what):
    """A failed comparison ends the run (an explicit raise, so that it
    also holds under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _check_comps_equal(label, got, ref):
    check(np.array_equal(got.capacity_fractions, ref.capacity_fractions),
          f"{label}: capacity fractions {got.capacity_fractions} vs "
          f"{ref.capacity_fractions}")
    check(got.quantization == ref.quantization,
          f"{label}: quantization differs")
    rel = abs(got.energy_j - ref.energy_j) / abs(ref.energy_j)
    check(rel <= ENERGY_RTOL, f"{label}: energy rel diff {rel:.3e}")
    return rel


def compose_phase(session):
    worst = 0.0
    for policy in POLICIES:
        got = {}
        for engine in ("jax", "numpy"):
            session.compose(policy=policy, engine=engine)
            got[engine] = {n: session.composition(n) for n in ("L1", "L2")}
        for n in ("L1", "L2"):
            rel = _check_comps_equal(f"compose {policy} {n}",
                                     got["jax"][n], got["numpy"][n])
            worst = max(worst, rel)
            print(f"compose {policy} {n}: jax == numpy "
                  f"(energy rel diff {rel:.3e}) "
                  f"{got['jax'][n].summary()}", flush=True)
    return worst


def sweep_phase(session):
    from repro.sweep import FamilyGrid
    grid = FamilyGrid("sot-mram",
                      axes={"delta": tuple(np.linspace(40.0, 80.0, 256))})
    res = {e: session.sweep(grid, engine=e, attach=False)
           for e in ("jax", "numpy")}
    pj, pn = res["jax"].points, res["numpy"].points
    check(len(pj) == len(pn) == 2 * len(grid),
          f"sweep points {len(pj)} (jax) vs {len(pn)} (numpy)")
    worst = 0.0
    for a, b in zip(pj, pn):
        check((a.candidate, a.subpartition) == (b.candidate, b.subpartition),
              f"sweep point order {a.candidate} vs {b.candidate}")
        worst = max(worst, _check_comps_equal(
            f"sweep {a.candidate} {a.subpartition}", a.composition,
            b.composition))
    fj, fn = res["jax"].frontiers(), res["numpy"].frontiers()
    check(fj.keys() == fn.keys(), "frontier keys differ")
    for key in fj:
        cj = [p.candidate for p in fj[key].points]
        cn = [p.candidate for p in fn[key].points]
        check(cj == cn, f"frontier {key}: {cj} vs {cn}")
    print(f"sweep: {len(grid)} candidates x 2 subpartitions, jax == numpy "
          f"(worst energy rel diff {worst:.3e}), frontier sizes "
          f"{ {str(k): len(v.points) for k, v in fj.items()} }",
          flush=True)
    return worst


def cachesim_oracle_phase(workload, cfg):
    from repro.backends import cachesim
    from repro.backends.opstream import StreamBuilder
    sb = StreamBuilder(sample=cfg["sample"])
    workload(sb)
    _, byte_addr, is_write = sb.finish()
    l1 = cachesim.HierarchyConfig().l1
    lines = np.asarray(byte_addr, np.int64)[:ORACLE_PREFIX] // l1.line_bytes
    w = np.asarray(is_write, bool)[:ORACLE_PREFIX]
    par = cachesim._simulate_cache_set_parallel(
        lines, w, l1.n_sets, l1.ways, True)
    ref = cachesim._simulate_cache(lines, w, l1.n_sets, l1.ways, True)
    for label, a, b in zip(("hit", "fill", "evict_addr", "evict_dirty"),
                           par, ref):
        check(np.array_equal(a, b), f"cachesim oracle: {label} differs")
    evicting_sets = np.unique(lines[ref[2] >= 0] % l1.n_sets).size
    check(evicting_sets == l1.n_sets,
          f"prefix evicts in only {evicting_sets}/{l1.n_sets} L1 sets")
    print(f"oracle cachesim: set-parallel == scalar on {len(lines)} L1 "
          f"events ({int(ref[0].sum())} hits, {int((ref[2] >= 0).sum())} "
          f"evictions, all {l1.n_sets} sets evict)", flush=True)


def lifetime_oracle_phase(session):
    from repro.core.accumulate import TraceAccumulator
    from repro.core.trace import chunk_trace
    acc = TraceAccumulator(mode="cache")
    for chunk in chunk_trace(session.trace, CHUNK_EVENTS):
        acc.update(chunk)
    for sub, name in enumerate(("L1", "L2")):
        st_m = session.subpartition_stats(name)[0]
        st_s = acc.stats(sub)[0]
        for field in ("n_reads", "n_writes", "n_unique_addrs"):
            check(getattr(st_m, field) == getattr(st_s, field),
                  f"lifetime oracle {name}: {field}")
        check(abs(st_m.duration_s - st_s.duration_s)
              <= 1e-12 * st_s.duration_s,
              f"lifetime oracle {name}: duration")
        check(len(st_m.lifetimes_s) == len(st_s.lifetimes_s),
              f"lifetime oracle {name}: lifetime count")
        for field in ("lifetimes_s", "accesses_per_lifetime"):
            check(np.array_equal(np.sort(getattr(st_m, field)),
                                 np.sort(getattr(st_s, field))),
                  f"lifetime oracle {name}: {field}")
        check(abs(st_m.orphan_fraction - st_s.orphan_fraction) <= 1e-15,
              f"lifetime oracle {name}: orphan fraction")
        print(f"oracle lifetimes {name}: device extraction == streaming "
              f"fold ({len(st_m.lifetimes_s)} lifetimes, "
              f"{CHUNK_EVENTS}-event chunks)", flush=True)


def placement_phase(spies):
    for spy in spies:
        print(f"placement {spy.name}: {spy.calls} call(s), outputs on "
              f"{sorted(spy.platforms)}", flush=True)
        check(spy.calls > 0 or not spy.required, f"{spy.name} never ran")
        check(spy.platforms <= {"tpu"},
              f"{spy.name} returned arrays on {sorted(spy.platforms)}")


def stage_totals():
    """Per span name: count, total and self seconds; then counters."""
    snap = obs.snapshot()
    own = obs.self_ns(snap["spans"])
    rows = {}
    for s in snap["spans"]:
        n, total, self_ = rows.get(s["name"], (0, 0, 0))
        rows[s["name"]] = (n + 1, total + s["end_ns"] - s["start_ns"],
                           self_ + own[s["id"]])
    for name, (n, total, self_) in sorted(rows.items(),
                                          key=lambda kv: -kv[1][1]):
        print(f"stage {name:20s} n={n:4d} total_s={total * 1e-9:.3f} "
              f"self_s={self_ * 1e-9:.3f}")
    for name, v in sorted(snap["totals"].items()):
        print(f"counter {name} {v}")


def main() -> int:
    cache = compile_cache.configure()        # before the first jit
    print(f"compile cache: {cache}", flush=True)
    device = device_phase()

    from repro.backends import cachesim
    from repro.compose import executor
    from repro.core import lifetime
    spies = [Spy(cachesim, "_simulate_cache_sets"),
             Spy(cachesim, "_simulate_cache_scan"),
             Spy(lifetime, "_extract_lifetimes"),
             Spy(executor, "_rf_fused"),
             Spy(executor, "_ra_grouped"),
             # reached only by lifetimes without address groups
             Spy(executor, "_ra_ungrouped", required=False)]

    session, workload, cfg = profile_phase()
    analyze_phase(session)
    compose_phase(session)
    sweep_phase(session)
    cachesim_oracle_phase(workload, cfg)
    lifetime_oracle_phase(session)
    placement_phase(spies)

    stats = executor.compile_stats()
    print(f"compile: jit_entries={stats['jit_entries']} "
          f"persistent_cache_hits={stats['persistent_cache_hits']} "
          f"persistent_cache_misses={stats['persistent_cache_misses']}")
    stage_totals()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
