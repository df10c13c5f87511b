"""Plain reference of the composition layer: the device table, the three
assignment policies, and the energy and capacity they bill.

NumPy and the standard library only; nothing of the program under test
is imported.  Semantics (GainSight section 7.1.5, Algorithm 1):

* Devices are ordered cheapest read+write energy per bit first, ties
  by name.  Retention degrades as ``retention / max(1, f_w / knee)``
  with the subpartition's write frequency ``f_w``.
* ``refresh-free``: each lifetime goes to the first device whose
  retention covers it (else the last device) and is billed one write
  plus its reads; each address goes to the first device covering its
  longest lifetime.
* ``refresh-aware``: each lifetime is billed on the device of least
  ``bits * (E_w + n_r * E_r + (ceil(T / t_ret) - 1)^+ * (E_r + E_w))``;
  each address goes to the device of least summed energy over its
  lifetimes (first on ties).
* ``bank-quantized:<base>@<n>``: the base policy's capacity fractions
  rounded up to multiples of ``1 / n``.
* Capacity fractions are address counts over the number of addresses;
  energy is divided by the subpartition's all-SRAM energy (reads and
  writes of every event, no refresh) for ``energy_vs_sram``.

``dtype`` sets the float precision of every real-valued step, so the
same code computed in float32 is the control for a float64 program.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

#: limits of the compared numbers; ``energy_rel_err`` is the program's
#: stated contract for its accelerated engine (energy within 1e-9
#: relative of the oracle, capacity fractions bit-identical).
LIMITS = {"capacity_mismatch": 0, "energy_rel_err": 1e-9}

_SRAM_AREA = 0.021          # um^2 per bit, N5 6T cell
_SRAM_READ = 15.0           # fJ per bit
_SRAM_WRITE = 18.0

#: (name, area ratio, energy ratio, retention s, knee Hz) of the paper's
#: device set, relative to SRAM
_PAPER = (("SRAM", 1.0, 1.0, math.inf, math.inf),
          ("Si-GCRAM", 0.4197, 0.3323, 1.0e-6, math.inf),
          ("Hybrid-GCRAM", 0.2263, 0.8481, 1.0e-5, 1.0e7))


def device(name, area, read, write, retention, knee=math.inf):
    return {"name": name, "area": area, "read": read, "write": write,
            "retention": retention, "knee": knee}


def _sram():
    return device("SRAM", _SRAM_AREA, _SRAM_READ, _SRAM_WRITE, math.inf)


def paper_devices():
    """SRAM, Si-GCRAM and Hybrid-GCRAM at TSMC N5."""
    out = [_sram()]
    for name, area, energy, ret, knee in _PAPER[1:]:
        out.append(device(name, area * _SRAM_AREA, energy * _SRAM_READ,
                          energy * _SRAM_WRITE, ret, knee))
    return out


def gain_cell(mix, r, a, e):
    """A gain cell between Si (mix 0) and Hybrid (mix 1): area, access
    energy and retention interpolate log-linearly, the write-frequency
    knee as ``knee_hybrid / mix``; then scaled by ``r``, ``a``, ``e``."""
    _, si, hy = paper_devices()

    def geo(key):
        return si[key] ** (1.0 - mix) * hy[key] ** mix

    knee = math.inf if mix == 0.0 else hy["knee"] / mix
    return device(f"GC[m={mix:g},r={r:g},a={a:g},e={e:g}]",
                  geo("area") * a, geo("read") * e, geo("write") * e,
                  geo("retention") * r, knee)


def grid_candidates(mixes, retention_scales, area_scales, energy_scales):
    """The SRAM-only anchor, then SRAM plus one gain cell per mix for
    every (retention, area, energy) scale triple in product order."""
    out = [[_sram()]]
    for r, a, e in itertools.product(retention_scales, area_scales,
                                      energy_scales):
        out.append([_sram()] + [gain_cell(m, r, a, e) for m in mixes])
    return out


def _retention(d, write_freq_hz):
    if not math.isfinite(d["retention"]):
        return math.inf
    if not math.isfinite(d["knee"]) or write_freq_hz <= 0:
        return d["retention"]
    return d["retention"] / max(1.0, write_freq_hz / d["knee"])


def parse_policy(spec):
    """``(base, n_banks or None)`` of a policy spec."""
    base, n_banks = spec, None
    if spec.startswith("bank-quantized"):
        rest = spec[len("bank-quantized"):]
        rest, _, banks = rest.partition("@")
        base = rest.lstrip(":") or "refresh-free"
        n_banks = int(banks) if banks else 16
    if base not in ("refresh-free", "refresh-aware"):
        raise ValueError(f"unknown policy {spec!r}")
    return base, n_banks


def compose(seg, stats, devices, policy, *, clock_hz, dtype=np.float64):
    """One composition of a subpartition's lifetimes ``seg`` (``addr,
    start, lifetime_cycles, n_reads``) onto ``devices``."""
    f = np.dtype(dtype).type
    devs = sorted(devices, key=lambda d: (d["read"] + d["write"], d["name"]))
    n_dev = len(devs)
    addr, _, lt_cyc, n_reads = (np.asarray(x, np.int64) for x in seg)
    clock = f(clock_hz)
    lt = lt_cyc.astype(dtype) / clock
    reads = n_reads.astype(dtype)
    bits = f(stats["block_bits"])
    ret = np.array([_retention(d, stats["write_freq_hz"]) for d in devs],
                   dtype)
    rd = np.array([d["read"] for d in devs], dtype)
    wr = np.array([d["write"] for d in devs], dtype)

    order = np.argsort(addr, kind="stable")
    a_sorted = addr[order]
    starts = np.flatnonzero(np.concatenate(
        [[True], a_sorted[1:] != a_sorted[:-1]])) if len(addr) else \
        np.zeros(0, np.int64)
    n_addr = len(starts)

    base, n_banks = parse_policy(policy)
    if base == "refresh-free":
        pick = np.full(len(lt), n_dev - 1)
        open_ = np.ones(len(lt), bool)
        for i in range(n_dev):
            m = open_ & (lt <= ret[i])
            pick[m] = i
            open_ &= ~m
        e = bits * (wr[pick] + reads * rd[pick])
        energy = e.sum(dtype=dtype) * f(1e-15)
        max_lt = np.maximum.reduceat(lt_cyc[order], starts).astype(dtype) \
            / clock if n_addr else np.zeros(0, dtype)
        apick = np.full(n_addr, n_dev - 1)
        open_ = np.ones(n_addr, bool)
        for i in range(n_dev):
            m = open_ & (max_lt <= ret[i])
            apick[m] = i
            open_ &= ~m
    else:
        with np.errstate(divide="ignore", invalid="ignore"):
            refresh = np.maximum(np.ceil(lt[None, :] / ret[:, None]) - 1, 0)
        e = bits * (wr[:, None] + reads[None, :] * rd[:, None]
                    + refresh * (rd + wr)[:, None])
        energy = e.min(axis=0).sum(dtype=dtype) * f(1e-15)
        per_addr = np.add.reduceat(e[:, order], starts, axis=1) \
            if n_addr else np.zeros((n_dev, 0), dtype)
        apick = np.argmin(per_addr, axis=0)
    counts = np.bincount(apick, minlength=n_dev)[:n_dev]
    frac = counts.astype(dtype) / f(max(n_addr, 1))
    banks = None
    if n_banks is not None:
        banks = np.ceil(frac * f(n_banks))
        frac = banks / f(n_banks)
        banks = [int(b) for b in banks]
    sram = next(d for d in devs if d["name"] == "SRAM")
    sram_energy = (f(sram["read"]) * f(stats["n_reads"]) * bits
                   + f(sram["write"]) * f(stats["n_writes"]) * bits) \
        * f(1e-15)
    return {"devices": [d["name"] for d in devs],
            "capacity_fractions": np.asarray(frac, np.float64),
            "banks": banks,
            "energy_j": float(energy),
            "energy_vs_sram": float(energy / sram_energy)}


def compare(got, ref):
    """``capacity_mismatch``: capacity fractions (and bank counts, and
    the device order they refer to) that differ, compared exactly.
    ``energy_rel_err``: the largest relative gap of ``energy_j`` or
    ``energy_vs_sram``.  ``got`` and ``ref`` are equally long lists of
    compositions in the same order."""
    bad, rel = 0, 0.0
    for g, r in zip(got, ref):
        if list(g["devices"]) != list(r["devices"]):
            bad += len(r["devices"])
            continue
        gf = np.asarray(g["capacity_fractions"], np.float64)
        rf = np.asarray(r["capacity_fractions"], np.float64)
        bad += int((gf != rf).sum()) if gf.shape == rf.shape else len(rf)
        if g["banks"] != r["banks"]:
            bad += 1
        for key in ("energy_j", "energy_vs_sram"):
            rel = max(rel, abs(g[key] - r[key]) / abs(r[key])
                      if r[key] else abs(g[key]))
    bad += abs(len(got) - len(ref))
    return {"capacity_mismatch": bad, "energy_rel_err": rel}


def numbers(rec, ref):
    return compare(rec["compositions"], ref["compositions"])
