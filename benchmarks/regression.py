"""Benchmark-regression gate: ``python -m benchmarks.regression``.

Runs selected benchmarks from :mod:`benchmarks.run`, writes their CSV
rows to a machine-readable artifact (``BENCH_ci.json``), and compares
``us_per_call`` against the committed reference in
``benchmarks/baseline.json``: any row regressing beyond the threshold
(default 2x — generous, to ride out shared-runner noise) exits non-zero.
CI runs this in a ``continue-on-error`` job, so regressions flag the run
without blocking the merge.

  PYTHONPATH=src python -m benchmarks.regression \
      --only pipeline --only cachesim --out BENCH_ci.json

``baseline.json`` rows carry a reference ``us_per_call`` (deliberately
slack vs a warm local run — CI runners are slower) and an optional
``higher_is_better`` flag for ratio rows like ``cachesim.speedup``,
where a *drop* below ``baseline / threshold`` is the regression.
Refresh the baseline whenever a benchmark's scale or workload changes:
run the benches locally and commit roughly 1.5x the observed numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE = os.path.join(_HERE, "baseline.json")


def parse_rows(rows) -> list:
    """``name,us_per_call,derived`` CSV rows -> dicts."""
    out = []
    for r in rows:
        name, us, derived = r.split(",", 2)
        out.append({"name": name, "us_per_call": float(us),
                    "derived": derived})
    return out


def compare(measured: list, baseline: dict) -> list:
    """Regressions of ``measured`` rows vs the ``baseline`` reference."""
    threshold = float(baseline.get("threshold", 2.0))
    regressions = []
    base_rows = baseline.get("rows", {})
    for row in measured:
        ref = base_rows.get(row["name"])
        if ref is None:
            continue
        base = float(ref["us_per_call"])
        got = row["us_per_call"]
        if ref.get("higher_is_better"):
            bad = got < base / threshold
            limit = base / threshold
        else:
            bad = got > base * threshold
            limit = base * threshold
        if bad:
            regressions.append({
                "name": row["name"], "us_per_call": got,
                "baseline_us_per_call": base, "limit": limit,
                "ratio": got / base if base else float("inf"),
                "higher_is_better": bool(ref.get("higher_is_better")),
            })
    return regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run benchmarks and fail on >threshold regressions "
                    "vs benchmarks/baseline.json")
    ap.add_argument("--only", action="append", default=None,
                    help="bench name (repeatable); default: every bench "
                         "named in the baseline's `benches` list")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--out", default="BENCH_ci.json")
    args = ap.parse_args(argv)

    with open(args.baseline) as f:
        baseline = json.load(f)
    only = args.only or baseline.get("benches", ["pipeline", "cachesim"])

    from benchmarks.run import bench_registry
    registry = bench_registry()
    unknown = [n for n in only if n not in registry]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; have {sorted(registry)}")

    rows = []
    for name in registry:       # registry order: see benchmarks.run
        if name in only:
            rows.extend(registry[name]())
    measured = parse_rows(rows)
    regressions = compare(measured, baseline)

    artifact = {
        "benches": list(only),
        "threshold": float(baseline.get("threshold", 2.0)),
        "baseline": os.path.relpath(args.baseline, os.getcwd()),
        "rows": measured,
        "regressions": regressions,
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)

    print(f"\n{len(measured)} bench rows -> {args.out} "
          f"(baseline: {args.baseline})")
    if regressions:
        for r in regressions:
            direction = "below" if r["higher_is_better"] else "above"
            print(f"REGRESSION {r['name']}: {r['us_per_call']:.1f} is "
                  f"{direction} the {r['limit']:.1f} limit "
                  f"(baseline {r['baseline_us_per_call']:.1f}, "
                  f"ratio {r['ratio']:.2f}x)")
        return 1
    print("no benchmark regressions vs baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
