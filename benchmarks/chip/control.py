#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

For each seed, in one process, the cell's sampled request runs through
the program's timed path at the cell's own size, and is compared with
the plain reference, as a run compares it: these are the sound
readings.  Then the control - the reference computed in float32, put in
the program's place - is compared with the float64 reference.  One JSON
line per seed, then one with the largest sound reading and the smallest
control reading of every number.  The benchmark's own runs never run
the control.  Needs the chips the cell asks for.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, HERE)


def readings(cell, seeds, *, log=sys.stderr):
    """``(per-seed rows, summary)`` of the sound runs and the control."""
    from chipbench import cell as cellmod
    from chipbench import program, reference
    cellmod.chip_devices(cell.chips)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        driver = program.load_driver(cell, seed, program.Spans())
        driver.sampled_request()
        rec = driver.after_window()
        t1 = time.perf_counter()
        nums, ref = reference.numbers(cell, seed, rec)
        t2 = time.perf_counter()
        ctrl = reference.control_numbers(cell, seed, rec, ref)
        row = {"seed": seed, "program": nums, "control": ctrl,
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "control_s": time.perf_counter() - t2}
        print(json.dumps(row), file=log, flush=True)
        rows.append(row)
    summary = {
        "lower": {k: max(r["program"][k] for r in rows)
                  for k in rows[0]["program"]},
        "upper": {k: min(r["control"][k] for r in rows)
                  for k in rows[0]["control"]},
        "limits": reference.limits(), "seeds": list(seeds)}
    return rows, summary


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated whole numbers")
    args = p.parse_args()
    from chipbench.manifest import ROOT, resolve
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax-cache")
    from repro.runtime import compile_cache
    compile_cache.configure()
    cell = resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    _, summary = readings(cell, seeds)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
