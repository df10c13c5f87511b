"""The comparison that decides ``correct``.

For the request the check samples, the plain references under
``checks/`` rebuild everything from the configuration, the traffic
parameters and the request's seed-drawn values alone: the lowered
stream and simulated trace (``checks/backend_<backend>.py``), the
lifetimes and statistics of every subpartition, and each composition
the program returned (listed by the traffic kind's
``reference_compositions``).  Nothing the program made is used.  Every
file under ``checks/`` with ``LIMITS`` is a layer check: its
``numbers(rec, ref)`` gives the compared numbers, ``LIMITS`` their
limits.
"""

from __future__ import annotations

import glob
import os

import numpy as np

from chipbench.manifest import BENCH_DIR, load_module


def layer_checks():
    """The layer check modules under ``checks/``, by file name."""
    mods = (load_module("checks", os.path.basename(p)[:-3]) for p in
            sorted(glob.glob(os.path.join(BENCH_DIR, "checks", "*.py"))))
    return [m for m in mods if hasattr(m, "LIMITS")]


def limits():
    out = {}
    for mod in layer_checks():
        out.update(mod.LIMITS)
    return out


def reference_session(config, key):
    """Reference trace and per-subpartition ``(segments, stats)`` of the
    configuration, relabelled by ``key``."""
    lifetime = load_module("checks", "lifetime")
    ref = load_module("checks", "backend_" + config["run"]["backend"]) \
        .reference_trace(config, key)
    trace = ref["trace"]
    subs = {}
    for i, name in enumerate(config["run"]["subpartitions"]):
        m = trace[4] == i
        t_, a_, w_, h_ = (x[m] for x in trace[:4])
        segs = lifetime.segments(t_, a_, w_, h_, ref["mode"])
        subs[name] = (segs, lifetime.subpartition_stats(
            t_, a_, w_, segs, clock_hz=ref["clock_hz"],
            block_bits=ref["block_bits"]))
    return {"trace": trace, "subs": subs, "clock_hz": ref["clock_hz"]}


def reference_compositions(cell, seed, rec, ref, dtype=np.float64):
    """The reference's compositions of the sampled request, as the
    traffic kind (``drivers/<kind>.py``) lists them."""
    return load_module("drivers", cell.traffic["kind"]) \
        .reference_compositions(cell, seed, rec, ref, dtype)


def numbers(cell, seed, rec):
    """Every compared number of one run: the program's sampled request
    against the reference.  Returns ``(numbers, reference)``."""
    ref = reference_session(cell.config, rec["key"])
    ref["compositions"] = reference_compositions(cell, seed, rec, ref)
    out = {}
    for mod in layer_checks():
        out.update(mod.numbers(rec, ref))
    return out, ref


def control_numbers(cell, seed, rec, ref):
    """The control: the reference computed in float32 put in the
    program's place, against the float64 reference."""
    comp = load_module("checks", "composition")
    f32 = reference_compositions(cell, seed, rec, ref, dtype=np.float32)
    return comp.compare(f32, ref["compositions"])
