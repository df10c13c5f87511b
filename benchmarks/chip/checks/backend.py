"""Check of the backend layer: the trace the program simulated against
the plain reference of the configuration's backend
(``checks/backend_<run.backend>.py``).

NumPy only; nothing of the program under test is imported.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"trace_mismatch": 0}


def numbers(rec, ref):
    return compare_trace(rec["trace"], ref["trace"])


def compare_trace(got, ref):
    """Events at which the program's trace differs from the reference in
    any field (time, address, write flag, hit flag, subpartition); a
    length difference counts every missing or extra event."""
    n = min(len(got[0]), len(ref[0]))
    bad = np.zeros(n, bool)
    for g, r in zip(got, ref):
        bad |= np.asarray(g)[:n] != np.asarray(r)[:n]
    return {"trace_mismatch": int(bad.sum())
            + abs(len(got[0]) - len(ref[0]))}
