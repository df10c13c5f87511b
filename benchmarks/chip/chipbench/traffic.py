"""The one traffic generator: requests drawn from ``(seed, index)``.

A traffic file (``traffic/<mix>.json``) holds only parameters.  Its
``kind`` says what one request is:

``answer``
    one profiler answer: lower, relabel, simulate, analyze, then compose
    under each of ``policies``.  The seed draws a relabelling key per
    request: byte (or slot) addresses are XORed with ``key <<
    relabel_shift``, where ``relabel_shift`` (from the configuration)
    lies above every bit that picks a cache set, so every request
    simulates the same hits, fills and evictions on fresh addresses.
``sweep``
    one design-space sweep over a ``DeviceGrid`` whose retention, area
    and energy scales are drawn log-uniform in ``scale_range``, of the
    sizes the file gives, so every request has the same shape.  The
    warm-up sweep draws a grid of the sizes ``warmup_grid`` gives.

Index -1 is the warm-up request, -2 the set-up profile of a sweep cell;
the window draws 0, 1, 2, ...  Seeds may exceed 64 bits.
"""

from __future__ import annotations

import numpy as np

WARMUP, SETUP = -1, -2


def rng(seed, index, stream=0):
    return np.random.default_rng([int(seed), int(index) + 2, stream])


def relabel_key(traffic, seed, index):
    return int(rng(seed, index).integers(1, 1 << traffic["relabel_bits"]))


def _scales(traffic, seed, index, sizes):
    lo, hi = (np.log(v) for v in traffic["scale_range"])
    g = rng(seed, index, 1)
    return tuple(tuple(float(v) for v in np.sort(np.exp(g.uniform(lo, hi, n))))
                 for n in sizes)


def grid_scales(traffic, seed, index):
    """``(retention, area, energy)`` scale tuples of one sweep request."""
    return _scales(traffic, seed, index, (traffic["retention_scales"],
                                          traffic["area_scales"],
                                          traffic["energy_scales"]))


def warmup_scales(traffic, seed):
    """Scale tuples of the warm-up sweep, of the sizes ``warmup_grid``
    gives: the fewest candidates whose sweep runs every executable the
    window's sweeps run."""
    return _scales(traffic, seed, WARMUP, traffic["warmup_grid"])


def answer_sample(traffic, seed):
    """The answer compared with the reference: drawn before the window
    among the first ``check_within``, so that only its state is kept
    (the last one completed, if the window ends before it)."""
    return int(rng(seed, 0, 2).integers(0, traffic["check_within"]))


def sweep_sample(traffic, seed, n_done):
    """The sweep request whose every (candidate, subpartition)
    composition is compared with the reference."""
    return int(rng(seed, 0, 2).integers(0, n_done))
