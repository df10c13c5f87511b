"""The profiler trace of a window, reduced to what the metrics read.

A traced run records the measured window with ``jax.profiler`` and
wraps it, and each layer's call, in ``TraceAnnotation`` spans of the
benchmark's own.  :class:`Summary` keeps, from the ``.xplane.pb``:

* the device operations (``XLA Ops`` lines of the ``/device:TPU:<n>``
  planes) and the compiled modules they belong to (``XLA Modules``);
* the benchmark's host spans (``/host:CPU`` plane), among them the
  ``window`` span that bounds every reduction.

Busy time is the union of a chip's operation intervals inside the
window, averaged over the chips; a layer's device time is the busy time
inside its host spans; idle gaps are named by the innermost host span
around their middle.
"""

from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


class Summary:
    """``ops``/``modules``: per chip, lists of ``[name, start_ns,
    duration_ns]``; ``spans``: ``[name, start_ns, duration_ns]`` of the
    benchmark's host spans."""

    def __init__(self, ops, modules, spans, lines=None):
        self.ops = ops
        self.modules = modules
        self.spans = spans
        self.lines = lines or {}    # plane -> line -> events, for a look
        w = next((s for s in spans if s[0] == WINDOW), None)
        if w is None:
            raise ValueError("the trace holds no 'window' span")
        self.t0, self.t1 = w[1], w[1] + w[2]

    # -- reading ----------------------------------------------------------
    @classmethod
    def from_profile_dir(cls, trace_dir, span_names):
        """Read the newest ``.xplane.pb`` under ``trace_dir``."""
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no profiler trace under {trace_dir}")
        data = ProfileData.from_file(paths[-1])
        ops, modules, spans, lines = {}, {}, [], {}
        names = set(span_names) | {WINDOW}
        for plane in data.planes:
            lines[plane.name] = {line.name: sum(1 for _ in line.events)
                                 for line in plane.lines}
            if plane.name.startswith(DEVICE_PREFIX):
                chip = plane.name[len(DEVICE_PREFIX):]
                if not chip.isdigit():
                    continue
                for line in plane.lines:
                    dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(
                        line.name)
                    if dest is not None:
                        dest.setdefault(chip, []).extend(
                            [e.name, e.start_ns, e.duration_ns]
                            for e in line.events)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans.extend([e.name, e.start_ns, e.duration_ns]
                                 for e in line.events if e.name in names)
        return cls(ops, modules, spans, lines)

    @classmethod
    def from_json(cls, d):
        return cls(d["ops"], d["modules"], d["spans"], d.get("lines"))

    # -- reductions -------------------------------------------------------
    @property
    def window_s(self):
        return (self.t1 - self.t0) * 1e-9

    def _intervals(self, chip):
        """Merged ``[start, end)`` busy intervals of one chip, clipped
        to the window."""
        evs = self.ops.get(chip) or self.modules.get(chip) or []
        if not evs:
            return np.zeros((0, 2))
        a = np.array([[e[1], e[1] + e[2]] for e in evs], np.float64)
        a = np.clip(a, self.t0, self.t1)
        a = a[a[:, 1] > a[:, 0]]
        if len(a) == 0:
            return a
        a = a[np.argsort(a[:, 0], kind="stable")]
        ends = np.maximum.accumulate(a[:, 1])
        new = np.concatenate([[True], a[1:, 0] > ends[:-1]])
        starts = a[new, 0]
        idx = np.flatnonzero(new)
        stops = np.maximum.reduceat(a[:, 1], idx)
        return np.stack([starts, stops], axis=1)

    def chips(self):
        return sorted(set(self.ops) | set(self.modules), key=int)

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        chips = self.chips()
        if not chips:
            return 0.0
        return float(np.mean([(iv[:, 1] - iv[:, 0]).sum() * 1e-9
                              for iv in map(self._intervals, chips)]))

    def busy_in(self, span):
        """Seconds in which an operation ran inside the host spans named
        ``span``, averaged over the chips: the device time of the work
        those spans issue.  (The trace's module line is no measure of
        it: it can drop the event of a module that runs hundreds of
        thousands of operations, as a long cache-simulator scan does.)"""
        spans = np.array([[s[1], s[1] + s[2]] for s in self.spans
                          if s[0] == span], np.float64).reshape(-1, 2)
        chips = self.chips()
        if not chips or not len(spans):
            return 0.0
        total = 0.0
        for iv in map(self._intervals, chips):
            for lo, hi in spans:
                total += (np.minimum(iv[:, 1], hi)
                          - np.maximum(iv[:, 0], lo)).clip(0).sum()
        return float(total) * 1e-9 / len(chips)

    def top_ops(self, k=10):
        """The ``k`` device operations (by module and op name) that took
        most time in the window, with their seconds.  An operation that
        encloses others (a loop around its body) is left out, so that no
        time counts twice."""
        mods = {c: sorted((s, s + d, n) for n, s, d in evs)
                for c, evs in self.modules.items()}
        tot = {}
        for chip, evs in self.ops.items():
            m = mods.get(chip, [])
            starts = np.array([x[0] for x in m], np.float64)
            evs = sorted(evs, key=lambda e: (e[1], -e[2]))
            for i, (name, start, dur) in enumerate(evs):
                if i + 1 < len(evs) and evs[i + 1][1] < start + dur:
                    continue
                if start < self.t0 or start + dur > self.t1:
                    continue
                i = int(np.searchsorted(starts, start, side="right")) - 1
                mod = m[i][2] if i >= 0 and start < m[i][1] else "?"
                key = f"{mod}/{name}"
                tot[key] = tot.get(key, 0) + dur
        if not self.ops:
            for evs in self.modules.values():
                for name, start, dur in evs:
                    if start >= self.t0 and start + dur <= self.t1:
                        tot[name] = tot.get(name, 0) + dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, k=10):
        """The ``k`` longest gaps between device operations on chip 0
        (window edges included), each named by the innermost host span
        around its middle."""
        chips = self.chips()
        iv = self._intervals(chips[0]) if chips else np.zeros((0, 2))
        edges = np.concatenate([[self.t0], iv.ravel(), [self.t1]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:k]
        spans = [s for s in self.spans if s[0] != WINDOW]
        out = []
        for lo, hi in gaps[order]:
            mid = (lo + hi) / 2
            inside = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
            name = min(inside, key=lambda s: s[2])[0] if inside else "other"
            out.append([name, float(hi - lo) * 1e-9])
        return out
