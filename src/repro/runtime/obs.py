"""Spans and counters of the profiler's own stages.

One recorder for the whole process, at stage and slab granularity
(never per event or per candidate)::

    from repro.runtime import obs

    with obs.span("cachesim.scan", level="L1"):
        obs.count("h2d_bytes", packed.nbytes)
        ...

* :func:`span` times a block with ``time.perf_counter_ns`` (the clock
  of the chip benchmark's own spans) and records its id, its parent (the
  innermost span open in the same context), its name and its attributes.
  The stack of open spans is a :mod:`contextvars` variable, so threads
  and tasks nest on their own; a worker run in a copy of its submitter's
  context (``SweepRunner(workers>1)``) nests under the submitter's span.
* When jax has been imported, a span is also a
  ``jax.profiler.TraceAnnotation`` of the same name and attributes, so a
  ``jax.profiler.trace`` shows it on the host plane, on the device
  trace's clock, next to the device operations it issued.  Outside a
  profiler session that costs about a microsecond.  This module never
  imports jax itself.
* :func:`count` adds to the innermost open span's ``counts`` and to a
  process total, so a request's counts are the sum over its spans.
* Finished spans go into a bounded ring; :func:`snapshot` returns them
  and the totals.

Import contract: stdlib-only at import (``repro check``), as the
jax-free ``compose.engine`` imports it.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import sys
import threading
import time

#: Finished spans kept, oldest dropped first.
RING = 1 << 16

_open: contextvars.ContextVar = contextvars.ContextVar("repro_obs_open",
                                                       default=())
_ids = itertools.count(1)
_ring: collections.deque = collections.deque(maxlen=RING)
_totals: collections.Counter = collections.Counter()
_lock = threading.Lock()


class _Span:
    __slots__ = ("id", "parent", "name", "attrs", "start_ns", "end_ns",
                 "counts")

    def __init__(self, parent, name, attrs):
        self.id = next(_ids)
        self.parent = parent
        self.name = name
        self.attrs = attrs
        self.start_ns = self.end_ns = 0
        self.counts = {}

    def asdict(self) -> dict:
        return {"id": self.id, "parent": self.parent, "name": self.name,
                "attrs": dict(self.attrs), "start_ns": self.start_ns,
                "end_ns": self.end_ns, "counts": dict(self.counts)}


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the enclosed block as a span ``name`` with ``attrs``
    (strings and numbers); usable as a decorator too."""
    stack = _open.get()
    rec = _Span(stack[-1].id if stack else None, name, attrs)
    token = _open.set(stack + (rec,))
    jax = sys.modules.get("jax")
    ann = jax.profiler.TraceAnnotation(name, **attrs) if jax else None
    if ann is not None:
        ann.__enter__()
    rec.start_ns = time.perf_counter_ns()
    try:
        yield
    finally:
        rec.end_ns = time.perf_counter_ns()
        if ann is not None:
            ann.__exit__(None, None, None)
        _open.reset(token)
        with _lock:
            _ring.append(rec)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span and to
    the process total."""
    stack = _open.get()
    with _lock:
        _totals[name] += n
        if stack:
            counts = stack[-1].counts
            counts[name] = counts.get(name, 0) + n


def snapshot() -> dict:
    """``{"spans": [...], "totals": {...}}``: the finished spans still
    in the ring, oldest first, each a dict of ``id``, ``parent``,
    ``name``, ``attrs``, ``start_ns``, ``end_ns`` and ``counts``; and
    every counter's process total."""
    with _lock:
        spans = list(_ring)
        totals = dict(_totals)
    return {"spans": [s.asdict() for s in spans], "totals": totals}


def self_ns(spans) -> dict:
    """``{id: ns}``: each span's duration less the part of it that its
    child spans among ``spans`` cover (children run in worker threads
    may overlap, so their union is taken)."""
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    out = {}
    for s in spans:
        lo, hi = s["start_ns"], s["end_ns"]
        covered, end = 0, lo
        for c in sorted(kids.get(s["id"], ()), key=lambda c: c["start_ns"]):
            a, b = max(c["start_ns"], end), min(c["end_ns"], hi)
            if b > a:
                covered += b - a
                end = b
        out[s["id"]] = hi - lo - covered
    return out
