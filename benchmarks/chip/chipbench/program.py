"""Calls into the program under test, each layer inside a span.

The timed path enters the program (``repro``) only through the files
loaded here by name: a driver per traffic kind (``drivers/<kind>.py``)
turns a configuration and a traffic mix into requests on
``ProfileSession`` (``profile``/``from_trace``, ``analyze``,
``compose``, ``sweep``) and keeps, for the request the check samples,
what the program produced; a backend adapter per backend
(``backends/<name>.py``) lowers and simulates the configuration.
(``cell.py`` also sets up the program's compile cache.)
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from chipbench.manifest import load_module


class Spans:
    """Host spans ``(name, request, start_s, end_s)``; in a traced run
    each is also a ``TraceAnnotation`` in the profiler's trace."""

    def __init__(self, annotate=False):
        self.rows = []
        self.names = set()
        self.request = None
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name):
        self.names.add(name)
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.rows.append((name, self.request, t0, t1))


# ---------------------------------------------------------------------------
# host copies of what the program produced, for the check
# ---------------------------------------------------------------------------

def composition_record(comp):
    q = comp.quantization
    return {"devices": list(comp.devices),
            "capacity_fractions": np.asarray(comp.capacity_fractions),
            "banks": list(q["banks"]) if q is not None else None,
            "energy_j": float(comp.energy_j),
            "energy_vs_sram": float(comp.energy_vs_sram)}


def session_record(session):
    """Trace, lifetimes and statistics of an analyzed session."""
    tr = session.trace
    fields = load_module("checks", "lifetime").STAT_FIELDS
    subs = {}
    for name in session.report()["subpartitions"]:
        st, raw = session.subpartition_stats(name)
        valid = np.asarray(raw.valid)
        segs = tuple(np.asarray(x)[valid] for x in (
            raw.addr, raw.start_cycles, raw.lifetime_cycles, raw.n_reads))
        subs[name] = (segs, {f: getattr(st, f) for f in fields})
    trace = tuple(np.asarray(x) for x in (
        tr.time_cycles, tr.addr, tr.is_write, tr.hit, tr.subpartition))
    return {"trace": trace, "subs": subs}


def work_of(session):
    """Shapes the roofline work counts need: events per subpartition,
    lifetimes and addresses per subpartition."""
    sub = np.asarray(session.trace.subpartition)
    out = {"events": {}, "lifetimes": {}, "addresses": {}}
    for i, name in enumerate(session.report()["subpartitions"]):
        st, raw = session.subpartition_stats(name)
        out["events"][name] = int((sub == np.unique(sub)[i]).sum())
        out["lifetimes"][name] = int(len(st.lifetimes_s))
        valid = np.asarray(raw.valid)
        out["addresses"][name] = int(len(np.unique(
            np.asarray(raw.addr)[valid])))
    return out


# ---------------------------------------------------------------------------
# drivers and backends, each a file of its own found by name
# ---------------------------------------------------------------------------

def load_driver(cell, seed, spans):
    """The driver of the cell's traffic kind (``drivers/<kind>.py``)."""
    return load_module("drivers", cell.traffic["kind"]).Driver(
        cell, seed, spans)


def load_backend(config):
    """The adapter of the configuration's backend
    (``backends/<run.backend>.py``)."""
    return load_module("backends", config["run"]["backend"]).Backend(config)
