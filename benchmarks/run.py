"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus human-readable tables).
Usage: ``PYTHONPATH=src python -m benchmarks.run [--only NAME]``.

All analysis benchmarks drive the :class:`repro.core.ProfileSession`
pipeline; ``pipeline`` additionally times the facade itself, monolithic
vs chunk-streamed through ``TraceAccumulator``.
"""

from __future__ import annotations

import argparse
import time


def pipeline_bench():
    """ProfileSession end-to-end: monolithic vs streaming frontend."""
    from repro.backends.systolic import GemmLayer
    from repro.core import ProfileSession, available_backends

    rows = []
    print("\n=== ProfileSession pipeline (backends: "
          f"{', '.join(available_backends())}) ===")
    layers = [GemmLayer("g0", 96, 128, 128), GemmLayer("g1", 64, 96, 192)]
    for label, cfg in (("monolithic", {}),
                       ("streamed-8k", {"chunk_events": 8192})):
        t0 = time.monotonic()
        report = ProfileSession("systolic").run(
            layers, rows=64, cols=64, dataflow="ws", **cfg)
        us = (time.monotonic() - t0) * 1e6
        n_lt = sum(v["n_lifetimes"]
                   for v in report["subpartitions"].values())
        print(f"{label:14s} {us / 1e3:8.1f} ms  lifetimes={n_lt}")
        rows.append(f"pipeline.{label},{us:.1f},lifetimes={n_lt}")
    return rows


#: name -> "module:function".  Modules are imported only when their
#: bench runs, and ``campaign`` comes first: its process-scheduler
#: section starts worker processes that need the accelerator, so it must
#: run from a parent that has not imported jax yet (one process per
#: chip).
_BENCHES = {
    "campaign": "benchmarks.campaign_bench:campaign_bench",
    "pipeline": "benchmarks.run:pipeline_bench",
    "cachesim": "benchmarks.cachesim_bench:cachesim_bench",
    "composer": "benchmarks.composer_bench:composer_bench",
    "devices": "benchmarks.devices_bench:devices_bench",
    "sweep": "benchmarks.sweep_bench:sweep_bench",
    "table4": "benchmarks.paper_tables:table4_pka",
    "fig5": "benchmarks.fig5_retention:fig5_retention",
    "table6": "benchmarks.paper_tables:table6_energy",
    "table7": "benchmarks.paper_tables:table7_hetero",
    "table8": "benchmarks.paper_tables:table8_orphans",
    "table9": "benchmarks.paper_tables:table9_pe_size",
    "fig8": "benchmarks.paper_tables:fig8_lifetimes",
    "fig10": "benchmarks.paper_tables:fig10_dataflow",
    "kernels": "benchmarks.kernels_bench:kernels_bench",
}


def _lazy(spec: str):
    def run():
        import importlib
        module, fn = spec.split(":")
        return getattr(importlib.import_module(module), fn)()
    return run


def bench_registry() -> dict:
    """name -> bench function, each returning CSV rows
    (``name,us_per_call,derived``), in the order they must run.  Shared
    with ``benchmarks.regression`` (the CI regression gate)."""
    return {name: _lazy(spec) for name, spec in _BENCHES.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="table4|table6|table7|table8|table9|fig8|fig10|"
                         "kernels|pipeline|cachesim|campaign|composer|"
                         "devices|sweep")
    args = ap.parse_args()

    rows = []
    for name, fn in bench_registry().items():
        if args.only and name != args.only:
            continue
        rows.extend(fn())

    print("\n=== CSV (name,us_per_call,derived) ===")
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
