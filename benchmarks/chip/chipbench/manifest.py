"""Resolve a cell of ``BENCHMARK.json`` to its files.

Everything that belongs to one configuration, traffic mix, per-layer
metric or layer check lives in a file of its own, found by name:

  configs/<config>.json     sizes as run, source, reduced, assumed
  traffic/<traffic>.json    parameters of the one traffic generator
  drivers/<kind>.py         requests of one traffic kind (``kind`` in the
                            traffic file) and its reference compositions
  backends/<backend>.py     the program's lowering and simulator of one
                            backend (``run.backend`` in the configuration)
  metrics/<metric>.py       reader of one per-layer metric: read(ctx)
  checks/<layer>.py         comparison of one layer: LIMITS, numbers()
  checks/backend_<backend>.py   plain reference of one backend
  peaks.json                published peaks per device kind
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple       # metric entries of BENCHMARK.json
    per_layer: tuple


def load_json(path):
    with open(path) as f:
        return json.load(f)


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(cell_name, manifest_path=None):
    """The :class:`Cell` named ``cell_name`` in ``BENCHMARK.json``."""
    bench = load_json(manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"unknown workload {cell_name!r}; "
                         f"known: {sorted(cells)}")
    w = cells[cell_name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=cell_name, chips=int(w["chips"]),
        config=load_json(os.path.join(ROOT, conf["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, cell_name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _applies(m, cell_name)))


def decoder(config):
    """Decoder widths and depth, read from the configuration's own keys
    through its ``decoder`` map."""
    return {k: config[v] for k, v in config["decoder"].items()}


@functools.lru_cache(maxsize=None)
def load_module(kind, name):
    """``<kind>/<name>.py`` under the benchmark's directory as a module,
    loaded once (file names may hold dots, so they are loaded by
    path)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind):
    """Published peaks of one chip; an unknown kind is an error."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["chips"]:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(table['chips'])}")
    return table["chips"][device_kind]
