"""BENCHMARK.json resolves: every cell to its configuration and traffic
files, every metric to its reader, every layer check to its module; and
the file keeps the benchmark contract's shape."""

import os
import re

import pytest

from chipbench import manifest, reference

BENCH = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = manifest.resolve(cell)
    assert c.chips == 1
    # the traffic kind and the backend each name files of their own
    driver = manifest.load_module("drivers", c.traffic["kind"])
    assert callable(driver.Driver) and callable(driver.reference_compositions)
    backend = c.config["run"]["backend"]
    assert callable(manifest.load_module("backends", backend).Backend)
    assert callable(manifest.load_module("checks", "backend_" + backend)
                    .reference_trace)
    # the configuration's decoder map names keys the file holds
    for key in c.config["decoder"].values():
        assert isinstance(c.config[key], int)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(manifest.load_module("metrics", metric).read)


def test_checks_and_limits_resolve():
    # every file under checks/ with LIMITS is a layer check
    assert [m.__name__ for m in reference.layer_checks()] == [
        "chipbench_checks_" + n for n in ("backend", "composition",
                                          "lifetime")]
    limits = reference.limits()
    assert set(limits) == {"trace_mismatch", "lifetime_mismatch",
                           "stats_mismatch", "capacity_mismatch",
                           "energy_rel_err"}
    assert limits["energy_rel_err"] == 1e-9
    assert all(v == 0 for k, v in limits.items() if k.endswith("mismatch"))


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cfg_names = {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == cfg_names
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        conf = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    assert layers == {"backend", "lifetime extraction", "composition",
                      "device"}
