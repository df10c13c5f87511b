"""Tests for the unified workload registry: registration round-trips,
cross-backend lowering of one transformer spec, spec identity hashing,
the legacy profile-CLI bit-for-bit lock, and the jax-free import
contract that keeps test collection fast."""

import json

import pytest

from repro.workloads import (WorkloadSpec, available_suites,
                             available_workloads, get_workload,
                             register_workload, resolve_workloads)
from repro.workloads.spec import _ALIASES, _REGISTRY


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_suites_registered():
    assert set(available_suites()) >= {"archs", "mlperf", "polybench",
                                       "cnn"}
    from repro.configs.base import ARCH_IDS
    assert set(available_workloads("archs")) == set(ARCH_IDS)
    assert "polybench-2mm" in available_workloads("polybench")
    assert "resnet-block" in available_workloads("cnn")


def test_unknown_workload_raises():
    with pytest.raises(ValueError, match="unknown workload"):
        get_workload("not-a-workload")


def test_resolve_workloads_selectors():
    assert resolve_workloads("tinyllama_1_1b,polybench-2mm") == (
        "tinyllama_1_1b", "polybench-2mm")
    assert set(resolve_workloads("suite:polybench")) == set(
        available_workloads("polybench"))
    assert resolve_workloads("all") == available_workloads()
    with pytest.raises(ValueError, match="unknown suite"):
        resolve_workloads("suite:nope")


def test_register_workload_decorator_roundtrip():
    @register_workload("dummy-test-workload", suite="test",
                       params={"n": 4}, backends=("systolic", "gpu"))
    def _build(params, backend):
        return [("gemm", params["n"])], {}

    try:
        spec = get_workload("dummy-test-workload")
        assert isinstance(spec, WorkloadSpec)
        # aliases canonicalize at registration: "gpu" -> "cachesim"
        assert spec.backends == ("systolic", "cachesim")
        assert spec.supports("gpu") and spec.supports("cachesim")
        workload, cfg = spec.build("systolic")
        assert workload == [("gemm", 4)] and cfg == {}
    finally:
        _REGISTRY.pop("dummy-test-workload", None)
        _ALIASES.pop("dummy-test-workload", None)


def test_build_unknown_backend_raises_clear_valueerror():
    spec = get_workload("tinyllama_1_1b")
    with pytest.raises(ValueError, match="no lowering for backend"):
        spec.build("accelsim")
    # polybench stencils have no systolic lowering
    with pytest.raises(ValueError, match="no lowering"):
        get_workload("polybench-2DConv").build("systolic")


def test_with_params_and_content_hash():
    spec = get_workload("tinyllama_1_1b")
    assert spec.with_params(seq=16).param_dict["seq"] == 16
    with pytest.raises(ValueError, match="no param"):
        spec.with_params(bogus=1)
    # identity hash: stable across lookups, sensitive to params
    again = get_workload("tinyllama_1_1b")
    assert spec.content_hash() == again.content_hash()
    assert spec.content_hash() != spec.with_params(seq=16).content_hash()
    assert spec.content_hash() != get_workload(
        "polybench-2mm").content_hash()


# ---------------------------------------------------------------------------
# import hygiene: the registry must not drag JAX into test collection
# ---------------------------------------------------------------------------

def test_workloads_package_imports_without_jax():
    """Analyzer-based: the static import graph proves repro.workloads
    (recursively) never reaches jax/numpy at import time — stronger than
    the old one-interpreter subprocess probe, which only witnessed a
    single import order."""
    from repro.analysis import AnalysisContext, default_root
    from repro.analysis.imports import (ImportContract, ImportPurityRule,
                                        build_import_graph)
    ctx = AnalysisContext(default_root())
    rule = ImportPurityRule(contracts=(
        ImportContract("repro.workloads", ("jax", "numpy"),
                       recursive=True),))
    assert rule.run(ctx) == []
    # the graph must actually cover the package (guards against the
    # contract silently matching zero modules)
    graph = build_import_graph(ctx)
    covered = [m for m in graph
               if m == "repro.workloads"
               or m.startswith("repro.workloads.")]
    assert len(covered) >= 3, covered


# ---------------------------------------------------------------------------
# cross-backend lowering of one registered transformer spec
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_spec():
    return get_workload("tinyllama_1_1b").with_params(seq=8, n_layers=1)


def _session_report(spec, backend, **extra_cfg):
    from repro.core import ProfileSession
    workload, cfg = spec.build(backend)
    session = ProfileSession(backend)
    session.profile(workload, **{**cfg, **extra_cfg})
    return session, session.analyze().compose().report()


@pytest.mark.parametrize("backend,extra", [
    ("systolic", {"rows": 16, "cols": 16}),
    ("opstream", {}),
    ("gpu", {}),
])
def test_lowering_produces_valid_profile(tiny_spec, backend, extra):
    session, report = _session_report(tiny_spec, backend, **extra)
    res = session._result
    assert res.trace is not None and res.trace.n_events > 0
    assert report["subpartitions"]
    for entry in report["subpartitions"].values():
        assert entry["n_reads"] + entry["n_writes"] > 0
        assert "composition" in entry


def test_lowering_kernel_naming_consistent(tiny_spec):
    """The trace backends agree on the layer-prefixed kernel naming
    convention, so per-kernel attribution lines up across backends."""
    _, sys_report = _session_report(tiny_spec, "systolic", rows=16,
                                    cols=16)
    _, op_report = _session_report(tiny_spec, "opstream")
    sys_names = {k["name"] for k in sys_report["kernels"]}
    op_names = {k["name"] for k in op_report["kernels"]}
    assert sys_names and op_names
    assert all(n.startswith("L0.") for n in sys_names)
    assert all(n.startswith("L0.") for n in op_names)
    # the GEMM stack itself is a subset view of the op stream's GEMMs
    assert {"L0.qkv", "L0.scores", "L0.pv", "L0.o"} <= sys_names


def test_lowering_tpu_graph(tiny_spec):
    session, report = _session_report(tiny_spec, "tpu")
    assert session.backend.name == "tpu_graph"
    assert "VMEM" in report["subpartitions"]
    assert report["n_ops"] > 0


# ---------------------------------------------------------------------------
# legacy `python -m repro profile` output is bit-for-bit unchanged
# ---------------------------------------------------------------------------

def _seed_transformer_gemms(cfg, seq, n_layers=2):
    """The seed-era lowering, replicated verbatim as the oracle."""
    from repro.backends.systolic import GemmLayer
    hd = cfg.hd
    kvd = cfg.kv_heads * hd
    layers = []
    for i in range(n_layers):
        layers += [
            GemmLayer(f"L{i}.qkv", seq, cfg.d_model + 2 * kvd, cfg.d_model),
            GemmLayer(f"L{i}.scores", seq, seq, hd),
            GemmLayer(f"L{i}.pv", seq, hd, seq),
            GemmLayer(f"L{i}.o", seq, cfg.d_model, cfg.d_model),
            GemmLayer(f"L{i}.up", seq, cfg.d_ff or cfg.d_model * 4,
                      cfg.d_model),
            GemmLayer(f"L{i}.down", seq, cfg.d_model,
                      cfg.d_ff or cfg.d_model * 4),
        ]
    return layers


def test_profile_cli_systolic_bit_for_bit_legacy():
    from repro.configs.base import get_config
    from repro.core import ProfileSession
    from repro.launch.profile import main

    cfg = get_config("tinyllama_1_1b", smoke=False)
    session = ProfileSession("systolic")
    session.profile(_seed_transformer_gemms(cfg, 24), rows=32, cols=32,
                    dataflow="ws")
    old = session.analyze().compose().report()

    new = main(["--arch", "tinyllama_1_1b", "--backend", "systolic",
                "--seq", "24", "--pe", "32"])
    assert json.dumps(old, sort_keys=True) == json.dumps(
        new, sort_keys=True)


def test_profile_cli_opstream_bit_for_bit_legacy():
    from repro.backends.opstream import transformer_ops
    from repro.configs.base import get_config
    from repro.core import ProfileSession
    from repro.launch.profile import main

    cfg = get_config("tinyllama_1_1b", smoke=False)

    def seed_program(sb):    # the seed's _op_program (a dense config)
        transformer_ops(sb, cfg.d_model, max(cfg.n_heads, 1),
                        max(cfg.kv_heads, 1), cfg.d_ff or 4 * cfg.d_model,
                        16, n_layers=2)

    session = ProfileSession("opstream")
    session.profile(seed_program, sample=8)
    old = session.analyze().compose().report()

    new = main(["--arch", "tinyllama_1_1b", "--backend", "opstream",
                "--seq", "16"])
    assert json.dumps(old, sort_keys=True) == json.dumps(
        new, sort_keys=True)
