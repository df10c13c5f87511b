"""Tests for the ``repro check`` contract analyzer.

Each rule runs against a violating fixture mini-tree under
``tests/fixtures/analysis/`` and its clean twin (the fixtures are
parsed, never imported), plus the suppression/baseline machinery, the
CLI exit codes, the schema-drift pin -> edit -> detect round-trip — on
the fixture tree *and* on a copy of the real cache-key functions — and
the lock that the repo's own tree stays clean.
"""

import json
import os
import shutil

from repro.analysis import (AnalysisContext, AtomicWriteRule,
                            DtypeSafetyRule, ImportContract,
                            ImportPurityRule, RegistryConformanceRule,
                            SchemaDriftRule, default_root, default_rules,
                            load_baseline, run_check,
                            update_schema_manifest, write_baseline)
from repro.analysis.cli import main as check_main
from repro.analysis.compat import JaxCompatRule

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "analysis")


def fx(name: str) -> str:
    return os.path.join(FIXTURES, name)


def fixture_ctx(name: str) -> AnalysisContext:
    return AnalysisContext(fx(name))


# ---------------------------------------------------------------------------
# import-purity
# ---------------------------------------------------------------------------

def test_import_purity_flags_transitive_chain():
    rule = ImportPurityRule(contracts=(
        ImportContract("repro.workloads", ("jax", "numpy"),
                       recursive=True),))
    findings = rule.run(fixture_ctx("import_bad"))
    by_ext = {("numpy" if "numpy" in f.message else "jax"): f
              for f in findings}
    assert set(by_ext) == {"numpy", "jax"}
    # the numpy leak is transitive: the finding anchors at the import
    # inside the internal helper and spells out the chain
    leak = by_ext["numpy"]
    assert leak.path == "repro/helper.py"
    assert "repro.workloads -> repro.helper -> numpy" in leak.message
    assert "lazy import" in leak.remediation
    # the jax leak is the try-block import (counted: it runs at import
    # time), anchored in the package itself
    assert by_ext["jax"].path == "repro/workloads/__init__.py"


def test_import_purity_clean_twin_allows_lazy_and_type_checking():
    rule = ImportPurityRule(contracts=(
        ImportContract("repro.workloads", ("jax", "numpy"),
                       recursive=True),))
    assert rule.run(fixture_ctx("import_ok")) == []


_EXEMPT_CONTRACT = ImportContract(
    "repro.compose", ("jax",), recursive=True,
    exempt=("repro.compose.jax_engine", "repro.compose.executor"))


def test_import_purity_exempt_modules_may_import_jax():
    rule = ImportPurityRule(contracts=(_EXEMPT_CONTRACT,))
    assert rule.run(fixture_ctx("import_exempt")) == []


def test_import_purity_without_exemption_flags_both_backends():
    rule = ImportPurityRule(contracts=(
        ImportContract("repro.compose", ("jax",), recursive=True),))
    findings = rule.run(fixture_ctx("import_exempt"))
    paths = {f.path for f in findings}
    assert "repro/compose/jax_engine.py" in paths
    assert "repro/compose/executor.py" in paths
    # the lazy importers stay clean even without the exemption
    assert "repro/compose/engine.py" not in paths
    assert "repro/compose/__init__.py" not in paths


def test_import_purity_exemption_is_shallow(tmp_path):
    # A *covered* module that eagerly imports an exempt backend still
    # drags jax into the import graph and must be flagged: the
    # exemption waives the backend's own imports, not chains that pass
    # through it.
    root = tmp_path / "tree"
    shutil.copytree(fx("import_exempt"), root)
    (root / "repro" / "compose" / "eager.py").write_text(
        '"""Covered module importing an exempt backend eagerly."""\n\n'
        "from repro.compose.executor import run_batch\n\n"
        "__all__ = [\"run_batch\"]\n")
    rule = ImportPurityRule(contracts=(_EXEMPT_CONTRACT,))
    findings = rule.run(AnalysisContext(str(root)))
    # anchored at the import that actually pulls jax in, with the
    # chain spelled out from the covered module
    eager = [f for f in findings
             if "repro.compose.eager" in f.message]
    assert eager, findings
    assert eager[0].path == "repro/compose/executor.py"
    assert ("repro.compose.eager -> repro.compose.executor"
            in eager[0].message)


# ---------------------------------------------------------------------------
# jax-compat
# ---------------------------------------------------------------------------

def test_jax_compat_flags_direct_spellings_outside_the_shim(tmp_path):
    pkg = tmp_path / "repro"
    pkg.mkdir()
    files = {
        "compat.py": "import jax\n\ndef enable_x64():\n"
                     "    return jax.enable_x64(True)\n",
        "old.py": "from jax.experimental import enable_x64\n",
        "new.py": "import jax\n\nwith jax.enable_x64(True):\n"
                  "    pass\n",
        "clean.py": "from repro.compat import enable_x64\n\n"
                    "with enable_x64():\n    pass\n",
    }
    for name, src in files.items():
        (pkg / name).write_text(src)
    findings = JaxCompatRule().run(AnalysisContext(str(tmp_path)))
    assert sorted((f.path, f.line) for f in findings) == [
        ("repro/new.py", 3), ("repro/old.py", 1)]
    assert all("repro.compat" in f.remediation for f in findings)


# ---------------------------------------------------------------------------
# dtype-safety
# ---------------------------------------------------------------------------

def test_dtype_rule_flags_every_construction_hazard():
    findings = DtypeSafetyRule(
        scope=("repro/backends/*.py",)).run(fixture_ctx("dtype_bad"))
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 5
    assert "np.zeros(dtype=int32) feeds 'addr_buf'" in msgs
    assert "dtype-less np.asarray() feeds 'time_arr'" in msgs
    assert "dtype-less np.asarray() feeds 'addr'" in msgs
    assert "Trace(time_cycles=...)" in msgs
    assert "cycle_stamps.astype(int32)" in msgs
    assert all(f.path == "repro/backends/sim.py" for f in findings)
    assert all(f.remediation for f in findings)


def test_dtype_rule_clean_twin():
    findings = DtypeSafetyRule(
        scope=("repro/backends/*.py",)).run(fixture_ctx("dtype_ok"))
    # explicit int64, int32-on-subpartition, and dtype-preserving
    # re-wraps are all fine
    assert findings == []


# ---------------------------------------------------------------------------
# registry-conformance
# ---------------------------------------------------------------------------

def test_registry_rule_flags_every_failure_mode():
    findings = RegistryConformanceRule().run(fixture_ctx("registry_bad"))
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 13
    assert "duplicate workload registration 'dup'" in msgs
    assert "workload alias 'dup' collides" in msgs
    assert "registers no backends" in msgs
    assert "3 required positional parameter(s)" in msgs
    assert "neither a literal decorator name" in msgs
    assert "duplicate backend registration 'sim'" in msgs
    assert "no run() method" in msgs
    assert "no `mode` attribute" in msgs
    assert "missing/stale for alias 'fast'" in msgs
    assert "'gone'" in msgs
    assert "duplicate device-family registration 'cell'" in msgs
    assert "device-family alias 'cell' collides" in msgs
    assert ("device-family builder 'build_other' takes 2 required "
            "positional parameter(s)") in msgs


def test_registry_rule_clean_twin_accepts_factory_idiom():
    assert RegistryConformanceRule().run(fixture_ctx("registry_ok")) == []


# ---------------------------------------------------------------------------
# atomic-write + suppressions + baselines
# ---------------------------------------------------------------------------

def test_atomic_rule_flags_raw_writes():
    findings = AtomicWriteRule().run(fixture_ctx("atomic_bad"))
    # the bare rule sees both raw opens; suppressions are a layer above
    assert len(findings) == 2
    assert all(f.rule == "atomic-write" for f in findings)
    assert all("open(..., 'w')" in f.message for f in findings)


def test_atomic_rule_clean_twin_accepts_sanctioned_idioms():
    # tmp+os.replace, O_EXCL fd, and append-only logs: all exempt
    assert AtomicWriteRule().run(fixture_ctx("atomic_ok")) == []


def test_inline_suppression_drops_only_the_waived_finding():
    findings = run_check(root=fx("atomic_bad"),
                         rules=(AtomicWriteRule(),))
    assert len(findings) == 1
    ctx = fixture_ctx("atomic_bad")
    lines = ctx.source_lines(ctx.abs(findings[0].path))
    assert "allow(atomic-write)" not in lines[findings[0].line - 1]


def test_baseline_roundtrip(tmp_path):
    findings = run_check(root=fx("atomic_bad"),
                         rules=(AtomicWriteRule(),))
    assert findings
    baseline = tmp_path / "baseline.json"
    write_baseline(findings, str(baseline))
    survivors = run_check(root=fx("atomic_bad"),
                          rules=(AtomicWriteRule(),),
                          baseline=load_baseline(str(baseline)))
    assert survivors == []


# ---------------------------------------------------------------------------
# schema-drift: pin -> edit -> detect
# ---------------------------------------------------------------------------

def _copy_schema_fixture(tmp_path):
    root = str(tmp_path / "tree")
    shutil.copytree(fx("schema"), root)
    return root


def test_schema_drift_roundtrip(tmp_path):
    root = _copy_schema_fixture(tmp_path)
    rule = SchemaDriftRule()

    # unpinned tree: the missing manifest is itself a finding
    [f] = rule.run(AnalysisContext(root))
    assert "manifest missing" in f.message

    update_schema_manifest(AnalysisContext(root))
    assert rule.run(AnalysisContext(root)) == []

    # comments / docstrings / moving code never trip the fingerprint
    campaign = os.path.join(root, "repro", "launch", "campaign.py")
    src = open(campaign).read()
    open(campaign, "w").write(src.replace(
        "SCHEMA_VERSION = 1",
        "# a comment, some blank lines\n\n\nSCHEMA_VERSION = 1"))
    assert rule.run(AnalysisContext(root)) == []

    # a semantic edit to the key without a version bump: the bug
    src = open(campaign).read()
    open(campaign, "w").write(src.replace(
        ':{backend}"', ':{backend}:salt"'))
    [f] = rule.run(AnalysisContext(root))
    assert f.path == "repro/launch/campaign.py"
    assert "changed but SCHEMA_VERSION is still 1" in f.message
    assert "--update-schema-manifest" in f.remediation

    # bumping the version flips the finding to "manifest is stale"
    src = open(campaign).read()
    open(campaign, "w").write(src.replace(
        "SCHEMA_VERSION = 1", "SCHEMA_VERSION = 2"))
    [f] = rule.run(AnalysisContext(root))
    assert "manifest still pins" in f.message

    # re-pinning closes the loop
    update_schema_manifest(AnalysisContext(root))
    assert rule.run(AnalysisContext(root)) == []


def test_real_cache_key_edit_without_bump_is_caught(tmp_path):
    """The acceptance scenario, against the *real* pinned functions: a
    deliberate edit to CampaignRunner._key with no SCHEMA_VERSION bump
    must produce a schema-drift finding."""
    src_root = default_root()
    for rel in ("repro/launch/campaign.py", "repro/workloads/spec.py",
                "repro/analysis/schema_manifest.json"):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(src_root, rel), dst)
    assert SchemaDriftRule().run(AnalysisContext(str(tmp_path))) == []

    campaign = tmp_path / "repro" / "launch" / "campaign.py"
    src = campaign.read_text()
    needle = '"policy": self.policy,'
    assert needle in src, "cache-key payload changed; update this test"
    campaign.write_text(src.replace(
        needle, '"policy": self.policy, "salt": 1,'))
    findings = SchemaDriftRule().run(AnalysisContext(str(tmp_path)))
    assert len(findings) == 1
    assert "CampaignRunner._key" in findings[0].message
    assert "SCHEMA_VERSION" in findings[0].message


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_exit_codes():
    assert check_main(["--root", fx("import_ok")]) == 0
    assert check_main(["--root", fx("atomic_bad")]) == 1
    assert check_main(["--root", fx("atomic_bad"),
                       "--rules", "no-such-rule"]) == 2
    assert check_main(["--root", os.path.join(FIXTURES, "missing")]) == 2


def test_cli_json_format(capsys):
    rc = check_main(["--root", fx("atomic_bad"), "--format", "json"])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert data["count"] == 1
    [finding] = data["findings"]
    assert finding["rule"] == "atomic-write"
    assert finding["path"] == "repro/cluster/state.py"
    assert finding["remediation"]


def test_cli_list_rules(capsys):
    assert check_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in default_rules():
        assert rule.id in out


def test_cli_write_baseline_then_clean(tmp_path):
    baseline = str(tmp_path / "baseline.json")
    assert check_main(["--root", fx("atomic_bad"),
                       "--write-baseline", "--baseline", baseline]) == 0
    assert check_main(["--root", fx("atomic_bad"),
                       "--baseline", baseline]) == 0


# ---------------------------------------------------------------------------
# the lock: the repo's own tree stays clean
# ---------------------------------------------------------------------------

def test_repo_tree_is_clean():
    """`python -m repro check` on the real source tree reports nothing:
    the contracts in docs/API.md hold at head."""
    assert run_check() == []


def test_repo_schema_manifest_is_committed():
    manifest = os.path.join(default_root(), "repro", "analysis",
                            "schema_manifest.json")
    assert os.path.isfile(manifest)
    data = json.load(open(manifest))
    assert set(data) == {"schema_version", "fingerprints"}
    assert len(data["fingerprints"]) == 2
