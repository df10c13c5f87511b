"""Static contract analysis over the repo's own AST: ``python -m repro check``.

Six PRs in, GainSight's correctness rests on architectural contracts
that no single test file owns: stdlib-only-at-import planning modules,
the int64 end-to-end trace contract, registry conformance, the
``SCHEMA_VERSION`` trace-cache key, and the tmp-file+``os.replace`` /
``O_EXCL`` write discipline of the distributed store.  This package
makes each of them machine-checkable: a pluggable set of AST rules runs
over the source tree (never importing it) and emits structured findings
with remediations, inline ``# repro: allow(<rule>)`` suppressions, a
committed-baseline mechanism, and ``--format json`` for CI artifacts.

Rules (see docs/API.md, "Architecture contracts"):

  import-purity         declared stdlib-only modules never transitively
                        import jax/numpy at module import time
  dtype-safety          time/addr trace arrays are constructed with an
                        explicit dtype and never narrowed to int32
  registry-conformance  @register_workload/@register_backend sites have
                        the required shape; no duplicate names or alias
                        collisions; the workload-side backend alias map
                        stays in sync with the backend registry
  schema-drift          an AST fingerprint of the trace-cache key
                        functions is pinned in schema_manifest.json;
                        changing the key without bumping SCHEMA_VERSION
                        fails the check
  atomic-write          cluster/ and checkpoint/ never write files with
                        a raw ``open(path, "w")`` outside the
                        tmp-file+rename / O_EXCL helpers
  jax-compat            jax-version shims (``enable_x64``) come from
                        ``repro.compat`` only

Import contract: this package is stdlib-only (it must run in CI and in
campaign planning environments without jax/numpy) — and declares itself
so in its own import-purity contract.
"""

from repro.analysis.context import AnalysisContext, default_root
from repro.analysis.findings import (Finding, filter_baseline,
                                     filter_suppressed, load_baseline,
                                     write_baseline)
from repro.analysis.imports import ImportContract, ImportPurityRule
from repro.analysis.dtypes import DtypeSafetyRule
from repro.analysis.registry import RegistryConformanceRule
from repro.analysis.schema import SchemaDriftRule, update_schema_manifest
from repro.analysis.atomic import AtomicWriteRule
from repro.analysis.compat import JaxCompatRule


def default_rules():
    """The repo's rule set, in stable reporting order."""
    return (ImportPurityRule(), DtypeSafetyRule(),
            RegistryConformanceRule(), SchemaDriftRule(),
            AtomicWriteRule(), JaxCompatRule())


def run_check(root: str | None = None, rules=None,
              baseline: dict | None = None) -> list:
    """Run ``rules`` (default: all six) over the tree at ``root`` and
    return the surviving findings — suppressions and the baseline
    already applied, sorted for stable output."""
    ctx = AnalysisContext(default_root() if root is None else root)
    out: list = []
    for rule in (default_rules() if rules is None else rules):
        out.extend(rule.run(ctx))
    out = filter_suppressed(out, ctx)
    if baseline is not None:
        out = filter_baseline(out, baseline)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule, f.message))


__all__ = [
    "AnalysisContext", "AtomicWriteRule", "DtypeSafetyRule", "Finding",
    "ImportContract", "ImportPurityRule", "JaxCompatRule",
    "RegistryConformanceRule", "SchemaDriftRule", "default_root",
    "default_rules", "filter_baseline",
    "filter_suppressed", "load_baseline", "run_check",
    "update_schema_manifest", "write_baseline",
]
