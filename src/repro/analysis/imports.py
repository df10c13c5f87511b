"""import-purity: prove stdlib-only-at-import contracts over the AST.

Several subsystems promise to be cheap to import — campaign planning,
``--dry-run``/``--status``, and test collection all depend on it (the
PR-4 contract).  Until now each promise was guarded by one subprocess
test asserting ``'jax' not in sys.modules``; this rule proves the same
property statically, for *every* declared module, with the full import
chain in the finding.

The module-level import graph counts every import statement that
executes at import time: top-level statements, class bodies, ``try``/
``if`` blocks (conservatively both branches) — but not function bodies
(the lazy-import idiom the contracts are built on) and not
``if TYPE_CHECKING:`` blocks.  Importing ``repro.a.b`` also executes
``repro/a/__init__.py``, so internal edges include existing package
ancestors.
"""

from __future__ import annotations

import ast
import dataclasses

from repro.analysis.findings import Finding

RULE_ID = "import-purity"


@dataclasses.dataclass(frozen=True)
class ImportContract:
    """One declared contract: ``module`` (and its submodules when
    ``recursive``) must not transitively import any ``banned``
    top-level external package at import time.

    ``exempt`` names submodules excluded from the contract — the
    designated lazy-import backends (e.g. the jax engine modules under
    ``repro.compose``).  Exemption is *shallow*: an exempt module may
    import the banned package itself, but any covered module that
    imports an exempt module at module level still reaches the banned
    package through it and is flagged — the analyzer proves the exempt
    modules are only ever imported lazily."""
    module: str
    banned: tuple
    recursive: bool = False
    exempt: tuple = ()

    def covers(self, module: str) -> bool:
        if module in self.exempt:
            return False
        return module == self.module or (
            self.recursive and module.startswith(self.module + "."))


#: The repo's declared stdlib-only-at-import surface.  compose.policies
#: is numpy+stdlib by design (PR-5: campaign planning validates policy
#: specs without jax), so only jax is banned there.
DEFAULT_CONTRACTS = (
    ImportContract("repro.workloads", ("jax", "numpy"), recursive=True),
    ImportContract("repro.devices", ("jax", "numpy"), recursive=True),
    ImportContract("repro.cluster", ("jax", "numpy"), recursive=True),
    ImportContract("repro.analysis", ("jax", "numpy"), recursive=True),
    ImportContract("repro.launch.campaign", ("jax", "numpy")),
    # recursive with the jax engine modules exempted: jax_engine and
    # executor are the only compose modules allowed to import jax at
    # import time (the engine package lazy-imports them only when
    # engine="jax" is requested); everything else — policies, engine,
    # types, the package itself — stays jax-free at import
    ImportContract("repro.compose", ("jax",), recursive=True,
                   exempt=("repro.compose.jax_engine",
                           "repro.compose.executor")),
    ImportContract("repro.__main__", ("jax", "numpy")),
    # a campaign parent resolves its workers' compile cache without jax
    ImportContract("repro.runtime.compile_cache", ("jax", "numpy")),
    # the span recorder: compose.engine imports it at module level
    ImportContract("repro.runtime.obs", ("jax", "numpy")),
)


def _is_type_checking(test: ast.expr) -> bool:
    return (isinstance(test, ast.Name) and test.id == "TYPE_CHECKING") or (
        isinstance(test, ast.Attribute) and test.attr == "TYPE_CHECKING")


def module_level_imports(ctx, path: str) -> list:
    """``(target, line)`` pairs for every import executed when ``path``
    is imported.  ``target`` is a dotted module name (internal) or the
    imported name as written (external)."""
    module = ctx.module_name(path)
    package = module.rsplit(".", 1)[0] if "." in module else ""
    out: list = []

    def visit(stmts):
        for node in stmts:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    out.append((alias.name, node.lineno))
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:      # relative: resolve against package
                    anchor = module.split(".")
                    if not path.endswith("__init__.py"):
                        anchor = anchor[:-1]
                    anchor = anchor[:len(anchor) - node.level + 1]
                    base = ".".join(anchor + ([base] if base else []))
                if base:
                    out.append((base, node.lineno))
                    # `from a.b import c` may bind submodule a.b.c
                    for alias in node.names:
                        sub = f"{base}.{alias.name}"
                        if ctx.module_path(sub) is not None:
                            out.append((sub, node.lineno))
            elif isinstance(node, ast.If):
                if _is_type_checking(node.test):
                    visit(node.orelse)
                else:
                    visit(node.body)
                    visit(node.orelse)
            elif isinstance(node, (ast.Try, ast.With)):
                visit(node.body)
                for h in getattr(node, "handlers", ()):
                    visit(h.body)
                visit(getattr(node, "orelse", ()))
                visit(getattr(node, "finalbody", ()))
            elif isinstance(node, ast.ClassDef):
                visit(node.body)    # class bodies run at import time
            # FunctionDef / AsyncFunctionDef bodies are lazy: skip
    visit(ctx.ast_of(path).body)
    _ = package
    return out


def _expand_internal(ctx, target: str):
    """A dotted internal target plus every existing package ancestor
    (their ``__init__`` modules execute on import)."""
    parts = target.split(".")
    for i in range(1, len(parts) + 1):
        mod = ".".join(parts[:i])
        if ctx.module_path(mod) is not None:
            yield mod


def build_import_graph(ctx) -> dict:
    """``{module: [(target_module_or_external, line), ...]}`` over every
    file in the tree.  Internal edges point at existing module names
    (ancestors included); external edges carry the top-level name."""
    graph: dict = {}
    for path in ctx.files():
        module = ctx.module_name(path)
        edges = []
        for target, line in module_level_imports(ctx, path):
            internal = list(_expand_internal(ctx, target))
            if internal:
                edges.extend((m, line) for m in internal)
            else:
                edges.append((target.split(".")[0], line))
        graph[module] = edges
    return graph


def trace_banned_imports(ctx, graph: dict, start: str,
                         banned: tuple) -> list:
    """BFS the import graph from ``start``; for each reachable banned
    external, return ``(external, chain, line)`` where ``chain`` is the
    module path that reaches it and ``line`` the offending import line
    in the chain's last internal module."""
    hits = []
    seen = {start}
    queue = [(start, (start,))]
    found = set()
    while queue:
        module, chain = queue.pop(0)
        for target, line in graph.get(module, ()):
            if target in graph:      # internal
                if target not in seen:
                    seen.add(target)
                    queue.append((target, chain + (target,)))
            elif target in banned and (target not in found):
                found.add(target)
                hits.append((target, chain, line))
    return hits


class ImportPurityRule:
    id = RULE_ID
    description = ("declared stdlib-only modules must not transitively "
                   "import jax/numpy at import time")

    def __init__(self, contracts=DEFAULT_CONTRACTS):
        self.contracts = tuple(contracts)

    def run(self, ctx) -> list:
        graph = build_import_graph(ctx)
        findings = []
        for contract in self.contracts:
            members = sorted(m for m in graph if contract.covers(m))
            reported: set = set()     # one finding per offending import
            for module in members:
                for ext, chain, line in trace_banned_imports(
                        ctx, graph, module, contract.banned):
                    if (chain[-1], line, ext) in reported:
                        continue
                    reported.add((chain[-1], line, ext))
                    # anchor at the import statement in the last
                    # internal module of the chain
                    last = ctx.module_path(chain[-1])
                    findings.append(Finding(
                        rule=self.id, path=ctx.rel(last), line=line,
                        message=(f"{module} transitively imports "
                                 f"{ext!r} at import time "
                                 f"(chain: {' -> '.join(chain)} -> "
                                 f"{ext}), violating its "
                                 "stdlib-only-at-import contract"),
                        remediation=(
                            "move the import inside the function that "
                            "needs it (lazy import), or drop the "
                            "dependency; planning/--dry-run paths must "
                            "stay importable without "
                            f"{ext}")))
        return findings
