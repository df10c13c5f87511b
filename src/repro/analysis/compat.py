"""jax-compat: jax-version shims are taken from ``repro.compat`` only.

jax 0.9 removed ``jax.experimental.enable_x64`` and every module that
named it stopped importing.  The scoped 64-bit switch now comes from
:func:`repro.compat.enable_x64`, so the next rename is a one-line
change.  This rule flags any other module that names jax's spelling of
a shimmed symbol: ``from jax... import enable_x64`` or a
``jax....enable_x64`` attribute.
"""

from __future__ import annotations

import ast

from repro.analysis.findings import Finding

RULE_ID = "jax-compat"
SHIM_MODULE = "repro.compat"
SHIMMED = ("enable_x64",)


def _rooted_at_jax(node: ast.expr) -> bool:
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, ast.Name) and node.id == "jax"


class JaxCompatRule:
    id = RULE_ID
    description = ("jax-version shims (enable_x64) are taken from "
                   "repro.compat, never from jax directly")

    def run(self, ctx) -> list:
        findings = []
        for path in ctx.files():
            if ctx.module_name(path) == SHIM_MODULE:
                continue
            for node in ast.walk(ctx.ast_of(path)):
                if isinstance(node, ast.ImportFrom):
                    names = [a.name for a in node.names]
                    hit = ((node.module or "").split(".")[0] == "jax"
                           and any(n in SHIMMED for n in names))
                    spelled = f"from {node.module} import {', '.join(names)}"
                elif isinstance(node, ast.Attribute):
                    hit = node.attr in SHIMMED and _rooted_at_jax(node)
                    spelled = ast.unparse(node)
                else:
                    continue
                if hit:
                    findings.append(Finding(
                        rule=self.id, path=ctx.rel(path),
                        line=node.lineno,
                        message=f"names jax's own spelling ({spelled})",
                        remediation=("use repro.compat.enable_x64(); "
                                     "version shims live in "
                                     "repro/compat.py only")))
        return findings
