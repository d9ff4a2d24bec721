"""print pass — no bare ``print(`` in framework code.

Migrated from ``ci/check_print.py`` (shim removed after its deprecation cycle).  Framework
output flows through logging or telemetry; a stray print pollutes
stdout, which ``benchmark/run.py``'s and ``chip_smoke.py``'s JSON-line
contracts and launcher scrapers treat as machine-readable.  ``visualization.py`` is exempt wholesale
(its prints are the feature); legacy ``# noqa`` honored."""

from __future__ import annotations

import ast

from ..core import Pass


class PrintPass(Pass):
    id = "print"
    title = "no bare print() in framework code"
    excluded_files = frozenset({"visualization.py"})
    legacy_tags = ("# noqa",)

    def check_source(self, src, ctx):
        findings = []
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "print":
                findings.append(self.find(
                    src, node, "bare-print",
                    "bare 'print(' in framework code (use logging or "
                    "telemetry; '# noqa' with a reason for CLI display "
                    "paths)"))
        return findings
