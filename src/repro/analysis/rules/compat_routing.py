"""compat-routing: version-sensitive JAX surfaces only in ``repro.compat``.

PR 1's lesson: a renamed JAX symbol at module scope silently drops whole
test modules at collection.  The fix was to route every version-sensitive
surface through ``src/repro/compat.py`` — and a string grep-ban in
tests/test_import_sweep.py to keep it that way.  This rule is that ban as
a real AST check (strings and comments no longer trip it; imports,
attribute chains and call vocabulary do):

- the banned *names* ``AxisType`` / ``CompilerParams`` /
  ``TPUCompilerParams`` may not be referenced (as imports, names, or
  attributes) outside the shim;
- ``shard_map`` must be spelled ``compat.shard_map`` — direct
  ``jax.shard_map`` / ``jax.experimental.shard_map`` imports or attribute
  chains are flagged, as is the legacy ``check_rep=`` vocabulary;
- ``pallas_call`` must be spelled ``compat.pallas_call`` (that is where
  the compile-on-TPU / interpret-on-CPU choice lives) — ``pl.pallas_call`` and
  ``from jax.experimental.pallas import pallas_call`` are flagged, and an
  ``interpret=`` keyword on such a direct call is flagged on its own line
  so the fix is obvious.
"""
from __future__ import annotations

import ast

from repro.analysis.engine import (Finding, ParsedModule, Rule, dotted_name)

BANNED_NAMES = ("AxisType", "CompilerParams", "TPUCompilerParams")

#: modules whose import is itself a routing violation
BANNED_IMPORT_MODULES = ("jax.experimental.shard_map",)

#: function names that must only ever be reached through ``compat.``
ROUTED_FUNCS = ("shard_map", "pallas_call")


def _base_is_compat(dotted: str) -> bool:
    """True for ``compat.shard_map`` / ``repro.compat.pallas_call``."""
    parts = dotted.split(".")
    return len(parts) >= 2 and parts[-2] == "compat"


class CompatRoutingRule(Rule):
    name = "compat-routing"
    description = ("version-sensitive JAX surfaces (AxisType, CompilerParams, "
                   "shard_map, pallas_call/interpret=) must route through "
                   "repro.compat")
    # the one rule that also covers tests/benches/examples, like the grep
    # ban it replaces
    roots = ("src", "tests", "benchmarks", "examples")
    exclude = (
        "src/repro/compat.py",       # the shim itself
        "tests/test_compat.py",      # spells both branches via monkeypatch
        "tests/test_import_sweep.py",
    )

    def check_module(self, mod: ParsedModule) -> list[Finding]:
        out: list[Finding] = []

        def flag(node: ast.AST, msg: str) -> None:
            out.append(mod.finding(self.name, node, msg))

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom):
                module = node.module or ""
                if module in BANNED_IMPORT_MODULES:
                    flag(node, f"import of '{module}' outside compat.py; "
                               "use repro.compat.shard_map")
                for alias in node.names:
                    if alias.name in BANNED_NAMES:
                        flag(node, f"import of version-sensitive name "
                                   f"'{alias.name}' outside compat.py")
                    if alias.name in ROUTED_FUNCS and module.startswith("jax"):
                        flag(node, f"direct import of '{alias.name}' from "
                                   f"'{module}'; use repro.compat."
                                   f"{alias.name}")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name in BANNED_IMPORT_MODULES:
                        flag(node, f"import of '{alias.name}' outside "
                                   "compat.py; use repro.compat.shard_map")
            elif isinstance(node, ast.Name):
                if node.id in BANNED_NAMES:
                    flag(node, f"version-sensitive name '{node.id}' outside "
                               "compat.py (route through the compat shim)")
            elif isinstance(node, ast.Attribute):
                if node.attr in BANNED_NAMES:
                    flag(node, f"version-sensitive attribute '.{node.attr}' "
                               "outside compat.py (route through the compat "
                               "shim)")
                elif node.attr in ROUTED_FUNCS:
                    dotted = dotted_name(node)
                    if dotted and not _base_is_compat(dotted):
                        flag(node, f"'{dotted}' bypasses the compat shim; "
                                   f"use compat.{node.attr} (the "
                                   "backend dispatch / argument "
                                   "translation live there)")
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "check_rep":
                        flag(kw.value, "legacy shard_map vocabulary "
                                       "'check_rep='; compat.shard_map "
                                       "accepts the new 'check_vma='")
                    elif kw.arg == "interpret":
                        dotted = dotted_name(node.func) or ""
                        if (dotted.split(".")[-1] == "pallas_call"
                                and not _base_is_compat(dotted)):
                            flag(kw.value, "'interpret=' on a direct "
                                           "pallas_call; route through "
                                           "compat.pallas_call")
        return out
