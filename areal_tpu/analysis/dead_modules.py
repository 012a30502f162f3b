"""C4 — dead modules: package code no runtime entry point can reach.

VERDICT r5 flagged `dataset/gsm8k_synth.py` shipped with zero importers;
this checker finds that class mechanically.  Semantics: a module under the
package is ALIVE iff it is reachable through the import graph from a
non-test root:

- roots are every scanned file OUTSIDE the package tree (scripts/,
  examples/, benchmarks/, other top-level modules) plus any package module
  with an ``if __name__ == "__main__":`` guard (an executable entry
  point, e.g. `python -m areal_tpu.gen.server`);
- edges are `import` / `from ... import ...` statements (relative imports
  resolved), `importlib.import_module("...")` / `__import__("...")` with
  literal arguments, and dotted `areal_tpu.*` strings in alive files
  (launchers spawn `python -m areal_tpu...` command lines);
- importing a submodule executes its parent packages, so parents of alive
  modules are alive; a package `__init__` keeps its submodules alive only
  via its own (re-export) imports.

Reachability — not direct-importer counting — is deliberate: a package
whose `__init__` imports its own submodules but which nothing outside
imports is dead as a whole, and must not keep itself alive through the
internal cycle.  Test-only importers (anything under tests/) never count.

Suppression is file-scoped: a ``# areal-lint: disable=dead-module
<reason>`` anywhere in the module marks it an intentional library/
experimental surface.
"""

import ast
import os
import re
from typing import Dict, List, Set

from areal_tpu.analysis.core import Finding, SourceFile

RULE = "dead-module"

_DOTTED_STR_RE_TMPL = r"{pkg}(?:\.[A-Za-z_]\w*)+"


def _module_name(rel: str) -> str:
    parts = rel[:-3].split(os.sep)  # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _has_main_guard(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.If):
            test = node.test
            if isinstance(test, ast.Compare):
                names = [
                    n.id for n in ast.walk(test) if isinstance(n, ast.Name)
                ]
                consts = [
                    c.value
                    for c in ast.walk(test)
                    if isinstance(c, ast.Constant)
                ]
                if "__name__" in names and "__main__" in consts:
                    return True
    return False


def _imports_of(sf: SourceFile, rel: str, pkg: str) -> Set[str]:
    """Dotted module names referenced by this file (absolute, with
    relative imports resolved against the file's package path)."""
    out: Set[str] = set()
    if sf.tree is None:
        return out
    # containing package = the file's directory, for modules and for
    # __init__ alike (relative level L resolves against it minus L-1)
    file_pkg = rel[:-3].split(os.sep)[:-1]
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.add(a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = file_pkg[: len(file_pkg) - (node.level - 1)]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            if mod:
                out.add(mod)
                for a in node.names:
                    out.add(f"{mod}.{a.name}")
        elif isinstance(node, ast.Call):
            fname = ""
            if isinstance(node.func, ast.Attribute):
                fname = node.func.attr
            elif isinstance(node.func, ast.Name):
                fname = node.func.id
            if fname in ("import_module", "__import__") and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    out.add(arg.value)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            for m in re.findall(
                _DOTTED_STR_RE_TMPL.format(pkg=re.escape(pkg)), node.value
            ):
                out.add(m)
    return out


def check_dead_modules(
    root: str, files: Dict[str, SourceFile], package: str = "areal_tpu"
) -> List[Finding]:
    pkg_prefix = package + os.sep
    modules: Dict[str, str] = {}  # dotted -> rel path
    for rel in files:
        if rel.startswith(pkg_prefix):
            modules[_module_name(rel)] = rel

    imports: Dict[str, Set[str]] = {
        rel: _imports_of(sf, rel, package) for rel, sf in files.items()
    }

    # seed: non-package files and executable package modules
    alive: Set[str] = set()
    queue: List[str] = []

    def mark(dotted: str):
        # a reference to pkg.a.b executes pkg and pkg.a on the way in
        parts = dotted.split(".")
        for i in range(1, len(parts) + 1):
            prefix = ".".join(parts[:i])
            if prefix in modules and prefix not in alive:
                alive.add(prefix)
                queue.append(prefix)

    for rel, sf in files.items():
        if rel.startswith(pkg_prefix):
            if sf.tree is not None and _has_main_guard(sf.tree):
                mark(_module_name(rel))
        else:
            for name in imports[rel]:
                mark(name)

    while queue:
        dotted = queue.pop()
        rel = modules[dotted]
        for name in imports.get(rel, ()):
            mark(name)

    findings: List[Finding] = []
    for dotted, rel in sorted(modules.items()):
        if dotted in alive or dotted == package:
            continue
        sf = files[rel]
        f = Finding(
            RULE,
            rel,
            1,
            f"module `{dotted}` is unreachable from any non-test entry "
            "point (scripts/, examples/, top-level modules, or a "
            "__main__ guard) — dead code: wire it in, delete it, or "
            "suppress with a reason",
        )
        sup = sf.file_suppression_for(RULE)
        if sup is not None:
            sup.used = True
            f.suppressed = True
            f.suppress_reason = sup.reason or "(no reason)"
        findings.append(f)
    return findings


def scan_tree(root: str, package: str) -> List[Finding]:
    """Standalone entry for fixture trees: load + check in one call."""
    from areal_tpu.analysis.core import load_files

    return check_dead_modules(root, load_files(root), package=package)
