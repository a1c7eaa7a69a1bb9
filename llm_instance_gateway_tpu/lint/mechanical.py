"""Mechanical layer: the ruff-equivalent checks (``mech-*`` rules).

The committed ``pyproject.toml`` configures ruff (F401 unused imports, F811
redefinitions, F821 undefined names, B006 mutable default arguments) and
``make lint`` runs it when the binary exists.  The container images this
repo grows on do not all ship ruff, so the two highest-value checks are
reimplemented here as a fallback — the invariant linter must not silently
lose its mechanical layer on a machine without the tool:

- ``mech-unused-import``: an import bound but never referenced (module
  ``__init__.py`` re-export files are exempt, matching the ruff per-file
  ignore; ``# noqa`` on the import line is honored).
- ``mech-mutable-default``: a list/dict/set literal (or constructor call)
  as a parameter default — shared across calls, the classic aliasing bug.
"""

from __future__ import annotations

import ast
import re

from llm_instance_gateway_tpu.lint import PKG, Finding, Tree, rule

_IDENT_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def _scan_targets(tree: Tree) -> list[str]:
    # Same scope ruff scans (pyproject.toml): the package, tools/, tests/
    # — the two layers must agree on what "clean" means, or a ruff-less
    # host certifies a tree a ruff-ful CI then rejects.
    return [f for f in tree.py_files(PKG, "tools", "tests",
                                     exclude=(f"{PKG}/lint/",))
            if not f.endswith("_pb2.py")          # generated protobuf
            and not f.endswith("_pb2_grpc.py")]


def _noqa_lines(src: str) -> set[int]:
    return {i for i, line in enumerate(src.splitlines(), 1)
            if "# noqa" in line}


def _annotation_idents(mod: ast.Module) -> set[str]:
    """Identifiers inside string annotations (``from __future__ import
    annotations`` keeps real names as AST, but quoted forward refs are
    plain strings)."""
    idents: set[str] = set()

    def from_node(node: ast.AST | None) -> None:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            idents.update(_IDENT_RE.findall(node.value))

    for node in ast.walk(mod):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for arg in (list(node.args.args) + list(node.args.posonlyargs)
                        + list(node.args.kwonlyargs)
                        + [node.args.vararg, node.args.kwarg]):
                if arg is not None:
                    from_node(arg.annotation)
            from_node(node.returns)
        elif isinstance(node, ast.AnnAssign):
            from_node(node.annotation)
    return idents


@rule("mech-unused-import")
def check_unused_imports(tree: Tree) -> list[Finding]:
    findings: list[Finding] = []
    for rel in _scan_targets(tree):
        if rel.endswith("__init__.py"):
            continue  # re-export surface (ruff: per-file-ignores F401)
        src = tree.read(rel)
        mod = tree.parse(rel)
        if src is None or mod is None:
            continue
        noqa = _noqa_lines(src)
        bound: list[tuple[str, int]] = []  # (name, lineno)
        for node in ast.walk(mod):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    bound.append((name, node.lineno))
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound.append((alias.asname or alias.name, node.lineno))
        if not bound:
            continue
        used: set[str] = set()
        for node in ast.walk(mod):
            if isinstance(node, ast.Name):
                used.add(node.id)
            elif isinstance(node, ast.Attribute):
                pass  # base resolves through a Name node anyway
        used |= _annotation_idents(mod)
        # __all__ entries count as use (re-export).
        for node in ast.walk(mod):
            if (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "__all__"
                            for t in node.targets)
                    and isinstance(node.value, (ast.List, ast.Tuple))):
                for el in node.value.elts:
                    if isinstance(el, ast.Constant) and isinstance(
                            el.value, str):
                        used.add(el.value)
        for name, lineno in bound:
            if name in used or lineno in noqa:
                continue
            findings.append(Finding(
                "mech-unused-import", rel, lineno,
                f"{name!r} imported but unused"))
    return findings


_MUTABLE_CTORS = {"list", "dict", "set"}


@rule("mech-mutable-default")
def check_mutable_defaults(tree: Tree) -> list[Finding]:
    findings: list[Finding] = []
    for rel in _scan_targets(tree):
        src = tree.read(rel)
        mod = tree.parse(rel)
        if src is None or mod is None:
            continue
        noqa = _noqa_lines(src)
        for fn in ast.walk(mod):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            defaults = list(fn.args.defaults) + [
                d for d in fn.args.kw_defaults if d is not None]
            for d in defaults:
                mutable = isinstance(d, (ast.List, ast.Dict, ast.Set,
                                         ast.ListComp, ast.DictComp,
                                         ast.SetComp)) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in _MUTABLE_CTORS)
                if mutable and d.lineno not in noqa:
                    findings.append(Finding(
                        "mech-mutable-default", rel, d.lineno,
                        f"{fn.name}: mutable default argument (shared "
                        f"across calls) — default to None and build "
                        f"inside"))
    return findings
