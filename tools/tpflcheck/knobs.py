"""Settings-knob lint.

Four invariants over ``tpfl/settings.py``:

1. **Existence** — every ``Settings.X`` attribute reference in code
   (``tpfl/``, ``tools/``; AST-based, so docstring
   mentions don't count) names a declared knob. A typo'd knob
   silently reads as AttributeError at runtime, usually inside a
   rarely-exercised branch.
2. **Profile totality** — the three profile methods
   (``set_test_settings`` / ``set_standalone_settings`` /
   ``set_scale_settings``) must assign the SAME set of knobs. A knob
   tuned in one profile but not the others LEAKS across profile
   switches: ``set_scale_settings()`` arming ``AGGREGATION_STALL``
   and a later ``set_test_settings()`` not resetting it changes test
   behavior depending on call history — the class-level-mutable
   Settings design makes profiles correct only when they are total
   over the tuned set.
3. **Docs mention** — every declared knob appears by name somewhere in
   ``docs/*.md`` or ``README.md`` (the knob reference lives in
   docs/settings.md; this lint is what keeps it in sync).
4. **Unused knobs** are *reported* (returned as warnings, not
   violations): dead configuration is a maintenance smell but not a
   correctness bug.
"""

from __future__ import annotations

import ast
import pathlib

from tools.tpflcheck import core
from tools.tpflcheck.core import Violation, py_files, rel, repo_root

PROFILE_METHODS = (
    "set_test_settings",
    "set_standalone_settings",
    "set_scale_settings",
)


def _settings_decl(root: pathlib.Path) -> "tuple[set[str], dict[str, set[str]]]":
    """(declared knobs, profile method -> assigned knobs)."""
    path = root / "tpfl" / "settings.py"
    tree = core.parse(path)
    settings_cls = next(
        n
        for n in tree.body
        if isinstance(n, ast.ClassDef) and n.name == "Settings"
    )
    knobs: set[str] = set()
    profiles: dict[str, set[str]] = {}
    for node in settings_cls.body:
        tgt = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            tgt = node.target
        if isinstance(tgt, ast.Name) and tgt.id.isupper():
            knobs.add(tgt.id)
        if isinstance(node, ast.FunctionDef) and node.name in PROFILE_METHODS:
            assigned: set[str] = set()
            for stmt in ast.walk(node):
                if isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "cls"
                            and t.attr.isupper()
                        ):
                            assigned.add(t.attr)
            profiles[node.name] = assigned
    return knobs, profiles


def _referenced_knobs(root: pathlib.Path) -> dict[str, list[tuple[str, int]]]:
    """knob -> [(file, line)] for every ``Settings.X`` attribute access
    outside settings.py itself."""
    refs: dict[str, list[tuple[str, int]]] = {}
    files = py_files(root)
    tools_dir = root / "tools"
    if tools_dir.exists():
        files.extend(
            p
            for p in sorted(tools_dir.rglob("*.py"))
            if "__pycache__" not in p.parts
        )
    for path in files:
        r = rel(root, path)
        if r == "tpfl/settings.py":
            continue
        tree = core.parse(path)
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "Settings"
                and node.attr.isupper()
            ):
                refs.setdefault(node.attr, []).append((r, node.lineno))
    return refs


def _docs_text(root: pathlib.Path) -> str:
    chunks = []
    for p in sorted((root / "docs").glob("*.md")) if (root / "docs").exists() else []:
        chunks.append(p.read_text(encoding="utf-8"))
    readme = root / "README.md"
    if readme.exists():
        chunks.append(readme.read_text(encoding="utf-8"))
    return "\n".join(chunks)


def check_knobs(
    repo: "pathlib.Path | None" = None,
) -> "tuple[list[Violation], list[str]]":
    """Returns (violations, warnings). Warnings are the unused-knob
    report — informational, never a failure."""
    root = repo_root(repo)
    violations: list[Violation] = []
    warnings: list[str] = []
    knobs, profiles = _settings_decl(root)
    refs = _referenced_knobs(root)

    # 1. existence
    for name, sites in sorted(refs.items()):
        if name not in knobs:
            f, line = sites[0]
            violations.append(
                Violation(
                    "knobs", f, line,
                    f"Settings.{name} referenced but not declared in "
                    "tpfl/settings.py"
                    + (f" (+{len(sites) - 1} more sites)" if len(sites) > 1 else ""),
                    f"knobs:undeclared:{name}",
                )
            )

    # 2. profile totality
    if profiles:
        union: set[str] = set()
        for assigned in profiles.values():
            union |= assigned
        for method in PROFILE_METHODS:
            assigned = profiles.get(method, set())
            for name in sorted(assigned - knobs):
                violations.append(
                    Violation(
                        "knobs", "tpfl/settings.py", 0,
                        f"{method} assigns unknown knob {name}",
                        f"knobs:unknown:{method}:{name}",
                    )
                )
            missing = sorted(union - assigned)
            if missing:
                violations.append(
                    Violation(
                        "knobs", "tpfl/settings.py", 0,
                        f"{method} does not assign {missing} — profiles "
                        "must be total over the tuned-knob union, or "
                        "values leak across profile switches",
                        f"knobs:partial:{method}",
                    )
                )

    # 3. docs mention
    docs = _docs_text(root)
    for name in sorted(knobs):
        if name not in docs:
            violations.append(
                Violation(
                    "knobs", "tpfl/settings.py", 0,
                    f"knob {name} is not mentioned anywhere in docs/ or "
                    "README.md (add it to docs/settings.md)",
                    f"knobs:undocumented:{name}",
                )
            )

    # 4. unused report (warnings only)
    for name in sorted(knobs - set(refs)):
        warnings.append(
            f"knob Settings.{name} is declared but never referenced in "
            "tpfl/ or tools/"
        )
    return violations, warnings
