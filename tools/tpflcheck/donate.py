"""Donation lint: a donated buffer is DEAD after dispatch.

``jax.jit(..., donate_argnums=...)`` CONSUMES the listed arguments —
the dispatch aliases (or frees) their buffers, and the caller's Python
binding keeps pointing at the deleted array. Any later use raises
``RuntimeError: Array has been deleted`` at best, and at worst only on
the backend that actually honors the donation — exactly the class of
bug the PR-9 verify drive hit by hand (``run_rounds`` re-stacking
committed inputs) and PR-13's donated-by-default engine path makes
easy to reintroduce.

The lint catches the locally-visible form statically, per function
scope over ``tpfl/``:

1. a callable known to donate: a name bound to
   ``jax.jit(f, donate_argnums=<literal>)`` in the same scope/module,
   or a function decorated with ``@partial(jax.jit,
   donate_argnums=...)`` / ``@jax.jit`` carrying the kwarg;
2. a call of that callable whose donated positions are plain NAME
   arguments;
3. a READ of one of those names on a later line of the same function,
   with no intervening rebind of the name.

Indirect dispatch (``fn(*args)``, attribute-held programs, donation
decided at a different call depth) is out of static reach — the lint
is best-effort on the engine/learner seams, and waivable
(``donate:<file>::<scope>::<name>``). The dynamic complement is
``tests/test_engine_wire.py::test_donation_report_clean``
(``tpfl.parallel.engine.donation_analysis``), which checks what the
compiled executable really aliases.
"""

from __future__ import annotations

import ast
import pathlib
from typing import Optional

from tools.tpflcheck import core
from tools.tpflcheck.core import Violation, py_files, rel, repo_root


def _is_jax_jit(node: ast.AST) -> bool:
    """``jax.jit`` (Attribute) or bare ``jit`` imported from jax."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return isinstance(node.value, ast.Name) and node.value.id == "jax"
    return isinstance(node, ast.Name) and node.id == "jit"


def _donated_positions(call: ast.Call) -> Optional[tuple[int, ...]]:
    """Donated argnums when ``call`` is a jax.jit(...) (or
    partial(jax.jit, ...)) carrying a LITERAL donate_argnums."""
    fn = call.func
    if isinstance(fn, ast.Name) and fn.id == "partial" and call.args:
        if not _is_jax_jit(call.args[0]):
            return None
    elif not _is_jax_jit(fn):
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            out = []
            for elt in v.elts:
                if not (
                    isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)
                ):
                    return None  # dynamic — out of static reach
                out.append(elt.value)
            return tuple(out)
        return None  # dynamic expression (e.g. the engine's `dn`)
    return None


def _collect_donating(tree: ast.Module) -> dict[str, tuple[int, ...]]:
    """name -> donated positions, for every statically-visible donating
    callable in the module: assignments of jax.jit(...) results and
    decorated function defs. Scope-flattened (the lint only ever
    matches calls by bare name, so a shadowed name just re-binds)."""
    donating: dict[str, tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            pos = _donated_positions(node.value)
            if pos:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        donating[t.id] = pos
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    pos = _donated_positions(dec)
                    if pos:
                        donating[node.name] = pos
    return donating


def _scope_events(fn: ast.AST, donating: dict[str, tuple[int, ...]]):
    """(donating calls, name loads, name stores) within one function
    scope, excluding nested function/class bodies (their bindings are
    their own scope)."""
    calls: list[tuple[int, str, str]] = []  # (line, donated name, callee)
    loads: list[tuple[int, str]] = []
    stores: list[tuple[int, str]] = []

    def visit(node, top=False):
        if not top and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            return  # nested scope
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            pos = donating.get(node.func.id)
            if pos:
                for i in pos:
                    if i < len(node.args) and isinstance(
                        node.args[i], ast.Name
                    ):
                        calls.append(
                            (node.lineno, node.args[i].id, node.func.id)
                        )
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load):
                loads.append((node.lineno, node.id))
            else:
                stores.append((node.lineno, node.id))
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(fn, top=True)
    return calls, loads, stores


def check_donate(repo: "pathlib.Path | None" = None) -> list[Violation]:
    root = repo_root(repo)
    violations: list[Violation] = []
    for path in py_files(root, "tpfl"):
        try:
            tree = core.parse(path)
        except SyntaxError:
            continue
        donating = _collect_donating(tree)
        if not donating:
            continue
        scopes: list[tuple[str, ast.AST]] = [("<module>", tree)]
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append((node.name, node))
        f = rel(root, path)
        for qual, scope in scopes:
            calls, loads, stores = _scope_events(scope, donating)
            for call_line, name, callee in calls:
                # a Store at or after the call line re-binds the name
                # (covers `p = step(p, x)`, the canonical safe shape)
                rebinds = sorted(
                    ln for ln, n in stores if n == name and ln >= call_line
                )
                for load_line, load_name in loads:
                    if load_name != name or load_line <= call_line:
                        continue
                    if rebinds and rebinds[0] <= load_line:
                        break  # re-bound before (or at) this read
                    violations.append(
                        Violation(
                            "donate", f, load_line,
                            f"`{name}` was donated to `{callee}(...)` on "
                            f"line {call_line} and is read again here — "
                            "a donated buffer is deleted by the "
                            "dispatch; re-bind from the program's "
                            "outputs instead",
                            f"donate:{f}::{qual}::{name}",
                        )
                    )
                    break  # one finding per (call, name)
    return violations
