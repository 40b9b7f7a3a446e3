"""Metric-name drift lint: every ``tpfl_*`` series name registered
anywhere in ``tpfl/`` must appear in ``docs/observability.md``.

The events lint's contract, extended to the registry plane: the metric
taxonomy is DOCUMENTED DATA (the per-plane series tables in
docs/observability.md — what scrapes and dashboards key on), and a new
``metrics.counter/gauge/observe`` site whose name
never lands in the doc rots it silently. This pass closes the loop:

- **emitted** names are collected by AST walk over ``tpfl/``: the
  first argument of any ``.counter(...)`` / ``.gauge(...)`` /
  ``.observe(...)`` call when it is a ``"tpfl_"``-prefixed string
  literal — receiver-agnostic on purpose (the module singleton
  ``metrics``, ``telemetry.metrics``, a bound registry all count);
  the ``tpfl_`` prefix is what keeps unrelated ``.counter()`` methods
  out. F-strings with a constant ``tpfl_``-head
  (``f"tpfl_system_{metric}"``) lint as a name PREFIX.
- **documented** names are every backticked ``tpfl_*`` token in
  ``docs/observability.md``, with the doc's two compression
  conventions expanded: a brace FAMILY after a trailing underscore
  (``tpfl_engine_{loss,delta_norm}`` → both full names; a ``*``-tailed
  member like ``net_*`` becomes a prefix) vs a LABEL annotation after
  a full name (``tpfl_mfu{program}`` → ``tpfl_mfu``), and a trailing
  ``*`` wildcard (``tpfl_contrib_*``) covering the whole prefix.

Waivable like every check (``metrics:<name>`` keys) for deliberately
internal series — the taxonomy can evolve without the lint blocking,
but never silently.
"""

from __future__ import annotations

import ast
import pathlib
import re

from tools.tpflcheck import core
from tools.tpflcheck.core import Violation, py_files, rel, repo_root

DOC = "docs/observability.md"

_BACKTICK_RE = re.compile(r"`([^`]+)`")

_REGISTRY_CALLS = ("counter", "gauge", "observe")


def _documented_names(
    root: pathlib.Path,
) -> "tuple[set[str], set[str]]":
    """(exact names, wildcard prefixes) from the doc's backticked
    ``tpfl_*`` tokens, brace families and ``*`` wildcards expanded."""
    doc = root / DOC
    exact: set[str] = set()
    prefixes: set[str] = set()
    if not doc.exists():
        return exact, prefixes
    # Per-line matching, like the events lint: one unbalanced backtick
    # must not flip every subsequent code-span pairing.
    tokens: set[str] = set()
    for line in doc.read_text(encoding="utf-8").splitlines():
        tokens.update(
            t for t in _BACKTICK_RE.findall(line) if t.startswith("tpfl_")
        )
    for tok in tokens:
        head, brace, rest = tok.partition("{")
        if brace and head.endswith("_"):
            # Family: tpfl_engine_{loss,delta_norm} — each member is a
            # full name; a *-tailed member is a prefix.
            for member in rest.rstrip("}").split(","):
                member = member.strip()
                if member.endswith("*"):
                    prefixes.add(head + member[:-1])
                elif member:
                    exact.add(head + member)
            continue
        if brace:
            # Label annotation: tpfl_mfu{program} — the braces name
            # the series' labels, not sibling metrics.
            tok = head
        if tok.endswith("*"):
            prefixes.add(tok[:-1])
        else:
            exact.add(tok)
    return exact, prefixes


def _constant_head(node: ast.JoinedStr) -> "str | None":
    """The leading constant of an f-string metric name
    (``f"tpfl_system_{metric}"`` → ``"tpfl_system_"``), else None."""
    if node.values and isinstance(node.values[0], ast.Constant):
        head = str(node.values[0].value)
        if head.startswith("tpfl_"):
            return head
    return None


def _emitted_names(
    root: pathlib.Path,
) -> "list[tuple[str, bool, str, int]]":
    """[(name, is_prefix, file, line)] for every statically-visible
    ``tpfl_*`` registry call in tpfl/."""
    out: list[tuple[str, bool, str, int]] = []
    for path in py_files(root):
        r = rel(root, path)
        tree = core.parse(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            if (
                not isinstance(fn, ast.Attribute)
                or fn.attr not in _REGISTRY_CALLS
            ):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if arg.value.startswith("tpfl_"):
                    out.append((arg.value, False, r, arg.lineno))
            elif isinstance(arg, ast.JoinedStr):
                head = _constant_head(arg)
                if head is not None:
                    out.append((head, True, r, arg.lineno))
    return out


def check_metrics(repo: "pathlib.Path | None" = None) -> list[Violation]:
    root = repo_root(repo)
    exact, prefixes = _documented_names(root)
    out: list[Violation] = []
    for name, is_prefix, file, line in _emitted_names(root):
        if is_prefix:
            # A family head is documented when any doc name lives
            # under it, or a doc wildcard overlaps it either way.
            ok = any(e.startswith(name) for e in exact) or any(
                p.startswith(name) or name.startswith(p) for p in prefixes
            )
        else:
            ok = name in exact or any(
                name.startswith(p) for p in prefixes
            )
        if ok:
            continue
        kind = "metric-name family" if is_prefix else "metric name"
        out.append(
            Violation(
                "metrics", file, line,
                f"{kind} {name!r} is registered here but not documented "
                f"in {DOC} — add it to the series tables (or waive with "
                "a reason)",
                f"metrics:{name}",
            )
        )
    return out
