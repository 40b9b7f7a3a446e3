"""Timing/logging-path lint: spans and metrics are the only sanctioned
timing path.

Two invariants over ``tpfl/``, ``tools/`` and the root dry-run script
(the management layer is exempt — it IS the telemetry/profiling
implementation and owns the wall-clock anchor):

1. **No ``time.time()``** — every duration, deadline, and stamp must
   come from ``time.monotonic()`` / ``time.perf_counter()`` (NTP-step
   immunity — the aggregator stall clock and round deadlines moved
   first; this lint keeps the rest, INCLUDING the profiling
   subsystem's call sites, from regressing) or
   flow through the spans in :mod:`tpfl.management.tracing` /
   :mod:`tpfl.management.profiling`, which timestamp monotonically and
   carry the process wall anchor for cross-process merges.

2. **No raw ``logging`` calls** — ``logging.getLogger``/``logging.info``
   etc. bypass the framework logger's routing (node tagging, async
   queue, web push) and the metrics registry. Everything observable
   goes through ``tpfl.management.logger`` / ``logger.metrics``.

AST-based (docstrings and comments mentioning ``time.time()`` don't
count — only actual call sites).
"""

from __future__ import annotations

import ast
import pathlib

from tools.tpflcheck import core
from tools.tpflcheck.core import Violation, py_files, rel, repo_root

#: Modules exempt from the lint: the management layer implements the
#: telemetry/logging machinery itself (the flight recorder's wall
#: anchor is the one sanctioned ``time.time()`` call). NEW management
#: modules are NOT automatically exempt — they consume the telemetry
#: core like everyone else; the ledger (PR 7) is the first one linted.
ALLOWED_PREFIX = "tpfl/management/"

#: Management modules the lint DOES cover (consumers of the telemetry
#: core, not implementors of it).
LINTED_MANAGEMENT = (
    "tpfl/management/ledger.py",
    "tpfl/management/quarantine.py",
    "tpfl/management/engine_obs.py",
)

_LOGGING_CALLS = {
    "debug", "info", "warning", "error", "critical", "exception",
    "log", "getLogger", "basicConfig",
}


#: Root-level scripts with timing code the lint also covers (their
#: timing must ride monotonic()/perf_counter() or the profiling API,
#: same as the package).
ROOT_SCRIPTS = ("__graft_entry__.py",)


def _lint_files(root: "pathlib.Path") -> "list[pathlib.Path]":
    files = list(py_files(root))
    files += py_files(root, "tools")
    files += [root / s for s in ROOT_SCRIPTS if (root / s).exists()]
    return files


def check_trace(repo: "pathlib.Path | None" = None) -> list[Violation]:
    root = repo_root(repo)
    out: list[Violation] = []
    for path in _lint_files(root):
        r = rel(root, path)
        if r.startswith(ALLOWED_PREFIX) and r not in LINTED_MANAGEMENT:
            continue
        tree = core.parse(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (
                isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name)
            ):
                continue
            if fn.value.id == "time" and fn.attr == "time":
                out.append(
                    Violation(
                        "trace", r, node.lineno,
                        "time.time() outside tpfl/management — use "
                        "time.monotonic() (NTP-step immune) or a tracing "
                        "span (tpfl.management.tracing)",
                        f"trace:{r}:{node.lineno}",
                    )
                )
            elif fn.value.id == "logging" and fn.attr in _LOGGING_CALLS:
                out.append(
                    Violation(
                        "trace", r, node.lineno,
                        f"raw logging.{fn.attr}() outside tpfl/management — "
                        "route through tpfl.management.logger (node "
                        "tagging, async queue, metrics registry)",
                        f"trace:{r}:{node.lineno}",
                    )
                )
    return out
