"""Device time by PLAIN named scopes — the names a model gives its own
parts inside ``tpfl.train`` (``ssm_scan``, ``mamba``, ``diff_attention``,
``gmu``, ``mlp``, ``head_cross_entropy``), which ``program_trace``'s
tables do not split: those sum by the ``tpfl.*`` legs of the round body.

An operation carries a scope if the scope's name is a whole component of
its ``op_name`` path, bare or inside autodiff's wrappers
(``.../transpose(jvp(mlp))/...``). Time is SELF time on the busiest
device, as everywhere in the benchmark. A trace of a program without the
scope (the parent commit's) gives None, never an error.
"""

from __future__ import annotations

import re
from typing import Optional

from benchmark import program_trace, trace_reduce


def carries(op_path: str, scope: str) -> bool:
    """``scope`` is a whole name somewhere in ``op_path``."""
    return re.search(
        rf"(?<![A-Za-z0-9_.]){re.escape(scope)}(?![A-Za-z0-9_.])", op_path
    ) is not None


def busiest_device_rows(events: list) -> tuple:
    """``(plane, [(operation name, self ns)])`` of the device with the
    most operation time; ``("", [])`` where no device has operations."""
    best = ("", [])
    busy_of_best = -1
    for plane, ops in trace_reduce.device_ops(events).items():
        rows = [(name, ns) for name, ns, _ in trace_reduce.self_times(ops)]
        busy = sum(ns for _, ns in rows)
        if busy > busy_of_best:
            busy_of_best, best = busy, (plane, rows)
    return best


def self_ms_by_scope(rows: tuple, paths: dict, scopes: tuple) -> dict:
    """scope -> self milliseconds of the operations of ``rows`` (what
    :func:`busiest_device_rows` returns) that carry it; an operation
    under two of the scopes counts under both."""
    plane, ops = rows
    of_name = paths.get(plane, {})
    out = dict.fromkeys(scopes, 0.0)
    for name, self_ns in ops:
        path = of_name.get(name, "")
        for scope in scopes:
            if carries(path, scope):
                out[scope] += self_ns / 1e6
    return out


# The readers of one run ask one after another: a trace's self times
# are worked out once (keyed by the trace file ``find_trace`` accepted).
_rows: dict = {}


def scope_ms_per_round(obs: dict, *scopes: str) -> Optional[dict]:
    """scope -> milliseconds a round for the run ``obs`` describes; None
    where there is no trace, no round, or NO operation carries any of
    the scopes (the program does not name them)."""
    found = program_trace.find_trace(obs)
    rounds = int(obs.get("trace_rounds") or 0)
    if found is None or not rounds:
        return None
    path, events, paths = found
    if path not in _rows:
        _rows.clear()
        _rows[path] = busiest_device_rows(events)
    table = self_ms_by_scope(_rows[path], paths, scopes)
    if not any(table.values()):
        return None
    return {scope: ms / rounds for scope, ms in table.items()}
