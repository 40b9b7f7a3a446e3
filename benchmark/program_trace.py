"""The program's own names in a profiler trace: device time by the round
body's named scopes (``tpfl.train``, ``tpfl.optimizer``, ``tpfl.codec``,
``tpfl.telemetry``, ``tpfl.fold``) and host time by the engine's own
spans (``tpfl:dispatch``, ``tpfl:finalize``, ... — the
``jax.profiler.TraceAnnotation`` spans of
``tpfl.management.tracing.engine_span``).

``trace_reduce.reduce_trace`` keeps HLO-name stems and the benchmark's
``bench:`` spans only, and a per-layer reader receives that reduction,
not the events. So :func:`find_trace` opens again the trace the harness
has just written, and takes it only if its reduction is the one the
reader was handed. (A later ``benchmark`` PR should pass the events in
``obs`` and delete the finder.)

Everything but :func:`op_paths` and :func:`find_trace` works on
``trace_reduce``'s plain list of events and on a mapping

    device plane -> {operation event name: its op_name path}

(``jit(tpfl_window)/while/body/tpfl.train/vmap()/...``), so that
``tests/benchmark/test_benchmark_program_trace.py`` pins the numbers on
hand-built ones. An operation belongs to the LAST ``tpfl.*`` component
of its path: the innermost scope. A fusion counts under the one path its
event carries (the compiler's choice among what it fused). Time is SELF
time (``trace_reduce.self_times``): a ``while`` is charged only what its
body's operations leave over. (Looked for on the v5e and not there: a
name-scope line on the device plane, and the path among the stats that
``jax.profiler.ProfileData`` lists.) A trace from a program without
these scopes or spans (the parent commit's) yields empty tables, and
every reader built on them returns None.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Optional

from benchmark import trace_reduce
from benchmark.cells import ROOT

SPAN_PREFIX = "tpfl:"
UNSCOPED = "unscoped"
OUTSIDE_PROGRAM = "outside_program"
TABLE_FILE = "program_trace.json"
#: The stats of an operation's event metadata that hold its op_name path
#: (``tf_op`` is the TPU profiler's name for it).
OP_PATH_STATS = ("tf_op", "op_name")
_SCOPE_IN_PATH = re.compile(r"(?:^|/)(tpfl\.[A-Za-z0-9_]+)(?=$|[/;])")

# The one trace held in this process: its file's identity, its events,
# its operations' paths, the events' reduction, and the tables made from
# them (by rounds).
_held: dict = {}


def _varint(buf: memoryview, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: memoryview):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes (a view, not a copy) of anything else. What is not
    asked for is skipped by its length, so walking past a plane's lines
    costs nothing."""
    at, end = 0, len(buf)
    while at < end:
        tag, at = _varint(buf, at)
        wire = tag & 7
        if wire == 0:
            value, at = _varint(buf, at)
        else:
            size = 8 if wire == 1 else 4
            if wire == 2:
                size, at = _varint(buf, at)
            elif wire not in (1, 5):
                raise ValueError(f"wire type {wire} in an xplane")
            value, at = buf[at:at + size], at + size
        yield tag >> 3, value


def _text(view: memoryview) -> str:
    return bytes(view).decode("utf-8", "replace")


def op_paths(path: str) -> dict:
    """device plane -> {operation event name: its ``op_name`` path}, from
    the ``.xplane.pb`` itself. The TPU profiler keeps the path as the stat
    ``tf_op`` of the operation's EVENT METADATA, which
    ``jax.profiler.ProfileData`` does not show (it lists an event's own
    stats only), so the few fields needed are read off the wire format
    (``tsl/profiler/protobuf/xplane.proto``: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5, maps as
    entries key = 1 / value = 2; XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7, a reference to a stat metadata's name)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                metadata.append(value)
            elif field == 5:
                entry = dict(_fields(value))
                stat_names[entry.get(1, 0)] = _text(dict(_fields(entry[2])).get(2, b""))
        if not name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        paths = out.setdefault(name, {})
        for entry in metadata:
            event_name, op_path = "", None
            for field, value in _fields(dict(_fields(entry))[2]):
                if field == 2:
                    event_name = _text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) in OP_PATH_STATS:
                        op_path = (
                            _text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            if op_path:
                paths[event_name] = op_path
    return out


def scope_of_path(op_path: str) -> Optional[str]:
    """``jit(f)/tpfl.train/vmap()/tpfl.optimizer/mul`` ->
    ``tpfl.optimizer``: the innermost scope is the last one."""
    found = _SCOPE_IN_PATH.findall(op_path)
    return found[-1] if found else None


def scope_self_times(events: list, paths: dict) -> dict:
    """device plane -> {scope or ``unscoped``: self nanoseconds of its
    operations}, for every device plane that has operations."""
    out = {}
    for plane, ops in trace_reduce.device_ops(events).items():
        of_name = paths.get(plane, {})
        table: dict = defaultdict(int)
        for name, self_ns, _ in trace_reduce.self_times(ops):
            table[scope_of_path(of_name.get(name, "")) or UNSCOPED] += self_ns
        out[plane] = dict(table)
    return out


def program_spans(events: list) -> dict:
    """engine span name (``dispatch``, ``finalize``, ...; prefix cut) ->
    its ``(start, end)`` intervals on the host planes."""
    return trace_reduce.host_spans(events, SPAN_PREFIX)


def gaps_by_program_span(events: list) -> dict:
    """Idle nanoseconds of the traced slice (per device: first
    operation's start to last one's end, less the union of the
    operations), summed over the devices, by the innermost engine span
    that covers them; ``outside_program`` for what none covers."""
    spans = program_spans(events)
    out: dict = defaultdict(int)
    for ops in trace_reduce.device_ops(events).values():
        union = trace_reduce.merge((start, end) for _, start, end in ops)
        gaps = trace_reduce.subtract([(union[0][0], union[-1][1])], union)
        for name, ns in trace_reduce.attribute_gaps(gaps, spans).items():
            out[OUTSIDE_PROGRAM if name == trace_reduce.OUTSIDE_SPANS else name] += ns
    return dict(out)


def _newest_xplane() -> Optional[str]:
    found = glob.glob(str(
        ROOT / ".bench_out" / "*" / "trace" / "plugins" / "profile" / "*"
        / "*.xplane.pb"
    ))
    return max(found, key=os.path.getmtime) if found else None


def find_trace(obs: dict) -> Optional[tuple]:
    """``(path, events, op paths)`` of the trace ``obs["trace"]`` was
    reduced from, or None: the newest ``.xplane.pb`` where ``run.py``
    makes the traced slice write (loaded once per process), accepted
    only if its reduction gives the same ``busy_s_max`` and ``window_s``
    — a stale file, or a run that wrote elsewhere, reads as nothing to
    read, never as another run's trace."""
    reduced = obs.get("trace")
    path = _newest_xplane() if reduced is not None else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if _held.get("key") != key:
        events = trace_reduce.load_events(path)
        _held.clear()
        _held.update(
            key=key, events=events, paths=op_paths(path), tables={},
            reduction=trace_reduce.reduce_trace(events),
        )
    mine = _held["reduction"]
    if mine is None or any(
        mine[name] != reduced.get(name) for name in ("busy_s_max", "window_s")
    ):
        return None
    return path, _held["events"], _held["paths"]


def analyse_events(events: list, paths: dict, rounds: int) -> dict:
    """The tables of ``program_trace.json``."""
    by_device = scope_self_times(events, paths)
    busiest = max(by_device, key=lambda plane: sum(by_device[plane].values()))
    spans = program_spans(events)
    n = max(1, len(by_device))

    def span_row(intervals: list) -> dict:
        ms = sorted((end - start) / 1e6 for start, end in intervals)
        return {"count": len(ms), "median_ms": ms[len(ms) // 2], "total_ms": sum(ms)}

    return {
        "rounds": rounds,
        "devices": len(by_device),
        "busiest_device": busiest,
        "scope_ms_per_round": {
            scope: ns / 1e6 / max(1, rounds)
            for scope, ns in sorted(by_device[busiest].items())
        },
        "spans": {name: span_row(ivs) for name, ivs in sorted(spans.items())},
        # Mean over the devices, like ``breakdown.idle_gaps``.
        "idle_ms_by_span": {
            name: ns / n / 1e6
            for name, ns in sorted(gaps_by_program_span(events).items())
        },
    }


def analyse(obs: dict) -> Optional[dict]:
    """The tables for the run ``obs`` describes (None where
    :func:`find_trace` finds nothing), computed once and written as
    ``program_trace.json`` into the trace's directory, beside the
    harness's ``inventory.json``. Nothing is printed."""
    found = find_trace(obs)
    if found is None:
        return None
    rounds = int(obs.get("trace_rounds") or 0)
    tables = _held["tables"].get(rounds)
    if tables is None:
        tables = _held["tables"][rounds] = analyse_events(*found[1:], rounds)
        trace_dir = found[0].split(os.sep + "plugins" + os.sep)[0]
        with open(os.path.join(trace_dir, TABLE_FILE), "w") as f:
            json.dump(tables, f, indent=1)
    return tables


# --- what the readers under layer_metrics/ return -----------------------------


def _scoped(tables: Optional[dict]) -> Optional[dict]:
    """The busiest device's per-round table, or None where the program
    named no leg at all (nothing was split, so nothing can be read)."""
    if tables is None or not tables["rounds"]:
        return None
    per_round = tables["scope_ms_per_round"]
    return None if set(per_round) <= {UNSCOPED} else per_round


def scope_ms_per_round(obs: dict, *scopes: str) -> Optional[float]:
    """Milliseconds a round that the busiest device spends in the given
    scopes (summed). 0.0 for a scope no operation carries while others
    are carried (XLA fused that leg into a neighbour's operations)."""
    per_round = _scoped(analyse(obs))
    if per_round is None:
        return None
    return sum(per_round.get(scope, 0.0) for scope in scopes)


def unscoped_pct(obs: dict) -> Optional[float]:
    """Share of the busiest device's operation time that lies under no
    ``tpfl.*`` scope."""
    per_round = _scoped(analyse(obs))
    if per_round is None:
        return None
    return 100.0 * per_round.get(UNSCOPED, 0.0) / sum(per_round.values())


def span_median_ms(obs: dict, span: str) -> Optional[float]:
    """Median duration of one engine span over the traced slice."""
    tables = analyse(obs)
    if tables is None or span not in tables["spans"]:
        return None
    return tables["spans"][span]["median_ms"]
