"""From a profiler trace to numbers: device busy and idle time, time per
group of device operations, collectives exposed or hidden, and idle
gaps named by what the host was doing.

Everything below ``load_events`` works on a plain list of events

    (plane, line, name, start_ns, duration_ns)

so that ``tests/benchmark/test_benchmark_trace_reduce.py`` pins its
numbers on a hand-built list. ``load_events`` is the only function that
touches the profiler's file format (an ``.xplane.pb``, read with
``jax.profiler.ProfileData``).

What is read, and why:

- A device is a plane whose name starts with ``/device:``. Its
  operations are the events of the line ``XLA Ops`` (the TPU profiler's
  name for the HLO instruction line). Where a plane has no such line,
  every line except the ones that repeat the same time under another
  heading (steps, modules, name scopes) is taken.
- Busy is the UNION of the operation intervals, so that an operation
  nested in a ``while`` is not counted twice. The traced slice of a
  device runs from its first operation's start to its last one's end:
  what the profiler records before and after is its own start and stop.
- An operation's SELF time is its duration less that of the operations
  nested in it on the same line; groups are summed over self time, by
  the stem of the HLO name (``fusion.12`` -> ``fusion``).
- A collective is an operation whose name starts with ``all-reduce``,
  ``all-gather``, ``reduce-scatter`` or ``collective-permute``. An
  asynchronous one is the interval from its ``-start`` to the end of
  its ``-done``. It is EXPOSED while no other operation (one with no
  operation nested in it, and not a collective) runs on that device.
- Host spans are the events whose name starts with ``bench:`` — the
  ``jax.profiler.TraceAnnotation`` spans the benchmark emits — on any
  plane that is not a device. Idle time is attributed to the innermost
  span that covers it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Iterable, Optional

Event = tuple  # (plane, line, name, start_ns, duration_ns)
Interval = tuple  # (start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
#: Lines of a device plane that show the same time again under another
#: heading; never read as operations.
NOT_OP_LINES = frozenset({
    "Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
    "Framework Name Scope", "TensorFlow Ops", "TensorFlow Name Scope",
    "Source code",
})
COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
)
SPAN_PREFIX = "bench:"
OUTSIDE_SPANS = "outside_spans"
_STEM = re.compile(r"%?([A-Za-z_][A-Za-z_\-]*)")
_ASYNC = re.compile(r"%?(.+?)-(start|done)((?:\.\d+)*)$")


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}"
        )
    return found[0]


def load_events(path: str) -> list:
    """Every event of an ``.xplane.pb`` as a plain tuple."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((
                    plane.name, line.name, ev.name,
                    int(ev.start_ns), int(ev.duration_ns),
                ))
    return events


# --- interval arithmetic -----------------------------------------------------


def merge(intervals: Iterable[Interval]) -> list:
    """Sorted union of intervals."""
    out: list = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(end - start for start, end in intervals)


def intersect(a: list, b: list) -> list:
    """Intersection of two MERGED interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        start, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if start < end:
            out.append((start, end))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """The parts of MERGED ``a`` that MERGED ``b`` does not cover."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cursor = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


# --- devices and their operations --------------------------------------------


def device_ops(events: list) -> dict:
    """plane -> its operation events ``(name, start, end)``, for every
    device plane that has any."""
    by_plane: dict = defaultdict(lambda: defaultdict(list))
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PLANE_PREFIX):
            by_plane[plane][line].append((name, start, start + dur))
    out = {}
    for plane, lines in by_plane.items():
        if OP_LINE in lines:
            ops = lines[OP_LINE]
        else:
            ops = [
                ev for line, evs in lines.items()
                if line not in NOT_OP_LINES for ev in evs
            ]
        if ops:
            out[plane] = ops
    return out


def stem(name: str) -> str:
    """``%fusion.12`` -> ``fusion``; ``all-reduce-start.3`` ->
    ``all-reduce-start``."""
    m = _STEM.match(name)
    return m.group(1).rstrip("-_") if m else name


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVE_PREFIXES)


def self_times(ops: list) -> list:
    """``(name, self_ns, has_child)`` per operation of ONE line:
    duration less the operations nested in it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child_ns = [0] * len(ops)
    has_child = [False] * len(ops)
    stack: list = []
    for i in order:
        _, start, end = ops[i]
        while stack and ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            child_ns[parent] += min(end, ops[parent][2]) - start
            has_child[parent] = True
        stack.append(i)
    return [
        (ops[i][0], max(0, (ops[i][2] - ops[i][1]) - child_ns[i]), has_child[i])
        for i in range(len(ops))
    ]


def collective_intervals(ops: list) -> list:
    """MERGED intervals in which a collective is in flight: a
    synchronous one is its own event; an asynchronous one runs from its
    ``-start`` to the end of the matching ``-done``."""
    intervals, open_starts = [], defaultdict(list)
    for name, start, end in sorted(ops, key=lambda ev: ev[1]):
        if not is_collective(name):
            continue
        m = _ASYNC.match(name)
        if not m:
            intervals.append((start, end))
        elif m.group(2) == "start":
            open_starts[(m.group(1), m.group(3))].append(start)
            intervals.append((start, end))
        else:
            began = open_starts[(m.group(1), m.group(3))]
            intervals.append((began.pop(0) if began else start, end))
    return merge(intervals)


def host_spans(events: list, prefix: str = SPAN_PREFIX) -> dict:
    """span name (prefix cut) -> its ``(start, end)`` intervals."""
    spans = defaultdict(list)
    for plane, _line, name, start, dur in events:
        if name.startswith(prefix) and not plane.startswith(DEVICE_PLANE_PREFIX):
            spans[name[len(prefix):]].append((start, start + dur))
    return dict(spans)


def attribute_gaps(gaps: list, spans: dict) -> dict:
    """Idle nanoseconds by the innermost host span that covers them
    (``OUTSIDE_SPANS`` for what no span covers). ``gaps`` is MERGED."""
    def typical(name):
        durations = sorted(end - start for start, end in spans[name])
        return durations[len(durations) // 2]

    out, rest = {}, gaps
    for name in sorted(spans, key=typical):  # innermost (shortest) first
        covered = merge(spans[name])
        ns = total(intersect(rest, covered))
        if ns:
            out[name] = ns
        rest = subtract(rest, covered)
    if total(rest):
        out[OUTSIDE_SPANS] = total(rest)
    return out


def reduce_trace(events: list, top: int = 10) -> Optional[dict]:
    """The numbers the per-layer metrics read, or None for a trace in
    which no operation ran on a device. Seconds; ``busy_s`` is the mean
    over the devices, ``busy_s_max`` and the collective times are the
    slowest device's, ``window_s`` the longest traced slice."""
    devices = device_ops(events)
    if not devices:
        return None
    spans = host_spans(events)
    busy, window, coll, exposed = [], [], [], []
    groups: dict = defaultdict(int)
    gaps_by_span: dict = defaultdict(int)
    for ops in devices.values():
        union = merge((start, end) for _, start, end in ops)
        first, last = union[0][0], union[-1][1]
        busy.append(total(union))
        window.append(last - first)
        timed = self_times(ops)
        for name, self_ns, _ in timed:
            groups[stem(name)] += self_ns
        others = merge(
            (start, end)
            for (name, start, end), (_, _, has_child) in zip(ops, timed)
            if not has_child and not is_collective(name)
        )
        in_flight = collective_intervals(ops)
        coll.append(total(in_flight))
        exposed.append(total(subtract(in_flight, others)))
        for name, ns in attribute_gaps(
            subtract([(first, last)], union), spans
        ).items():
            gaps_by_span[name] += ns
    n = len(devices)
    slowest = max(range(n), key=lambda i: coll[i])

    def ranked(table: dict) -> list:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n / 1e9] for name, ns in rows]

    return {
        "devices": n,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_max": max(busy) / 1e9,
        "window_s": max(window) / 1e9,
        "collective_s": coll[slowest] / 1e9,
        "collective_exposed_s": exposed[slowest] / 1e9,
        "device_ops": ranked(groups),
        "idle_gaps": ranked(gaps_by_span),
        "span_counts": {name: len(ivs) for name, ivs in spans.items()},
    }


def inventory(events: list) -> list:
    """``[plane, line, events, seconds]`` rows — what a trace holds,
    for looking at one by hand before trusting a reduction of it."""
    rows: dict = defaultdict(lambda: [0, 0])
    for plane, line, _name, _start, dur in events:
        rows[(plane, line)][0] += 1
        rows[(plane, line)][1] += dur
    return [
        [plane, line, count, ns / 1e9]
        for (plane, line), (count, ns) in sorted(rows.items())
    ]
