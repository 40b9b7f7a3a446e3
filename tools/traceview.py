"""traceview — reconstruct round timelines from tpfl telemetry dumps.

Input: flight-recorder dumps (``flight-<node>-<reason>.json``, written
by ``Node.stop()`` / the chaos harness into
``Settings.TELEMETRY_DUMP_DIR``) and/or in-process span exports
(``tpfl.management.tracing.export()``). Every entry is a span
(``{"kind": "span", "name", "node", "trace", "t0", "t1", ...}``) or an
event (``{"kind": "event", ..., "t"}``); timestamps are
``time.monotonic()`` seconds with a per-process ``wall_anchor`` in the
dump envelope, so dumps from different processes merge onto one
wall-clock axis.

Output: per-trace timelines — for each model payload's 16-byte trace
id, the ordered chain of spans it crossed
(``encode@a → send@a→b → recv@b → decode@b → fold@b``), across every
node that handled it. This is the view no single node ever has: the
gossip hops, retries, breaker trips, chunk streams, decodes and
aggregation folds of one payload, stitched back together.

Run::

    python -m tools.traceview logs/flight-*.json
    python -m tools.traceview --summary dumps/

``--fleet`` switches input to per-node ``MetricsRegistry.dump_json``
documents (``metrics-<node>.json``) and renders ONE labeled-by-node
Prometheus/JSON view of the whole simulation's registries
(:func:`fleet_view` / :func:`render_fleet`; the in-process equivalent
is ``MetricsRegistry.merge``). Paths may also be live ``http(s)://``
endpoints — ``MetricsHTTPServer``'s ``/metrics.json`` (one process) or
rank 0's ``/fleet.json`` (the already-merged cross-host fold) — so the
same command works against a RUNNING federation.

``--population`` is the cross-device cohort view: each
``population_round`` flight event (``ClientPopulation.complete_round``'s
per-round sketch — census coverage, participation fairness, straggler
cutoff) joined with the quarantine engine's ``quarantine`` / ``readmit``
verdicts for that round (:func:`population_report`).

``--ledger`` joins the learning-plane ledger's ``contrib`` / ``anomaly``
events (``tpfl.management.ledger``, recorded into the same flight rings
when ``Settings.LEDGER_ENABLED``) with the hop timelines by trace id:
one command answers "which peer's update was this payload, what were
its statistics, and was it flagged" — the update's network journey and
its learning-plane verdict on one line.

Pure functions (:func:`build_timeline`, :func:`hop_path`,
:func:`ledger_report`) are the test surface; the CLI is a thin
formatter over them.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Any, Iterable


def load(paths: Iterable[str]) -> list[dict]:
    """Load spans/events from dump files (or directories of them).

    Accepts flight-recorder dump envelopes (``{"node", "reason",
    "wall_anchor", "events": [...]}``) and bare JSON lists of entries.
    Each entry gains a wall-clock timestamp (``wt``) from its dump's
    anchor so cross-process entries order correctly."""
    entries: list[dict] = []
    files: list[pathlib.Path] = []
    for p in paths:
        path = pathlib.Path(p)
        if path.is_dir():
            files.extend(sorted(path.glob("flight-*.json")))
        else:
            files.append(path)
    for path in files:
        doc = json.loads(path.read_text(encoding="utf-8"))
        if isinstance(doc, dict):
            anchor = float(doc.get("wall_anchor", 0.0))
            batch = doc.get("events", [])
        else:
            anchor, batch = 0.0, doc
        for e in batch:
            e = dict(e)
            e["wt"] = anchor + float(e.get("t0", e.get("t", 0.0)))
            entries.append(e)
    return entries


def _stamp(e: dict) -> float:
    return float(e.get("wt", e.get("t0", e.get("t", 0.0))))


def build_timeline(entries: Iterable[dict]) -> dict[str, list[dict]]:
    """Group spans/events by trace id, each trace's entries in time
    order. Entries without a trace id (stage spans, system events) are
    grouped under ``""`` — the per-node backbone the payload traces
    hang between. Duplicate spans are dropped by span id: a node that
    dumped twice (crash dump, then its stop dump) contributes each
    span once."""
    timeline: dict[str, list[dict]] = {}
    seen: set = set()
    for e in entries:
        # Span ids are unique per node; events dedup on their full
        # identity (identical copies across overlapping dumps).
        sid = e.get("span")
        key = (
            (e.get("node"), sid)
            if sid is not None
            else (e.get("node"), e.get("name"), e.get("trace"), e.get("t"))
        )
        if key in seen:
            continue
        seen.add(key)
        timeline.setdefault(str(e.get("trace", "")), []).append(dict(e))
    for chain in timeline.values():
        chain.sort(key=_stamp)
    return timeline


def hop_path(chain: list[dict]) -> list[str]:
    """A trace's condensed hop chain: ``op@node`` (send shows the
    peer: ``send@a->b``), retries/events included in order."""
    out: list[str] = []
    for e in chain:
        name, node = str(e.get("name", "?")), str(e.get("node", "?"))
        if name in ("send", "retry") and e.get("peer"):
            out.append(f"{name}@{node}->{e['peer']}")
        else:
            out.append(f"{name}@{node}")
    return out


def trace_complete(chain: list[dict]) -> bool:
    """A payload trace is reconstructable end-to-end when it shows the
    encode AND a consuming hop (decode or fold) — on a different node
    unless the federation is single-node."""
    names = {str(e.get("name", "")) for e in chain}
    if "encode" not in names:
        return False
    if not ({"decode", "fold"} & names):
        return False
    encode_nodes = {
        e.get("node") for e in chain if e.get("name") == "encode"
    }
    consume_nodes = {
        e.get("node") for e in chain if e.get("name") in ("decode", "fold")
    }
    return bool(consume_nodes - encode_nodes) or encode_nodes == consume_nodes


def ledger_report(timeline: dict[str, list[dict]]) -> list[dict]:
    """Join learning-plane ledger entries with their hop timelines.

    For every ``contrib`` event (one accepted contribution's on-device
    stats, recorded by ``tpfl.management.ledger``) returns::

        {"trace", "peer", "observer", "round", "update_norm",
         "cos_ref", "num_samples", "flagged", "reasons", "hops"}

    ``hops`` is the payload's condensed hop chain (``encode@a →
    send@a->b → ... → fold@b``) when the contribution's trace id is
    reconstructable (tracing was on), else ``[]`` — a locally-fitted
    contribution has no wire journey. ``anomaly`` events merge into
    their contribution's row (reasons/z), and the quarantine engine's
    ``quarantine`` / ``readmit`` actions (tpfl.management.quarantine)
    merge as the row's ``action`` — the payload's network journey, its
    learning-plane verdict, AND the defense decision it triggered on
    one line; untraceable ledger rows sort last."""
    ledger_names = ("contrib", "anomaly", "quarantine", "readmit")
    rows: dict[tuple, dict] = {}
    for trace, chain in timeline.items():
        hops = [e for e in chain if e.get("name") not in ledger_names]
        for e in chain:
            if e.get("name") != "contrib":
                continue
            key = (str(e.get("node", "")), str(e.get("peer", "")),
                   int(e.get("round", -1)))
            rows[key] = {
                "trace": trace,
                "peer": str(e.get("peer", "")),
                "observer": str(e.get("node", "")),
                "round": int(e.get("round", -1)),
                "update_norm": float(e.get("update_norm", 0.0)),
                "cos_ref": float(e.get("cos_ref", 0.0)),
                "num_samples": int(e.get("num_samples", 0)),
                "flagged": bool(e.get("flagged", False)),
                "reasons": [],
                "hops": hop_path(hops) if trace else [],
            }
        for e in chain:
            name = e.get("name")
            if name not in ("anomaly", "quarantine", "readmit"):
                continue
            key = (str(e.get("node", "")), str(e.get("peer", "")),
                   int(e.get("round", -1)))
            row = rows.get(key)
            if row is None:
                if name == "anomaly":
                    continue
                # Quarantine actions can outlive their triggering
                # contribution's ring entry: surface them standalone.
                row = rows[key] = {
                    "trace": trace,
                    "peer": str(e.get("peer", "")),
                    "observer": str(e.get("node", "")),
                    "round": int(e.get("round", -1)),
                    "update_norm": 0.0,
                    "cos_ref": 0.0,
                    "num_samples": 0,
                    "flagged": False,
                    "reasons": [],
                    "hops": hop_path(hops) if trace else [],
                }
            if name == "anomaly":
                row["flagged"] = True
                row["reasons"] = [
                    r for r in str(e.get("reasons", "")).split(",") if r
                ]
                if "z_norm" in e:
                    row["z_norm"] = float(e["z_norm"])
            else:
                row["action"] = name
                if name == "quarantine":
                    row["flagged"] = True
                    if not row["reasons"]:
                        row["reasons"] = [
                            r
                            for r in str(e.get("reasons", "")).split(",")
                            if r
                        ]
    return sorted(
        rows.values(),
        key=lambda r: (r["round"], r["peer"], r["observer"]),
    )


def render_ledger(timeline: dict[str, list[dict]]) -> str:
    rows = ledger_report(timeline)
    if not rows:
        return "no ledger entries (was Settings.LEDGER_ENABLED on?)"
    lines = [
        f"{len(rows)} ledger entries "
        f"({sum(1 for r in rows if r['flagged'])} flagged)",
        f"{'rnd':>3} {'peer':<18} {'observer':<18} {'|update|':>10} "
        f"{'cos_ref':>8}  flags",
    ]
    for r in rows:
        mark = ",".join(r["reasons"]) if r["reasons"] else (
            "FLAGGED" if r["flagged"] else "-"
        )
        if r.get("action"):
            mark = f"{mark} [{r['action'].upper()}]"
        lines.append(
            f"{r['round']:>3} {r['peer']:<18} {r['observer']:<18} "
            f"{r['update_norm']:>10.4g} {r['cos_ref']:>8.3f}  {mark}"
        )
        if r["hops"]:
            lines.append(f"      hops: {' -> '.join(r['hops'])}")
    return "\n".join(lines)


def load_metric_dumps(paths: Iterable[str]) -> dict[str, dict]:
    """Load per-node ``MetricsRegistry.dump_json`` documents for the
    fleet view: files (or directories of ``metrics-*.json``) keyed by
    node name — the ``metrics-`` / ``.json`` trimmed file stem.

    ``http(s)://`` paths scrape a LIVE endpoint instead
    (``MetricsHTTPServer`` — ``/metrics.json`` for one process,
    ``/fleet.json`` for rank 0's already-merged cross-host view), so
    ``--fleet`` works against a running federation, not just its
    post-mortem dumps. Live documents key by host:port."""
    docs: dict[str, dict] = {}
    files: list[pathlib.Path] = []
    for p in paths:
        if str(p).startswith(("http://", "https://")):
            import urllib.parse
            import urllib.request

            with urllib.request.urlopen(str(p), timeout=10) as resp:
                doc = json.loads(resp.read().decode("utf-8"))
            docs[urllib.parse.urlparse(str(p)).netloc or str(p)] = doc
            continue
        path = pathlib.Path(p)
        if path.is_dir():
            files.extend(sorted(path.glob("metrics-*.json")))
        else:
            files.append(path)
    for path in files:
        name = path.stem
        if name.startswith("metrics-"):
            name = name[len("metrics-"):]
        docs[name] = json.loads(path.read_text(encoding="utf-8"))
    return docs


def _with_origin(series: str, origin: str) -> str:
    if series.endswith("}"):
        return f"{series[:-1]},origin={origin}}}"
    return f"{series}{{origin={origin}}}"


def fleet_view(docs: dict[str, dict]) -> dict[str, Any]:
    """Merge per-node metrics dumps into ONE labeled-by-node view —
    today each node's registry scrapes in isolation; this is the whole
    simulation on one axis. Every series gains an ``origin=<node>``
    label (the in-process equivalent is
    ``MetricsRegistry.merge(*regs, names=...)``); series strings keep
    the ``name{k=v,...}`` JSON-dump format."""
    out: dict[str, Any] = {
        "nodes": sorted(docs),
        "counters": {},
        "gauges": {},
        "histograms": {},
    }
    for name in sorted(docs):
        doc = docs[name]
        for kind in ("counters", "gauges"):
            for series, v in sorted((doc.get(kind) or {}).items()):
                out[kind][_with_origin(series, name)] = v
        for series, h in sorted((doc.get("histograms") or {}).items()):
            out["histograms"][_with_origin(series, name)] = h
    return out


def render_fleet(view: dict[str, Any]) -> str:
    """Prometheus-flavored text of a :func:`fleet_view` (histograms
    condense to their ``_sum`` / ``_count`` series — the merged view is
    for reading across nodes, not for re-scraping)."""
    lines = [
        f"# fleet view: {len(view['nodes'])} nodes: "
        f"{', '.join(view['nodes'])}"
    ]
    for series in sorted(view["counters"]):
        lines.append(f"{series} {view['counters'][series]:g}")
    for series in sorted(view["gauges"]):
        lines.append(f"{series} {view['gauges'][series]:g}")
    for series in sorted(view["histograms"]):
        h = view["histograms"][series]
        name, _, labels = series.partition("{")
        labels = "{" + labels if labels else ""
        lines.append(f"{name}_sum{labels} {h.get('sum', 0):g}")
        lines.append(f"{name}_count{labels} {h.get('count', 0)}")
    return "\n".join(lines) + "\n"


def population_report(timeline: dict[str, list[dict]]) -> list[dict]:
    """Cohort health per population round, joined with the defense
    plane: every ``population_round`` flight event (the cross-device
    observatory's per-round sketch — census/coverage/fairness/
    stragglers, recorded by ``ClientPopulation.complete_round``)
    becomes one row, and any ``quarantine`` / ``readmit`` actions the
    quarantine engine took in the same round merge into it — "how
    healthy was this round's cohort, and what did the defense do about
    it" on one line."""
    rounds: dict[int, dict] = {}
    actions: dict[int, list[str]] = {}
    for chain in timeline.values():
        for e in chain:
            name = e.get("name")
            if name == "population_round":
                r = int(e.get("round", -1))
                rounds[r] = {
                    "round": r,
                    "census": int(e.get("census", 0)),
                    "sampled": int(e.get("sampled", 0)),
                    "folded": int(e.get("folded", 0)),
                    "cut": int(e.get("cut", 0)),
                    "touched": int(e.get("touched", 0)),
                    "coverage": float(e.get("coverage", 0.0)),
                    "fairness": float(e.get("fairness", 0.0)),
                    "actions": [],
                }
            elif name in ("quarantine", "readmit"):
                r = int(e.get("round", -1))
                actions.setdefault(r, []).append(
                    f"{name}:{e.get('peer', '?')}"
                )
    for r, acts in actions.items():
        if r in rounds:
            rounds[r]["actions"] = sorted(acts)
    return [rounds[r] for r in sorted(rounds)]


def render_population(timeline: dict[str, list[dict]]) -> str:
    rows = population_report(timeline)
    if not rows:
        return (
            "no population_round events (is a ClientPopulation "
            "attached and completing rounds?)"
        )
    lines = [
        f"{len(rows)} population rounds "
        f"(census {rows[-1]['census']}, "
        f"coverage {rows[-1]['coverage']:.4f}, "
        f"touched {rows[-1]['touched']})",
        f"{'rnd':>4} {'sampled':>7} {'folded':>6} {'cut':>4} "
        f"{'touched':>7} {'coverage':>8} {'fairness':>8}  defense",
    ]
    for r in rows:
        acts = ", ".join(r["actions"]) if r["actions"] else "-"
        lines.append(
            f"{r['round']:>4} {r['sampled']:>7} {r['folded']:>6} "
            f"{r['cut']:>4} {r['touched']:>7} {r['coverage']:>8.4f} "
            f"{r['fairness']:>8.4f}  {acts}"
        )
    return "\n".join(lines)


def summarize(timeline: dict[str, list[dict]]) -> dict[str, Any]:
    traced = {t: c for t, c in timeline.items() if t}
    complete = {t: c for t, c in traced.items() if trace_complete(c)}
    nodes = sorted(
        {str(e.get("node", "?")) for c in timeline.values() for e in c}
    )
    return {
        "traces": len(traced),
        "complete_traces": len(complete),
        "nodes": nodes,
        "entries": sum(len(c) for c in timeline.values()),
    }


def render(timeline: dict[str, list[dict]], limit: int = 0) -> str:
    lines: list[str] = []
    s = summarize(timeline)
    lines.append(
        f"{s['entries']} entries, {s['traces']} payload traces "
        f"({s['complete_traces']} complete) across {len(s['nodes'])} "
        f"nodes: {', '.join(s['nodes'])}"
    )
    shown = 0
    for trace in sorted(t for t in timeline if t):
        chain = timeline[trace]
        if limit and shown >= limit:
            lines.append(f"... ({s['traces'] - shown} more traces)")
            break
        shown += 1
        t0 = _stamp(chain[0])
        mark = "✓" if trace_complete(chain) else "…"
        lines.append(f"\ntrace {trace[:16]} {mark}")
        for e in chain:
            dt = _stamp(e) - t0
            name, node = str(e.get("name", "?")), str(e.get("node", "?"))
            dur = ""
            if "t1" in e and "t0" in e:
                dur = f"  ({(float(e['t1']) - float(e['t0'])) * 1e3:.2f} ms)"
            peer = f" -> {e['peer']}" if e.get("peer") else ""
            err = f"  ERROR {e['error']}" if e.get("error") else ""
            lines.append(
                f"  +{dt * 1e3:9.2f} ms  {name:<12} {node}{peer}{dur}{err}"
            )
    return "\n".join(lines)


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(
        description="Reconstruct tpfl round timelines from telemetry dumps"
    )
    ap.add_argument("paths", nargs="+", help="dump files or directories")
    ap.add_argument(
        "--summary", action="store_true",
        help="counts only (no per-trace chains)",
    )
    ap.add_argument(
        "--ledger", action="store_true",
        help="learning-plane view: contribution stats + anomaly flags "
        "joined with each payload's hop chain by trace id",
    )
    ap.add_argument(
        "--fleet", action="store_true",
        help="fleet metrics view: merge per-node MetricsRegistry JSON "
        "dumps (metrics-<node>.json) into one labeled-by-node "
        "Prometheus text (--summary: the merged JSON document)",
    )
    ap.add_argument(
        "--population", action="store_true",
        help="population-plane view: per-round cohort health "
        "(coverage/fairness/stragglers from population_round events) "
        "joined with quarantine/readmit verdicts",
    )
    ap.add_argument(
        "--limit", type=int, default=20,
        help="max traces to render (0 = all)",
    )
    args = ap.parse_args(argv)
    if args.fleet:
        view = fleet_view(load_metric_dumps(args.paths))
        if args.summary:
            print(json.dumps(view, indent=2, sort_keys=True))
        else:
            print(render_fleet(view), end="")
        return 0
    timeline = build_timeline(load(args.paths))
    if args.population:
        print(render_population(timeline))
    elif args.ledger:
        print(render_ledger(timeline))
    elif args.summary:
        print(json.dumps(summarize(timeline), indent=2))
    else:
        print(render(timeline, limit=args.limit))
    return 0


if __name__ == "__main__":
    sys.exit(main())
