"""Fleet observatory: cross-host metric federation, population-plane
telemetry, and live SLO watchdogs — the fourth observability plane.

Every plane built so far (registry/tracing, profiling, ledger, engine
telemetry) is strictly process-local: a multi-host run has N disjoint
``/metrics`` endpoints, the million-client population tier emits no
client-level health at all, and nothing watches a running federation
for a regression. This module is the fleet-level closure over all of them,
three coordinated pieces:

1. **Cross-host metric federation** — :func:`snapshot` folds a
   process' :class:`~tpfl.management.telemetry.MetricsRegistry` into a
   JSON-safe document; :func:`fold` rebuilds one registry per snapshot
   and merges them through ``MetricsRegistry.merge`` (``origin=<rank>``
   labels on every series), yielding ONE fleet registry that
   ``MetricsHTTPServer`` serves at ``/fleet.json``. Snapshots travel
   two ways: embedded in the crosshost receipt
   (``tpfl.parallel.crosshost.demo_run`` → ``launch`` →
   :func:`fold_receipts`) and — for long-running fleets — published
   periodically by :class:`FleetPublisher` as
   ``fleetsnap-<origin>.json`` files rank 0 folds from a shared
   directory (:func:`fleet_from_dir`). Determinism: a snapshot
   restricted to deterministic series (``prefixes``, default
   :data:`DETERMINISTIC_PREFIXES`) renders byte-identically across
   same-seed runs — the merged view is regression-gateable data, not
   just a dashboard.

2. **Population observatory** — :func:`population_round` fans a
   round's cross-device sketch (census coverage, participation
   fairness, straggler cutoff, staleness distribution — all
   O(1)/O(touched) state kept by
   :class:`~tpfl.parallel.population.ClientPopulation`, never
   O(census) beyond its coverage bitset) into ``tpfl_pop_*`` series
   and a ``population_round`` flight event. The always-on PR-5 rule
   applies: the sketch already paid its compute in
   ``complete_round``'s existing O(touched) walk; registry updates are
   cheap dict writes.

3. **Live SLO watchdog** — :class:`SLOWatchdog` evaluates the declared
   targets in ``Settings.SLO_TARGETS`` (grammar: ``rate(counter) /
   gauge(name) / ratio(a, b)`` vs a threshold) over the live registry,
   EWMA-smoothed (``Settings.SLO_EWMA``); ``SLO_BREACH_WINDOWS``
   consecutive violations emit a ``slo_breach`` flight event and bump
   ``tpfl_slo_breach_total`` — a regression gate inside running
   federations, and the verdict behind
   ``MetricsHTTPServer``'s ``/healthz``.

Live-view gauges: :func:`register_view` / :func:`register_population`
hold weak references to attached membership views / populations so
:class:`~tpfl.management.node_monitor.NodeMonitor` can emit
membership-tier occupancy and census/touched gauges
(:func:`emit_fleet_gauges`) without the monitor importing the parallel
layer.

Concurrency: module registries sit under ``_meta_lock``; the publisher
thread is named and daemon like every protocol thread; snapshot writes
are tmp+rename so a concurrent fold never reads a torn document.
jax is never imported — everything here is host-side dict/numpy work.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import weakref
from typing import Any, Callable, Iterable

from tpfl.concurrency import make_lock
from tpfl.management.telemetry import (
    DEFAULT_BUCKETS,
    WALL_ANCHOR,
    MetricsRegistry,
    flight,
    metrics,
)
from tpfl.settings import Settings

__all__ = [
    "DETERMINISTIC_PREFIXES",
    "FleetPublisher",
    "SLOWatchdog",
    "emit_fleet_gauges",
    "fleet_from_dir",
    "fold",
    "fold_receipts",
    "load_fleet_dir",
    "population_round",
    "register_population",
    "register_view",
    "registry_from_snapshot",
    "snapshot",
]

#: Series-name prefixes whose values are pure functions of the seeded
#: run (engine-carry fan-out, population sketches, SLO counters) — the
#: default snapshot filter for receipts that must compare byte-equal
#: across same-seed runs. Wall-clock series (``tpfl_system_*``, timing
#: histograms) are deliberately outside this set.
DETERMINISTIC_PREFIXES: tuple[str, ...] = (
    "tpfl_engine_",
    "tpfl_pop_",
    "tpfl_slo_",
)

#: Staleness-gap buckets (rounds since a client last folded) for the
#: population observatory's ``tpfl_pop_staleness`` histogram.
POP_STALENESS_BUCKETS: tuple[float, ...] = (
    0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
)


# --- snapshot / fold: the cross-host federation ------------------------


def _series_name(key: "tuple[str, tuple]") -> str:
    """``(name, labels)`` → the flattened ``name{k=v,...}`` form used
    by ``MetricsRegistry.dump_json`` (and parsed back by
    :func:`_parse_series`). Label keys/values must not contain ``,``
    ``=`` ``{`` ``}`` — true of every label this repo emits (node
    addresses, model names, rank ordinals)."""
    name, labels = key
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


def _parse_series(series: str) -> "tuple[str, tuple[tuple[str, str], ...]]":
    name, brace, rest = series.partition("{")
    if not brace:
        return name, ()
    labels = []
    for item in rest.rstrip("}").split(","):
        k, _, v = item.partition("=")
        labels.append((k, v))
    return name, tuple(sorted(labels))


def snapshot(
    registry: "MetricsRegistry | None" = None,
    origin: str = "",
    prefixes: "Iterable[str] | None" = None,
) -> dict:
    """One process' registry folded into a JSON-safe fleet-snapshot
    document (the unit the federation ships: crosshost receipts embed
    one, :class:`FleetPublisher` writes one per period).

    ``prefixes`` restricts to series whose metric name starts with any
    given prefix (``None`` = everything; pass
    :data:`DETERMINISTIC_PREFIXES` for receipts that must compare
    byte-equal across same-seed runs). Histograms ship their raw
    ``[bucket counts..., +inf, sum, count]`` row plus their bucket
    edges so :func:`registry_from_snapshot` rebuilds them exactly."""
    reg = registry if registry is not None else metrics
    pref = tuple(prefixes) if prefixes is not None else None

    def keep(name: str) -> bool:
        return pref is None or any(name.startswith(p) for p in pref)

    folded = reg.fold()
    hists = {
        _series_name(k): [float(c) for c in h]
        for k, h in folded["histograms"].items()
        if keep(k[0])
    }
    buckets = {
        k[0]: [float(e) for e in reg._buckets.get(k[0], DEFAULT_BUCKETS)]
        for k in folded["histograms"]
        if keep(k[0])
    }
    return {
        "origin": str(origin),
        "counters": {
            _series_name(k): float(v)
            for k, v in folded["counters"].items()
            if keep(k[0])
        },
        "gauges": {
            _series_name(k): float(v)
            for k, v in folded["gauges"].items()
            if keep(k[0])
        },
        "histograms": hists,
        "buckets": buckets,
        "wall_anchor": WALL_ANCHOR,
    }


def registry_from_snapshot(snap: dict) -> MetricsRegistry:
    """Rebuild a live :class:`MetricsRegistry` from a :func:`snapshot`
    document — the inverse leg of the federation (series land in one
    shard; bucket edges restore so merged histograms stay
    bucket-compatible)."""
    reg = MetricsRegistry()
    shard = reg._shard()
    for series, v in (snap.get("counters") or {}).items():
        shard.counters[_parse_series(series)] = float(v)
    for series, v in (snap.get("gauges") or {}).items():
        shard.gauges[_parse_series(series)] = (next(reg._gauge_seq), float(v))
    for name, edges in (snap.get("buckets") or {}).items():
        reg._buckets[name] = tuple(float(e) for e in edges)
    for series, h in (snap.get("histograms") or {}).items():
        row = [int(c) for c in h[:-2]] + [float(h[-2]), int(h[-1])]
        shard.hists[_parse_series(series)] = row
    return reg


def fold(snapshots: Iterable[dict]) -> MetricsRegistry:
    """Merge snapshot documents into ONE fleet registry via
    ``MetricsRegistry.merge``: every series gains an
    ``origin=<snapshot origin>`` label, counters sum, gauges
    latest-win, bucket-compatible histograms sum elementwise.
    Snapshots fold in origin order so the merged view is a pure
    function of the snapshot SET (rank arrival order cannot perturb
    the rendered bytes)."""
    snaps = sorted(snapshots, key=lambda s: str(s.get("origin", "")))
    regs = [registry_from_snapshot(s) for s in snaps]
    names = [str(s.get("origin", "")) for s in snaps]
    return MetricsRegistry.merge(*regs, names=names)


def fold_receipts(results: Iterable[dict]) -> MetricsRegistry:
    """The crosshost leg: fold the ``metrics_snapshot`` documents out
    of ``tpfl.parallel.crosshost.launch`` worker receipts into the
    fleet registry (ranks without a snapshot contribute nothing)."""
    return fold(
        r["metrics_snapshot"]
        for r in results
        if isinstance(r.get("metrics_snapshot"), dict)
    )


def load_fleet_dir(directory: str) -> list[dict]:
    """Read every ``fleetsnap-*.json`` under ``directory`` (the
    :class:`FleetPublisher` drop point) — unreadable/torn files are
    skipped, not fatal: observability must never take a fold down."""
    snaps: list[dict] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return snaps
    for fname in names:
        if not (fname.startswith("fleetsnap-") and fname.endswith(".json")):
            continue
        try:
            with open(os.path.join(directory, fname), encoding="utf-8") as f:
                doc = json.load(f)
            if isinstance(doc, dict):
                snaps.append(doc)
        except (OSError, ValueError):
            continue
    return snaps


def fleet_from_dir(directory: "str | None" = None) -> MetricsRegistry:
    """The rank-0 fold: every published snapshot in ``directory``
    (default ``Settings.FLEETOBS_DIR``) merged into one fleet
    registry — what ``MetricsHTTPServer`` serves at ``/fleet.json``."""
    d = directory if directory is not None else Settings.FLEETOBS_DIR
    return fold(load_fleet_dir(d) if d else ())


class FleetPublisher(threading.Thread):
    """Periodic snapshot publisher: every
    ``Settings.FLEETOBS_SNAPSHOT_PERIOD`` seconds, fold this process'
    registry and write ``fleetsnap-<origin>.json`` into
    ``Settings.FLEETOBS_DIR`` (tmp+rename — a concurrent
    :func:`load_fleet_dir` never reads a torn document). One per
    process, like the registry it snapshots; :meth:`publish_once` is
    the thread-free unit tests and one-shot callers drive."""

    def __init__(
        self,
        origin: str,
        directory: "str | None" = None,
        period: "float | None" = None,
        registry: "MetricsRegistry | None" = None,
        prefixes: "Iterable[str] | None" = None,
    ) -> None:
        safe = "".join(
            c if c.isalnum() or c in "-._" else "_" for c in str(origin)
        )
        super().__init__(daemon=True, name=f"fleet-publisher-{safe}")
        self._origin = str(origin)
        self._safe = safe
        self._directory = directory
        self._period = period
        self._registry = registry
        self._prefixes = tuple(prefixes) if prefixes is not None else None
        self._running = threading.Event()
        self._running.set()

    def publish_once(self) -> "str | None":
        directory = (
            self._directory
            if self._directory is not None
            else Settings.FLEETOBS_DIR
        )
        if not directory:
            return None
        os.makedirs(directory, exist_ok=True)
        doc = snapshot(
            self._registry, origin=self._origin, prefixes=self._prefixes
        )
        path = os.path.join(directory, f"fleetsnap-{self._safe}.json")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, sort_keys=True)
        os.replace(tmp, path)
        return path

    def stop(self) -> None:
        self._running.clear()

    def run(self) -> None:
        while self._running.is_set():
            try:
                self.publish_once()
            except Exception:
                pass  # observability must never take a node down
            period = (
                self._period
                if self._period is not None
                else float(Settings.FLEETOBS_SNAPSHOT_PERIOD)
            )
            if period <= 0:
                return
            deadline = time.monotonic() + period
            while self._running.is_set():
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                # Short hops so stop() lands within ~0.2 s regardless
                # of how long the publish period is.
                time.sleep(min(left, 0.2))


# --- population observatory --------------------------------------------


def population_round(
    node: str,
    *,
    round: int,
    census: int,
    sampled: int,
    folded: int,
    cut: int,
    touched: int,
    coverage: float,
    fairness: float,
    staleness: "Iterable[float]" = (),
) -> None:
    """Fan one committed population round's sketch out into the
    registry + flight ring (called by
    ``ClientPopulation.complete_round`` — the sketch values are all
    O(1) reads of state the commit walk already maintains):

    - ``tpfl_pop_census`` / ``tpfl_pop_touched`` / ``tpfl_pop_round``
      gauges — census scale vs the sparse-record reality;
    - ``tpfl_pop_coverage`` — fraction of the census the sampler has
      EVER reached (the coverage bitset's popcount);
    - ``tpfl_pop_fairness`` — Jain's index over touched clients'
      participation counts (1.0 = perfectly even service);
    - ``tpfl_pop_folded_total`` / ``tpfl_pop_cutoff_total`` counters
      and the ``tpfl_pop_cutoff_frac`` gauge — straggler accounting;
    - ``tpfl_pop_staleness`` histogram — rounds since each folding
      client last folded (0 = first participation);
    - one ``population_round`` flight event carrying the row
      ``tools/traceview.py --population`` joins with quarantine
      verdicts.
    """
    labels = {"node": node}
    metrics.gauge("tpfl_pop_census", float(census), labels=labels)
    metrics.gauge("tpfl_pop_touched", float(touched), labels=labels)
    metrics.gauge("tpfl_pop_round", float(round), labels=labels)
    metrics.gauge("tpfl_pop_coverage", float(coverage), labels=labels)
    metrics.gauge("tpfl_pop_fairness", float(fairness), labels=labels)
    metrics.counter("tpfl_pop_folded_total", float(folded), labels=labels)
    if cut:
        metrics.counter("tpfl_pop_cutoff_total", float(cut), labels=labels)
    metrics.gauge(
        "tpfl_pop_cutoff_frac",
        float(cut) / max(float(sampled), 1.0),
        labels=labels,
    )
    for gap in staleness:
        metrics.observe(
            "tpfl_pop_staleness", float(gap),
            labels=labels, buckets=POP_STALENESS_BUCKETS,
        )
    flight.record(
        node,
        {
            "kind": "event",
            "name": "population_round",
            "node": node,
            "trace": "",
            "t": time.monotonic(),
            "round": int(round),
            "census": int(census),
            "sampled": int(sampled),
            "folded": int(folded),
            "cut": int(cut),
            "touched": int(touched),
            "coverage": round_sig(coverage),
            "fairness": round_sig(fairness),
        },
    )


def round_sig(x: float, digits: int = 6) -> float:
    """Round for event payloads (events are documents, not math — six
    digits keeps dumps stable and diff-able)."""
    return round(float(x), digits)


# --- live-view gauges (NodeMonitor's fleet sample) ---------------------

_meta_lock = make_lock("fleetobs._meta_lock")
# guarded-by: _meta_lock
_views: "weakref.WeakSet[Any]" = weakref.WeakSet()
# guarded-by: _meta_lock
_populations: "weakref.WeakSet[Any]" = weakref.WeakSet()


def register_view(view: Any) -> None:
    """Weakly register an attached MembershipView so
    :func:`emit_fleet_gauges` can sample its tier occupancy (called by
    ``FederationEngine.attach_membership``; the weak reference means
    registration never extends an engine's lifetime)."""
    if view is None:
        return
    with _meta_lock:
        _views.add(view)


def register_population(population: Any) -> None:
    """Weakly register an attached ClientPopulation for census/touched
    gauges (called by ``FederationEngine.attach_population``)."""
    if population is None:
        return
    with _meta_lock:
        _populations.add(population)


def emit_fleet_gauges(node: str) -> None:
    """Sample every live membership view / population into gauges
    (``NodeMonitor._sample_fleet`` cadence): membership capacity /
    live / quarantined / fill, population census / touched. Host-side
    attribute reads only — no device work, no protocol locks."""
    with _meta_lock:
        views = list(_views)
        pops = list(_populations)
    labels = {"node": node}
    for view in views:
        try:
            capacity = float(view.capacity)
            # MembershipView exposes `live` as a property; accept a
            # zero-arg callable too so duck-typed views register.
            live_attr = view.live
            live = float(live_attr() if callable(live_attr) else live_attr)
            metrics.gauge("tpfl_membership_capacity", capacity, labels=labels)
            metrics.gauge("tpfl_membership_live", live, labels=labels)
            metrics.gauge(
                "tpfl_membership_quarantined",
                float(len(view.quarantined())),
                labels=labels,
            )
            metrics.gauge(
                "tpfl_membership_fill",
                live / max(capacity, 1.0),
                labels=labels,
            )
        except Exception:
            continue
    for pop in pops:
        try:
            metrics.gauge(
                "tpfl_pop_census", float(pop.registered), labels=labels
            )
            metrics.gauge(
                "tpfl_pop_touched", float(pop.touched), labels=labels
            )
        except Exception:
            continue


# --- live SLO watchdog -------------------------------------------------

_CLAUSE_RE = re.compile(
    r"^\s*(rate|gauge|ratio)\s*\(\s*([A-Za-z_][\w:]*)\s*"
    r"(?:,\s*([A-Za-z_][\w:]*)\s*)?\)\s*(<=|>=|<|>)\s*"
    r"([-+]?[0-9.][0-9.eE+-]*)\s*$"
)

_OPS: dict[str, Callable[[float, float], bool]] = {
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


class SLOTarget:
    """One parsed ``Settings.SLO_TARGETS`` clause + its online state
    (EWMA signal, breach streak). Mutated only by the owning
    watchdog's :meth:`SLOWatchdog.evaluate`."""

    __slots__ = (
        "kind", "metric", "metric_b", "op", "threshold", "key",
        "ewma", "streak", "breached", "evaluations",
        "_last_value", "_last_value_b", "_last_t",
    )

    def __init__(
        self, kind: str, metric: str, metric_b: "str | None",
        op: str, threshold: float,
    ) -> None:
        self.kind = kind
        self.metric = metric
        self.metric_b = metric_b
        self.op = op
        self.threshold = float(threshold)
        inner = metric if metric_b is None else f"{metric},{metric_b}"
        self.key = f"{kind}({inner}){op}{threshold:g}"
        self.ewma: "float | None" = None
        self.streak = 0
        self.breached = False
        self.evaluations = 0
        self._last_value: "float | None" = None
        self._last_value_b: "float | None" = None
        self._last_t: "float | None" = None

    def verdict(self) -> dict:
        healthy = True
        if self.ewma is not None:
            healthy = _OPS[self.op](self.ewma, self.threshold)
        return {
            "target": self.key,
            "kind": self.kind,
            "metric": self.metric,
            "op": self.op,
            "threshold": self.threshold,
            "signal": None if self.ewma is None else round(self.ewma, 6),
            "healthy": bool(healthy),
            "breached": bool(self.breached),
            "evaluations": int(self.evaluations),
        }


def parse_targets(spec: "str | None" = None) -> list[SLOTarget]:
    """Parse the ``Settings.SLO_TARGETS`` grammar (semicolon-separated
    ``rate(c) / gauge(g) / ratio(a, b)`` clauses vs a threshold).
    Raises ``ValueError`` naming the clause on any syntax error — a
    silently-dropped SLO is worse than none."""
    text = Settings.SLO_TARGETS if spec is None else spec
    targets: list[SLOTarget] = []
    for clause in str(text or "").split(";"):
        if not clause.strip():
            continue
        m = _CLAUSE_RE.match(clause)
        if m is None:
            raise ValueError(
                f"unparseable SLO clause {clause.strip()!r} (grammar: "
                "'rate(counter) | gauge(name) | ratio(a, b)  <op>  "
                "<number>', clauses ';'-separated)"
            )
        kind, a, b, op, value = m.groups()
        if kind == "ratio" and b is None:
            raise ValueError(
                f"SLO ratio clause {clause.strip()!r} needs two metrics"
            )
        if kind != "ratio" and b is not None:
            raise ValueError(
                f"SLO {kind} clause {clause.strip()!r} takes one metric"
            )
        targets.append(SLOTarget(kind, a, b, op, float(value)))
    return targets


def _metric_totals(folded: dict) -> "tuple[dict[str, float], dict[str, float]]":
    """(counter totals, gauge totals) summed across label sets per
    metric name — SLOs are fleet-level statements, not per-series
    ones (a per-model breakdown belongs on the dashboard)."""
    counters: dict[str, float] = {}
    for (name, _), v in folded["counters"].items():
        counters[name] = counters.get(name, 0.0) + float(v)
    gauges: dict[str, float] = {}
    for (name, _), v in folded["gauges"].items():
        gauges[name] = gauges.get(name, 0.0) + float(v)
    return counters, gauges


class SLOWatchdog:
    """Online breach detection over live registry series.

    ``evaluate()`` is one watchdog window: derive each target's signal
    from the (folded) registry — per-second counter rates and
    counter/counter ratios use deltas between evaluations, so the
    first call only warms the state — EWMA-smooth it
    (``Settings.SLO_EWMA``), and count consecutive violations;
    ``Settings.SLO_BREACH_WINDOWS`` of them fire ONE ``slo_breach``
    flight event + ``tpfl_slo_breach_total{target=...}`` bump, then
    re-arm when the target recovers. ``now`` is injectable so tests
    drive deterministic windows; live callers omit it
    (monotonic clock). :meth:`start` runs evaluations on a named
    daemon thread for long-running federations; ``/healthz`` reads
    :meth:`healthy` / :meth:`verdicts`.
    """

    def __init__(
        self,
        targets: "str | list[SLOTarget] | None" = None,
        registry: "MetricsRegistry | None" = None,
        node: str = "fleet-watchdog",
    ) -> None:
        self._registry = registry if registry is not None else metrics
        self._node = node
        self._lock = make_lock("SLOWatchdog._lock")
        # guarded-by: _lock
        self._targets = (
            list(targets)
            if isinstance(targets, list)
            else parse_targets(targets)
        )
        self._thread: "threading.Thread | None" = None
        self._running = threading.Event()

    def evaluate(self, now: "float | None" = None) -> list[dict]:
        """Run one watchdog window; returns the per-target verdicts
        (also kept for :meth:`verdicts`). Breach side effects (flight
        event + counter) happen here, outside the watchdog lock."""
        t = time.monotonic() if now is None else float(now)
        folded = self._registry.fold()
        counters, gauges = _metric_totals(folded)
        alpha = min(max(float(Settings.SLO_EWMA), 1e-6), 1.0)
        need = max(1, int(Settings.SLO_BREACH_WINDOWS))
        breaches: list[dict] = []
        out: list[dict] = []
        with self._lock:
            for tgt in self._targets:
                signal = self._signal(tgt, counters, gauges, t)
                if signal is None:
                    out.append(tgt.verdict())
                    continue
                tgt.evaluations += 1
                tgt.ewma = (
                    signal
                    if tgt.ewma is None
                    else alpha * signal + (1.0 - alpha) * tgt.ewma
                )
                if _OPS[tgt.op](tgt.ewma, tgt.threshold):
                    tgt.streak = 0
                    tgt.breached = False
                else:
                    tgt.streak += 1
                    if tgt.streak >= need and not tgt.breached:
                        tgt.breached = True
                        breaches.append(
                            {
                                "target": tgt.key,
                                "signal": round(tgt.ewma, 6),
                                "threshold": tgt.threshold,
                                "windows": tgt.streak,
                            }
                        )
                out.append(tgt.verdict())
        for b in breaches:
            metrics.counter(
                "tpfl_slo_breach_total", labels={"target": b["target"]}
            )
            flight.record(
                self._node,
                {
                    "kind": "event",
                    "name": "slo_breach",
                    "node": self._node,
                    "trace": "",
                    "t": t,
                    **b,
                },
            )
        return out

    def _signal(
        self,
        tgt: SLOTarget,
        counters: "dict[str, float]",
        gauges: "dict[str, float]",
        t: float,
    ) -> "float | None":
        if tgt.kind == "gauge":
            return gauges.get(tgt.metric)
        cur = counters.get(tgt.metric)
        if cur is None:
            return None
        if tgt.kind == "rate":
            last_v, last_t = tgt._last_value, tgt._last_t
            tgt._last_value, tgt._last_t = cur, t
            if last_v is None or last_t is None or t <= last_t:
                return None
            return (cur - last_v) / (t - last_t)
        # ratio(a, b): delta(a)/delta(b) between evaluations — the
        # "per current round" reading; a window with no b-progress
        # yields no signal (nothing happened to hold an SLO over).
        cur_b = counters.get(tgt.metric_b or "")
        last_v, last_b = tgt._last_value, tgt._last_value_b
        tgt._last_value, tgt._last_value_b = cur, cur_b
        if cur_b is None or last_v is None or last_b is None:
            return None
        db = cur_b - last_b
        if db <= 0:
            return None
        return (cur - last_v) / db

    def verdicts(self) -> list[dict]:
        with self._lock:
            return [t.verdict() for t in self._targets]

    def healthy(self) -> bool:
        """False only when a target is in active breach — warming-up
        targets count healthy (a fresh process must not 503 before it
        has produced a single window)."""
        with self._lock:
            return not any(t.breached for t in self._targets)

    # --- background evaluation ----------------------------------------

    def start(self, period: float = 5.0) -> None:
        """Evaluate every ``period`` seconds on a named daemon thread
        (long-running federations; tests drive :meth:`evaluate`
        directly with explicit ``now`` stamps)."""
        if self._thread is not None:
            return
        self._running.set()

        def loop() -> None:
            while self._running.is_set():
                try:
                    self.evaluate()
                except Exception:
                    pass  # observability must never take a node down
                deadline = time.monotonic() + max(float(period), 0.05)
                while self._running.is_set():
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.2))

        self._thread = threading.Thread(
            target=loop, daemon=True, name=f"slo-watchdog-{self._node}"
        )
        self._thread.start()

    def stop(self) -> None:
        self._running.clear()
        if self._thread is not None:
            self._thread.join(timeout=3)
            self._thread = None
