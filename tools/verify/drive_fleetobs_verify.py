"""Fleet-observatory verify drive (ISSUE 20).

Run from the repo root under the CPU-mesh env:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python - < tools/verify/drive_fleetobs_verify.py

Covers, end to end on real objects (no mocks, no pytest):

  (a) snapshot/fold federation: wire round-trip, origin labels, fold
      order-independence at the byte level, prefix filtering;
  (b) FleetPublisher -> fleet dir -> fleet_from_dir, torn files skipped;
  (c) a REAL 2-process jax.distributed (gloo) launch twice at the same
      seed: per-rank snapshots carry origin + only deterministic
      prefixes, the folded fleet view renders BYTE-IDENTICAL;
  (d) SLO watchdog: target grammar errors, warm-up, an injected ~20%
      rounds/sec regression breached within SLO_BREACH_WINDOWS,
      single-shot events + re-arm on recovery, uninjected silent;
  (e) live endpoints: /metrics, /healthz 200 -> 503 across a breach,
      /fleet.json merged view, traceview --fleet over live HTTP;
  (f) population observatory: coverage/fairness/staleness sketches on a
      real ClientPopulation, tpfl_pop_* fan-out, population_round
      flight events joined with quarantine actions in traceview,
      sketch state round-trip (bytes bitset) + legacy rebuild;
  (g) engine attach registrations + emit_fleet_gauges + NodeMonitor;
  (h) the tpflcheck metrics lint: full suite green, plus a doctored
      mini-repo proof that an undocumented tpfl_* name is caught;
  (i) the bench `fleetobs` tier booleans (merged determinism, watchdog
      catch, overhead budget, pop-sketch RSS bound).
"""

import json
import math
import os
import pathlib
import tempfile
import urllib.error
import urllib.request

import numpy as np

from tpfl.management import fleetobs
from tpfl.management.telemetry import MetricsRegistry, flight, metrics
from tpfl.settings import Settings

Settings.set_test_settings()


def check(cond, msg):
    if not cond:
        raise SystemExit(f"FAIL: {msg}")
    print(f"  ok: {msg}")


# --- (a) snapshot / fold federation ----------------------------------------
print("[a] snapshot/fold federation")
regs = []
for rank in range(2):
    r = MetricsRegistry()
    r.counter("tpfl_engine_rounds_total", 3.0 + rank, labels={"model": "m"})
    r.gauge("tpfl_engine_loss", 0.5 - 0.1 * rank, labels={"model": "m"})
    r.observe("tpfl_pop_staleness", 2.0, labels={"node": "population"})
    r.gauge("tpfl_system_cpu_percent", 50.0)  # outside the filter
    regs.append(r)
snaps = [
    fleetobs.snapshot(
        registry=regs[i],
        origin=str(i),
        prefixes=fleetobs.DETERMINISTIC_PREFIXES,
    )
    for i in range(2)
]
snaps = [json.loads(json.dumps(s)) for s in snaps]  # wire round-trip
for i, s in enumerate(snaps):
    check(s["origin"] == str(i), f"snapshot {i} stamps origin")
    names = {fleetobs._parse_series(k)[0] for k in s["counters"]} | {
        fleetobs._parse_series(k)[0] for k in s["gauges"]
    }
    check(
        all(
            n.startswith(fleetobs.DETERMINISTIC_PREFIXES) for n in names
        ),
        f"snapshot {i} filtered to deterministic prefixes",
    )
text01 = fleetobs.fold(snaps).render_prometheus()
text10 = fleetobs.fold(list(reversed(snaps))).render_prometheus()
check(text01 == text10, "fold is order-independent at the byte level")
check(
    'tpfl_engine_rounds_total{model="m",origin="1"} 4' in text01,
    "fold rewrites series with origin labels",
)
check("tpfl_system_cpu_percent" not in text01, "filter excluded system")

# --- (b) publisher + fleet dir ---------------------------------------------
print("[b] FleetPublisher -> fleet dir -> fleet_from_dir")
with tempfile.TemporaryDirectory() as d:
    for i in range(2):
        pub = fleetobs.FleetPublisher(
            f"host{i}", directory=d, registry=regs[i]
        )
        path = pub.publish_once()
        check(
            path is not None and os.path.basename(path) == f"fleetsnap-host{i}.json",
            f"publisher {i} wrote its snapshot file",
        )
    # A torn/partial file must be skipped, not crash the fold.
    (pathlib.Path(d) / "fleetsnap-torn.json").write_text("{ nope")
    merged = fleetobs.fleet_from_dir(d).render_prometheus()
    check(
        'origin="host0"' in merged and 'origin="host1"' in merged,
        "fleet_from_dir folds every intact publisher",
    )
check(
    fleetobs.FleetPublisher("x", directory=None).publish_once() is None,
    "publisher without a directory is disabled",
)

# --- (c) REAL 2-process cross-host federation ------------------------------
print("[c] 2-process gloo launch x2 (same seed): merged view determinism")
from tpfl.parallel import crosshost

knobs = {"SHARD_NODES": True, "SHARD_HOSTS": 0, "ENGINE_TELEMETRY": True}
texts = []
for attempt in range(2):
    results = crosshost.launch(
        num_processes=2, devices_per_proc=4, rounds=2, knobs=knobs
    )
    for r in results:
        snap = r["metrics_snapshot"]
        check(
            snap["origin"] == str(r["process_id"]),
            f"run {attempt}: rank {r['process_id']} snapshot origin",
        )
        check(
            bool(snap["counters"]) and bool(snap["gauges"]),
            f"run {attempt}: rank {r['process_id']} emitted series",
        )
    texts.append(fleetobs.fold_receipts(results).render_prometheus())
check(
    'origin="0"' in texts[0] and 'origin="1"' in texts[0],
    "merged fleet registry carries every rank's origin",
)
check("tpfl_engine_rounds_total" in texts[0], "engine series federated")
check(texts[0] == texts[1], "merged view BYTE-IDENTICAL across same-seed runs")

# --- (d) SLO watchdog -------------------------------------------------------
print("[d] SLO watchdog: grammar, warm-up, breach-within-2, re-arm")
for bad, msg in [
    ("bogus", "unparseable SLO clause"),
    ("ratio(tpfl_a) >= 1", "needs two metrics"),
    ("rate(tpfl_a, tpfl_b) >= 1", "takes one metric"),
]:
    try:
        fleetobs.parse_targets(bad)
        raise SystemExit(f"FAIL: {bad!r} should not parse")
    except ValueError as e:
        check(msg in str(e), f"grammar rejects {bad!r}")

wreg = MetricsRegistry()
wd = fleetobs.SLOWatchdog(
    "rate(tpfl_engine_rounds_total) >= 2.4",
    registry=wreg,
    node="drive-watchdog",
)
flight.clear("drive-watchdog")
total, now = 0.0, 0.0
verdicts = wd.evaluate(now=now)
check(
    verdicts[0]["signal"] is None and verdicts[0]["healthy"],
    "warm-up window has no signal and stays healthy",
)


def window(rate):
    global total, now
    total += rate
    now += 1.0
    wreg.counter("tpfl_engine_rounds_total", rate)
    return wd.evaluate(now=now)[0]


for _ in range(4):
    v = window(2.5)
    check(v["healthy"] and not v["breached"], "healthy window stays silent")
breach_at = None
for i in range(1, Settings.SLO_BREACH_WINDOWS + 2):
    v = window(2.0)  # the injected ~20% regression
    if v["breached"]:
        breach_at = i
        break
check(
    breach_at is not None and breach_at <= Settings.SLO_BREACH_WINDOWS + 1,
    f"injected regression breached in {breach_at} windows (<= 2 + warmup)",
)
events = [
    e for e in flight.snapshot("drive-watchdog") if e.get("name") == "slo_breach"
]
check(len(events) == 1, "exactly one slo_breach event fired")
check(
    events[0]["threshold"] == 2.4 and events[0]["signal"] < 2.4,
    "breach event carries target threshold + failing signal",
)
window(2.0)
check(
    len([e for e in flight.snapshot("drive-watchdog") if e.get("name") == "slo_breach"]) == 1,
    "sustained breach does not re-fire",
)
for _ in range(8):
    v = window(3.5)
check(v["healthy"], "recovery re-arms the target")
breach_counters = [
    val
    for (name, labels), val in metrics.fold()["counters"].items()
    if name == "tpfl_slo_breach_total"
    and any(k == "target" and wd._targets[0].key in v for k, v in labels)
]
check(breach_counters == [1.0], "tpfl_slo_breach_total == 1.0")

# Uninjected control: a steady healthy rate must stay silent.
qreg = MetricsRegistry()
qd = fleetobs.SLOWatchdog(
    "rate(tpfl_engine_rounds_total) >= 2.4", registry=qreg, node="drive-quiet"
)
flight.clear("drive-quiet")
qd.evaluate(now=0.0)
qt = 0.0
for i in range(1, 9):
    qt += 2.5
    qreg.counter("tpfl_engine_rounds_total", 2.5)
    v = qd.evaluate(now=float(i))[0]
    check(v["healthy"], f"uninjected window {i} healthy")
check(
    not [e for e in flight.snapshot("drive-quiet") if e.get("name") == "slo_breach"],
    "uninjected run fired zero breach events",
)

# --- (e) live endpoints -----------------------------------------------------
print("[e] /metrics + /healthz + /fleet.json + traceview --fleet over HTTP")
from tpfl.management.web_services import MetricsHTTPServer

with tempfile.TemporaryDirectory() as d:
    for i in range(2):
        fleetobs.FleetPublisher(
            f"r{i}", directory=d, registry=regs[i]
        ).publish_once()
    sreg = MetricsRegistry()
    sreg.counter("tpfl_engine_rounds_total", 7.0)
    swd = fleetobs.SLOWatchdog(
        "gauge(tpfl_engine_loss) <= 1.0", registry=sreg, node="drive-server"
    )
    srv = MetricsHTTPServer(0, registry=sreg, watchdog=swd, fleet_dir=d)
    port = srv.start()
    try:
        base = f"http://127.0.0.1:{port}"
        body = urllib.request.urlopen(f"{base}/healthz").read().decode()
        check('"healthy": true' in body or "ok" in body.lower(), "/healthz 200 while healthy")
        fleet = json.loads(urllib.request.urlopen(f"{base}/fleet.json").read())
        ckeys = list(fleet.get("counters", fleet))
        check(
            any("origin=r0" in k or 'origin="r0"' in k for k in ckeys)
            or any("origin" in k for k in ckeys),
            "/fleet.json serves the merged origin-labelled view",
        )
        promtext = urllib.request.urlopen(f"{base}/metrics").read().decode()
        check("tpfl_engine_rounds_total" in promtext, "/metrics serves the registry")

        # traceview reads the live endpoint like a dump file.
        import tools.traceview as traceview

        docs = traceview.load_metric_dumps([f"{base}/metrics.json"])
        check(
            f"127.0.0.1:{port}" in docs, "traceview keys live dumps by netloc"
        )
        fv = traceview.fleet_view(docs)
        check(
            any("origin=" in k for k in fv["counters"])
            and f"127.0.0.1:{port}" in fv["nodes"],
            "traceview --fleet rewrites live series with origin",
        )

        # Drive the watchdog unhealthy; /healthz must flip to 503.
        sreg.gauge("tpfl_engine_loss", 5.0)
        swd.evaluate(now=0.0)
        for i in range(1, Settings.SLO_BREACH_WINDOWS + 2):
            swd.evaluate(now=float(i))
        check(not swd.healthy(), "watchdog unhealthy after sustained breach")
        try:
            urllib.request.urlopen(f"{base}/healthz")
            raise SystemExit("FAIL: /healthz should be 503 after breach")
        except urllib.error.HTTPError as e:
            check(e.code == 503, "/healthz flips to 503 on breach")
    finally:
        srv.stop()

# --- (f) population observatory --------------------------------------------
print("[f] population sketches + tpfl_pop_* fan-out + traceview join")
from tpfl.parallel import ClientPopulation

flight.clear("population")
pop = ClientPopulation(registered=512, sample=8, seed=3)
ids = pop.begin_round()
w = pop.round_weights(ids, cutoff_frac=0.25)
pop.complete_round(ids, w, np.full(len(ids), 0.4, np.float32))
check(pop.coverage == 8 / 512, "coverage == sampled/registered after r0")
check(0.0 < pop.fairness <= 1.0, "fairness in (0, 1]")
check(pop.touched == int((w > 0).sum()), "touched counts folders only")
pfold = metrics.fold()
pg = {
    name: val
    for (name, labels), val in pfold["gauges"].items()
    if name.startswith("tpfl_pop_") and ("node", "population") in labels
}
check(
    math.isclose(pg["tpfl_pop_coverage"], pop.coverage),
    "tpfl_pop_coverage gauge matches the sketch",
)
check(pg["tpfl_pop_census"] == 512.0, "tpfl_pop_census gauge")
evs = [
    e for e in flight.snapshot("population") if e.get("name") == "population_round"
]
check(len(evs) == 1 and evs[0]["census"] == 512, "population_round flight event")

# traceview join: quarantine action lands in the same round's row.
import tools.traceview as traceview

timeline = {"population": list(flight.snapshot("population"))}
timeline["population"].append(
    {"kind": "event", "name": "quarantine", "round": 0, "peer": "evil"}
)
rows = traceview.population_report(timeline)
check(
    rows and rows[0]["actions"] == ["quarantine:evil"],
    "traceview joins quarantine actions into the population row",
)
check("no population_round" not in traceview.render_population(timeline),
      "render_population renders the joined rows")

# Sketch state round-trip: raw-bytes bitset, legacy rebuild lower bound.
state = pop.state_export()
check(
    isinstance(state["coverage"], bytes)
    and len(state["coverage"]) == (512 + 7) // 8,
    "exported coverage is a one-bit-per-client bytes bitset",
)
twin = ClientPopulation.from_state(json.loads(json.dumps({
    k: v for k, v in state.items() if k != "coverage"
})) | {"coverage": state["coverage"]})
check(
    twin.coverage == pop.coverage
    and np.array_equal(twin._coverage, pop._coverage),
    "sketches survive the state round-trip exactly",
)
legacy = {k: v for k, v in state.items() if k != "coverage"}
old = ClientPopulation.from_state(legacy)
check(
    old._sampled_count == old.touched <= pop._sampled_count,
    "legacy checkpoints rebuild coverage as a lower bound",
)

# --- (g) engine attach + fleet gauges + NodeMonitor -------------------------
print("[g] engine registrations, emit_fleet_gauges, NodeMonitor sample")
from tpfl.models import MLP
from tpfl.parallel import FederationEngine
from tpfl.parallel.membership import MembershipView

eng = FederationEngine(MLP(hidden_sizes=(4,)), 4, seed=0, learning_rate=0.1)
view = MembershipView([f"n{i}" for i in range(4)])
eng.attach_membership(view)
eng.attach_population(ClientPopulation(registered=100, sample=4, seed=0))
with fleetobs._meta_lock:
    check(view in fleetobs._views, "attach_membership registered the view")
    check(
        eng.population in fleetobs._populations,
        "attach_population registered the population",
    )
fleetobs.emit_fleet_gauges("drive-fleet")
gf = {
    name
    for (name, labels) in metrics.fold()["gauges"]
    if ("node", "drive-fleet") in labels
}
check(
    {"tpfl_membership_capacity", "tpfl_membership_live", "tpfl_pop_census"} <= gf,
    "emit_fleet_gauges covers membership + population",
)

from tpfl.management.node_monitor import NodeMonitor

NodeMonitor("drive-mon")._sample()
gm = {
    name
    for (name, labels) in metrics.fold()["gauges"]
    if ("node", "drive-mon") in labels
}
check(
    "tpfl_membership_live" in gm and "tpfl_system_cpu_percent" in gm,
    "NodeMonitor samples fleet gauges next to system gauges",
)

# --- (h) metrics lint: suite green + doctored-repo proof --------------------
print("[h] tpflcheck metrics lint")
import subprocess
import sys

proc = subprocess.run(
    [sys.executable, "-m", "tools.tpflcheck"], capture_output=True, text=True
)
check(proc.returncode == 0, "full tpflcheck suite exits 0")

from tools.tpflcheck.metrics import check_metrics

with tempfile.TemporaryDirectory() as d:
    root = pathlib.Path(d)
    (root / "tpfl").mkdir()
    (root / "docs").mkdir()
    (root / "tpfl" / "mod.py").write_text(
        'metrics.counter("tpfl_undocumented_x_total", 1.0)\n'
    )
    (root / "docs" / "observability.md").write_text("# nothing here\n")
    vs = check_metrics(root)
    check(
        len(vs) == 1 and "tpfl_undocumented_x_total" in vs[0].message,
        "lint catches an undocumented tpfl_* registration",
    )
    (root / "docs" / "observability.md").write_text(
        "`tpfl_undocumented_x_total` documented now\n"
    )
    check(not check_metrics(root), "documenting the name clears the lint")

# --- (i) bench fleetobs tier ------------------------------------------------
print("[i] bench fleetobs tier (2-proc determinism, watchdog, overhead, RSS)")
import bench

extra = {}
bench._fleetobs_tier(extra)
fo = extra.get("fleetobs")
check(fo is not None, f"tier produced receipts (err={extra.get('fleetobs_error')})")
for key in (
    "merged_byte_identical",
    "origin_labels_present",
    "watchdog_catch_within_2",
    "uninjected_silent",
    "overhead_within_budget",
):
    check(fo[key] is True, f"bench receipt {key}")
check(fo["pop_sketch"]["rss_bounded"] is True, "pop sketch RSS bounded")
check(fo["pop_sketch"]["bitset_bytes_exact"] is True, "bitset bytes exact")
print(f"  overhead_frac={fo['overhead_frac']:.4f} rounds_per_sec={fo['rounds_per_sec']:.2f}")

print("ALL FLEETOBS DRIVE CHECKS PASSED")
