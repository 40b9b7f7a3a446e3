"""Flight-recorder telemetry tests (ISSUE 5).

Coverage map:

- MetricsRegistry: cross-thread counter folding (seeded thread work,
  joined — no timing sleeps), histogram bucket-edge semantics
  (``value <= edge`` inclusive), label-cardinality cap (overflow
  series), gauge last-write-wins, Prometheus/JSON export.
- metric_storage bounds: per-series point cap + oldest-first eviction
  under Settings.METRIC_MAX_POINTS.
- Tracing: deterministic trace-id minting for a fixed seed, span
  recording into the bounded flight ring, wire-envelope ``tid``
  round-trips for v1/v2/v3 and InprocModelRef, Message ``trace``
  field wire round-trip (and old-envelope compatibility).
- FlightRecorder: ring bound, crash-dump file emission, traceview
  timeline reconstruction from dumps.
- MetricsHTTPServer: a real GET /metrics scrape.
- E2E (chaos-marked): a seeded 4-node federation with
  TELEMETRY_ENABLED and an injected crash — complete hop paths
  (encode -> send -> recv -> decode/fold) reconstruct across nodes,
  and the crash dump is emitted.
"""

import json
import pathlib
import sys
import threading

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # `tools` package import

from tpfl.management import tracing  # noqa: E402
from tpfl.management.telemetry import (  # noqa: E402
    FlightRecorder,
    MetricsRegistry,
    flight,
)
from tpfl.settings import Settings  # noqa: E402

from tools.traceview import (  # noqa: E402
    build_timeline,
    fleet_view,
    hop_path,
    load,
    load_metric_dumps,
    render_fleet,
    summarize,
    trace_complete,
)


# --- metrics registry -----------------------------------------------------


def test_registry_counter_folds_across_threads():
    reg = MetricsRegistry()

    def work(n):
        for _ in range(n):
            reg.counter("t_ops_total", labels={"node": "a"})

    threads = [
        threading.Thread(target=work, args=(100,), name=f"t{i}", daemon=True)
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reg.counter("t_ops_total", 5, labels={"node": "a"})  # main thread shard
    folded = reg.fold()
    assert folded["counters"][("t_ops_total", (("node", "a"),))] == 405.0


def test_registry_histogram_bucket_edges():
    reg = MetricsRegistry()
    # Custom edges pin the semantics: value <= edge lands in that bucket.
    for v in (0.1, 0.5, 0.50001, 2.0, 99.0):
        reg.observe("t_lat", v, buckets=(0.5, 1.0, 10.0))
    folded = reg.fold()
    h = folded["histograms"][("t_lat", ())]
    # buckets: <=0.5 -> 2 (0.1, 0.5 inclusive), <=1.0 -> 1 (0.50001),
    # <=10.0 -> 1 (2.0), +inf -> 1 (99.0); then sum, count.
    assert h[:4] == [2, 1, 1, 1]
    assert h[-1] == 5
    assert abs(h[-2] - (0.1 + 0.5 + 0.50001 + 2.0 + 99.0)) < 1e-9
    # Cumulative rendering: +Inf bucket equals total count.
    text = reg.render_prometheus()
    assert 't_lat_bucket{le="+Inf"} 5' in text
    assert 't_lat_bucket{le="0.5"} 2' in text


def test_registry_label_cardinality_cap():
    cap = Settings.TELEMETRY_MAX_LABELSETS
    try:
        Settings.TELEMETRY_MAX_LABELSETS = 4
        reg = MetricsRegistry()
        for i in range(10):
            reg.counter("t_card_total", labels={"peer": f"p{i}"})
        folded = reg.fold()
        series = [k for k in folded["counters"] if k[0] == "t_card_total"]
        # 4 real label sets + the shared overflow bucket.
        assert len(series) == 5
        overflow = ("t_card_total", (("overflow", "true"),))
        assert folded["counters"][overflow] == 6.0
    finally:
        Settings.TELEMETRY_MAX_LABELSETS = cap


def test_registry_gauge_last_write_wins_across_threads():
    reg = MetricsRegistry()
    reg.gauge("t_g", 1.0)

    def setter():
        reg.gauge("t_g", 2.0)

    t = threading.Thread(target=setter, name="setter", daemon=True)
    t.start()
    t.join()
    # The other thread's shard wrote later (higher seq) -> it wins.
    assert reg.fold()["gauges"][("t_g", ())] == 2.0
    reg.gauge("t_g", 3.0)
    assert reg.fold()["gauges"][("t_g", ())] == 3.0


def test_registry_collector_and_json_dump():
    reg = MetricsRegistry()

    def collector(r):
        r.gauge("t_pool_bytes", 4096.0, labels={"node": "n"})

    reg.register_collector(collector)
    doc = json.loads(reg.dump_json())
    assert doc["gauges"]["t_pool_bytes{node=n}"] == 4096.0
    reg.unregister_collector(collector)


def test_logger_metrics_facade_and_transport_mirror():
    from tpfl.management.logger import logger

    # The registry is process-global and earlier federation tests may
    # have filled this metric's label budget (overflow collapse is the
    # DESIGNED behavior, tested above) — start from a clean slate so
    # the exact-label assertions below are well-defined.
    logger.metrics.reset()
    logger.transport_metrics.record_send("fa-node", "fa-peer", ok=True, attempts=2)
    logger.transport_metrics.record_breaker("fa-node", "fa-peer", "open")
    folded = logger.metrics.fold()
    key = ("tpfl_transport_sends_total", (("node", "fa-node"), ("ok", "1")))
    assert folded["counters"][key] >= 1.0
    assert (
        folded["counters"][("tpfl_breaker_opens_total", (("node", "fa-node"),))]
        >= 1.0
    )
    # The legacy store still answers, as a snapshot copy.
    logs = logger.get_transport_logs()
    assert logs["fa-node"]["fa-peer"]["sends_ok"] == 1
    logs["fa-node"]["fa-peer"]["sends_ok"] = 999  # mutating the copy…
    assert logger.get_transport_logs()["fa-node"]["fa-peer"]["sends_ok"] == 1


# --- metric storage bounds ------------------------------------------------


def test_local_metric_storage_bounded_eviction():
    from tpfl.management.metric_storage import LocalMetricStorage

    cap = Settings.METRIC_MAX_POINTS
    try:
        Settings.METRIC_MAX_POINTS = 16
        s = LocalMetricStorage()
        for step in range(50):
            s.add_log("exp", 0, "loss", "n", float(step), step=step)
        series = s.get_all_logs()["exp"][0]["n"]["loss"]
        assert len(series) == 16
        # Oldest evicted first: the survivors are the LAST 16 points.
        assert series[0] == (34, 34.0)
        assert series[-1] == (49, 49.0)
    finally:
        Settings.METRIC_MAX_POINTS = cap


def test_global_metric_storage_bounded_eviction():
    from tpfl.management.metric_storage import GlobalMetricStorage

    cap = Settings.METRIC_MAX_POINTS
    try:
        Settings.METRIC_MAX_POINTS = 8
        s = GlobalMetricStorage()
        for rnd in range(20):
            s.add_log("exp", rnd, "acc", "n", rnd / 20)
        series = s.get_all_logs()["exp"]["n"]["acc"]
        assert len(series) == 8
        assert series[0][0] == 12 and series[-1][0] == 19
    finally:
        Settings.METRIC_MAX_POINTS = cap


# --- tracing --------------------------------------------------------------


def test_trace_id_mint_deterministic_for_fixed_seed():
    seed = Settings.SEED
    try:
        Settings.SEED = 99
        tracing.reset()
        a = [tracing.mint("node-x") for _ in range(5)]
        tracing.reset()
        b = [tracing.mint("node-x") for _ in range(5)]
        assert a == b
        assert len(set(a)) == 5  # distinct per ordinal
        assert all(len(t) == 32 for t in a)  # 16 bytes hex
        Settings.SEED = 100
        tracing.reset()
        c = [tracing.mint("node-x") for _ in range(5)]
        assert a != c  # seed-sensitive
    finally:
        Settings.SEED = seed
        tracing.reset()


def test_span_gating_and_ring_bound():
    ring = Settings.TELEMETRY_RING
    try:
        Settings.TELEMETRY_ENABLED = False
        flight.clear("gate-n")
        with tracing.maybe_span("encode", "gate-n"):
            pass
        assert flight.snapshot("gate-n") == []  # gated off: nothing

        Settings.TELEMETRY_ENABLED = True
        Settings.TELEMETRY_RING = 8
        flight.clear("gate-n")
        for i in range(20):
            tracing.event("tick", "gate-n", i=i)
        events = flight.snapshot("gate-n")
        assert len(events) == 8  # bounded ring
        assert [e["i"] for e in events] == list(range(12, 20))  # latest kept
    finally:
        Settings.TELEMETRY_ENABLED = False
        Settings.TELEMETRY_RING = ring
        flight.clear("gate-n")


def test_engine_span_off_records_nothing():
    """Off, an engine span is only the profiler's annotation (a flag
    test outside a profiler session): the flight ring stays empty."""
    flight.clear("engine")
    Settings.TELEMETRY_ENABLED = False
    with tracing.engine_span("dispatch", 0):
        with tracing.engine_span("prepare_args", 0):
            pass
    assert flight.snapshot("engine") == []


def test_engine_span_on_records_window_and_parent():
    flight.clear("engine")
    Settings.TELEMETRY_ENABLED = True
    try:
        with tracing.engine_span("dispatch", 12):
            with tracing.engine_span("prepare_args", 12):
                pass
            with tracing.engine_span("program_call", 12):
                pass
        with tracing.engine_span("wait", 12):
            pass
        with pytest.raises(ValueError):
            with tracing.engine_span("finalize", 12):
                raise ValueError("boom")
        with tracing.engine_span("dispatch", 16):  # the stack was unwound
            pass
        spans = flight.snapshot("engine")
    finally:
        Settings.TELEMETRY_ENABLED = False
        flight.clear("engine")
    # Children close (and are recorded) before their parent.
    assert [e["name"] for e in spans] == [
        "tpfl:prepare_args", "tpfl:program_call", "tpfl:dispatch",
        "tpfl:wait", "tpfl:finalize", "tpfl:dispatch",
    ]
    assert all(e["kind"] == "span" and e["node"] == "engine" for e in spans)
    assert [e["trace"] for e in spans] == ["r12"] * 5 + ["r16"]
    assert all(e["t0"] <= e["t1"] for e in spans)
    args, call, dispatch, wait, finalize, later = spans
    assert args["parent"] == call["parent"] == dispatch["span"]
    assert dispatch["parent"] == wait["parent"] == later["parent"] == ""
    assert dispatch["t0"] <= args["t0"] and call["t1"] <= dispatch["t1"]
    assert finalize["error"].startswith("ValueError")


def test_payload_tid_roundtrip_all_versions():
    import numpy as np

    from tpfl.learning import compression, serialization

    params = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
    v1 = serialization.encode_model_payload(params, ["a"], 3, {}, trace_id="aa" * 16)
    assert tracing.payload_trace_id(v1) == "aa" * 16
    v3 = serialization.encode_model_payload_v3(
        params, ["a"], 3, {}, trace_id="bb" * 16
    )
    assert tracing.payload_trace_id(v3) == "bb" * 16
    v2 = compression.encode_model_payload(
        params, ["a"], 3, {}, "zlib", trace_id="cc" * 16
    )
    assert tracing.payload_trace_id(v2) == "cc" * 16
    ref = serialization.InprocModelRef(params, ["a"], 3, {}, trace="dd" * 16)
    assert tracing.payload_trace_id(ref) == "dd" * 16
    # Untagged payloads (and pre-telemetry peers' payloads) peek empty.
    bare = serialization.encode_model_payload_v3(params, ["a"], 3, {})
    assert tracing.payload_trace_id(bare) == ""
    # All tagged envelopes still decode normally.
    for blob in (v1, v3, v2):
        p, contribs, n, _ = serialization.decode_model_payload(blob)
        assert contribs == ["a"] and n == 3
        np.testing.assert_array_equal(np.asarray(p["w"]), params["w"])


def test_message_trace_field_wire_roundtrip():
    import msgpack

    from tpfl.communication.message import Message

    msg = Message(source="a", cmd="full_model", payload=b"\x03xxxx", trace="ff" * 16)
    back = Message.from_bytes(msg.to_bytes())
    assert back.trace == "ff" * 16
    # A pre-telemetry envelope (no "t" key) decodes with trace="".
    d = msgpack.unpackb(msg.to_bytes(), raw=False)
    d.pop("t")
    old = Message.from_bytes(msgpack.packb(d, use_bin_type=True))
    assert old.trace == ""


# --- flight recorder + traceview ------------------------------------------


def test_flight_dump_and_traceview_roundtrip(tmp_path):
    rec = FlightRecorder()
    dump_dir = Settings.TELEMETRY_DUMP_DIR
    try:
        Settings.TELEMETRY_DUMP_DIR = str(tmp_path)
        rec.record(
            "n-a",
            {"kind": "span", "name": "encode", "node": "n-a",
             "trace": "t1", "t0": 1.0, "t1": 1.01},
        )
        rec.record(
            "n-a",
            {"kind": "span", "name": "send", "node": "n-a", "peer": "n-b",
             "trace": "t1", "t0": 1.02, "t1": 1.03},
        )
        rec.record(
            "n-b",
            {"kind": "span", "name": "decode", "node": "n-b",
             "trace": "t1", "t0": 1.05, "t1": 1.06},
        )
        paths = rec.dump_all("crash")
        assert len(paths) == 2
        timeline = build_timeline(load(paths))
        assert trace_complete(timeline["t1"])
        assert hop_path(timeline["t1"]) == [
            "encode@n-a", "send@n-a->n-b", "decode@n-b",
        ]
        s = summarize(timeline)
        assert s["complete_traces"] == 1 and s["nodes"] == ["n-a", "n-b"]
    finally:
        Settings.TELEMETRY_DUMP_DIR = dump_dir


def test_flight_dump_disabled_without_dir():
    rec = FlightRecorder()
    rec.record("n-x", {"kind": "event", "name": "e", "node": "n-x", "t": 0.0})
    assert Settings.TELEMETRY_DUMP_DIR == ""
    assert rec.dump("n-x", "stop") is None  # no dir -> no file, no error


# --- fleet-merged metrics (MetricsRegistry.merge / traceview --fleet) ----


def test_registry_merge_sums_and_labels():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("t_m_total", 3, labels={"node": "x"})
    b.counter("t_m_total", 4, labels={"node": "x"})
    a.gauge("t_m_gauge", 1.0)
    b.gauge("t_m_gauge", 2.0)
    a.observe("t_m_hist", 0.01)
    b.observe("t_m_hist", 0.02)

    # Unlabeled merge: counters sum, gauges later-wins, histograms sum.
    merged = MetricsRegistry.merge(a, b)
    folded = merged.fold()
    assert folded["counters"][("t_m_total", (("node", "x"),))] == 7.0
    assert folded["gauges"][("t_m_gauge", ())] == 2.0
    hist = folded["histograms"][("t_m_hist", ())]
    assert hist[-1] == 2  # observation count

    # Named merge: every series gains origin=<name> — the fleet view.
    fleet = MetricsRegistry.merge(a, b, names=["n0", "n1"])
    folded = fleet.fold()
    assert folded["counters"][
        ("t_m_total", (("node", "x"), ("origin", "n0")))
    ] == 3.0
    assert folded["counters"][
        ("t_m_total", (("node", "x"), ("origin", "n1")))
    ] == 4.0
    assert 'origin="n0"' in fleet.render_prometheus()
    with pytest.raises(ValueError, match="names"):
        MetricsRegistry.merge(a, b, names=["only-one"])


def test_registry_merge_histogram_bucket_mismatch_keeps_first():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.observe("t_m_edges", 1.5, buckets=(1.0, 2.0, 4.0))
    b.observe("t_m_edges", 1.5, buckets=(1.0, 8.0))  # incompatible edges
    merged = MetricsRegistry.merge(a, b, names=["n0", "n1"])
    folded = merged.fold()
    # n0's labeled series survives intact; n1's mismatched one is the
    # conflict loser — dropped, never summed into corrupt buckets.
    first = folded["histograms"][("t_m_edges", (("origin", "n0"),))]
    assert first[-1] == 1 and first[-2] == 1.5
    # Same edges from a third registry DO fold into n0's series when
    # the merge is unlabeled (that's the same-series sum path).
    c = MetricsRegistry()
    c.observe("t_m_edges", 2.5, buckets=(1.0, 2.0, 4.0))
    folded = MetricsRegistry.merge(a, c).fold()
    assert folded["histograms"][("t_m_edges", ())][-1] == 2


def test_registry_merge_under_concurrent_shard_updates():
    """merge() folds registries other threads are actively writing:
    per-shard locking means the merged totals land between the
    written-so-far floor and the final total, and the writers' own
    post-join fold is exact."""
    regs = [MetricsRegistry() for _ in range(3)]
    n_incr = 400
    stop = threading.Event()
    errors: list[BaseException] = []

    def hammer(reg: MetricsRegistry) -> None:
        try:
            for i in range(n_incr):
                reg.counter("t_m_conc_total", 1, labels={"k": "v"})
                reg.gauge("t_m_conc_gauge", float(i))
                reg.observe("t_m_conc_hist", float(i % 5))
        except BaseException as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [
        threading.Thread(target=hammer, args=(r,), daemon=True)
        for r in regs
        for _ in range(2)  # two writer threads per registry
    ]
    for t in threads:
        t.start()
    # Merge repeatedly WHILE the writers run — must never raise, and
    # every observed total must be a plausible mid-flight value.
    key = ("t_m_conc_total", (("k", "v"),))
    try:
        while any(t.is_alive() for t in threads):
            folded = MetricsRegistry.merge(*regs).fold()
            total = folded["counters"].get(key, 0.0)
            assert 0.0 <= total <= 6 * n_incr
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors
    folded = MetricsRegistry.merge(*regs, names=["a", "b", "c"]).fold()
    for name in ("a", "b", "c"):
        per = folded["counters"][
            ("t_m_conc_total", (("k", "v"), ("origin", name)))
        ]
        assert per == 2 * n_incr
        hist = folded["histograms"][
            ("t_m_conc_hist", (("origin", name),))
        ]
        assert hist[-1] == 2 * n_incr


def test_traceview_fleet_view(tmp_path):
    for name, val in (("alpha", 1.0), ("beta", 2.0)):
        reg = MetricsRegistry()
        reg.counter("t_f_total", val, labels={"node": name})
        reg.gauge("t_f_gauge", val)
        (tmp_path / f"metrics-{name}.json").write_text(reg.dump_json())
    docs = load_metric_dumps([str(tmp_path)])
    assert sorted(docs) == ["alpha", "beta"]
    view = fleet_view(docs)
    assert view["nodes"] == ["alpha", "beta"]
    assert view["counters"]["t_f_total{node=alpha,origin=alpha}"] == 1.0
    assert view["counters"]["t_f_total{node=beta,origin=beta}"] == 2.0
    assert view["gauges"]["t_f_gauge{origin=alpha}"] == 1.0
    text = render_fleet(view)
    assert "t_f_total{node=beta,origin=beta} 2" in text
    assert text.startswith("# fleet view: 2 nodes")


# --- prometheus HTTP endpoint ---------------------------------------------


def test_metrics_http_server_scrape():
    import urllib.request

    from tpfl.management.web_services import MetricsHTTPServer

    reg = MetricsRegistry()
    reg.counter("t_scrape_total", 7, labels={"node": "s"})
    srv = MetricsHTTPServer(registry=reg)
    port = srv.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as resp:
            text = resp.read().decode()
        assert 't_scrape_total{node="s"} 7' in text
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5
        ) as resp:
            doc = json.loads(resp.read())
        assert doc["counters"]["t_scrape_total{node=s}"] == 7.0
    finally:
        srv.stop()


def test_metrics_http_server_concurrent_scrape_live_federation():
    """Threaded scrape loop against the process registry while a live
    2-node federation mutates it: every response is a 200 with
    parseable, internally-consistent content — no torn reads, no 500s
    (the fold path snapshots mutating shards via bounded retry)."""
    import urllib.request

    from tpfl.communication.memory import clear_registry
    from tpfl.learning.dataset import (
        RandomIIDPartitionStrategy,
        synthetic_mnist,
    )
    from tpfl.management.logger import logger
    from tpfl.management.web_services import MetricsHTTPServer
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.utils import wait_convergence, wait_to_finish

    clear_registry()
    Settings.SEED = 99
    Settings.ELECTION = "hash"
    Settings.LOG_LEVEL = "ERROR"
    logger.set_level("ERROR")

    srv = MetricsHTTPServer()  # the process-wide registry
    port = srv.start()
    failures: list[str] = []
    scraped: list[int] = []
    stop = threading.Event()

    def scrape_loop(path: str) -> None:
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5
                ) as resp:
                    body = resp.read()
                    if resp.status != 200:
                        failures.append(f"{path}: HTTP {resp.status}")
                        continue
                if path == "/metrics.json":
                    json.loads(body)
                elif b"# TYPE" not in body:
                    failures.append(f"{path}: no TYPE lines")
                scraped.append(1)
            except Exception as e:  # torn read / refused / 500
                failures.append(f"{path}: {type(e).__name__}: {e}")

    scrapers = [
        threading.Thread(
            target=scrape_loop, args=(p,), name=f"t-scrape-{i}", daemon=True
        )
        for i, p in enumerate(("/metrics", "/metrics.json", "/metrics"))
    ]
    ds = synthetic_mnist(n_train=160, n_test=40, seed=0, noise=0.8)
    parts = ds.generate_partitions(2, RandomIIDPartitionStrategy, seed=1)
    nodes = [
        Node(
            create_model("mlp", (28, 28), seed=7, hidden_sizes=(16,)),
            parts[i],
            addr=f"t-scrape-fed-{i}",
            learning_rate=0.05,
            batch_size=32,
        )
        for i in range(2)
    ]
    for t in scrapers:
        t.start()
    for nd in nodes:
        nd.start()
    try:
        nodes[0].connect(nodes[1].addr)
        wait_convergence(nodes, 1, only_direct=False, wait=10)
        nodes[0].set_start_learning(rounds=2, epochs=1)
        wait_to_finish(nodes, timeout=240)
    finally:
        for nd in nodes:
            nd.stop()
        stop.set()
        for t in scrapers:
            t.join(timeout=5)
        srv.stop()
    assert not failures, failures[:10]
    assert len(scraped) > 10  # the loop genuinely scraped mid-round


# --- e2e: traced chaos federation (acceptance criterion) ------------------


@pytest.mark.chaos
def test_traced_chaos_federation_reconstructs_hop_paths(tmp_path):
    """A seeded 4-node federation with TELEMETRY_ENABLED and a trainer
    crashed mid-run: every surviving node's spans merge into timelines
    with complete payload hop paths (encode on the producer -> decode/
    fold on consumers), and the injected crash emits a flight dump."""
    from tpfl.communication.faults import FaultInjector, FaultPlan
    from tpfl.communication.memory import clear_registry
    from tpfl.learning.dataset import (
        RandomIIDPartitionStrategy,
        synthetic_mnist,
    )
    from tpfl.management.logger import logger
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.utils import wait_convergence, wait_to_finish

    clear_registry()
    Settings.TELEMETRY_ENABLED = True
    Settings.TELEMETRY_DUMP_DIR = str(tmp_path)
    Settings.ELECTION = "hash"  # n <= TRAIN_SET_SIZE: all elected
    Settings.SEED = 1234
    Settings.LOG_LEVEL = "ERROR"
    logger.set_level("ERROR")
    flight.clear()
    tracing.reset()

    n, rounds = 4, 3
    ds = synthetic_mnist(n_train=120 * n, n_test=40, seed=0, noise=0.8)
    parts = ds.generate_partitions(n, RandomIIDPartitionStrategy, seed=1)
    nodes = [
        Node(
            create_model("mlp", (28, 28), seed=7, hidden_sizes=(16,)),
            parts[i],
            addr=f"tchaos-{i}",
            learning_rate=0.05,
            batch_size=32,
        )
        for i in range(n)
    ]
    fi = FaultInjector(FaultPlan.from_dict({}), seed=1234)
    for nd in nodes:
        fi.attach(nd.communication)
    for nd in nodes:
        nd.start()
    try:
        for nd in nodes[1:]:
            nodes[0].connect(nd.addr)
        wait_convergence(nodes, n - 1, only_direct=False, wait=10)
        nodes[0].set_start_learning(rounds=rounds, epochs=1)
        # Crash the last node once the experiment is moving: survivors
        # must still finish (quorum degradation) and its flight dump
        # must land on disk.
        import time as _time

        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 60 and (nodes[-1].state.round or 0) < 1:
            _time.sleep(0.05)
        fi.crash(nodes[-1].addr)
        wait_to_finish(nodes[:-1], timeout=240)
    finally:
        for nd in nodes:
            nd.stop()

    # (a) Crash dump emitted for the victim.
    crash_dumps = list(tmp_path.glob("flight-tchaos-3-crash.json"))
    assert crash_dumps, list(tmp_path.iterdir())

    # (b) Timelines reconstruct complete cross-node hop paths.
    timeline = build_timeline(tracing.export())
    s = summarize(timeline)
    assert s["complete_traces"] > 0, s
    complete = [
        t for t, chain in timeline.items() if t and trace_complete(chain)
    ]
    cross_node = 0
    for t in complete:
        chain = timeline[t]
        names = [e["name"] for e in chain]
        assert names[0] == "encode"  # minted at first encode
        nodes_seen = {e["node"] for e in chain}
        if len(nodes_seen) > 1:
            cross_node += 1
    assert cross_node > 0  # at least one payload traced across nodes

    # (c) Stop dumps for survivors (Node.stop flushes the ring).
    assert list(tmp_path.glob("flight-tchaos-0-stop.json"))
