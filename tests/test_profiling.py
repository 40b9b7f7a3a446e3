"""Device-plane performance observatory tests (ISSUE 6).

Coverage map:

- CompileObservatory: cache hit/miss accounting, recompile detection
  on a shape-churn fixture (distinct abstract signatures), storm event
  at the threshold, and the disabled path being a pure passthrough.
- RoundProfiler: span/add bookkeeping, and an e2e seeded 2-node digits
  federation whose per-round attribution components
  (train/dispatch/fold/gossip/host_other) sum to >=95% of each round's
  measured wall-clock.
- CostModel: analytic FLOPs vs hand-computed MLP/CNN counts, the
  xla_flops path on a compiled matmul, MFU math against a fake device.
- HbmTracker: high-water-mark semantics over injected memory_stats.
- Compiled-program cache gauges (collector) + clears counter.
- Experiment profile_dir capture + maybe_trace being a no-op without a
  directory.
"""

import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # `tools` imports

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpfl.management import profiling  # noqa: E402
from tpfl.management.telemetry import MetricsRegistry, flight  # noqa: E402
from tpfl.settings import Settings  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_profiling():
    profiling.observatory.reset()
    profiling.rounds.reset()
    yield
    profiling.observatory.reset()
    profiling.rounds.reset()
    flight.clear(profiling.PROFILING_RING)


# --- CompileObservatory ---------------------------------------------------


def test_observatory_recompile_detection_on_shape_churn():
    Settings.PROFILING_ENABLED = True
    Settings.PROFILING_RECOMPILE_WARN = 3

    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    w = profiling.observatory.wrap(f, "t_probe")
    w(jnp.zeros((4,)))
    w(jnp.zeros((4,)))  # same abstract signature: a hit, not a compile
    assert profiling.observatory.signature_counts()["t_probe"] == 1

    # Shape churn: every distinct shape is a fresh signature/compile.
    for n in (8, 16):
        w(jnp.zeros((n,)))
    assert profiling.observatory.signature_counts()["t_probe"] == 3

    # The storm threshold (3) fired: a recompile_storm event is in the
    # profiling ring.
    events = flight.snapshot(profiling.PROFILING_RING)
    storms = [e for e in events if e.get("name") == "recompile_storm"]
    assert storms and storms[-1]["fn"] == "t_probe"
    assert storms[-1]["signatures"] == 3


def test_observatory_dtype_and_static_changes_count_as_recompiles():
    Settings.PROFILING_ENABLED = True

    @jax.jit
    def f(x, n=2):
        return x * n

    w = profiling.observatory.wrap(f, "t_sig")
    w(jnp.zeros((4,), jnp.float32))
    w(jnp.zeros((4,), jnp.int32))  # dtype change
    w(jnp.zeros((4,), jnp.float32), 3)  # static int value change
    assert profiling.observatory.signature_counts()["t_sig"] == 3


def test_observatory_disabled_is_passthrough_and_records_nothing():
    Settings.PROFILING_ENABLED = False
    calls = []

    def f(x):
        calls.append(x)
        return x

    w = profiling.observatory.wrap(f, "t_off")
    assert w(7) == 7
    assert calls == [7]
    assert "t_off" not in profiling.observatory.signature_counts()


def test_observatory_wrap_preserves_lowering_handle():
    Settings.PROFILING_ENABLED = True
    f = jax.jit(lambda x: x + 1)
    w = profiling.observatory.wrap(f, "t_lower")
    compiled = w.lower(jnp.zeros((2,))).compile()
    assert profiling.cost_model.cost_analysis(compiled) is not None


def test_shared_program_cache_events_counted():
    from tpfl.learning.jax_learner import (
        _SHARED_PROGRAMS,
        _shared_program,
    )

    reg_before = _fold_counter(
        "tpfl_compiled_cache_requests_total",
        (("cache", "shared_programs"), ("result", "hit")),
    )
    key = ("test_profiling", "cache_events")
    try:
        _shared_program(key, lambda: (lambda: 1))
        _shared_program(key, lambda: (lambda: 2))  # hit
        assert (
            _fold_counter(
                "tpfl_compiled_cache_requests_total",
                (("cache", "shared_programs"), ("result", "hit")),
            )
            >= reg_before + 1
        )
    finally:
        _SHARED_PROGRAMS.pop(key, None)


def _fold_counter(name, labels):
    from tpfl.management.telemetry import metrics

    return metrics.fold()["counters"].get((name, labels), 0.0)


def test_clear_compiled_caches_increments_clears_counter():
    from tpfl.learning.jax_learner import clear_compiled_caches

    before = _fold_counter("tpfl_compiled_cache_clears_total", ())
    clear_compiled_caches()
    assert _fold_counter("tpfl_compiled_cache_clears_total", ()) == before + 1


def test_compiled_cache_entries_gauge_via_collector():
    from tpfl.learning.jax_learner import _SHARED_PROGRAMS, _shared_program
    from tpfl.management.telemetry import metrics

    key = ("test_profiling", "gauge")
    try:
        _shared_program(key, lambda: (lambda: 1))
        gauges = metrics.fold()["gauges"]
        entries = gauges.get(
            ("tpfl_compiled_cache_entries", (("cache", "shared_programs"),))
        )
        assert entries is not None and entries >= 1
    finally:
        _SHARED_PROGRAMS.pop(key, None)


# --- RoundProfiler --------------------------------------------------------


def test_round_profiler_attribution_bookkeeping():
    Settings.PROFILING_ENABLED = True
    profiling.rounds.begin_round("n0", 3)
    with profiling.rounds.span("n0", "gossip"):
        time.sleep(0.02)
    profiling.rounds.add("n0", "train", 0.004)
    rec = profiling.rounds.end_round("n0", 3)
    assert rec["round"] == 3
    assert rec["parts"]["gossip"] >= 0.02
    assert rec["parts"]["train"] == pytest.approx(0.004)
    # host_other is the residual: the five components sum to the wall
    # (coverage 1.0) unless concurrent components overlapped past it.
    assert rec["coverage"] >= 0.95
    assert sum(rec["parts"].values()) == pytest.approx(
        rec["wall"] * rec["coverage"], rel=1e-6
    )
    assert profiling.rounds.attribution("n0") == [rec]


def test_round_profiler_disabled_is_noop():
    Settings.PROFILING_ENABLED = False
    profiling.rounds.begin_round("n0", 0)
    profiling.rounds.add("n0", "train", 1.0)
    assert profiling.rounds.end_round("n0", 0) is None
    assert profiling.rounds.attribution() == []


def test_round_profiler_add_outside_round_is_dropped():
    Settings.PROFILING_ENABLED = True
    profiling.rounds.add("nowhere", "train", 1.0)  # no open round: no-op
    assert profiling.rounds.attribution("nowhere") == []


def test_round_attribution_e2e_two_node_digits():
    """Seeded 2-node digits federation with profiling on: every round's
    attribution components must cover >=95% of its wall-clock (the
    residual bucket makes this exact unless time is dropped), and the
    compute components must be live."""
    from tpfl.learning.dataset import RandomIIDPartitionStrategy
    from tpfl.learning.dataset.synthetic import synthetic_mnist
    from tpfl.management.logger import logger
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.utils import wait_convergence, wait_to_finish

    Settings.LOG_LEVEL = "ERROR"
    logger.set_level("ERROR")
    Settings.ELECTION = "hash"
    Settings.SEED = 31
    Settings.PROFILING_ENABLED = True

    n, rounds_n = 2, 2
    ds = synthetic_mnist(n_train=100 * n, n_test=20, seed=0, noise=0.6)
    parts = ds.generate_partitions(n, RandomIIDPartitionStrategy, seed=1)
    nodes = [
        Node(
            create_model("mlp", (28, 28), seed=7, hidden_sizes=(16,)),
            parts[i],
            addr=f"t-prof-{i}",
            learning_rate=0.05,
            batch_size=32,
        )
        for i in range(n)
    ]
    for nd in nodes:
        nd.start()
    try:
        nodes[0].connect(nodes[1].addr)
        wait_convergence(nodes, n - 1, only_direct=False, wait=10)
        nodes[0].set_start_learning(rounds=rounds_n, epochs=1)
        wait_to_finish(nodes, timeout=120)
    finally:
        for nd in nodes:
            nd.stop()

    recs = profiling.rounds.attribution()
    assert len(recs) == n * rounds_n
    for rec in recs:
        assert set(rec["parts"]) == set(profiling.COMPONENTS)
        # The acceptance bar: components sum to >=95% of measured wall.
        assert sum(rec["parts"].values()) >= 0.95 * rec["wall"]
        assert rec["coverage"] >= 0.95
    # Trainers did real device work somewhere (dispatch+train covers
    # both the sync- and async-dispatch backends).
    assert any(
        r["parts"]["train"] + r["parts"]["dispatch"] > 0 for r in recs
    )
    # Registry carries the per-component histograms.
    from tpfl.management.telemetry import metrics

    hists = metrics.fold()["histograms"]
    assert any(k[0] == "tpfl_round_attr_seconds" for k in hists)


# --- CostModel ------------------------------------------------------------


def test_cost_model_mlp_flops_vs_hand_computed():
    from tpfl.models import MLP

    mlp = MLP(hidden_sizes=(32,))
    # 28x28 flattened -> 32 -> 10: mults = 784*32 + 32*10.
    mults = profiling.cost_model.analytic_fwd_mults(mlp, (28, 28))
    assert mults == 784 * 32 + 32 * 10
    # Train flops: 2 flops/mult, x3 fwd+bwd, x samples.
    assert profiling.cost_model.analytic_train_flops(
        mlp, (28, 28), samples=64
    ) == 3 * 2 * mults * 64


def test_cost_model_cnn_flops_match_hand_formula():
    from tpfl.models import CNN

    cnn = CNN(out_channels=10)
    got = profiling.cost_model.analytic_fwd_mults(cnn, (32, 32, 3))
    # By hand: 3x3 SAME convs, 2x2 max-pools, dense head.
    h = w = 32
    cin = 3
    mults = 0
    for c in cnn.channels:
        mults += h * w * 9 * cin * c
        cin = c
        h //= 2
        w //= 2
    mults += (h * w * cin) * cnn.dense
    mults += cnn.dense * cnn.out_channels
    assert got == mults


def test_cost_model_xla_flops_on_compiled_matmul():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    compiled = jax.jit(lambda x, y: x @ y).lower(a, b).compile()
    flops = profiling.cost_model.xla_flops(compiled)
    assert flops is not None
    # 2*M*K*N, allowing backend slack (epilogue/layout ops).
    assert flops >= 2 * 64 * 128 * 32


def test_cost_model_mfu_math():
    class FakeDev:
        device_kind = "TPU v5 lite"

    # 19.7 Tflop/s against a 197 Tflop/s peak = 10% MFU.
    assert profiling.cost_model.mfu(19.7e12, FakeDev()) == pytest.approx(0.1)
    assert profiling.cost_model.mfu(1.0, object()) is None  # unknown kind


def test_scaling_analyze_compiled_rides_cost_model():
    from tpfl.parallel.scaling import analyze_compiled

    a = jnp.zeros((32, 32), jnp.float32)
    compiled = jax.jit(lambda x: x @ x).lower(a).compile()
    rec = analyze_compiled(compiled)
    assert rec["flops"] == profiling.cost_model.xla_flops(compiled)


# --- HbmTracker -----------------------------------------------------------


def test_hbm_tracker_high_water_mark():
    tracker = profiling.HbmTracker()
    dev, in_use, peak = tracker.observe("7", {"bytes_in_use": 100})
    assert (in_use, peak) == (100.0, 100.0)
    # Runtime-reported peak wins when larger.
    _, _, peak = tracker.observe(
        "7", {"bytes_in_use": 50, "peak_bytes_in_use": 300}
    )
    assert peak == 300.0
    # The mark never regresses, even when usage falls.
    _, in_use, peak = tracker.observe("7", {"bytes_in_use": 10})
    assert (in_use, peak) == (10.0, 300.0)
    assert tracker.peaks() == {"7": 300.0}


def test_hbm_tracker_gauges_the_reserved_peak_where_reported():
    from tpfl.management.telemetry import metrics

    key = ("tpfl_hbm_peak_bytes_reserved", (("device", "hbm-test-9"),))
    tracker = profiling.HbmTracker()
    tracker.observe("hbm-test-9", {"bytes_in_use": 100})
    assert key not in metrics.fold()["gauges"]  # not reported: no gauge
    tracker.observe(
        "hbm-test-9", {"bytes_in_use": 100, "peak_bytes_reserved": 900}
    )
    assert metrics.fold()["gauges"][key] == 900.0


# --- trace wrap / Experiment capture --------------------------------------


def test_experiment_captures_profile_dir():
    from tpfl.experiment import Experiment

    Settings.PROFILING_TRACE_DIR = ""
    assert Experiment("e", 1).profile_dir == ""
    Settings.PROFILING_TRACE_DIR = "/tmp/trace-here"
    try:
        assert Experiment("e", 1).profile_dir == "/tmp/trace-here"
        assert Experiment("e", 1, profile_dir="/x").profile_dir == "/x"
    finally:
        Settings.PROFILING_TRACE_DIR = ""


def test_maybe_trace_noop_without_directory():
    with profiling.maybe_trace(None):
        pass
    with profiling.maybe_trace(""):
        pass
    assert profiling.stop_trace() is False  # nothing active


def test_registry_isolation_smoke():
    """The module uses the PROCESS registry; this sanity check pins the
    collector contract on a private registry instead (collectors get
    the registry they are registered on)."""
    reg = MetricsRegistry()
    seen = []
    reg.register_collector(lambda r: seen.append(r))
    reg.fold()
    assert seen == [reg]
