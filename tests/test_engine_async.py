"""Free-running engine tests (ISSUE 16): the Sebulba-split window
pipeline and the on-device FedBuff round variant.

Pins the four async contracts: (a) the pipelined driver is
BYTE-identical to sequential dispatch — same seed, 1 and 8 devices,
donation report still clean — because it reorders host work only;
(b) the fedbuff program's staleness weighting is bit-parity with the
host aggregator's ``staleness_weight`` math (and the all-arrive τ=0
schedule compiles to the sync program's exact bytes); (c) speed-plan →
device-mask lowering is deterministic; (d) pipeline shutdown (natural
end AND mid-run interrupt) leaks no prefetch threads.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.models import MLP
from tpfl.parallel import (
    FederationEngine,
    FedBuffSchedule,
    WindowPipeline,
    create_mesh,
)
from tpfl.settings import Settings


@pytest.fixture(autouse=True)
def _clean_observatory():
    """Telemetry-enabled runs here write flight events and convergence
    state under the same ``engine:<tag>`` node tags test_engine_obs
    asserts over — clear the shared rings after each test."""
    yield
    from tpfl.management import ledger
    from tpfl.management.telemetry import flight

    flight.clear()
    ledger.convergence.reset()


def _mlp():
    return MLP(hidden_sizes=(16,), compute_dtype=jnp.float32)


def _data(n, nb=2, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.random((n, nb, bs, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (n, nb, bs)).astype(np.int32)
    return xs, ys


def _bytes(tree):
    return b"".join(
        np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)
    )


def _engine(n, mesh=None):
    return FederationEngine(_mlp(), n, mesh=mesh, seed=0)


def _run_sequential(n, mesh, n_rounds, window, schedule=None):
    eng = _engine(n, mesh)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(n))
    done = 0
    losses = None
    while done < n_rounds:
        k = min(window, n_rounds - done)
        sub = None if schedule is None else schedule.window(done, k)
        p, losses = eng.run_rounds(p, dx, dy, n_rounds=k, schedule=sub)
        done += k
    return p, losses


def _run_pipelined(n, mesh, n_rounds, window, schedule=None, **kw):
    eng = _engine(n, mesh)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(n))
    pipe = WindowPipeline(eng)
    (p, losses), done = pipe.run(
        p, dx, dy, n_rounds=n_rounds, window=window, schedule=schedule, **kw
    )
    assert done == n_rounds
    return p, losses, pipe


# --- (a) pipelined == sequential, byte for byte ---------------------------


@pytest.mark.parametrize("mesh_devices", [None, 8])
def test_pipeline_byte_identical_to_sequential(mesh_devices):
    mesh = (
        None if mesh_devices is None else create_mesh({"nodes": mesh_devices})
    )
    ps, ls = _run_sequential(4, mesh, n_rounds=6, window=2)
    pp, lp, _pipe = _run_pipelined(4, mesh, n_rounds=6, window=2)
    assert _bytes(ps) == _bytes(pp)
    assert _bytes(ls) == _bytes(lp)


def test_pipeline_byte_identical_with_fedbuff_and_telemetry():
    """The full free-running stack at once: async schedule + telemetry
    carry + pipelining — model bytes still match sequential dispatch."""
    Settings.ENGINE_TELEMETRY = True
    sched = FedBuffSchedule.from_periods([1, 1, 2, 3], 6)
    ps, _ = _run_sequential(4, None, n_rounds=6, window=2, schedule=sched)
    pp, _, _ = _run_pipelined(
        4, None, n_rounds=6, window=2,
        schedule=FedBuffSchedule.from_periods([1, 1, 2, 3], 6),
    )
    assert _bytes(ps) == _bytes(pp)


@pytest.mark.parametrize("mesh_devices", [None, 8])
def test_pipelined_fedbuff_same_seed_byte_identical(mesh_devices):
    """Two from-scratch pipelined FedBuff runs with one seed end on the
    same bytes, at 1 device and on the 8-device ``nodes`` mesh."""
    mesh = (
        create_mesh({"nodes": mesh_devices}) if mesh_devices else None
    )
    sched = FedBuffSchedule.from_periods([1 + (i % 3) for i in range(8)], 6)

    def run():
        p, _, _ = _run_pipelined(8, mesh, 6, 2, schedule=sched)
        return _bytes(p)

    assert run() == run()


def test_learner_prefetch_changes_no_byte_and_leaks_no_thread():
    """``ENGINE_PREFETCH`` is a host-side overlap only: a
    ``FederationLearner`` fit with it on and off gives the same model
    bytes, and no ``tpfl-window-prefetch`` thread outlives the fit."""
    from tpfl.learning.dataset import synthetic_mnist
    from tpfl.models import create_model
    from tpfl.parallel import FederationLearner

    ds = synthetic_mnist(n_train=256, n_test=32, seed=0, noise=0.4)
    Settings.SHARD_ROUNDS_PER_DISPATCH = 2

    def fit_bytes(prefetch):
        Settings.ENGINE_PREFETCH = prefetch
        learner = FederationLearner(
            model=create_model("mlp", (28, 28), seed=7, hidden_sizes=(16,)),
            data=ds,
            n_local_nodes=4,
            local_rounds=4,
            batch_size=16,
            seed=0,
        )
        return _bytes(learner.fit().get_parameters())

    assert fit_bytes(False) == fit_bytes(True)
    assert not [t for t in threading.enumerate() if "prefetch" in t.name]


def test_donation_still_clean():
    """The dispatch_window refactor kept end-to-end buffer aliasing:
    every donated state leaf still aliases an output buffer."""
    eng = _engine(4)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(4))
    report = eng.donation_report(p, dx, dy, n_rounds=2)
    assert report["clean"], report


# --- (b) fedbuff staleness math vs the host aggregator --------------------


def test_fedbuff_tau_zero_bit_parity_with_sync():
    """An all-arrive schedule (every node, every round, τ=0) must
    reproduce the sync program's bytes exactly — staleness weighting
    degrades to 1.0 like ``aggregator.staleness_weight(0)``."""
    n_rounds = 3
    sync_p, sync_l = _run_sequential(4, None, n_rounds, window=n_rounds)
    sched = FedBuffSchedule.from_periods([1, 1, 1, 1], n_rounds)
    assert sched.arrivals.all() and not sched.taus.any()
    fb_p, fb_l = _run_sequential(
        4, None, n_rounds, window=n_rounds, schedule=sched
    )
    assert _bytes(sync_p) == _bytes(fb_p)
    assert _bytes(sync_l) == _bytes(fb_l)


def test_fedbuff_staleness_weight_matches_host_math():
    """The engine folds arrival i at ``w_i * (1+τ_i)**-exp`` — exactly
    ``aggregator.staleness_weight``. Proven against a hand-computed
    single-round fold: params_out = Σ w̃_i·trained_i / Σ w̃_i over the
    arriving nodes."""
    from tpfl.learning.aggregators.aggregator import staleness_weight

    Settings.ASYNC_STALENESS_EXP = 0.5
    n = 4
    taus = [0, 1, 2, 3]

    # Reference: per-node trained params from a no-fold single-node run
    # (weights elect one node at a time, sync program, one round).
    eng = _engine(n)
    p0 = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(n))
    trained = []
    for i in range(n):
        w = np.zeros((n,), np.float32)
        w[i] = 1.0
        pi, _ = eng.run_rounds(p0, dx, dy, weights=w, n_rounds=1,
                               donate=False)
        trained.append(
            [np.asarray(x) for x in jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda t: t[i], pi)
            )]
        )

    # Engine fedbuff fold: all nodes arrive in round 0 with the given
    # taus (a one-round schedule can carry any τ ordinals).
    sched = FedBuffSchedule(
        np.ones((1, n), np.float32), np.asarray([taus], np.float32)
    )
    fb, _ = eng.run_rounds(p0, dx, dy, n_rounds=1, schedule=sched,
                           donate=False)
    got = [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda t: t[0], fb)
    )]

    sw = np.asarray([staleness_weight(t) for t in taus], np.float64)
    assert np.allclose(sw, (1.0 + np.asarray(taus, np.float64)) ** -0.5)
    for li, leaf in enumerate(got):
        expect = sum(
            sw[i] * trained[i][li].astype(np.float64) for i in range(n)
        ) / sw.sum()
        np.testing.assert_allclose(
            leaf.astype(np.float64), expect, rtol=2e-5, atol=2e-6
        )


def test_fedbuff_stragglers_keep_local_state():
    """A node in flight (no arrival) neither folds nor receives the
    broadcast — it keeps its locally-trained params, so its next
    arrival carries the accumulated update."""
    n = 4
    eng = _engine(n)
    p0 = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(n))
    # Node 3 never arrives in round 0 (arrives round 1 — schedule
    # validity needs every round to have SOME arrival).
    sched = FedBuffSchedule(
        np.asarray([[1, 1, 1, 0]], np.float32),
        np.zeros((1, n), np.float32),
    )
    fb, _ = eng.run_rounds(p0, dx, dy, n_rounds=1, schedule=sched,
                           donate=False)
    # Reference: node 3's pure local training (elected alone, but what
    # it KEEPS under fedbuff is its trained params pre-fold).
    w3 = np.asarray([0, 0, 0, 1], np.float32)
    solo, _ = eng.run_rounds(p0, dx, dy, weights=w3, n_rounds=1,
                             donate=False)
    row = jax.tree_util.tree_map(lambda t: t[3], fb)
    ref = jax.tree_util.tree_map(lambda t: t[3], solo)
    assert _bytes(row) == _bytes(ref)
    # ...and the arrived rows all hold the fold (identical to row 0).
    r0 = jax.tree_util.tree_map(lambda t: t[0], fb)
    r1 = jax.tree_util.tree_map(lambda t: t[1], fb)
    assert _bytes(r0) == _bytes(r1)
    assert _bytes(r0) != _bytes(row)


# --- (c) speed-plan lowering determinism ----------------------------------


def test_speed_plan_mask_determinism():
    from tpfl.communication.faults import TrainerSpeedPlan

    addrs = [f"node-{i}" for i in range(10)]
    plan_a = TrainerSpeedPlan.skewed(addrs, slow_frac=0.2, skew=10.0, seed=3)
    plan_b = TrainerSpeedPlan.skewed(addrs, slow_frac=0.2, skew=10.0, seed=3)
    sa = FedBuffSchedule.from_plan(plan_a, addrs, n_rounds=20)
    sb = FedBuffSchedule.from_plan(plan_b, addrs, n_rounds=20)
    assert np.array_equal(sa.arrivals, sb.arrivals)
    assert np.array_equal(sa.taus, sb.taus)
    # 10x-skewed tail: slow nodes arrive every ~10 rounds with τ=9,
    # fast nodes every round with τ=0.
    slow = [i for i, a in enumerate(addrs)
            if plan_a.delay_for(a) > plan_a.delays[addrs[0]] or
            plan_a.delay_for(a) == max(plan_a.delays.values())]
    arrivals_per_node = sa.arrivals.sum(axis=0)
    fast_count = max(arrivals_per_node)
    assert fast_count == 20
    assert min(arrivals_per_node) == 2  # every 10th round
    assert sa.taus.max() == 9.0
    # Every round folds someone (the schedule invariant).
    assert (sa.arrivals.sum(axis=1) > 0).all()
    # Chained windows continue one global schedule.
    full = FedBuffSchedule.from_plan(plan_a, addrs, n_rounds=20)
    parts = [full.window(0, 8), full.window(8, 8), full.window(16, 4)]
    assert np.array_equal(
        np.concatenate([p.arrivals for p in parts]), full.arrivals
    )


def test_schedule_rejects_empty_round():
    with pytest.raises(ValueError, match="no arrivals"):
        FedBuffSchedule(
            np.asarray([[1, 1], [0, 0]], np.float32),
            np.zeros((2, 2), np.float32),
        )


# --- (d) shutdown hygiene -------------------------------------------------


def _prefetch_threads():
    return [t for t in threading.enumerate() if "prefetch" in t.name]


def test_pipeline_prefetch_no_leaked_threads():
    calls = []

    def data_for(widx, start, k):
        calls.append((widx, start, k, threading.current_thread().name))
        return None

    _p, _l, _pipe = _run_pipelined(
        4, None, n_rounds=6, window=2, data_for=data_for, prefetch=True
    )
    assert _prefetch_threads() == []
    # Window 0 staged inline; 1 and 2 on the named background thread.
    assert [c[:3] for c in calls] == [(0, 0, 2), (1, 2, 2), (2, 4, 2)]
    assert calls[0][3] == "MainThread"
    assert all("tpfl-window-prefetch" in c[3] for c in calls[1:])


def test_pipeline_interrupt_stops_between_windows():
    eng = _engine(4)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(4))
    polls = {"n": 0}

    def should_stop():
        # Polled once per window, before its dispatch: let windows 0
        # and 1 through, interrupt before window 2.
        polls["n"] += 1
        return polls["n"] > 2

    pipe = WindowPipeline(eng)
    result, done = pipe.run(
        p, dx, dy, n_rounds=6, window=2, prefetch=True,
        should_stop=should_stop,
    )
    assert done == 4  # windows 0 and 1 ran; window 2 never dispatched
    assert result is not None  # the last dispatched window finalized
    assert _prefetch_threads() == []


def test_pipeline_supplier_error_propagates_and_joins():
    def data_for(widx, start, k):
        if widx == 1:
            raise RuntimeError("staging exploded")
        return None

    eng = _engine(4)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(4))
    with pytest.raises(RuntimeError, match="staging exploded"):
        WindowPipeline(eng).run(
            p, dx, dy, n_rounds=6, window=2, data_for=data_for,
            prefetch=True,
        )
    assert _prefetch_threads() == []


# --- telemetry fan-out: staleness + controller feed -----------------------


def test_fedbuff_telemetry_staleness_fanout():
    from tpfl.management import ledger
    from tpfl.management.telemetry import metrics

    Settings.ENGINE_TELEMETRY = True
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    try:
        n = 4
        eng = _engine(n)
        p = eng.init_params((28, 28))
        dx, dy = eng.shard_data(*_data(n))
        # Node 3 arrives only at round 2, with τ=2.
        sched = FedBuffSchedule.from_periods([1, 1, 1, 3], 3)
        eng.run_rounds(p, dx, dy, n_rounds=3, schedule=sched)

        gauges = metrics.fold()["gauges"]
        stale_series = {
            k: v for k, v in gauges.items()
            if k[0] == "tpfl_engine_staleness"
        }
        # Last round: three τ=0 arrivals + one τ=2 → mean 0.5.
        assert stale_series and pytest.approx(0.5) == next(
            iter(stale_series.values())
        )

        entries = [
            e for e in ledger.contrib.entries()
            if str(e.get("peer", "")).startswith("engine-node-")
        ]
        # Ledger entries exist ONLY for arrivals: rounds 0/1 have 3
        # each (nodes 0-2), round 2 has 4.
        assert len(entries) == 10
        late = [e for e in entries if e["peer"] == "engine-node-3"]
        assert len(late) == 1
        assert late[0]["round"] == 2
        assert late[0]["staleness"] == 2
        assert late[0]["version"] == 0  # trained from the round-0 pull
    finally:
        ledger.contrib.reset()


def test_fedbuff_feeds_async_controller():
    from tpfl.learning.async_control import AsyncController

    Settings.ENGINE_TELEMETRY = True
    Settings.ASYNC_ADAPTIVE = True
    n = 4
    eng = _engine(n)
    ctrl = AsyncController()
    eng.controller = ctrl
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(n))
    eng.run_rounds(
        p, dx, dy, n_rounds=3,
        schedule=FedBuffSchedule.from_periods([1, 1, 1, 3], 3),
    )
    # The controller saw every engine round's arrival list (the same
    # observe_round feed the gRPC aggregator produces on buffer flush):
    # last round has all 4 arrivals (node 3 with τ=2), folded into the
    # EWMA staleness state.
    assert ctrl._last_reason == "buffer_full"
    assert ctrl._last_arrivals == n
    assert ctrl._tau_mean is not None and ctrl._tau_mean > 0.0
    k, deadline = ctrl.round_open(3, n)
    assert 1 <= k <= n
    assert deadline > 0


# --- the program's own names (ISSUE 24) -------------------------------------

DISPATCH_SPANS = [
    "tpfl:prepare_args", "tpfl:program_lookup", "tpfl:program_call",
    "tpfl:dispatch",
]
FINALIZE_SPANS = ["tpfl:tele_fetch", "tpfl:replay_window", "tpfl:finalize"]


def _engine_spans():
    from tpfl.management.telemetry import flight

    return [
        e for e in flight.snapshot("engine")
        if e["kind"] == "span" and e["name"].startswith("tpfl:")
    ]


def test_engine_spans_of_one_window_and_of_a_pipeline_run():
    """Under TELEMETRY_ENABLED the engine's host phases land in the
    ``engine`` flight ring under exactly the fixed names, each tagged
    with its window's first round and nested under its parent."""
    from tpfl.management.telemetry import flight

    Settings.ENGINE_TELEMETRY = True
    Settings.TELEMETRY_ENABLED = True
    eng = _engine(4)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(*_data(4))
    flight.clear("engine")
    handle = eng.dispatch_window(p, dx, dy, n_rounds=2, donate=False)
    handle.wait()
    handle.finalize()
    handle.finalize()  # the cached return is no second span
    spans = _engine_spans()
    assert [e["name"] for e in spans] == (
        DISPATCH_SPANS + ["tpfl:wait"] + FINALIZE_SPANS
    )
    assert {e["trace"] for e in spans} == {"r0"}
    by_name = {e["name"]: e for e in spans}
    for child in DISPATCH_SPANS[:3]:
        assert by_name[child]["parent"] == by_name["tpfl:dispatch"]["span"]
    for child in FINALIZE_SPANS[:2]:
        assert by_name[child]["parent"] == by_name["tpfl:finalize"]["span"]
    assert by_name["tpfl:dispatch"]["parent"] == ""

    flight.clear("engine")
    WindowPipeline(eng).run(
        p, dx, dy, n_rounds=4, window=2, donate=False,
        data_for=lambda widx, start, k: None, prefetch=False,
        snapshot_every=1, snapshot_to=lambda rounds, state: None,
    )
    spans = _engine_spans()
    window = ["tpfl:data_take"] + DISPATCH_SPANS
    assert [e["name"] for e in spans] == (
        window + ["tpfl:pipeline_window"]  # window 0: nothing to finalize yet
        + ["tpfl:snapshot"] + window + FINALIZE_SPANS + ["tpfl:pipeline_window"]
        + FINALIZE_SPANS + ["tpfl:pipeline_window"]  # the closing finalize
        + ["tpfl:snapshot"]
    )
    # The engine has run 2 rounds before: the windows start at 2 and 4.
    assert [e["trace"] for e in spans if e["name"] == "tpfl:pipeline_window"] == [
        "r2", "r4", "r4",
    ]
    assert [e["trace"] for e in spans if e["name"] == "tpfl:finalize"] == ["r2", "r4"]
    windows = {e["span"] for e in spans if e["name"] == "tpfl:pipeline_window"}
    for e in spans:
        if e["name"] in ("tpfl:dispatch", "tpfl:finalize", "tpfl:data_take"):
            assert e["parent"] in windows, e


def test_round_body_legs_are_named_scopes_and_change_no_byte(monkeypatch):
    """The five scopes are in the lowered program's debug metadata, each
    only where its leg is compiled, and are metadata only: the same
    seeded rounds traced with ``jax.named_scope`` disabled give the same
    bytes and the same program text."""
    scopes = (
        "tpfl.train", "tpfl.optimizer", "tpfl.codec", "tpfl.telemetry",
        "tpfl.fold",
    )

    def lower_and_run():
        eng = _engine(4)
        p = eng.init_params((28, 28))
        dx, dy = eng.shard_data(*_data(4))
        fn, args = eng._donating_program(p, dx, dy, n_rounds=2)
        lowered = fn.lower(*args)
        out = eng.run_rounds(p, dx, dy, n_rounds=2, donate=False)
        return lowered.as_text(debug_info=True), lowered.as_text(), _bytes(out)

    dense_debug, _, _ = lower_and_run()
    assert "tpfl.train" in dense_debug and "tpfl.fold" in dense_debug
    assert "tpfl.codec" not in dense_debug and "tpfl.telemetry" not in dense_debug

    Settings.ENGINE_TELEMETRY = True
    Settings.ENGINE_WIRE_CODEC = "quant8"
    debug, text, out = lower_and_run()
    for scope in scopes:
        assert scope in debug, scope
    assert "tpfl." not in text  # nothing but metadata carries them

    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    bare_debug, bare_text, bare_out = lower_and_run()
    assert not any(scope in bare_debug for scope in scopes)
    assert bare_text == text and bare_out == out
