"""Engine-plane telemetry tests (ISSUE 12): the ENGINE_TELEMETRY carry
in the fused round program + the management/engine_obs fan-out.

Pins the tentpole's contracts:

(a) ``ENGINE_TELEMETRY=False`` lowers the byte-identical round program
    of the pre-telemetry engine (HLO digest stability across a toggle;
    the program-cache key splits) and the carry variant lowers a
    DIFFERENT program;
(b) ``=True`` keeps same-seed ``run_rounds`` model outputs
    byte-identical at 1 and 8 devices — telemetry is read-only over
    the carry;
(c) the fan-out replays the carry into all three planes (per-round
    profiler rows, convergence events, ledger entries, ``tpfl_engine_*``
    registry series) honoring each plane's own gate;
(d) an engine-tier seeded sign-flip adversary (AttackPlan lowered into
    the program via ``attack_scales``) is flagged by the
    ledger/quarantine from the carry at precision = recall = 1.0;
(e) an exception inside the dispatch dumps
    ``flight-engine-<reason>.json`` like the Node.stop/crash paths.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.attacks.plan import AttackPlan, AttackSpec
from tpfl.management import engine_obs, ledger, profiling, quarantine
from tpfl.management.telemetry import flight, metrics
from tpfl.models import MLP
from tpfl.parallel import FederationEngine, create_mesh
from tpfl.settings import Settings


def _mlp():
    return MLP(hidden_sizes=(16,), compute_dtype=jnp.float32)


def _data(n, nb=1, bs=4, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.random((n, nb, bs, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (n, nb, bs)).astype(np.int32)
    return xs, ys


def _engine(n=8, mesh=None):
    return FederationEngine(_mlp(), n, mesh=mesh, seed=0)


def _model_bytes(mesh, tele, n=8, rounds=3, scales=None, weights=None):
    Settings.ENGINE_TELEMETRY = tele
    eng = _engine(n, mesh)
    p = eng.init_params((28, 28))
    xs, ys = _data(n)
    dx, dy = eng.shard_data(xs, ys)
    p, _ = eng.run_rounds(
        p, dx, dy, weights=weights, n_rounds=rounds, attack_scales=scales
    )
    return b"".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(p)
    )


# --- (a) program split: off is byte-identical, on is a new program --------


def test_off_program_hlo_identical_across_toggle():
    def digest(eng, tele):
        fn = eng.program("plain", 1, 2, 1, donate=False, telemetry=tele)
        n = eng.padded_nodes
        p = eng.init_params((28, 28))
        xs = jnp.zeros((n, 1, 4, 28, 28), jnp.float32)
        ys = jnp.zeros((n, 1, 4), jnp.int32)
        low = fn.lower(p, {}, {}, {}, xs, ys, eng.pad_weights(None), eng.valid)
        return hashlib.sha256(low.as_text().encode()).hexdigest()

    e1 = _engine()
    off_before = digest(e1, False)
    on = digest(e1, True)
    # A second engine that compiled the telemetry variant FIRST must
    # still lower the identical disabled program (cache-key split, no
    # cross-contamination).
    e2 = _engine()
    digest(e2, True)
    off_after = digest(e2, False)
    assert off_before == off_after
    assert on != off_before  # the carry exists when asked for


def test_telemetry_program_returns_carry_schema():
    from tpfl.parallel.engine import (
        TELEMETRY_NODE_FIELDS,
        TELEMETRY_ROUND_FIELDS,
    )

    eng = _engine()
    fn = eng.program("plain", 1, 3, 1, donate=False, telemetry=True)
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)
    out = fn(
        eng.init_params((28, 28)), {}, {}, {}, dx, dy,
        eng.pad_weights(None), eng.valid,
    )
    assert len(out) == 6
    tele = out[5]
    for k in TELEMETRY_NODE_FIELDS:
        assert np.asarray(tele[k]).shape == (3, eng.padded_nodes)
    for k in TELEMETRY_ROUND_FIELDS:
        assert np.asarray(tele[k]).shape == (3,)
    # Uniform full participation: every node elected, weight mass = n.
    np.testing.assert_allclose(np.asarray(tele["participation"]), 8.0)
    np.testing.assert_allclose(np.asarray(tele["weight_mass"]), 8.0)
    # Honest nodes train a small step from the shared start: cosine vs
    # the round-start reference sits near +1.
    assert np.all(np.asarray(tele["cos_ref"]) > 0.9)
    assert np.all(np.asarray(tele["update_norm"]) > 0.0)
    assert np.all(np.asarray(tele["delta_norm"]) > 0.0)


# --- (b) byte determinism off vs on, 1 and 8 devices ----------------------


@pytest.mark.parametrize("devices", [1, 8])
def test_model_bytes_identical_with_telemetry(devices):
    mesh = create_mesh({"nodes": devices}) if devices > 1 else None
    w = np.asarray([1, 1, 0, 1, 0, 1, 1, 1], np.float32)
    off = _model_bytes(mesh, False, weights=w)
    on = _model_bytes(mesh, True, weights=w)
    assert off == on


# --- (c) fan-out into the three planes ------------------------------------


def _run_windowed(tele=True, n=8, rounds=3, scales=None, weights=None):
    Settings.ENGINE_TELEMETRY = tele
    eng = _engine(n)
    p = eng.init_params((28, 28))
    xs, ys = _data(n)
    dx, dy = eng.shard_data(xs, ys)
    eng.run_rounds(
        p, dx, dy, weights=weights, n_rounds=rounds, attack_scales=scales
    )
    return eng


def test_fanout_profiler_rows_per_round():
    Settings.PROFILING_ENABLED = True
    profiling.rounds.reset()
    try:
        _run_windowed(rounds=3)
        mine = [
            r
            for r in profiling.rounds.attribution()
            if r["node"].startswith("engine:")
        ]
        # One WINDOW record (the legacy dispatch/train span) plus one
        # per-round row replayed from the carry.
        per_round = [r for r in mine if r.get("external")]
        assert len(mine) == 4
        assert [r["round"] for r in per_round] == [0, 1, 2]
        for rec in per_round:
            assert rec["parts"]["dispatch"] >= 0.0
            assert rec["parts"]["train"] >= 0.0
            assert rec["coverage"] >= 0.95
    finally:
        profiling.rounds.reset()


def test_fanout_convergence_and_registry_series():
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    ledger.convergence.reset()
    try:
        eng = _run_windowed(rounds=3)
        folded = metrics.fold()
        names = {
            k[0]
            for kind in ("counters", "gauges", "histograms")
            for k in folded[kind]
        }
        for expect in (
            "tpfl_engine_rounds_total",
            "tpfl_engine_loss",
            "tpfl_engine_delta_norm",
            "tpfl_engine_participation",
            "tpfl_engine_weight_mass",
            "tpfl_engine_update_norm",
            "tpfl_engine_cos_ref",
            "tpfl_convergence_delta_norm",
        ):
            assert expect in names, expect
        # The window summary event landed in THIS engine's flight ring
        # (rings are process-wide: an engine of another test file that
        # ran in this worker keeps its own, under its module's tag).
        node = f"engine:{profiling.module_tag(eng.module)}"
        assert node in flight.nodes()
        events = [
            e
            for e in flight.snapshot(node)
            if e.get("name") == "engine_window"
        ]
        assert events and events[-1]["rounds"] == 3
    finally:
        ledger.contrib.reset()
        ledger.convergence.reset()


def test_fanout_ledger_respects_election():
    """Only elected (weight > 0) nodes become ledger entries — the
    engine-tier mirror of 'only contributors reach the aggregator'."""
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    try:
        w = np.asarray([1, 1, 0, 1, 0, 1, 1, 0], np.float32)
        _run_windowed(rounds=2, weights=w)
        entries = ledger.contrib.entries()
        peers = {e["peer"] for e in entries}
        assert peers == {
            f"engine-node-{i}" for i in np.flatnonzero(w > 0)
        }
        assert len(entries) == 2 * int((w > 0).sum())
    finally:
        ledger.contrib.reset()


def test_disabled_planes_record_nothing():
    """ENGINE_TELEMETRY on with every plane off: only the always-on
    registry series exist — no profiler rows, no ledger entries."""
    assert not Settings.PROFILING_ENABLED and not Settings.LEDGER_ENABLED
    ledger.contrib.reset()
    profiling.rounds.reset()
    _run_windowed(rounds=2)
    assert ledger.contrib.entries() == []
    assert profiling.rounds.attribution() == []


# --- (d) engine-tier seeded adversary through ledger/quarantine -----------


def test_engine_sign_flip_adversary_precision_recall_one():
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    try:
        n = 8
        plan = AttackPlan(
            {2: AttackSpec("sign_flip"), 5: AttackSpec("sign_flip")},
            seed=7,
        )
        addrs = engine_obs.peer_names(n)
        scales = plan.engine_scales(addrs, n_rounds=3)
        _run_windowed(rounds=3, scales=scales)
        det = ledger.contrib.detections()
        truth = set(plan.adversary_map(addrs))
        assert truth == {"engine-node-2", "engine-node-5"}
        flagged = set(det["flagged"])
        assert flagged == truth  # precision = recall = 1.0
        for peer in truth:
            assert "sign_flip" in det["flagged"][peer]["reasons"]
        # The quarantine replay reaches the same verdict from the same
        # deduped view.
        actions = quarantine.replay_decisions(det)
        assert quarantine.quarantined_from_replay(actions) == truth
    finally:
        ledger.contrib.reset()


def test_attack_scales_match_host_side_sign_flip():
    """scale = -1 inside the program IS the gRPC tier's negation: the
    attacked engine run equals an unattacked run whose trained rows
    cannot be compared directly, so pin semantics on the carry: the
    flipped node's cosine sits at ~-1, honest nodes at ~+1."""
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    try:
        scales = np.ones((2, 8), np.float32)
        scales[:, 3] = -1.0
        _run_windowed(rounds=2, scales=scales)
        entries = ledger.contrib.entries()
        for e in entries:
            if e["peer"] == "engine-node-3":
                assert e["cos_ref"] < -0.9
                assert e["flagged"] and "sign_flip" in e["reasons"]
            else:
                assert e["cos_ref"] > 0.9
    finally:
        ledger.contrib.reset()


def test_engine_scales_validation():
    plan = AttackPlan({0: AttackSpec("additive_noise")}, seed=1)
    with pytest.raises(ValueError, match="sign_flip"):
        plan.engine_scales(["a"], n_rounds=2)
    eng = _engine(6)
    with pytest.raises(ValueError, match="attack_scales"):
        eng.pad_attack_scales(np.ones((4,), np.float32))
    padded = eng.pad_attack_scales(np.ones((6,), np.float32))
    assert padded.shape == (eng.padded_nodes,)
    xs, ys = _data(6)
    dx, dy = eng.shard_data(xs, ys)
    with pytest.raises(ValueError, match="per-round attack_scales"):
        eng.run_rounds(
            eng.init_params((28, 28)), dx, dy, n_rounds=3,
            attack_scales=np.ones((2, 6), np.float32),
        )


# --- (e) flight dump on engine dispatch failure ---------------------------


def test_engine_failure_dumps_flight_ring(tmp_path, monkeypatch):
    Settings.TELEMETRY_DUMP_DIR = str(tmp_path)
    flight.clear("engine")
    eng = _engine()
    xs, ys = _data(8)
    dx, dy = eng.shard_data(xs, ys)

    def boom(*args, **kwargs):
        def fn(*a, **k):
            raise RuntimeError("injected dispatch failure")

        return fn

    monkeypatch.setattr(eng, "_wrapped_program", boom)
    with pytest.raises(RuntimeError, match="injected dispatch failure"):
        eng.run_rounds(eng.init_params((28, 28)), dx, dy, n_rounds=2)
    dumps = list(tmp_path.glob("flight-engine-runtimeerror.json"))
    assert dumps, list(tmp_path.iterdir())
    import json

    doc = json.loads(dumps[0].read_text())
    events = [e for e in doc["events"] if e["name"] == "engine_failure"]
    assert events and "injected dispatch failure" in events[-1]["error"]
    flight.clear("engine")


# --- plane-seam units (record_external / observe_delta) -------------------


def test_ledger_record_external_scores_like_intake():
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    try:
        node = "engine:unit"
        # Honest cluster then a sign-flipped + norm-outlier entry.
        for r in range(4):
            e = ledger.contrib.record_external(
                node, "p-honest", r, 1.0 + 0.01 * r, 0.99
            )
            assert e is not None and not e["flagged"]
        bad = ledger.contrib.record_external(node, "p-evil", 4, 500.0, -0.98)
        assert bad["flagged"]
        assert set(bad["reasons"]) == {"sign_flip", "norm_outlier"}
        # Dedup: same (peer, round) returns the existing entry.
        again = ledger.contrib.record_external(node, "p-evil", 4, 1.0, 0.9)
        assert again is bad or again["t"] == bad["t"]
    finally:
        ledger.contrib.reset()


def test_convergence_observe_delta_events():
    Settings.LEDGER_ENABLED = True
    Settings.LEDGER_CONVERGENCE_WINDOW = 3
    ledger.convergence.reset()
    try:
        node = "engine:unit"
        out = None
        for r, d in enumerate((1.0, 2.0, 3.0)):  # monotone growth
            out = ledger.convergence.observe_delta(node, r, d, 10.0)
        assert out is not None and out.get("event") == "divergence"
        ledger.convergence.reset()
        for r in range(3):  # relative delta ~ 1e-6 << PLATEAU_REL
            out = ledger.convergence.observe_delta(node, r, 1e-5, 10.0)
        assert out is not None and out.get("event") == "plateau"
    finally:
        ledger.convergence.reset()


def test_profiler_record_external_gated_and_emitting():
    profiling.rounds.reset()
    assert not Settings.PROFILING_ENABLED
    assert (
        profiling.rounds.record_external("n", 0, {"train": 0.1}, 0.2) is None
    )
    Settings.PROFILING_ENABLED = True
    try:
        rec = profiling.rounds.record_external(
            "n", 7, {"train": 0.1, "dispatch": 0.05}, 0.2
        )
        assert rec["round"] == 7
        assert rec["parts"]["host_other"] == pytest.approx(0.05)
        assert rec["coverage"] == pytest.approx(1.0)
        assert profiling.rounds.attribution("n") == [rec]
    finally:
        Settings.PROFILING_ENABLED = False
        profiling.rounds.reset()
