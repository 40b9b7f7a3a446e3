"""Learning-plane observatory tests: contribution ledger stats, anomaly
scoring (sign-flip / additive-noise signatures), deterministic
detections, convergence monitoring, the aggregator tap, the traceview
join, and the disabled-path zero-dispatch guarantee."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from tpfl.attacks.attacks import additive_noise, sign_flip
from tpfl.learning.model import TpflModel
from tpfl.management import ledger, telemetry
from tpfl.settings import Settings


@pytest.fixture(autouse=True)
def _clean_ledger():
    ledger.contrib.reset()
    ledger.convergence.reset()
    yield
    ledger.contrib.reset()
    ledger.convergence.reset()


def _ref_params(seed: int = 0, n: int = 2000):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    return {
        "dense": {"w": jax.random.normal(k1, (n // 20, 20)) * 0.3},
        "out": {"b": jax.random.normal(k2, (20,)) * 0.1},
    }


def _model(params, who: str, samples: int = 10) -> TpflModel:
    return TpflModel(params=params, contributors=[who], num_samples=samples)


def _honest(ref, rng_seed: int, scale: float = 0.01):
    key = jax.random.PRNGKey(1000 + rng_seed)
    leaves, treedef = jax.tree_util.tree_flatten(ref)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        out.append(leaf + scale * jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# --- stats + scoring ------------------------------------------------------


def test_record_stats_honest_flip_noise():
    """The fused reduction's features separate the attack families:
    honest ≈ (small norm, cos +1); sign-flip ≈ (2x ref norm, cos -1);
    additive noise ≈ (std·sqrt(d) norm, cos ≈ +1)."""
    Settings.LEDGER_ENABLED = True
    ref = _ref_params()
    ledger.contrib.open_round("obs", 2, ref)
    entries = []
    for i in range(6):
        e = ledger.contrib.record(
            "obs", _model(_honest(ref, i), f"honest-{i}"), trace=f"tr{i}"
        )
        entries.append(e)
    # Intake parks device scalars; flush materializes + scores them
    # (entry dicts mutate in place, so the held references fill in).
    assert entries[0]["update_norm"] is None  # pending until flushed
    ledger.contrib.flush()
    assert all(not e["flagged"] for e in entries)
    assert all(e["cos_ref"] > 0.99 for e in entries)
    assert all(e["round"] == 2 for e in entries)
    assert entries[0]["cos_mean"] is None  # nothing to compare against
    assert entries[1]["cos_mean"] is not None
    assert entries[0]["trace"] == "tr0"
    assert len(entries[0]["leaf_norms"]) == len(
        jax.tree_util.tree_leaves(ref)
    )

    flip = ledger.contrib.record(
        "obs", _model(sign_flip()(ref), "adv-flip")
    )
    ledger.contrib.flush()
    assert flip["flagged"] and "sign_flip" in flip["reasons"]
    assert flip["cos_ref"] < -0.99

    noise = ledger.contrib.record(
        "obs", _model(additive_noise(0.1, seed=7)(ref), "adv-noise")
    )
    ledger.contrib.flush()
    assert noise["flagged"] and "norm_outlier" in noise["reasons"]
    assert noise["z_norm"] >= Settings.LEDGER_ANOMALY_Z
    # Noise preserves direction: the cosine test must NOT fire.
    assert "sign_flip" not in noise["reasons"]


def test_scorer_min_n_gates_z_but_not_cosine():
    Settings.LEDGER_ENABLED = True
    Settings.LEDGER_ANOMALY_MIN_N = 4
    ref = _ref_params()
    ledger.contrib.open_round("obs", 0, ref)
    # First arrival is a noise adversary: no window yet, z-test must
    # abstain instead of dividing by an empty baseline...
    e = ledger.contrib.record(
        "obs", _model(additive_noise(0.2, seed=1)(ref), "adv-noise")
    )
    ledger.contrib.flush()
    assert not e["flagged"]
    # ...but a sign-flip needs no history.
    e = ledger.contrib.record("obs", _model(sign_flip()(ref), "adv-flip"))
    ledger.contrib.flush()
    assert e["flagged"] and e["reasons"] == ["sign_flip"]


def test_robust_z_floor_and_median():
    assert ledger.robust_z(5.0, []) == 0.0
    window = [1.0, 1.0, 1.0, 1.0]
    # Zero MAD: the relative floor (5% of median) keeps z finite.
    z = ledger.robust_z(2.0, window)
    assert z == pytest.approx((2.0 - 1.0) / 0.05)
    window = [0.9, 1.0, 1.1, 1.0, 10.0]
    assert ledger.robust_z(1.0, window) == pytest.approx(0.0, abs=1e-6)


def test_partial_aggregates_recorded_but_not_scored():
    Settings.LEDGER_ENABLED = True
    ref = _ref_params()
    ledger.contrib.open_round("obs", 1, ref)
    partial = TpflModel(
        params=sign_flip()(ref), contributors=["a", "b"], num_samples=20
    )
    e = ledger.contrib.record("obs", partial)
    assert e is not None and not e["single"]
    assert not e["flagged"]  # diluted mixtures are never flagged
    assert e["peer"] == "a+b"
    det = ledger.contrib.detections()
    assert det["entries"] == []  # and never scored in the global view


def test_ring_bounded():
    Settings.LEDGER_ENABLED = True
    Settings.LEDGER_RING = 8
    ref = _ref_params()
    ledger.contrib.open_round("obs", 0, ref)
    for i in range(30):
        ledger.contrib.record("obs", _model(_honest(ref, i), f"n{i}"))
    assert len(ledger.contrib.entries("obs")) == 8
    assert ledger.contrib.stats_for("obs") == {"entries": 8, "flagged": 0}


def test_close_round_drops_reference():
    Settings.LEDGER_ENABLED = True
    ref = _ref_params()
    ledger.contrib.open_round("obs", 0, ref)
    assert ledger.contrib.record("obs", _model(_honest(ref, 0), "a")) is not None
    ledger.contrib.close_round("obs")
    assert ledger.contrib.record("obs", _model(_honest(ref, 1), "b")) is None
    # No open round on a different node either.
    assert ledger.contrib.record("other", _model(ref, "c")) is None


# --- deterministic detections ---------------------------------------------


def test_detections_dedup_across_observers():
    """Two observers recording the same contribution produce ONE scored
    row per (peer, round), and flags aggregate per peer."""
    Settings.LEDGER_ENABLED = True
    ref = _ref_params()
    flip_params = sign_flip()(ref)
    for obs in ("obs-a", "obs-b"):
        ledger.contrib.open_round(obs, 0, ref)
        for i in range(4):
            ledger.contrib.record(obs, _model(_honest(ref, i), f"honest-{i}"))
        ledger.contrib.record(obs, _model(flip_params, "adv"))
    det = ledger.contrib.detections()
    assert len(det["entries"]) == 5  # 4 honest + 1 adversary, deduped
    assert set(det["flagged"]) == {"adv"}
    assert det["flagged"]["adv"]["rounds"] == [0]
    assert "sign_flip" in det["flagged"]["adv"]["reasons"]
    assert "honest-0" in det["peers"]

    # Same inputs -> byte-identical verdict.
    import json

    again = ledger.contrib.detections()
    assert json.dumps(det, sort_keys=True) == json.dumps(again, sort_keys=True)


def test_detections_byte_identical_across_arrival_orders():
    """The determinism receipt: the deduped verdict is a function of
    WHAT was contributed, not of the order gossip delivered it in or of
    which observer saw it — two "runs" of one seeded round, recorded in
    opposite arrival orders by differently named observers, give a
    byte-identical flag surface."""
    import json

    Settings.LEDGER_ENABLED = True
    ref = _ref_params()
    flip_params = sign_flip()(ref)

    def contributions():
        out = [(_honest(ref, i), f"honest-{i}") for i in range(4)]
        return out + [(flip_params, "adv")]

    def run(observers, reverse):
        ledger.contrib.reset()
        for obs in observers:
            ledger.contrib.open_round(obs, 0, ref)
            items = contributions()
            for params, who in reversed(items) if reverse else items:
                ledger.contrib.record(obs, _model(params, who))
        det = ledger.contrib.detections()
        return json.dumps(
            [
                {k: e[k] for k in ("peer", "round", "flagged", "reasons")}
                for e in det["entries"]
            ],
            sort_keys=True,
        ), set(det["flagged"])

    surface_a, flagged_a = run(("obs-a", "obs-b"), reverse=False)
    surface_b, flagged_b = run(("obs-c",), reverse=True)
    assert flagged_a == flagged_b == {"adv"}
    assert surface_a == surface_b


# --- disabled path --------------------------------------------------------


def test_disabled_ledger_adds_zero_dispatches(monkeypatch):
    """With LEDGER_ENABLED off every tap returns before any device
    work: poison the stat builders so a single dispatch would raise."""
    Settings.LEDGER_ENABLED = False

    def boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("device dispatch on the disabled path")

    monkeypatch.setattr(ledger, "_stats", boom)
    monkeypatch.setattr(ledger, "_delta_norm", boom)
    ref = _ref_params()
    ledger.contrib.open_round("obs", 0, ref)  # no-op
    assert ledger.contrib.record("obs", _model(ref, "a")) is None
    assert ledger.convergence.observe_global("obs", 0, ref) is None
    assert ledger.convergence.observe_loss("obs", 0, 1.0) is None
    assert ledger.contrib.entries() == []


# --- convergence monitor --------------------------------------------------


def test_convergence_delta_norm_and_plateau():
    Settings.LEDGER_ENABLED = True
    Settings.LEDGER_CONVERGENCE_WINDOW = 3
    telemetry.flight.clear("conv-node")
    ref = _ref_params()
    assert ledger.convergence.observe_global("conv-node", 0, ref) is None
    out = ledger.convergence.observe_global("conv-node", 1, _honest(ref, 1))
    assert out is not None and out["delta"] > 0
    # Identical params from here: relative delta 0 -> plateau once the
    # window fills.
    events = []
    for r in range(2, 6):
        o = ledger.convergence.observe_global("conv-node", r, ref)
        if o and "event" in o:
            events.append(o["event"])
    assert "plateau" in events
    names = {e["name"] for e in telemetry.flight.snapshot("conv-node")}
    assert "plateau" in names


def test_convergence_divergence_on_growing_deltas():
    Settings.LEDGER_ENABLED = True
    Settings.LEDGER_CONVERGENCE_WINDOW = 3
    ref = _ref_params()
    ledger.convergence.observe_global("div-node", 0, ref)
    events = []
    scale = 0.1
    params = ref
    for r in range(1, 7):
        params = jax.tree_util.tree_map(lambda p: p + scale, params)
        o = ledger.convergence.observe_global("div-node", r, params)
        if o and "event" in o:
            events.append(o["event"])
        scale *= 2.0  # strictly growing round-over-round delta
    assert "divergence" in events


def test_convergence_loss_slope():
    Settings.LEDGER_ENABLED = True
    Settings.LEDGER_CONVERGENCE_WINDOW = 4
    telemetry.flight.clear("loss-node")
    # Falling losses: negative slope, no event.
    for i, loss in enumerate([1.0, 0.8, 0.6, 0.4]):
        slope = ledger.convergence.observe_loss("loss-node", i, loss)
    assert slope == pytest.approx(-0.2)
    # Strictly rising full window: divergence event.
    for i, loss in enumerate([0.5, 0.7, 0.9, 1.1]):
        slope = ledger.convergence.observe_loss("loss-node", 10 + i, loss)
    assert slope == pytest.approx(0.2)
    names = [e["name"] for e in telemetry.flight.snapshot("loss-node")]
    assert "divergence" in names


# --- aggregator tap -------------------------------------------------------


def test_aggregator_tap_records_and_preserves_results():
    """add_model under LEDGER_ENABLED records entries (with the trace
    id) and the aggregation result is identical to the disabled run —
    detection is observational."""
    import numpy as np

    from tpfl.learning.aggregators import FedAvg

    ref = _ref_params()

    def run(enabled: bool):
        Settings.LEDGER_ENABLED = enabled
        ledger.contrib.reset()
        agg = FedAvg(node_name="tap-obs")
        agg.set_nodes_to_aggregate(["p0", "p1", "p2"])
        if enabled:
            ledger.contrib.open_round("tap-obs", 0, ref)
        for i in range(3):
            covered = agg.add_model(
                _model(_honest(ref, i), f"p{i}"), trace=f"trace-{i}"
            )
            assert f"p{i}" in covered
        out = agg.wait_and_get_aggregation(timeout=5)
        agg.clear()
        return out

    enabled_out = run(True)
    entries = ledger.contrib.entries("tap-obs")
    assert [e["peer"] for e in entries] == ["p0", "p1", "p2"]
    assert [e["trace"] for e in entries] == ["trace-0", "trace-1", "trace-2"]
    # clear() closed the ledger round too.
    assert ledger.contrib.record("tap-obs", _model(ref, "late")) is None

    disabled_out = run(False)
    assert ledger.contrib.entries("tap-obs") == []
    for a, b in zip(
        jax.tree_util.tree_leaves(enabled_out.get_parameters()),
        jax.tree_util.tree_leaves(disabled_out.get_parameters()),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- traceview join -------------------------------------------------------


def test_traceview_ledger_report_joins_hops():
    from tools.traceview import build_timeline, ledger_report, render_ledger

    entries = [
        {"kind": "span", "name": "encode", "node": "a", "trace": "tt1",
         "span": "s1", "t0": 1.0, "t1": 1.01},
        {"kind": "span", "name": "send", "node": "a", "peer": "b",
         "trace": "tt1", "span": "s2", "t0": 1.02, "t1": 1.05},
        {"kind": "span", "name": "decode", "node": "b", "trace": "tt1",
         "span": "s3", "t0": 1.06, "t1": 1.07},
        {"kind": "event", "name": "contrib", "node": "b", "trace": "tt1",
         "t": 1.08, "peer": "a", "round": 3, "update_norm": 0.5,
         "cos_ref": 0.99, "num_samples": 10, "flagged": False},
        # An untraced local contribution, flagged.
        {"kind": "event", "name": "contrib", "node": "c", "trace": "",
         "t": 2.0, "peer": "adv", "round": 3, "update_norm": 40.0,
         "cos_ref": -1.0, "num_samples": 10, "flagged": True},
        {"kind": "event", "name": "anomaly", "node": "c", "trace": "",
         "t": 2.0, "peer": "adv", "round": 3,
         "reasons": "sign_flip,norm_outlier", "z_norm": 120.0},
        # The quarantine engine's defense action for the same
        # contribution, joined by (observer, peer, round) + trace id.
        {"kind": "event", "name": "quarantine", "node": "c", "trace": "",
         "t": 2.01, "peer": "adv", "round": 3,
         "reasons": "sign_flip,norm_outlier"},
        # A standalone readmit (its contrib entry already rotated out).
        {"kind": "event", "name": "readmit", "node": "b", "trace": "tt1",
         "t": 9.0, "peer": "adv", "round": 7, "reasons": ""},
    ]
    rows = ledger_report(build_timeline(entries))
    assert len(rows) == 3
    traced = next(r for r in rows if r["peer"] == "a")
    assert traced["hops"] == ["encode@a", "send@a->b", "decode@b"]
    assert traced["observer"] == "b" and not traced["flagged"]
    adv = next(r for r in rows if r["peer"] == "adv" and r["round"] == 3)
    assert adv["flagged"] and adv["reasons"] == ["sign_flip", "norm_outlier"]
    assert adv["hops"] == []
    assert adv["action"] == "quarantine"
    readmit = next(
        r for r in rows if r["peer"] == "adv" and r["round"] == 7
    )
    assert readmit["action"] == "readmit" and readmit["observer"] == "b"
    text = render_ledger(build_timeline(entries))
    assert "sign_flip" in text and "encode@a" in text
    assert "[QUARANTINE]" in text and "[READMIT]" in text


# --- end-to-end detection -------------------------------------------------


def test_ledger_e2e_flags_adversary():
    """Seeded 4-node federation with one persistent sign-flip
    adversary: the deterministic detections view flags exactly it, and
    the harness exposes the ground truth."""
    from tpfl.attacks import adversary_map, run_seeded_experiment

    Settings.LEDGER_ENABLED = True
    Settings.ELECTION = "hash"
    Settings.TRAIN_SET_SIZE = 4
    exp = run_seeded_experiment(
        77, 4, 2,
        adversaries={2: sign_flip()},
        samples_per_node=60,
        batch_size=20,
        timeout=240.0,
    )
    truth = adversary_map(exp)
    assert set(truth) == {"seed77-n2"}
    assert truth["seed77-n2"] == "sign_flip"
    det = ledger.contrib.detections()
    assert set(det["flagged"]) == {"seed77-n2"}
    assert "sign_flip" in det["flagged"]["seed77-n2"]["reasons"]
    # Every trainer's per-round single contribution was scored.
    assert len(det["entries"]) == 8  # 4 peers x 2 rounds
    # The registry carries the contrib series.
    folded = telemetry.metrics.fold()
    assert any(
        k[0] == "tpfl_contrib_total" for k in folded["counters"]
    )
