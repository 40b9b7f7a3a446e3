"""Adaptive async control plane (tpfl.learning.async_control) +
staleness-aware defense satellites: controller tuning/bounds/
determinism, ASYNC_UNTAGGED_POLICY freshness semantics, deadline
re-arm observability, the ledger's stale_flood anomaly class, and the
stale-flooding chaos e2e."""

import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.learning.aggregators import FedAvg
from tpfl.learning.aggregators.aggregator import (
    staleness_weight,
    untagged_staleness,
)
from tpfl.learning.async_control import AsyncController
from tpfl.learning.model import TpflModel
from tpfl.management.logger import logger
from tpfl.settings import Settings


def mk_model(value, n_samples, contributors):
    params = {
        "w": jnp.full((3, 3), float(value), jnp.float32),
        "b": jnp.full((3,), float(value), jnp.float32),
    }
    return TpflModel(
        params=params, num_samples=n_samples, contributors=contributors
    )


def leaf_value(model):
    return float(np.asarray(model.get_parameters()["w"])[0, 0])


def _counter(name: str, node: str) -> float:
    folded = logger.metrics.fold()
    total = 0.0
    for (n, labels), v in folded["counters"].items():
        if n == name and dict(labels).get("node") == node:
            total += v
    return total


# --- controller tuning -----------------------------------------------------


def test_controller_passthrough_when_disabled():
    Settings.ASYNC_ADAPTIVE = False
    Settings.ASYNC_BUFFER_K = 7
    Settings.ASYNC_ROUND_DEADLINE = 33.0
    ctl = AsyncController("n")
    assert ctl.round_open(0, 100) == (7, 33.0)
    # Disabled controllers observe nothing and record nothing.
    ctl.observe_round(0, [(0, 1.0), (0, 2.0)], "buffer_full", 33.0)
    assert ctl.round_open(1, 100) == (7, 33.0)
    assert ctl.trajectory() == []


def test_controller_bounds_and_fleet_clamp():
    Settings.ASYNC_ADAPTIVE = True
    Settings.ASYNC_BUFFER_K = 64
    Settings.ASYNC_K_MIN = 2
    Settings.ASYNC_K_MAX = 16
    ctl = AsyncController("n")
    k, deadline = ctl.round_open(0, 5)
    assert 2 <= k <= 5  # fleet-clamped below K_MAX
    assert 0.0 < deadline <= Settings.ASYNC_ROUND_DEADLINE
    k, _ = ctl.round_open(1, 1000)
    assert k <= 16  # K_MAX-clamped below the fleet


def test_controller_shrinks_k_on_deadline_close():
    Settings.ASYNC_ADAPTIVE = True
    Settings.ASYNC_BUFFER_K = 8
    ctl = AsyncController("n")
    k0, dl = ctl.round_open(0, 20)
    assert k0 == 8
    # The round deadline-closed with only 3 arrivals: the buffer was
    # asking for contributors the fleet does not deliver in time.
    ctl.observe_round(
        0, [(0, 1.0), (0, 2.0), (0, 3.0)], "deadline", dl
    )
    k1, _ = ctl.round_open(1, 20)
    assert k1 == 3  # shrunk to what actually arrived
    ctl.observe_round(1, [(0, 1.0)], "deadline", dl)
    k2, _ = ctl.round_open(2, 20)
    assert k2 == Settings.ASYNC_K_MIN  # never below the floor


def test_controller_grows_k_when_buffer_fills_fast():
    Settings.ASYNC_ADAPTIVE = True
    Settings.ASYNC_BUFFER_K = 4
    # Deadline adaptation is free-running-only (serialized stamps are
    # virtual-clock, not wall seconds — see async_control.round_open).
    Settings.ASYNC_SERIALIZED = False
    ctl = AsyncController("n")
    k0, dl = ctl.round_open(0, 20)
    # Buffer filled in a fraction of the armed deadline at zero
    # staleness: headroom exists, widen by one.
    ctl.observe_round(
        0, [(0, 0.1), (0, 0.2), (0, 0.3), (0, 0.4)], "buffer_full", dl
    )
    k1, dl1 = ctl.round_open(1, 20)
    assert k1 == k0 + 1
    # And the deadline tightened toward K x inter-arrival-quantile x 4
    # instead of riding the static ceiling.
    assert dl1 < Settings.ASYNC_ROUND_DEADLINE


def test_controller_staleness_pressure_sheds_k():
    Settings.ASYNC_ADAPTIVE = True
    Settings.ASYNC_BUFFER_K = 8
    ctl = AsyncController("n")
    _, dl = ctl.round_open(0, 20)
    # Fast fills but heavily stale arrivals: rounds are outpacing the
    # trainers feeding them — K must shrink, not grow.
    ctl.observe_round(
        0, [(6, 0.1), (8, 0.2), (7, 0.3)], "buffer_full", dl
    )
    k1, _ = ctl.round_open(1, 20)
    assert k1 == 7


def test_controller_observations_are_order_invariant():
    """Same arrival MULTISET in any order => identical trajectories —
    the property serialized-mode determinism rests on."""
    Settings.ASYNC_ADAPTIVE = True
    rounds = [
        ([(0, 1.0), (1, 3.0), (0, 2.0)], "buffer_full"),
        ([(2, 5.0), (0, 4.5)], "deadline"),
        ([(0, 6.0), (0, 6.5), (1, 7.0)], "buffer_full"),
    ]
    a, b = AsyncController("a"), AsyncController("b")
    for rnd, (arrivals, reason) in enumerate(rounds):
        _, dla = a.round_open(rnd, 10)
        _, dlb = b.round_open(rnd, 10)
        a.observe_round(rnd, arrivals, reason, dla)
        b.observe_round(rnd, list(reversed(arrivals)), reason, dlb)
    assert a.trajectory() == b.trajectory()


def test_controller_reset_drops_learned_state():
    Settings.ASYNC_ADAPTIVE = True
    ctl = AsyncController("n")
    _, dl = ctl.round_open(0, 10)
    ctl.observe_round(0, [(0, 1.0), (0, 2.0)], "deadline", dl)
    ctl.reset()
    assert ctl.trajectory() == []
    k, deadline = ctl.round_open(0, 10)
    assert k == Settings.ASYNC_BUFFER_K
    assert deadline == Settings.ASYNC_ROUND_DEADLINE


# --- untagged freshness policy ---------------------------------------------


def test_untagged_policy_resolution():
    Settings.ASYNC_STALENESS_MAX = 16
    Settings.ASYNC_UNTAGGED_POLICY = "fresh"
    assert untagged_staleness() == 0
    Settings.ASYNC_UNTAGGED_POLICY = "max-stale"
    assert untagged_staleness() == 16
    Settings.ASYNC_UNTAGGED_POLICY = "reject"
    assert untagged_staleness() is None


def test_untagged_max_stale_discounts_fold_weight():
    """An untagged contribution under max-stale folds at the heaviest
    discount instead of full weight (the spoofing bypass closed)."""
    Settings.ASYNC_UNTAGGED_POLICY = "max-stale"
    Settings.ASYNC_STALENESS_MAX = 8
    Settings.ASYNC_STALENESS_EXP = 0.5
    agg = FedAvg("n")
    agg.set_nodes_to_aggregate(["a", "b"], async_k=2, round_ordinal=50)
    agg.add_model(mk_model(1.0, 10, ["a"]), start_version=50)  # fresh
    agg.add_model(mk_model(3.0, 10, ["b"]))  # untagged
    out = agg.wait_and_get_aggregation(timeout=1.0)
    w_stale = 10 * staleness_weight(8)
    assert leaf_value(out) == pytest.approx(
        (1.0 * 10 + 3.0 * w_stale) / (10 + w_stale), rel=1e-5
    )
    agg.clear()


def test_untagged_reject_refuses_at_intake():
    Settings.ASYNC_UNTAGGED_POLICY = "reject"
    agg = FedAvg("n")
    agg.set_nodes_to_aggregate(["a", "b"], async_k=2, round_ordinal=5)
    before = _counter("tpfl_agg_untagged_rejected_total", "n")
    assert agg.add_model(mk_model(3.0, 10, ["b"])) == []
    assert _counter("tpfl_agg_untagged_rejected_total", "n") == before + 1
    assert agg.get_aggregated_models() == []
    # Tagged contributions still fold normally.
    covered = agg.add_model(mk_model(1.0, 10, ["a"]), start_version=5)
    assert covered == ["a"]
    agg.clear()


def test_untagged_policy_ignored_in_sync_rounds():
    Settings.ASYNC_UNTAGGED_POLICY = "reject"
    agg = FedAvg("n")
    agg.set_nodes_to_aggregate(["a"])  # synchronous round
    covered = agg.add_model(mk_model(1.0, 10, ["a"]))  # untagged, fine
    assert covered == ["a"]
    agg.clear()


# --- deadline re-arm observability -----------------------------------------


def test_deadline_rearm_attempt_field_and_counter():
    """Repeated empty-buffer fail-open re-arms emit one round_deadline
    event per attempt with a monotonically increasing `attempt` and
    bump tpfl_agg_deadline_rearm_total — a flooded/partitioned node is
    visible instead of silently cycling."""
    from tpfl.management.telemetry import flight

    Settings.TELEMETRY_ENABLED = True
    flight.clear("rearm-n")
    try:
        agg = FedAvg("rearm-n")
        agg.set_nodes_to_aggregate(["a", "b"], async_k=2, round_ordinal=0)
        before = _counter("tpfl_agg_deadline_rearm_total", "rearm-n")
        assert agg.async_deadline_close() is False
        assert agg.async_deadline_close() is False
        assert (
            _counter("tpfl_agg_deadline_rearm_total", "rearm-n")
            == before + 2
        )
        events = [
            e
            for e in flight.snapshot("rearm-n")
            if e.get("name") == "round_deadline"
        ]
        assert [e["attempt"] for e in events] == [1, 2]
        assert all(e["outcome"] == "empty" for e in events)
        # A held contribution makes the third attempt a real close.
        agg.add_model(mk_model(1.0, 10, ["a"]), start_version=0)
        assert agg.async_deadline_close() is True
        events = [
            e
            for e in flight.snapshot("rearm-n")
            if e.get("name") == "round_deadline"
        ]
        assert events[-1]["attempt"] == 3
        assert events[-1]["outcome"] == "closed"
        agg.clear()
    finally:
        Settings.TELEMETRY_ENABLED = False
        flight.clear("rearm-n")


def test_deadline_attempt_resets_per_round():
    agg = FedAvg("n")
    agg.set_nodes_to_aggregate(["a"], async_k=1, round_ordinal=0)
    agg.async_deadline_close()
    agg.add_model(mk_model(1.0, 10, ["a"]), start_version=0)
    agg.wait_and_get_aggregation(timeout=1.0)
    agg.clear()
    agg.set_nodes_to_aggregate(["a"], async_k=1, round_ordinal=1)
    assert agg._deadline_attempt == 0
    agg.clear()


# --- the stale_flood anomaly class -----------------------------------------


def test_scorer_flags_implausible_staleness():
    from tpfl.management.ledger import AnomalyScorer

    Settings.ASYNC_STALENESS_MAX = 4
    flagged, reasons, _ = AnomalyScorer.score(1.0, 1.0, [], staleness=5)
    assert flagged and reasons == ["stale_flood"]
    # Boundary τ == max is plausible (an honest straggler's tail).
    flagged, reasons, _ = AnomalyScorer.score(1.0, 1.0, [], staleness=4)
    assert not flagged
    # Negative max disables the class entirely.
    Settings.ASYNC_STALENESS_MAX = -1
    flagged, _, _ = AnomalyScorer.score(1.0, 1.0, [], staleness=500)
    assert not flagged


def test_scorer_flags_version_regression():
    from tpfl.management.ledger import AnomalyScorer

    Settings.ASYNC_STALENESS_MAX = 16
    flagged, reasons, _ = AnomalyScorer.score(
        1.0, 1.0, [], staleness=0, version_regressed=True
    )
    assert flagged and reasons == ["stale_flood"]


def test_score_now_stale_flood_and_regression_end_to_end():
    """The live defense path: an implausibly-stale intake flags
    stale_flood; a later version-regressing intake from the same peer
    flags too; the deterministic detections() view agrees."""
    from tpfl.management import ledger

    Settings.QUARANTINE_ENABLED = True
    Settings.LEDGER_ENABLED = True
    Settings.ASYNC_STALENESS_MAX = 3
    ledger.contrib.reset()
    try:
        ref = mk_model(1.0, 1, ["ref"]).get_parameters()
        ledger.contrib.open_round("n", 10, ref)
        # Honest fresh contribution: clean.
        e = ledger.contrib.score_now(
            "n", mk_model(1.01, 10, ["good"]), staleness=1
        )
        assert not e["flagged"]
        # τ = 10 > max = 3: the flood signature, no baseline needed.
        e = ledger.contrib.score_now(
            "n", mk_model(1.02, 10, ["evil"]), staleness=10
        )
        assert e["flagged"] and "stale_flood" in e["reasons"]
        ledger.contrib.close_round("n")
        # Next round: "good" regresses from v9 to v5 — a replay.
        ledger.contrib.open_round("n", 11, ref)
        e = ledger.contrib.score_now(
            "n", mk_model(1.01, 10, ["good"]), staleness=6
        )
        assert e["flagged"] and "stale_flood" in e["reasons"]
        ledger.contrib.close_round("n")
        det = ledger.contrib.detections()
        assert "evil" in det["flagged"]
        assert "stale_flood" in det["flagged"]["evil"]["reasons"]
        assert "stale_flood" in det["flagged"]["good"]["reasons"]
    finally:
        ledger.contrib.reset()
        Settings.QUARANTINE_ENABLED = False
        Settings.LEDGER_ENABLED = False


# --- replay adversaries drive the detection (plan-level) --------------------


def test_stale_flood_quarantined_and_readmitted_via_aggregator():
    """The closed loop at aggregator scale: a stale-flooding peer's
    replayed old-version contributions are excluded from folds once τ
    crosses the bound, and clean post-window contributions earn
    readmission after probation."""
    from tpfl.management import ledger
    from tpfl.management.quarantine import QuarantineEngine

    Settings.QUARANTINE_ENABLED = True
    Settings.LEDGER_ENABLED = True
    Settings.ASYNC_STALENESS_MAX = 2
    Settings.QUARANTINE_PROBATION_ROUNDS = 1
    ledger.contrib.reset()
    try:
        eng = QuarantineEngine("n")
        agg = FedAvg("n")
        agg.set_quarantine(eng)
        ref = mk_model(1.0, 1, ["ref"]).get_parameters()
        # Rounds 0..3: "evil" always replays version 0 — τ grows 0..3
        # and crosses max=2 at round 3.
        for rnd in range(4):
            agg.set_nodes_to_aggregate(
                ["good", "evil"], async_k=2, round_ordinal=rnd
            )
            ledger.contrib.open_round("n", rnd, ref)
            agg.add_model(mk_model(1.0, 10, ["good"]), start_version=rnd)
            agg.add_model(mk_model(5.0, 10, ["evil"]), start_version=0)
            out = agg.wait_and_get_aggregation(timeout=1.0)
            if rnd < 3:
                assert leaf_value(out) > 1.0  # stale junk still folds
            else:
                # Quarantined: the fold is the honest contribution only.
                assert leaf_value(out) == pytest.approx(1.0)
            agg.clear()
        assert eng.quarantined() == {"evil"}
        # Attack window over: two clean rounds earn readmission
        # (probation = 1 round past the last flag).
        for rnd in range(4, 7):
            agg.set_nodes_to_aggregate(
                ["good", "evil"], async_k=2, round_ordinal=rnd
            )
            ledger.contrib.open_round("n", rnd, ref)
            agg.add_model(mk_model(1.0, 10, ["good"]), start_version=rnd)
            agg.add_model(mk_model(1.0, 10, ["evil"]), start_version=rnd)
            agg.wait_and_get_aggregation(timeout=1.0)
            agg.clear()
        assert eng.quarantined() == set()
        assert any(
            a["action"] == "readmit" and a["peer"] == "evil"
            for a in eng.actions()
        )
    finally:
        ledger.contrib.reset()
        Settings.QUARANTINE_ENABLED = False
        Settings.LEDGER_ENABLED = False


# --- e2e: controller determinism + the stale-flooding fleet ----------------


@pytest.mark.parametrize(
    "rounds", [2, pytest.param(4, marks=pytest.mark.slow)]
)
def test_controller_serialized_same_seed_identical_trajectories(rounds):
    """Two same-seed serialized runs with the adaptive controller on
    produce identical K/deadline trajectories at every node (the
    virtual-clock observation discipline), and stay byte-identical —
    across the runs and across the nodes of a run."""
    from tpfl.attacks import controller_trajectories, run_seeded_experiment
    from tpfl.attacks.harness import final_model_digests
    from tpfl.communication.faults import TrainerSpeedPlan

    Settings.ASYNC_ROUNDS = True
    Settings.ASYNC_BUFFER_K = 2
    Settings.ASYNC_SERIALIZED = True
    Settings.ASYNC_ADAPTIVE = True
    Settings.DISABLE_SIMULATION = True

    def run():
        plan = TrainerSpeedPlan.skewed(
            [f"seed151-n{i}" for i in range(3)],
            slow_frac=0.34, base_delay=0.05, skew=5.0, seed=151,
        )
        exp = run_seeded_experiment(
            151, 3, rounds, epochs=1, speed_plan=plan,
            samples_per_node=60, batch_size=20, timeout=180.0,
        )
        return final_model_digests(exp), controller_trajectories(exp)

    (d1, t1), (d2, t2) = run(), run()
    assert t1 == t2
    assert all(traj for traj in t1.values())  # every node decided
    assert d1 == d2
    assert len(set(d1.values())) == 1


@pytest.mark.slow
@pytest.mark.chaos
def test_stale_flood_fleet_quarantined_and_readmitted_e2e():
    """The acceptance e2e: a 20% stale-flooding fleet (5 nodes, 1
    flooder replaying its round-0 contribution) is quarantined once its
    τ crosses ASYNC_STALENESS_MAX and readmitted after the attack
    window + probation; the quarantine verdicts match the plan ground
    truth exactly."""
    from tpfl.attacks import (
        AttackPlan,
        AttackSpec,
        adversary_map,
        run_seeded_experiment,
    )
    from tpfl.management import ledger, quarantine

    Settings.ASYNC_ROUNDS = True
    Settings.ASYNC_BUFFER_K = 5
    Settings.ASYNC_SERIALIZED = True
    Settings.ASYNC_STALENESS_MAX = 2
    Settings.QUARANTINE_PROBATION_ROUNDS = 1
    Settings.QUARANTINE_ENABLED = True
    Settings.LEDGER_ENABLED = True
    ledger.contrib.reset()
    try:
        plan = AttackPlan(
            {1: AttackSpec("stale_flood", end=6)}, seed=77
        )
        exp = run_seeded_experiment(
            77, 5, 9, epochs=1, attack_plan=plan,
            samples_per_node=60, batch_size=20, timeout=240.0,
        )
        truth = adversary_map(exp)
        assert sorted(truth.values()) == ["stale_flood"]
        replay = quarantine.replay_decisions()
        flagged = {
            a["peer"] for a in replay if a["action"] == "quarantine"
        }
        assert flagged == set(truth)
        assert all(
            "stale_flood" in a["reasons"]
            for a in replay
            if a["action"] == "quarantine"
        )
        # The window ended at round 6 and probation is 1 round: the
        # flooder's clean tail earns readmission before the end.
        assert any(
            a["action"] == "readmit" and a["peer"] in truth
            for a in replay
        )
        assert quarantine.quarantined_from_replay(replay) == set()
    finally:
        ledger.contrib.reset()
