"""Aggregator tests, mirroring reference test/learning/aggregator_test.py
(numeric FedAvg checks, lifecycle/locking) and scaffold_test.py (math vs
hand-computed expectations, missing-info errors)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.learning.aggregators import (
    FedAvg,
    FedMedian,
    FedProx,
    Krum,
    MultiKrum,
    Scaffold,
    TrimmedMean,
)
from tpfl.learning.aggregators.aggregator import NoModelsToAggregateError
from tpfl.learning.model import TpflModel


def mk_model(value, n_samples, contributors, extra=None):
    params = {
        "w": jnp.full((2, 2), float(value), jnp.float32),
        "b": jnp.full((2,), float(value), jnp.float32),
    }
    m = TpflModel(params=params, num_samples=n_samples, contributors=contributors)
    if extra:
        m.additional_info.update(extra)
    return m


# --- FedAvg math (reference aggregator_test.py simple + weighted cases) ---


def test_fedavg_simple_mean():
    agg = FedAvg("t")
    out = agg.aggregate([mk_model(1, 1, ["a"]), mk_model(3, 1, ["b"])])
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.0)
    assert out.get_contributors() == ["a", "b"]
    assert out.get_num_samples() == 2


def test_fedavg_weighted_mean():
    agg = FedAvg("t")
    out = agg.aggregate([mk_model(0, 1, ["a"]), mk_model(4, 3, ["b"])])
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 3.0)


def test_fedmedian():
    agg = FedMedian("t")
    out = agg.aggregate(
        [mk_model(0, 1, ["a"]), mk_model(1, 1, ["b"]), mk_model(100, 1, ["c"])]
    )
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 1.0)


def test_trimmed_mean_robust_to_outlier():
    agg = TrimmedMean("t", trim=1)
    out = agg.aggregate(
        [mk_model(0, 1, ["a"]), mk_model(1, 1, ["b"]), mk_model(2, 1, ["c"]),
         mk_model(1000, 1, ["d"])]
    )
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 1.5)


def test_krum_picks_cluster_member():
    agg = Krum("t", n_byzantine=1)
    out = agg.aggregate(
        [mk_model(1.0, 1, ["a"]), mk_model(1.1, 1, ["b"]),
         mk_model(0.9, 1, ["c"]), mk_model(50.0, 1, ["evil"])]
    )
    assert float(np.asarray(out.get_parameters()["w"])[0, 0]) < 2.0


def test_multikrum_averages_best():
    agg = MultiKrum("t", n_byzantine=1, m=2)
    out = agg.aggregate(
        [mk_model(1.0, 1, ["a"]), mk_model(1.0, 1, ["b"]),
         mk_model(1.0, 1, ["c"]), mk_model(-99.0, 1, ["evil"])]
    )
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 1.0)


# --- lifecycle / state machine (reference aggregator_test.py:116+) ---


def test_aggregator_lifecycle_and_finish_event():
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b"])
    assert agg.get_missing_models() == {"a", "b"}
    assert agg.add_model(mk_model(1, 1, ["a"])) == ["a"]
    assert not agg._finish_aggregation_event.is_set()
    assert agg.add_model(mk_model(3, 1, ["b"])) == ["a", "b"]
    assert agg._finish_aggregation_event.is_set()
    out = agg.wait_and_get_aggregation(timeout=1)
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.0)
    agg.clear()
    assert agg.get_aggregated_models() == []


def test_aggregator_rejects_bad_contributions():
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b"])
    # not in train set
    assert agg.add_model(mk_model(1, 1, ["z"])) == []
    # ok
    assert agg.add_model(mk_model(1, 1, ["a"])) == ["a"]
    # duplicate
    assert agg.add_model(mk_model(2, 1, ["a"])) == []
    # overlapping partial
    assert agg.add_model(mk_model(2, 1, ["a", "b"])) == []


def test_aggregator_timeout_partial_and_empty():
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b"])
    agg.add_model(mk_model(5, 1, ["a"]))
    out = agg.wait_and_get_aggregation(timeout=0.1)  # b missing -> partial
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 5.0)
    agg.clear()
    agg.set_nodes_to_aggregate(["a"])
    with pytest.raises(NoModelsToAggregateError):
        agg.wait_and_get_aggregation(timeout=0.1)
    agg.clear()


def test_aggregator_double_start_raises():
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a"])
    with pytest.raises(Exception):
        agg.set_nodes_to_aggregate(["b"])
    agg.clear()


def test_partial_aggregation_get_model():
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    agg.add_model(mk_model(1, 1, ["a"]))
    agg.add_model(mk_model(3, 1, ["b"]))
    partial = agg.get_model(except_nodes=["a"])
    assert partial.get_contributors() == ["b"]
    both = agg.get_model(except_nodes=[])
    assert both.get_contributors() == ["a", "b"]
    np.testing.assert_allclose(np.asarray(both.get_parameters()["w"]), 2.0)
    assert agg.get_model(except_nodes=["a", "b"]) is None
    agg.clear()


def test_add_model_unblocks_waiter_thread():
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a"])
    result = {}

    def waiter():
        result["m"] = agg.wait_and_get_aggregation(timeout=5)

    th = threading.Thread(target=waiter)
    th.start()
    agg.add_model(mk_model(7, 1, ["a"]))
    th.join(timeout=5)
    assert not th.is_alive()
    np.testing.assert_allclose(np.asarray(result["m"].get_parameters()["w"]), 7.0)


# --- SCAFFOLD (reference scaffold_test.py:80-169) ---


def scaffold_model(y_val, dy_val, dc_val, contributors):
    m = mk_model(y_val, 1, contributors)
    dy = {"w": jnp.full((2, 2), float(dy_val)), "b": jnp.full((2,), float(dy_val))}
    dc = {"w": jnp.full((2, 2), float(dc_val)), "b": jnp.full((2,), float(dc_val))}
    m.add_info("scaffold", {"delta_y_i": dy, "delta_c_i": dc})
    return m


def test_scaffold_math_hand_computed():
    agg = Scaffold("t", global_lr=1.0)
    # round-start x = y - dy = 5 - 1 = 4 for the first model
    out = agg.aggregate(
        [scaffold_model(5, 1, 0.5, ["a"]), scaffold_model(7, 3, 1.5, ["b"])]
    )
    # x_new = 4 + mean(1,3) = 6 ; c = 0 + mean(0.5,1.5) = 1
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 6.0)
    np.testing.assert_allclose(
        np.asarray(out.get_info("scaffold")["global_c"]["w"]), 1.0
    )
    # second round: variates persist
    out2 = agg.aggregate([scaffold_model(9, 1, 1.0, ["a"])])
    np.testing.assert_allclose(np.asarray(out2.get_parameters()["w"]), 7.0)
    np.testing.assert_allclose(
        np.asarray(out2.get_info("scaffold")["global_c"]["w"]), 2.0
    )


def test_scaffold_missing_info_raises():
    agg = Scaffold("t")
    with pytest.raises(ValueError):
        agg.aggregate([mk_model(1, 1, ["a"])])
    with pytest.raises(ValueError):
        agg.aggregate([])


def test_scaffold_requires_callback():
    assert Scaffold("t").get_required_callbacks() == ["scaffold"]
    assert FedProx("t").get_required_callbacks() == ["fedprox"]
    assert FedAvg("t").get_required_callbacks() == []


def test_fedprox_callback_instantiable_and_mu_transport():
    from tpfl.learning.callbacks import CallbackFactory

    (cb,) = CallbackFactory.create(FedProx("t").get_required_callbacks())
    assert cb.get_name() == "fedprox"
    assert cb.prox_mu() == cb.DEFAULT_MU
    cb.set_info({"mu": 0.5})
    assert cb.prox_mu() == 0.5

    # The aggregator ships mu on the aggregated model.
    agg = FedProx("t", proximal_mu=0.123)
    out = agg.aggregate([mk_model(1.0, 4, ["a"]), mk_model(3.0, 4, ["b"])])
    assert out.get_info("fedprox") == {"mu": 0.123}


def test_fedprox_proximal_term_pulls_toward_anchor():
    """With a strong (but stable: lr*mu < 2(1+momentum)) mu the
    proximal pull dominates and parameters stay near the round-start
    anchor; with mu=0 they move freely."""
    import numpy as np

    from tpfl.learning.dataset import synthetic_mnist
    from tpfl.learning.jax_learner import JaxLearner
    from tpfl.models import create_model

    def drift(mu):
        ds = synthetic_mnist(n_train=128, n_test=16, seed=0)
        model = create_model("mlp", (28, 28), seed=1, hidden_sizes=(16,))
        ln = JaxLearner(
            model=model,
            data=ds,
            addr="prox-node",
            aggregator=FedProx("prox-node", proximal_mu=mu),
            learning_rate=0.1,
            batch_size=32,
        )
        # The aggregator seeds its configured mu at learner construction
        # (round 1 must not run on a default coefficient).
        (cb,) = [c for c in ln.callbacks if c.get_name() == "fedprox"]
        assert cb.prox_mu() == mu
        before = [np.asarray(x) for x in ln.get_model().get_parameters_list()]
        ln.set_epochs(2)
        ln.fit()
        after = [np.asarray(x) for x in ln.get_model().get_parameters_list()]
        return sum(float(np.abs(a - b).sum()) for a, b in zip(after, before))

    free = drift(0.0)
    pinned = drift(10.0)
    assert pinned < free * 0.3, (free, pinned)


# --- skipped-fit (num_samples == 0) contract ---


def test_fedavg_ignores_zero_weight_models():
    """A skipped fit's parameters (num_samples == 0) must not move the
    weighted mean, whatever garbage they hold."""
    agg = FedAvg("t")
    out = agg.aggregate(
        [
            mk_model(2, 10, ["a"]),
            mk_model(4, 10, ["b"]),
            mk_model(9999, 0, ["skipped"]),
        ]
    )
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 3.0)


def test_scaffold_ignores_skipped_models_info():
    """SCAFFOLD must ignore num_samples == 0 contributions entirely:
    no crash when they carry no info, and no control-variate pull when
    they carry a STALE round's info."""
    agg = Scaffold("t")
    delta = {
        "w": jnp.full((2, 2), 1.0, jnp.float32),
        "b": jnp.full((2,), 1.0, jnp.float32),
    }
    trained = mk_model(
        2,
        10,
        ["a"],
        extra={"scaffold": {"delta_y_i": delta, "delta_c_i": delta}},
    )
    # Skipped model WITHOUT info (the post-fix skip_fit contract):
    out = agg.aggregate([trained, mk_model(7, 0, ["skipped"])])
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.0)

    # Skipped model WITH stale info (pre-fix payloads on the wire must
    # still be harmless): deltas of 100 would visibly shift the mean.
    stale = {
        "w": jnp.full((2, 2), 100.0, jnp.float32),
        "b": jnp.full((2,), 100.0, jnp.float32),
    }
    agg2 = Scaffold("t")
    out2 = agg2.aggregate(
        [
            trained,
            mk_model(
                7,
                0,
                ["skipped"],
                extra={"scaffold": {"delta_y_i": stale, "delta_c_i": stale}},
            ),
        ]
    )
    np.testing.assert_allclose(np.asarray(out2.get_parameters()["w"]), 2.0)


def test_scaffold_all_skipped_raises():
    agg = Scaffold("t")
    with pytest.raises(ValueError, match="num_samples == 0"):
        agg.aggregate([mk_model(1, 0, ["a"]), mk_model(2, 0, ["b"])])


def test_stall_exit_detects_quiet_intake():
    """Aggregator.stalled: fires only while the round is open, with at
    least one contribution held, after intake has been quiet for the
    stall window — the scale profile's early exit when an elected peer
    never delivers (Settings.AGGREGATION_STALL)."""
    import time as _time

    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    # Open round, nothing held yet: never stalled (nothing to salvage).
    _time.sleep(0.3)
    assert not agg.stalled(0.25)
    agg.add_model(mk_model(1, 4, ["a"]))
    # Generous window right after intake: immune to CI preemption
    # (a tight window here would flake if the process is descheduled
    # between add_model and the assert).
    assert not agg.stalled(30.0)
    _time.sleep(0.3)
    assert agg.stalled(0.25)  # quiet past the window
    assert not agg.stalled(60.0)  # but not for a generous window
    agg.add_model(mk_model(2, 4, ["b"]))
    assert not agg.stalled(30.0)  # fresh intake resets the clock
    agg.add_model(mk_model(3, 4, ["c"]))
    _time.sleep(0.3)
    assert not agg.stalled(0.25)  # full coverage: round closed, not stalled
    # And the partial result is aggregatable the moment it stalls.
    agg2 = FedAvg("t2")
    agg2.set_nodes_to_aggregate(["a", "b"])
    agg2.add_model(mk_model(5, 4, ["a"]))
    out = agg2.wait_and_get_aggregation(timeout=0.0)
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 5.0)


# --- quorum-based round degradation (Settings.ROUND_QUORUM) ---


def test_remove_dead_nodes_shrinks_and_closes():
    """Heartbeat loss mid-round: the expected contributor set shrinks
    to the live members and aggregation closes once they all reported
    — instead of waiting out AGGREGATION_TIMEOUT on a crashed peer."""
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    agg.add_model(mk_model(1, 4, ["a"]))
    assert agg.is_open()
    # Dead peer with no contribution: removed; a+b still expected.
    assert not agg.remove_dead_nodes(["c"])
    assert agg.is_open()
    assert agg.get_missing_models() == {"b"}
    agg.add_model(mk_model(3, 4, ["b"]))
    assert not agg.is_open()  # live set fully covered -> closed
    out = agg.wait_and_get_aggregation(timeout=0.0)
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.0)


def test_remove_dead_nodes_keeps_received_contribution():
    """A member whose model already arrived is NOT removed on death —
    its contribution is valid; only the expectation of more drops."""
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b"])
    agg.add_model(mk_model(2, 4, ["b"]))
    assert not agg.remove_dead_nodes(["b"])  # already covered: kept
    assert agg.get_missing_models() == {"a"}
    agg.add_model(mk_model(4, 4, ["a"]))
    assert not agg.is_open()
    out = agg.wait_and_get_aggregation(timeout=0.0)
    assert sorted(out.get_contributors()) == ["a", "b"]


def test_removed_dead_member_readmitted_by_bundled_partial():
    """Peers can shrink at different times: a partial aggregate that
    still bundles a member we already declared dead must re-admit it
    (its contribution is real), not be rejected — rejection would
    deadlock the exchange and burn AGGREGATION_TIMEOUT."""
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b", "c"])
    agg.add_model(mk_model(1, 4, ["a"]))
    assert not agg.remove_dead_nodes(["c"])  # we think c is dead
    # A peer that received c's model before the crash pushes b+c.
    agg.add_model(mk_model(4, 4, ["b", "c"]))
    assert not agg.is_open()  # re-admitted and fully covered
    out = agg.wait_and_get_aggregation(timeout=0.0)
    assert sorted(out.get_contributors()) == ["a", "b", "c"]
    # Sample-weighted mean: (4*1 + 4*4) / 8.
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.5)
    # An unknown contributor is still rejected.
    agg2 = FedAvg("t")
    agg2.set_nodes_to_aggregate(["a", "b"])
    assert agg2.add_model(mk_model(1, 4, ["a", "z"])) == []


def test_round_quorum_closes_early():
    """ROUND_QUORUM < 1.0 closes aggregation once the fraction of the
    expected set has reported; the default 1.0 requires full coverage
    (reference behavior)."""
    from tpfl.settings import Settings

    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b", "c", "d"])
    agg.add_model(mk_model(1, 4, ["a"]))
    agg.add_model(mk_model(1, 4, ["b"]))
    assert agg.is_open()  # 2/4 < default quorum 1.0
    snap = Settings.ROUND_QUORUM
    try:
        Settings.ROUND_QUORUM = 0.75  # need ceil(0.75*4) = 3
        agg.add_model(mk_model(1, 4, ["c"]))
        assert not agg.is_open()  # 3/4 meets quorum
    finally:
        Settings.ROUND_QUORUM = snap


# --- streaming accumulate/finalize (O(1)-peak on-device reduction) ---


def test_fedavg_streaming_fold_matches_reference_math():
    """The donated running-accumulator fold must reproduce the stacked
    weighted mean (same inputs, same result, any fold order)."""
    agg = FedAvg("t")
    models = [mk_model(1, 1, ["a"]), mk_model(3, 2, ["b"]), mk_model(5, 3, ["c"])]
    expected = (1 * 1 + 3 * 2 + 5 * 3) / 6.0
    out = agg.aggregate(models)
    np.testing.assert_allclose(
        np.asarray(out.get_parameters()["w"]), expected, rtol=1e-6
    )
    # explicit streaming API, reversed order
    st = agg.acc_init(models[0])
    for m in reversed(models):
        st = agg.accumulate(st, m)
    out2 = agg.finalize(st)
    np.testing.assert_allclose(
        np.asarray(out2.get_parameters()["w"]), expected, rtol=1e-6
    )
    assert out2.get_contributors() == ["a", "b", "c"]
    assert out2.get_num_samples() == 6


def test_fedavg_fold_of_64_contributors_holds_one_model(monkeypatch):
    """Aggregation memory is flat in the contributor count: the fold is
    one running accumulator the size of ONE model after every
    contribution — the O(N x model) stack is never built."""
    from tpfl.learning.aggregators import aggregator as agg_mod

    def no_stack(models):
        raise AssertionError("stack_models materialises N models")

    monkeypatch.setattr(agg_mod, "stack_models", no_stack)
    agg = FedAvg("t")
    models = [mk_model(i, 1 + i % 3, [f"n{i}"]) for i in range(64)]
    one_model = sum(
        leaf.nbytes
        for leaf in jax.tree_util.tree_leaves(models[0].get_parameters())
    )
    st = agg.acc_init(models[0])
    held = set()
    for m in models:
        st = agg.accumulate(st, m)
        held.add(
            sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(st.acc))
        )
    assert st.count == 64
    # The same few bytes after the 1st contribution and after the 64th.
    assert len(held) == 1 and held.pop() < 3 * one_model
    out = agg.finalize(st)
    want = sum(i * (1 + i % 3) for i in range(64)) / sum(
        1 + i % 3 for i in range(64)
    )
    np.testing.assert_allclose(
        np.asarray(out.get_parameters()["w"]), want, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(agg.aggregate(models).get_parameters()["w"]), want, rtol=1e-5
    )


def test_eager_stream_reduces_on_arrival_and_closes_with_finalize():
    """Settings.AGG_STREAM_EAGER: add_model folds into the on-device
    accumulator as contributions arrive; wait_and_get_aggregation is a
    single finalize (no batch fold of held models)."""
    from tpfl.settings import Settings

    Settings.AGG_STREAM_EAGER = True
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b"])
    agg.add_model(mk_model(2, 1, ["a"]))
    assert agg._stream is not None and agg._stream.count == 1
    agg.add_model(mk_model(4, 1, ["b"]))
    out = agg.wait_and_get_aggregation(timeout=5)
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 3.0)
    assert agg._stream is None  # consumed exactly once (donated buffers)
    agg.clear()


def test_eager_stream_rejected_models_not_folded():
    from tpfl.settings import Settings

    Settings.AGG_STREAM_EAGER = True
    agg = FedAvg("t")
    agg.set_nodes_to_aggregate(["a", "b"])
    agg.add_model(mk_model(2, 1, ["a"]))
    agg.add_model(mk_model(999, 1, ["zz"]))  # not in train set: rejected
    agg.add_model(mk_model(999, 1, ["a"]))  # duplicate: rejected
    agg.add_model(mk_model(4, 1, ["b"]))
    out = agg.wait_and_get_aggregation(timeout=5)
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 3.0)
    agg.clear()


def test_fedprox_ships_mu_through_eager_finalize():
    from tpfl.settings import Settings

    Settings.AGG_STREAM_EAGER = True
    agg = FedProx("t", proximal_mu=0.123)
    agg.set_nodes_to_aggregate(["a", "b"])
    agg.add_model(mk_model(1, 1, ["a"]))
    agg.add_model(mk_model(3, 1, ["b"]))
    out = agg.wait_and_get_aggregation(timeout=5)
    assert out.get_info("fedprox") == {"mu": 0.123}
    agg.clear()


def test_scaffold_streaming_matches_batch():
    delta = {
        "w": jnp.full((2, 2), 1.0, jnp.float32),
        "b": jnp.full((2,), 1.0, jnp.float32),
    }
    mk = lambda v, c: mk_model(  # noqa: E731
        v, 10, [c], extra={"scaffold": {"delta_y_i": delta, "delta_c_i": delta}}
    )
    batch = Scaffold("t")
    out_b = batch.aggregate([mk(2, "a"), mk(4, "b")])
    stream = Scaffold("t")
    st = stream.acc_init(mk(2, "a"))
    st = stream.accumulate(st, mk(2, "a"))
    st = stream.accumulate(st, mk(4, "b"))
    out_s = stream.finalize(st)
    np.testing.assert_allclose(
        np.asarray(out_b.get_parameters()["w"]),
        np.asarray(out_s.get_parameters()["w"]),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(out_b.get_info("scaffold")["global_c"]["w"]),
        np.asarray(out_s.get_info("scaffold")["global_c"]["w"]),
        rtol=1e-6,
    )


def test_fedmedian_reservoir_is_bounded():
    from tpfl.settings import Settings

    Settings.AGG_MEDIAN_RESERVOIR = 4
    agg = FedMedian("t")
    models = [mk_model(i, 1, [f"n{i}"]) for i in range(10)]
    st = agg.acc_init(models[0])
    for m in models:
        st = agg.accumulate(st, m)
    assert len(st.extra["reservoir"]) == 4  # bounded past the cap
    out = agg.finalize(st)
    assert np.isfinite(np.asarray(out.get_parameters()["w"])).all()
    # below the cap the median is EXACT
    Settings.AGG_MEDIAN_RESERVOIR = 64
    exact = agg.aggregate(
        [mk_model(0, 1, ["a"]), mk_model(1, 1, ["b"]), mk_model(100, 1, ["c"])]
    )
    np.testing.assert_allclose(np.asarray(exact.get_parameters()["w"]), 1.0)


# --- streaming robust aggregators (bounded candidate buffers) ---


def mk_bf16(value, n_samples, contributors):
    params = {
        "w": jnp.full((2, 2), float(value), jnp.bfloat16),
        "b": jnp.full((2,), float(value), jnp.float32),
    }
    return TpflModel(
        params=params, num_samples=n_samples, contributors=contributors
    )


def stream_fold(agg, models):
    st = agg.acc_init(models[0])
    for m in models:
        st = agg.accumulate(st, m)
    return agg.finalize(st)


def test_krum_streaming_matches_batch_any_order():
    """Explicit accumulate/finalize (any arrival order) must select the
    same model as the all-at-once aggregate() fold."""
    # Distinct spacings -> a unique argmin (mutual-nearest-neighbor
    # ties would otherwise break by buffer order, not by score).
    models = [mk_model(1.0, 1, ["a"]), mk_model(1.1, 2, ["b"]),
              mk_model(1.3, 3, ["c"]), mk_model(1.6, 2, ["d"]),
              mk_model(50.0, 1, ["evil"])]
    batch = Krum("t", n_byzantine=1).aggregate(models)
    agg = Krum("t", n_byzantine=1)
    st = agg.acc_init(models[0])
    for m in reversed(models):
        st = agg.accumulate(st, m)
    out = agg.finalize(st)
    np.testing.assert_allclose(
        np.asarray(batch.get_parameters()["w"]),
        np.asarray(out.get_parameters()["w"]),
    )
    assert out.get_contributors() == ["a", "b", "c", "d", "evil"]
    # Krum keeps the CHOSEN model's sample count (it returns one model).
    assert out.get_num_samples() == batch.get_num_samples()


def test_multikrum_streaming_weighted_mean_and_metadata():
    """MultiKrum averages its selected models SAMPLE-WEIGHTED and keeps
    the full input picture in metadata (all contributors, total
    samples) — no per-model sample mass silently dropped."""
    models = [mk_model(1.0, 1, ["a"]), mk_model(1.2, 3, ["b"]),
              mk_model(5.0, 2, ["c"]), mk_model(-99.0, 1, ["evil"])]
    agg = MultiKrum("t", n_byzantine=1, m=2)
    out = agg.aggregate(models)
    # metadata: every input is represented
    assert out.get_contributors() == ["a", "b", "c", "evil"]
    assert out.get_num_samples() == 7
    # streaming == batch
    out2 = stream_fold(MultiKrum("t", n_byzantine=1, m=2), models)
    np.testing.assert_allclose(
        np.asarray(out.get_parameters()["w"]),
        np.asarray(out2.get_parameters()["w"]),
        rtol=1e-6,
    )
    # Selection keeps the tight (a, b) cluster; the mean is weighted
    # by num_samples: (1.0*1 + 1.2*3)/4 = 1.15, NOT the unweighted 1.1.
    val = float(np.asarray(out.get_parameters()["w"])[0, 0])
    assert val == pytest.approx(1.15, rel=1e-5)


def test_trimmed_mean_streaming_matches_batch_bfloat16():
    """Streaming-vs-batch equivalence with bfloat16 leaves: the
    per-leaf reservoir preserves leaf dtypes until the fused
    sort/mean."""
    models = [mk_bf16(v, 1, [c]) for v, c in
              [(0.0, "a"), (1.0, "b"), (2.0, "c"), (1000.0, "d")]]
    agg = TrimmedMean("t", trim=1)
    out_b = agg.aggregate(models)
    out_s = stream_fold(TrimmedMean("t", trim=1), models)
    for leaf in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(out_b.get_parameters()[leaf], np.float32),
            np.asarray(out_s.get_parameters()[leaf], np.float32),
        )
    assert out_s.get_parameters()["w"].dtype == jnp.bfloat16


def test_robust_single_model_edge():
    """All three robust aggregators handle the single-model round
    (timeout partials) identically in batch and streaming."""
    for agg_f in (lambda: Krum("t"), lambda: MultiKrum("t"),
                  lambda: TrimmedMean("t", trim=1)):
        m = mk_model(3.0, 5, ["only"])
        out_b = agg_f().aggregate([m])
        out_s = stream_fold(agg_f(), [m])
        np.testing.assert_allclose(
            np.asarray(out_b.get_parameters()["w"]),
            np.asarray(out_s.get_parameters()["w"]),
        )
        assert out_s.get_contributors() == ["only"]


def test_robust_buffer_bounded():
    """The candidate buffer is bounded at AGG_ROBUST_BUFFER: past the
    cap, seeded reservoir replacement keeps memory flat and the result
    finite."""
    from tpfl.settings import Settings

    Settings.AGG_ROBUST_BUFFER = 4
    models = [mk_model(float(i), 1, [f"n{i}"]) for i in range(12)]
    for agg in (Krum("t", n_byzantine=1), TrimmedMean("t", trim=1)):
        st = agg.acc_init(models[0])
        for m in models:
            st = agg.accumulate(st, m)
        assert len(st.extra["peers"]) == 4
        assert len(st.extra["params"]) == 4
        out = agg.finalize(st)
        assert np.isfinite(np.asarray(out.get_parameters()["w"], np.float32)).all()
        assert out.get_contributors() == sorted(f"n{i}" for i in range(12))


def test_krum_precondition_validated_not_clamped():
    """n < 2f+3 warns (Blanchard's requirement) instead of silently
    clamping the neighborhood to 1."""
    from tpfl.learning.aggregators.robust import krum_requirement_met

    assert krum_requirement_met(5, 1)
    assert not krum_requirement_met(4, 1)
    assert not krum_requirement_met(10, 4)
    warned = []
    from tpfl.management.logger import logger as _logger

    orig = _logger.warning
    _logger.warning = lambda node, msg, *a, **k: warned.append(msg)
    try:
        agg = Krum("t", n_byzantine=4)
        agg.aggregate([mk_model(float(i), 1, [f"n{i}"]) for i in range(5)])
    finally:
        _logger.warning = orig
    assert any("under-provisioned" in m for m in warned)


def test_trimmed_mean_no_trim_warns_and_surfaces():
    """n <= 2*trim keeps every coordinate (no trimming possible): warn +
    flight event instead of silence, and the effective trim lands in
    the registry."""
    from tpfl.management.logger import logger as _logger
    from tpfl.management.telemetry import flight

    warned = []
    orig = _logger.warning
    _logger.warning = lambda node, msg, *a, **k: warned.append(msg)
    try:
        flight.clear("t")
        agg = TrimmedMean("t", trim=2)
        out = agg.aggregate([mk_model(1.0, 1, ["a"]), mk_model(3.0, 1, ["b"])])
    finally:
        _logger.warning = orig
    np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.0)
    assert any("cannot trim" in m for m in warned)
    assert any(
        e.get("name") == "no_trim" for e in flight.snapshot("t")
    )


def test_robust_quarantine_shrinks_candidates():
    """A verdict landing AFTER a contribution was buffered still drops
    it at finalize (the candidate-set shrink)."""
    from tpfl.settings import Settings

    class FakeEngine:
        def quarantined(self):
            return {"evil"}

    Settings.QUARANTINE_ENABLED = True
    try:
        agg = TrimmedMean("t", trim=0)
        agg.set_quarantine(FakeEngine())
        models = [mk_model(1.0, 1, ["a"]), mk_model(3.0, 1, ["b"]),
                  mk_model(500.0, 1, ["evil"])]
        out = stream_fold(agg, models)
        # evil was buffered but shrunk out before the mean.
        np.testing.assert_allclose(np.asarray(out.get_parameters()["w"]), 2.0)

        krum = Krum("t", n_byzantine=1)
        krum.set_quarantine(FakeEngine())
        out2 = stream_fold(krum, models)
        assert float(np.asarray(out2.get_parameters()["w"])[0, 0]) < 4.0
    finally:
        Settings.QUARANTINE_ENABLED = False


def test_eager_stream_fold_error_falls_back_to_batch():
    """A mid-round fold failure (e.g. SCAFFOLD info missing at arrival)
    must not poison the round: the eager stream dies and round close
    batch-folds the held models (raising the aggregator's own error)."""
    from tpfl.settings import Settings

    Settings.AGG_STREAM_EAGER = True
    agg = Scaffold("t")
    agg.set_nodes_to_aggregate(["a"])
    agg.add_model(mk_model(1, 5, ["a"]))  # trained but NO scaffold info
    assert agg._stream is None and agg._stream_dead
    with pytest.raises(ValueError, match="delta_y_i"):
        agg.wait_and_get_aggregation(timeout=5)
    agg.clear()


# --- staleness-aware robust aggregation (async buffered rounds) ------------


def stream_fold_stale(agg, models_taus):
    st = agg.acc_init(models_taus[0][0])
    for m, tau in models_taus:
        st = agg.accumulate(st, m, staleness=tau)
    return agg.finalize(st)


def test_krum_rejects_candidates_past_staleness_max():
    """Boundary semantics: τ == max is kept, τ == max + 1 is rejected
    before scoring — a replayed old model can't win the selection just
    by sitting inside its own version's honest cluster."""
    from tpfl.settings import Settings

    Settings.ASYNC_STALENESS_MAX = 3
    # The stale candidate is the tightest cluster member — staleness-
    # blind Krum would select it.
    fresh = [(mk_model(1.0, 1, ["a"]), 0), (mk_model(1.2, 2, ["b"]), 1),
             (mk_model(1.4, 1, ["c"]), 3)]  # boundary τ: kept
    stale = (mk_model(1.1, 9, ["old"]), 4)  # τ > max: rejected
    out = stream_fold_stale(Krum("t", n_byzantine=0), fresh + [stale])
    val = float(np.asarray(out.get_parameters()["w"])[0, 0])
    assert val in (1.0, 1.2, 1.4)  # never the rejected 1.1
    # Coverage metadata still carries every contributor.
    assert out.get_contributors() == ["a", "b", "c", "old"]


def test_trimmedmean_all_stale_fails_open():
    """A buffer saturated by stale candidates must not brick the round:
    the staleness shrink fails open to the full (quarantine-kept)
    buffer with a loud warning."""
    from tpfl.settings import Settings

    Settings.ASYNC_STALENESS_MAX = 2
    models = [(mk_model(v, 1, [c]), 5) for v, c in
              [(1.0, "a"), (2.0, "b"), (3.0, "c")]]
    out = stream_fold_stale(TrimmedMean("t", trim=0), models)
    val = float(np.asarray(out.get_parameters()["w"])[0, 0])
    assert val == pytest.approx(2.0)  # plain mean of all three


def test_multikrum_staleness_discounts_selected_weights():
    """Multi-Krum's final average applies the FedBuff discount to each
    selected model's sample mass: w_i = num_samples * (1+τ)^-exp."""
    from tpfl.learning.aggregators.aggregator import staleness_weight
    from tpfl.settings import Settings

    Settings.ASYNC_STALENESS_MAX = 16
    Settings.ASYNC_STALENESS_EXP = 0.5
    models = [(mk_model(1.0, 10, ["a"]), 0), (mk_model(3.0, 10, ["b"]), 3)]
    out = stream_fold_stale(MultiKrum("t", n_byzantine=0, m=2), models)
    w_a = 10 * staleness_weight(0)
    w_b = 10 * staleness_weight(3)
    val = float(np.asarray(out.get_parameters()["w"])[0, 0])
    assert val == pytest.approx((1.0 * w_a + 3.0 * w_b) / (w_a + w_b),
                                rel=1e-5)


def test_krum_staleness_penalty_breaks_cluster_ties():
    """Two candidates equidistant from the cluster: the τ-stale one's
    score inflates by (1+τ)^exp and the fresh one is selected."""
    from tpfl.settings import Settings

    Settings.ASYNC_STALENESS_MAX = 16
    Settings.ASYNC_STALENESS_EXP = 1.0
    # Evenly spaced chain: the stale end and the fresh end have EQUAL
    # blind scores (each is 0.02 from its nearest neighbor) — the
    # (1+τ)^exp penalty must strictly order the fresh one first.
    models = [(mk_model(1.0, 1, ["stale"]), 8), (mk_model(1.02, 1, ["fresh"]), 0),
              (mk_model(1.04, 1, ["c"]), 0)]
    agg = Krum("t", n_byzantine=0)
    st = agg.acc_init(models[0][0])
    for m, tau in models:
        st = agg.accumulate(st, m, staleness=tau)
    kept = list(range(3))
    scores = np.asarray(agg._scores(st, kept))
    assert scores[1] < scores[0]  # fresh twin beats stale twin


def test_robust_streaming_mixed_tau_order_independent():
    """Streaming equivalence with a mixed-τ reservoir: permuted arrival
    orders produce the identical trimmed mean (the (candidate, τ)
    multiset — not the interleaving — determines the fold)."""
    from tpfl.settings import Settings

    Settings.ASYNC_STALENESS_MAX = 4
    entries = [(mk_model(0.0, 1, ["a"]), 0), (mk_model(1.0, 1, ["b"]), 2),
               (mk_model(2.0, 1, ["c"]), 4), (mk_model(99.0, 1, ["d"]), 5)]
    out1 = stream_fold_stale(TrimmedMean("t", trim=0), entries)
    out2 = stream_fold_stale(TrimmedMean("t", trim=0), list(reversed(entries)))
    np.testing.assert_array_equal(
        np.asarray(out1.get_parameters()["w"]),
        np.asarray(out2.get_parameters()["w"]),
    )
    # The τ=5 candidate was rejected: mean of the kept three.
    val = float(np.asarray(out1.get_parameters()["w"])[0, 0])
    assert val == pytest.approx(1.0)


def test_robust_sync_rounds_bit_identical_to_staleness_blind():
    """τ = 0 everywhere (every sync round): the staleness machinery is
    inert — selection and bytes match the plain streaming fold."""
    models = [mk_model(1.0, 1, ["a"]), mk_model(1.2, 2, ["b"]),
              mk_model(1.4, 3, ["c"]), mk_model(50.0, 1, ["evil"])]
    blind = stream_fold(Krum("t", n_byzantine=1), models)
    aware = stream_fold_stale(
        Krum("t", n_byzantine=1), [(m, 0) for m in models]
    )
    np.testing.assert_array_equal(
        np.asarray(blind.get_parameters()["w"]),
        np.asarray(aware.get_parameters()["w"]),
    )
