"""Elastic engine tests (ISSUE 17 tentpole (a)).

Membership churn — joins, leaves, crashes, quarantine verdicts — must
be pure weight-mask edits against engine programs compiled at padded
pow-2 capacity tiers: **zero recompiles** inside a tier (the
CompileObservatory's per-program signature counts are the receipt),
and masked results byte-identical to a fresh-compiled exact-size run
modulo padding. Runs on the conftest 8-virtual-device CPU platform.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.learning.dataset import RandomIIDPartitionStrategy, synthetic_mnist
from tpfl.management import profiling
from tpfl.models import MLP
from tpfl.parallel import VmapFederation, create_mesh
from tpfl.parallel.membership import MembershipView
from tpfl.parallel.mesh import capacity_tier
from tpfl.settings import Settings


def _node_data(n, n_batches=2, bs=8):
    ds = synthetic_mnist(n_train=n * n_batches * bs, n_test=32, seed=0, noise=0.4)
    parts = ds.generate_partitions(n, RandomIIDPartitionStrategy, seed=0)
    xs, ys = [], []
    for p in parts:
        b = p.export(batch_size=bs)
        x, y = b.stacked(num_batches=n_batches)
        xs.append(x)
        ys.append(y)
    return np.stack(xs), np.stack(ys)


def _fed(n, mesh=None, seed=0):
    return VmapFederation(
        MLP(hidden_sizes=(8,), compute_dtype=jnp.float32), n, mesh=mesh,
        seed=seed,
    )


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# --- capacity tiers -------------------------------------------------------


def test_capacity_tier_pow2_buckets():
    assert capacity_tier(0) == 1
    assert capacity_tier(1) == 1
    assert capacity_tier(2) == 2
    assert capacity_tier(3) == 4
    assert capacity_tier(5) == 8
    assert capacity_tier(8) == 8
    assert capacity_tier(9) == 16
    # The floor wins when larger than the live count.
    assert capacity_tier(1, floor=4) == 4
    assert capacity_tier(6, floor=4) == 8


# --- MembershipView units -------------------------------------------------


def test_membership_join_leave_slot_reuse():
    view = MembershipView(["a", "b", "c"], capacity_min=2)
    assert view.capacity == 4 and view.live == 3
    assert [view.slot_of(x) for x in "abc"] == [0, 1, 2]
    freed = view.leave("b")
    assert freed == 1 and view.slot_of("b") is None
    # Lowest-slot reuse: the next join lands in b's old slot.
    assert view.join("d") == 1
    # A rejoining member is idempotent.
    assert view.join("d") == 1
    assert view.crash("nobody") is None
    w = view.weights()
    assert w.shape == (4,) and w.dtype == np.float32
    np.testing.assert_array_equal(w, [1.0, 1.0, 1.0, 0.0])


def test_membership_promotion_doubles_capacity():
    view = MembershipView(["a", "b"], capacity_min=2)
    assert view.capacity == 2 and view.promotions() == 0
    view.join("c")  # full -> promote
    assert view.capacity == 4
    assert view.promotions() == 1
    view.join("d")
    view.join("e")  # full again -> promote
    assert view.capacity == 8
    assert view.promotions() == 2
    kinds = [e["kind"] for e in view.tier_events()]
    assert kinds == ["promote", "promote"]


def test_membership_demotion_hysteresis_and_compaction():
    view = MembershipView([f"n{i}" for i in range(8)], capacity_min=2)
    assert view.capacity == 8
    for i in range(2, 7):
        view.leave(f"n{i}")
    # 3 live of 8: above the 0.25 fill floor — the tier HOLDS.
    assert view.maybe_resize() is None and view.capacity == 8
    view.leave("n7")
    # 2 of 8 = the 0.25 fill floor: demote (the shed tier stays at
    # most half full). Slots compact to 0..n-1 so every row fits.
    assert view.maybe_resize() == 2
    assert view.capacity == 2
    assert view.slot_of("n0") == 0 and view.slot_of("n1") == 1
    assert view.weights().shape == (2,)
    assert [e["kind"] for e in view.tier_events()] == ["demote"]


def test_membership_demotion_defers_under_staleness_pressure():
    class _StaleController:
        def state_export(self):
            return {"tau_mean": 3.0}

    class _FreshController:
        def state_export(self):
            return {"tau_mean": 0.5}

    view = MembershipView([f"n{i}" for i in range(8)], capacity_min=2)
    for i in range(1, 8):
        view.leave(f"n{i}")
    assert view.maybe_resize(_StaleController()) is None
    assert view.capacity == 8  # held under staleness pressure
    assert view.maybe_resize(_FreshController()) == 2


def test_membership_quarantine_is_a_mask_edit():
    view = MembershipView(["a", "b", "c"], capacity_min=4)
    assert view.quarantine("b") and not view.quarantine("ghost")
    np.testing.assert_array_equal(view.weights(), [1.0, 0.0, 1.0, 0.0])
    assert view.slot_of("b") == 1  # slot KEPT, weight zeroed
    assert view.readmit("b") and not view.readmit("b")
    np.testing.assert_array_equal(view.weights(), [1.0, 1.0, 1.0, 0.0])
    # The verdict seam: reconcile with a quarantine engine's set.
    view.apply_verdicts({"a", "c", "not-a-member"})
    assert view.quarantined() == {"a", "c"}
    np.testing.assert_array_equal(view.weights(), [0.0, 1.0, 0.0, 0.0])
    view.apply_verdicts(set())
    np.testing.assert_array_equal(view.weights(), [1.0, 1.0, 1.0, 0.0])


def test_membership_weights_base_dict():
    view = MembershipView(["a", "b"], capacity_min=4)
    np.testing.assert_array_equal(
        view.weights({"a": 0.5}), [0.5, 1.0, 0.0, 0.0]
    )


def test_membership_state_round_trip():
    view = MembershipView(["a", "b", "c"], capacity_min=2)
    view.join("d")
    view.join("e")  # promote to 8
    view.leave("b")
    view.quarantine("c")
    state = view.state_export()
    back = MembershipView.from_state(state)
    assert back.capacity == view.capacity
    assert back.members() == view.members()
    assert back.quarantined() == {"c"}
    assert back.promotions() == view.promotions()
    np.testing.assert_array_equal(back.weights(), view.weights())
    # Slot stability survives the round trip: a rejoin reuses b's slot.
    assert back.join("b") == 1


# --- zero-recompile churn storm ------------------------------------------


def test_churn_storm_zero_recompiles_at_fixed_tier():
    """10 membership events inside one capacity tier: every engine
    program keeps exactly ONE compile signature (the observatory's
    recompile receipt) and the view logs zero promotions."""
    n = 4
    xs, ys = _node_data(n)
    addrs = [f"n{i}" for i in range(n)]
    view = MembershipView(addrs, capacity_min=4)
    fed = _fed(n)
    fed.engine.attach_membership(view)
    params = fed.init_params((28, 28))

    Settings.PROFILING_ENABLED = True
    profiling.observatory.reset()
    # Churn storm: leave/rejoin/crash/quarantine/readmit between
    # windows — all mask edits at tier 4.
    events = [
        ("leave", "n1"), ("join", "n1"), ("crash", "n2"),
        ("join", "n2"), ("quarantine", "n3"), ("readmit", "n3"),
        ("leave", "n0"), ("join", "n0"), ("quarantine", "n1"),
        ("readmit", "n1"),
    ]
    for kind, addr in events:
        getattr(view, kind)(addr)
        assert not fed.engine.sync_membership()  # tier never moves
        params, _ = fed.engine.run_rounds(
            params, xs, ys, weights=view.weights(), n_rounds=1,
            donate=False,
        )
    counts = {
        k: v
        for k, v in profiling.observatory.signature_counts().items()
        if k.startswith("engine_round")
    }
    assert counts, "storm compiled no engine program?"
    assert all(v == 1 for v in counts.values()), counts
    assert view.promotions() == 0
    # The tier is in the program name: churn shares one per-tier entry.
    assert all(":c4" in k for k in counts)


def test_tier_promotion_compiles_once_then_caches():
    """Crossing a tier boundary lowers ONE new program; demoting back
    re-uses the old tier's cached program (no second compile)."""
    xs4, ys4 = _node_data(4)
    xs8, ys8 = _node_data(8)
    view = MembershipView([f"n{i}" for i in range(4)], capacity_min=4)
    fed = _fed(4)
    fed.engine.attach_membership(view)
    p4 = fed.init_params((28, 28))

    Settings.PROFILING_ENABLED = True
    profiling.observatory.reset()
    fed.engine.run_rounds(p4, xs4, ys4, weights=view.weights(),
                          n_rounds=1, donate=False)
    view.join("n4")  # 5 live -> promote to 8
    assert view.promotions() == 1
    assert fed.engine.sync_membership()
    p8 = fed.init_params((28, 28))
    fed.engine.run_rounds(p8, xs8, ys8, weights=view.weights(),
                          n_rounds=1, donate=False)
    for a in ["n4", "n3", "n2", "n1"]:
        view.leave(a)
    assert fed.engine.sync_membership()  # demote back to tier 4
    assert view.capacity == 4
    fed.engine.run_rounds(p4, xs4, ys4, weights=view.weights(),
                          n_rounds=1, donate=False)
    counts = {
        k: v
        for k, v in profiling.observatory.signature_counts().items()
        if k.startswith("engine_round")
    }
    # One program per tier, each compiled exactly once — returning to
    # tier 4 was a cache hit, not a recompile.
    tiers = {k.split(":c", 1)[1].split(":", 1)[0] for k in counts}
    assert tiers == {"4", "8"}, counts
    assert all(v == 1 for v in counts.values()), counts


def test_masked_run_matches_exact_size_run_bitwise():
    """An elastic capacity-8 run with 4 live members produces the
    SAME bytes as a fresh-compiled exact-size n=4 run: on the 8-device
    mesh both pad to 8 rows (row-0 clones at zero weight), so the
    masked program IS the exact program over identical inputs."""
    n_live = 4
    xs, ys = _node_data(n_live)
    mesh = create_mesh({"nodes": 8})

    fed_exact = _fed(n_live, mesh=mesh)
    p = fed_exact.init_params((28, 28))
    xe, ye = fed_exact.shard_data(xs, ys)
    out_exact, _ = fed_exact.engine.run_rounds(
        p, xe, ye, n_rounds=2, donate=False
    )

    view = MembershipView([f"n{i}" for i in range(n_live)], capacity_min=8)
    assert view.capacity == 8
    fed_el = _fed(8, mesh=mesh, seed=0)
    fed_el.engine.attach_membership(view)
    # Same logical inputs: live rows 0-3, rows 4-7 cloned from row 0
    # exactly like the exact run's mesh padding.
    pad = lambda a: np.concatenate([a, np.broadcast_to(a[:1], (4, *a.shape[1:]))])
    xs8, ys8 = fed_el.engine.shard_data(pad(xs), pad(ys))
    p8 = fed_el.engine.pad_stacked(fed_exact.engine.unpad(p))
    out_el, _ = fed_el.engine.run_rounds(
        p8, xs8, ys8, weights=view.weights(), n_rounds=2, donate=False
    )
    live = jax.tree_util.tree_map(lambda t: np.asarray(t)[:n_live], out_el)
    exact = jax.tree_util.tree_map(
        lambda t: np.asarray(t)[:n_live], out_exact
    )
    assert _leaves_equal(live, exact)


# --- pipeline elastic hooks ----------------------------------------------


def test_pipeline_weights_for_and_snapshot_cadence():
    from tpfl.parallel.window_pipeline import WindowPipeline

    n = 4
    xs, ys = _node_data(n)
    fed = _fed(n)
    params = fed.init_params((28, 28))
    calls = []
    snaps = []

    def weights_for(widx):
        calls.append(widx)
        return np.ones((fed.engine.padded_nodes,), np.float32)

    pipe = WindowPipeline(fed.engine)
    result, done = pipe.run(
        params, xs, ys, n_rounds=6, window=2,
        weights_for=weights_for,
        snapshot_every=1,
        snapshot_to=lambda r, s: snaps.append((r, s)),
    )
    assert done == 6 and result is not None
    assert calls == [0, 1, 2]
    # Every window hit the cadence; states carry the pinned positions.
    assert [r for r, _ in snaps] == [2, 4, 6]
    assert [s["rounds_done"] for _, s in snaps] == [2, 4, 6]
    # The final snapshot equals the returned params (unpadded).
    assert _leaves_equal(
        snaps[-1][1]["params"], fed.engine.unpad(result[0])
    )


def test_pipeline_interrupt_abandons_cleanly():
    from tpfl.parallel import window_pipeline
    from tpfl.parallel.window_pipeline import WindowPipeline, interrupt_for

    assert interrupt_for("nobody-registered") is False
    n = 4
    xs, ys = _node_data(n)
    fed = _fed(n)
    params = fed.init_params((28, 28))
    pipe = WindowPipeline(fed.engine)
    hits = []

    def weights_for(widx):
        hits.append(widx)
        if widx == 1:
            # Churn thread (here: inline) interrupts the owner mid-run.
            assert interrupt_for("host-0")
        return None

    result, done = pipe.run(
        params, xs, ys, n_rounds=8, window=2,
        weights_for=weights_for, owner="host-0",
    )
    # The widx-1 window was dispatched, then the abort broke the loop
    # before widx 2; its in-flight handle was abandoned -> no result.
    assert result is None
    assert done == 4 and hits == [0, 1]
    with window_pipeline._ACTIVE_LOCK:
        assert "host-0" not in window_pipeline._ACTIVE


def test_engine_window_abandon_is_terminal():
    n = 2
    xs, ys = _node_data(n)
    fed = _fed(n)
    params = fed.init_params((28, 28))
    handle = fed.engine.dispatch_window(params, xs, ys, n_rounds=1,
                                        donate=False)
    handle.abandon()
    assert handle.finalize() is None  # finalized, no telemetry fan-out


# --- compile cache knob ---------------------------------------------------


@pytest.fixture
def _no_cache_env(monkeypatch):
    """These tests place a PRIVATE cache explicitly; an inherited
    JAX_COMPILATION_CACHE_DIR would (by the one rule) win over it."""
    monkeypatch.delenv(profiling.COMPILE_CACHE_ENV, raising=False)


def test_ensure_compile_cache_idempotent(tmp_path, _no_cache_env):
    d = str(tmp_path / "xla-cache")
    assert profiling.ensure_compile_cache(d) == d
    assert profiling.ensure_compile_cache(d) == d  # repeat: no-op
    assert jax.config.jax_compilation_cache_dir == profiling._COMPILE_CACHE_DIR


def test_compile_cache_knob_via_engine(tmp_path, _no_cache_env):
    d = str(tmp_path / "engine-cache")
    Settings.COMPILE_CACHE_DIR = d
    fed = _fed(2)
    p = fed.init_params((28, 28))
    xs, ys = _node_data(2)
    fed.engine.run_rounds(p, xs, ys, n_rounds=1, donate=False)
    assert profiling._COMPILE_CACHE_DIR == str(tmp_path / "engine-cache")
    import os

    assert os.path.isdir(d)


def test_cache_hit_donating_round_trains_and_checkpoint_owns_bytes(
    tmp_path, _no_cache_env
):
    """A persistent-cache HIT on the donating round program must still
    train, and an export_state snapshot must survive a later in-place
    donating round byte-identically. Deserialized executables (unlike
    fresh-compiled ones on this backend) exercise the may-alias
    donation for real: the output is written INTO the donated input
    buffer, so any zero-copy host view of pre-round state silently
    mutates — the checkpoint path must own its bytes."""
    profiling.ensure_compile_cache(str(tmp_path / "hit-cache"))
    xs, ys = _node_data(2)

    def one_round(fed):
        p = fed.init_params((28, 28))
        snap = fed.engine.export_state(p)  # owning host copy
        out, _ = fed.round(p, jnp.asarray(xs), jnp.asarray(ys))
        return snap, out

    snap_w, out_w = one_round(_fed(2))  # compiles + writes the entry
    snap_r, out_r = one_round(_fed(2))  # same program: cache hit
    # The hit leg trained: output differs from the pre-round snapshot.
    moved = [
        np.abs(np.asarray(a)[:2] - b).max()
        for a, b in zip(jax.tree_util.tree_leaves(out_r),
                        jax.tree_util.tree_leaves(snap_r["params"]))
    ]
    assert max(moved) > 0, "cache-hit donating round was a no-op"
    # ...and the checkpoint snapshot did NOT mutate under the donating
    # round: both legs exported the same seeded init state.
    for a, b in zip(jax.tree_util.tree_leaves(snap_w["params"]),
                    jax.tree_util.tree_leaves(snap_r["params"])):
        np.testing.assert_array_equal(a, b)
    # Hit and miss legs agree numerically (same program, same data).
    for a, b in zip(jax.tree_util.tree_leaves(out_w),
                    jax.tree_util.tree_leaves(out_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- learner-level elastic fit -------------------------------------------


def _learner(n_local=4, **kw):
    from tpfl.models import create_model
    from tpfl.parallel import FederationLearner

    model = create_model("mlp", (28, 28), seed=7, hidden_sizes=(8,))
    ds = synthetic_mnist(n_train=256, n_test=64, seed=0, noise=0.4)
    return FederationLearner(
        model=model, data=ds, addr="host-0", n_local_nodes=n_local,
        local_rounds=2, learning_rate=0.1, batch_size=8, seed=0, **kw
    )


def test_learner_fit_with_membership_mask():
    learner = _learner(n_local=4)
    view = MembershipView([f"n{i}" for i in range(4)], capacity_min=4)
    view.quarantine("n3")
    learner.set_membership(view)
    model = learner.fit()
    assert model.get_contributors() == ["host-0"]
    assert learner.n_local_nodes == 4  # same tier: no restack


def test_learner_fit_restacks_on_tier_change():
    learner = _learner(n_local=4)
    view = MembershipView([f"n{i}" for i in range(4)], capacity_min=4)
    learner.set_membership(view)
    learner.fit()
    fed_before = learner._fed
    for i in range(4, 6):
        view.join(f"n{i}")  # 6 live -> tier 8
    assert view.capacity == 8
    model = learner.fit()
    # Tier boundary: the federation restacked at the new capacity.
    assert learner.n_local_nodes == 8
    assert learner._fed is not fed_before
    assert learner._fed.engine.membership is view
    assert model.get_contributors() == ["host-0"]


def test_learner_interrupt_via_registry_skips_fit():
    """Node.stop's seam: interrupt_for(addr) during a pipelined fit
    abandons the in-flight window and fit() returns the pre-fit model
    as a skip (contribution 0)."""
    from tpfl.parallel.window_pipeline import interrupt_for

    Settings.ENGINE_PREFETCH = True
    Settings.SHARD_ROUNDS_PER_DISPATCH = 1
    learner = _learner(n_local=4)
    learner.local_rounds = 6
    view = MembershipView([f"n{i}" for i in range(4)], capacity_min=4)
    learner.set_membership(view)
    before = learner.get_model().get_parameters()

    fired = threading.Event()
    orig = learner._window_weights

    def tap(widx):
        if widx == 2 and not fired.is_set():
            fired.set()
            assert interrupt_for("host-0")
        return orig(widx)

    learner._window_weights = tap
    model = learner.fit()
    assert fired.is_set()
    assert model.get_num_samples() == 0  # skip_fit: no contribution
    assert _leaves_equal(before, model.get_parameters())
