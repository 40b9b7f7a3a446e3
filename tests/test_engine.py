"""Federation engine tests — the pod-scale seam on the 8-device
virtual CPU mesh (conftest forces XLA_FLAGS
--xla_force_host_platform_device_count=8).

Pins the engine's three contracts (ISSUE 9): (a) the sharded program
(gossip-as-psum-collective fold under shard_map) is numerically
equivalent to the single-device program for FedAvg/SCAFFOLD/FedProx,
including masked train sets and padded node axes; (b) same seed at a
fixed device count is BYTE-identical across from-scratch runs; (c) the
device-side multi-round window equals N single-round dispatches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpfl.models import MLP
from tpfl.parallel import (
    FederationEngine,
    SpecLayout,
    VmapFederation,
    create_mesh,
    layout_for_module,
    pad_node_axis,
    pad_node_weights,
    padded_node_count,
    sample_participants,
    shard_stacked,
    stacked_model_shardings,
    transformer_layout,
)
from tpfl.settings import Settings


def _mlp():
    return MLP(hidden_sizes=(16,), compute_dtype=jnp.float32)


def _data(n, nb=2, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.random((n, nb, bs, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (n, nb, bs)).astype(np.int32)
    return xs, ys


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _run_engine(n, mesh, algorithm, xs, ys, weights, n_rounds=1, epochs=1):
    eng = FederationEngine(_mlp(), n, mesh=mesh, seed=0, algorithm=algorithm)
    params = eng.init_params((28, 28))
    dx, dy = eng.shard_data(xs, ys)
    if algorithm == "scaffold":
        state = eng.init_scaffold_state(params)
        params, _aux, state, losses = eng.run_rounds(
            params, dx, dy, weights=weights, n_rounds=n_rounds,
            epochs=epochs, scaffold_state=state,
        )
        return eng, params, losses, state
    params, losses = eng.run_rounds(
        params, dx, dy, weights=weights, n_rounds=n_rounds, epochs=epochs
    )
    return eng, params, losses, None


# --- (a) sharded == single-device, incl. masks and padding ---------------


@pytest.mark.parametrize("algorithm", ["fedavg", "fedprox", "scaffold"])
def test_sharded_round_matches_single_device(algorithm):
    """The psum-collective fold over the 8-way mesh equals the
    single-program einsum fold, with a masked (partial-participation)
    train set."""
    n = 8
    xs, ys = _data(n)
    w = np.asarray([1, 1, 0, 1, 0, 1, 1, 0], np.float32)
    mesh = create_mesh({"nodes": 8})
    _, p1, l1, s1 = _run_engine(n, None, algorithm, xs, ys, w, n_rounds=2)
    _, p2, l2, s2 = _run_engine(n, mesh, algorithm, xs, ys, w, n_rounds=2)
    for a, b in zip(_leaves(p1), _leaves(p2)):
        np.testing.assert_allclose(a, b, atol=2e-6)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-5)
    if algorithm == "scaffold":
        for a, b in zip(_leaves(s1), _leaves(s2)):
            np.testing.assert_allclose(a, b, atol=2e-6)


@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_padded_node_axis_matches_unpadded(algorithm):
    """n=6 on an 8-device mesh pads to 8 with zero-weight clone rows;
    the REAL rows must equal the meshless unpadded run exactly (the
    masked fold ignores w=0 pad entries)."""
    n = 6
    xs, ys = _data(n)
    w = np.asarray([1, 1, 0, 1, 1, 0], np.float32)
    mesh = create_mesh({"nodes": 8})
    eng_a, p_a, _, s_a = _run_engine(n, None, algorithm, xs, ys, w)
    eng_b, p_b, _, s_b = _run_engine(n, mesh, algorithm, xs, ys, w)
    assert eng_a.padded_nodes == 6 and eng_b.padded_nodes == 8
    for a, b in zip(_leaves(eng_a.unpad(p_a)), _leaves(eng_b.unpad(p_b))):
        assert a.shape[0] == 6 and b.shape[0] == 6
        np.testing.assert_allclose(a, b, atol=2e-6)
    if algorithm == "scaffold":
        # c_global (replicated) must also agree under padding.
        for a, b in zip(_leaves(s_a[1]), _leaves(s_b[1])):
            np.testing.assert_allclose(a, b, atol=2e-6)


def test_all_zero_weights_fallback_ignores_padding():
    """All-zero round weights fall back to a uniform mean over REAL
    nodes only — pad rows never enter the fallback denominator."""
    n = 6
    xs, ys = _data(n)
    w = np.zeros((n,), np.float32)
    mesh = create_mesh({"nodes": 8})
    eng_a, p_a, _, _ = _run_engine(n, None, "fedavg", xs, ys, w)
    eng_b, p_b, _, _ = _run_engine(n, mesh, "fedavg", xs, ys, w)
    for a, b in zip(_leaves(eng_a.unpad(p_a)), _leaves(eng_b.unpad(p_b))):
        np.testing.assert_allclose(a, b, atol=2e-6)


# --- (b) byte-identical determinism at fixed device count ----------------


@pytest.mark.parametrize("devices", [1, 8])
def test_same_seed_same_devices_byte_identical(devices):
    n = 8
    xs, ys = _data(n)
    w = np.asarray([1, 0, 1, 1, 0, 1, 1, 1], np.float32)

    def digest():
        mesh = create_mesh({"nodes": devices}, devices=jax.devices()[:devices])
        mesh = mesh if devices > 1 else None
        _, p, _, _ = _run_engine(n, mesh, "fedavg", xs, ys, w, n_rounds=3)
        return b"".join(leaf.tobytes() for leaf in _leaves(p))

    assert digest() == digest()


# --- (c) multi-round window == N single-round dispatches -----------------


@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_window_equals_sequential_rounds(algorithm):
    n = 8
    mesh = create_mesh({"nodes": 8})
    xs, ys = _data(n)
    w = np.asarray([1, 1, 1, 0, 1, 0, 1, 1], np.float32)
    _, p_win, l_win, s_win = _run_engine(
        n, mesh, algorithm, xs, ys, w, n_rounds=3
    )

    eng = FederationEngine(_mlp(), n, mesh=mesh, seed=0, algorithm=algorithm)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(xs, ys)
    state = eng.init_scaffold_state(p) if algorithm == "scaffold" else None
    for _ in range(3):
        if algorithm == "scaffold":
            p, _aux, state, losses = eng.round(
                p, dx, dy, weights=w, scaffold_state=state
            )
        else:
            p, losses = eng.round(p, dx, dy, weights=w)
    for a, b in zip(_leaves(p_win), _leaves(p)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(l_win), np.asarray(losses), atol=1e-5
    )


def test_per_round_weight_schedule():
    """[n_rounds, n] weights rotate participation inside ONE dispatch;
    the result equals sequential rounds with the per-round masks."""
    n = 8
    mesh = create_mesh({"nodes": 8})
    xs, ys = _data(n)
    sched = np.zeros((2, n), np.float32)
    sched[0, :4] = 1.0
    sched[1, 4:] = 1.0
    _, p_win, _, _ = _run_engine(n, mesh, "fedavg", xs, ys, sched, n_rounds=2)

    eng = FederationEngine(_mlp(), n, mesh=mesh, seed=0)
    p = eng.init_params((28, 28))
    dx, dy = eng.shard_data(xs, ys)
    for r in range(2):
        p, _ = eng.round(p, dx, dy, weights=sched[r])
    for a, b in zip(_leaves(p_win), _leaves(p)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    with pytest.raises(ValueError, match="per-round weights"):
        _run_engine(n, mesh, "fedavg", xs, ys, sched, n_rounds=3)


# --- engine <-> VmapFederation parity ------------------------------------


def test_vmap_federation_rides_engine_byte_identical():
    """The legacy API's round program IS the engine's single-round
    program: identical bytes out for identical seed/data."""
    n = 4
    xs, ys = _data(n)
    w = np.asarray([1, 1, 0, 1], np.float32)
    fed = VmapFederation(_mlp(), n, seed=0)
    pf, lf = fed.round(
        fed.init_params((28, 28)), jnp.asarray(xs), jnp.asarray(ys), weights=w
    )
    _, pe, le, _ = _run_engine(n, None, "fedavg", xs, ys, w)
    for a, b in zip(_leaves(pf), _leaves(pe)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(le))


def test_vmap_federation_run_rounds_window():
    """VmapFederation.run_rounds (the FederationLearner window seam)
    matches repeated round() calls."""
    n = 4
    xs, ys = _data(n)
    fed_a = VmapFederation(_mlp(), n, seed=0)
    p_a = fed_a.init_params((28, 28))
    p_a, _ = fed_a.run_rounds(p_a, jnp.asarray(xs), jnp.asarray(ys), n_rounds=2)
    fed_b = VmapFederation(_mlp(), n, seed=0)
    p_b = fed_b.init_params((28, 28))
    for _ in range(2):
        p_b, _ = fed_b.round(p_b, jnp.asarray(xs), jnp.asarray(ys))
    for a, b in zip(_leaves(p_a), _leaves(p_b)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_auto_mesh_resolves_from_shard_knobs():
    Settings.SHARD_NODES = True
    Settings.SHARD_DEVICES = 0
    try:
        eng = FederationEngine(_mlp(), 16, mesh="auto", seed=0)
        assert eng.mesh is not None and eng.mesh.shape == {"nodes": 8}
        Settings.SHARD_DEVICES = 2
        eng2 = FederationEngine(_mlp(), 16, mesh="auto", seed=0)
        assert eng2.mesh.shape == {"nodes": 2}
        Settings.SHARD_NODES = False
        assert FederationEngine(_mlp(), 16, mesh="auto", seed=0).mesh is None
    finally:
        Settings.SHARD_NODES = False
        Settings.SHARD_DEVICES = 0


# --- mesh padding helpers (satellite: federation_sharding fix) -----------


def test_padded_node_count_and_helpers():
    mesh = create_mesh({"nodes": 8})
    assert padded_node_count(8, mesh) == 8
    assert padded_node_count(9, mesh) == 16
    assert padded_node_count(100, None) == 100
    t = {"a": np.arange(12, dtype=np.float32).reshape(6, 2)}
    padded = pad_node_axis(t, 8)
    assert np.asarray(padded["a"]).shape == (8, 2)
    # Pad rows clone row 0 (valid model rows, zero fold weight).
    np.testing.assert_array_equal(
        np.asarray(padded["a"])[6:], np.broadcast_to(t["a"][0], (2, 2))
    )
    w = pad_node_weights(np.ones(6, np.float32), 8)
    np.testing.assert_array_equal(np.asarray(w), [1, 1, 1, 1, 1, 1, 0, 0])
    w2 = pad_node_weights(np.ones((3, 6), np.float32), 8)
    assert w2.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(w2)[:, 6:], 0)


def test_shard_stacked_pads_instead_of_replicating():
    """An indivisible node count shards via padding — it must NOT
    degrade to a replicated (or host-local single-device) placement."""
    mesh = create_mesh({"nodes": 8})
    x = np.ones((10, 4), np.float32)
    placed = shard_stacked(mesh, {"x": x})["x"]
    assert placed.shape == (16, 4)
    assert not placed.sharding.is_fully_replicated
    # Each device holds exactly 2 rows of the padded axis.
    assert placed.addressable_shards[0].data.shape == (2, 4)
    # No mesh: unchanged.
    same = shard_stacked(None, {"x": x})["x"]
    assert np.asarray(same).shape == (10, 4)


# --- cross-device population sampling (sim100k pattern) ------------------


def test_sample_participants_deterministic_and_distinct():
    a = sample_participants(10_000, 64, seed=3, round=5)
    b = sample_participants(10_000, 64, seed=3, round=5)
    np.testing.assert_array_equal(a, b)
    assert len(set(a.tolist())) == 64
    c = sample_participants(10_000, 64, seed=3, round=6)
    assert not np.array_equal(a, c)
    with pytest.raises(ValueError):
        sample_participants(4, 8, seed=0, round=0)


def test_population_round_state_stays_o_active():
    """The sim100k pattern in miniature: a 10k population with K=8
    active per round — the only persistent state is ONE global model,
    and every stacked array the engine touches has K (padded) rows."""
    popl, K = 10_000, 8
    mesh = create_mesh({"nodes": 8})
    eng = FederationEngine(_mlp(), K, mesh=mesh, seed=0)
    glob = jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf[0]), eng.unpad(eng.init_params((28, 28)))
    )
    for r in range(2):
        idx = sample_participants(popl, K, seed=0, round=r)
        xs, ys = _data(K, nb=1, bs=4, seed=int(idx[0]))
        p = eng.broadcast_params(glob)
        assert all(
            np.shape(leaf)[0] == eng.padded_nodes
            for leaf in jax.tree_util.tree_leaves(p)
        )
        dx, dy = eng.shard_data(xs, ys)
        p, losses = eng.round(p, dx, dy)
        assert np.asarray(losses).shape == (eng.padded_nodes,)
        glob = jax.tree_util.tree_map(
            lambda leaf: np.asarray(leaf[0]), eng.unpad(p)
        )
    assert all(np.isfinite(leaf).all() for leaf in _leaves(glob))


# --- 2D nodes x model meshes (ISSUE 15) ----------------------------------


def _lm():
    from tpfl.models import TransformerLM

    return TransformerLM(
        vocab=64, dim=32, heads=4, n_layers=2, max_len=64,
        compute_dtype=jnp.float32,
    )


def _lm_data(n, nb=1, bs=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.integers(0, 64, (n, nb, bs, s)).astype(np.int32)
    ys = rng.integers(0, 64, (n, nb, bs, s)).astype(np.int32)
    return xs, ys


def _run_lm_engine(n, mesh, algorithm, xs, ys, weights, n_rounds=1, **kw):
    eng = FederationEngine(
        _lm(), n, mesh=mesh, seed=0, learning_rate=0.05,
        algorithm=algorithm, **kw,
    )
    params = eng.init_params((xs.shape[-1],))
    dx, dy = eng.shard_data(xs, ys)
    if algorithm == "scaffold":
        state = eng.init_scaffold_state(params)
        params, _aux, state, losses = eng.run_rounds(
            params, dx, dy, weights=weights, n_rounds=n_rounds,
            scaffold_state=state,
        )
        return eng, params, losses, state
    params, losses = eng.run_rounds(
        params, dx, dy, weights=weights, n_rounds=n_rounds
    )
    return eng, params, losses, None


@pytest.mark.parametrize("axes", [(8, 1), (4, 2), (2, 4)])
@pytest.mark.parametrize("algorithm", ["fedavg", "scaffold"])
def test_2d_mesh_matches_single_device(axes, algorithm):
    """The ISSUE-15 parity matrix: nodes=8 x model=1 runs the manual
    shard_map program (byte-identical lowering — pinned separately);
    4x2 and 2x4 run the GSPMD layout program — all must match the
    single-device round within accumulation tolerance, with a masked
    (partial-participation) train set on the federated TransformerLM."""
    n = 8
    xs, ys = _lm_data(n)
    w = np.asarray([1, 1, 0, 1, 0, 1, 1, 0], np.float32)
    nodes, model = axes
    mesh = create_mesh({"nodes": nodes, "model": model})
    _, p1, l1, s1 = _run_lm_engine(n, None, algorithm, xs, ys, w, n_rounds=2)
    eng, p2, l2, s2 = _run_lm_engine(n, mesh, algorithm, xs, ys, w, n_rounds=2)
    assert eng.model_axes == model
    for a, b in zip(_leaves(p1), _leaves(p2)):
        np.testing.assert_allclose(a, b, atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(l1), np.asarray(l2), atol=5e-4
    )
    if algorithm == "scaffold":
        for a, b in zip(_leaves(s1), _leaves(s2)):
            np.testing.assert_allclose(a, b, atol=5e-4)


def test_2d_mesh_padded_and_masked_matches_unpadded():
    """n=6 on a nodes=4 x model=2 mesh pads the NODE axis to 8 (never
    the model axis); the real rows must match the meshless run."""
    n = 6
    xs, ys = _lm_data(n)
    w = np.asarray([1, 1, 0, 1, 1, 0], np.float32)
    mesh = create_mesh({"nodes": 4, "model": 2})
    eng_a, p_a, _, _ = _run_lm_engine(n, None, "fedavg", xs, ys, w)
    eng_b, p_b, _, _ = _run_lm_engine(n, mesh, "fedavg", xs, ys, w)
    assert eng_a.padded_nodes == 6 and eng_b.padded_nodes == 8
    for a, b in zip(_leaves(eng_a.unpad(p_a)), _leaves(eng_b.unpad(p_b))):
        assert a.shape[0] == 6 and b.shape[0] == 6
        np.testing.assert_allclose(a, b, atol=5e-4)


def test_2d_mesh_per_device_param_bytes_drop():
    """The acceptance metric: on a 4x2 mesh each device holds ~1/2 the
    model bytes of the node-replicated layout (exact for the sharded
    kernels/embeddings; small LayerNorm/bias leaves ride replicated)."""
    n = 4
    xs, ys = _lm_data(n)
    mesh = create_mesh({"nodes": 4, "model": 2})
    eng, p, _, _ = _run_lm_engine(n, mesh, "fedavg", xs, ys, None)
    leaves = jax.tree_util.tree_leaves(p)
    total = sum(leaf.nbytes for leaf in leaves)
    per_device = sum(
        leaf.addressable_shards[0].data.nbytes for leaf in leaves
    )
    # nodes axis alone gives 4x; the model axis must push well past it.
    assert total / per_device > 4 * 1.5
    assert any(
        not leaf.sharding.is_fully_replicated
        and leaf.addressable_shards[0].data.shape[1:] != leaf.shape[1:]
        for leaf in leaves
    )


def test_2d_mesh_clones_the_module_onto_the_model_axis_ring():
    """The module-clone seam: on a ``model`` axis > 1 the engine trains
    a CLONE of the caller's TransformerLM whose attention is the
    model-axis ring; on one device, and on a ``model=1`` mesh, it
    trains the caller's module as it is."""
    module = _lm()
    assert module.attention_fn is None
    one = FederationEngine(module, 8, mesh=None, seed=0)
    flat = FederationEngine(
        module, 8, mesh=create_mesh({"nodes": 8, "model": 1}), seed=0
    )
    ring = FederationEngine(
        module, 8, mesh=create_mesh({"nodes": 4, "model": 2}), seed=0
    )
    assert one.module is module and flat.module is module
    assert ring.module is not module
    assert ring.module.attention_fn is not None
    assert module.attention_fn is None  # the caller's module is untouched


def test_2d_mesh_same_seed_byte_identical():
    """Same-seed determinism at a FIXED 2D mesh shape (the mesh shape,
    not just the device count, is the reproducibility key)."""
    n = 8
    xs, ys = _lm_data(n)
    w = np.asarray([1, 0, 1, 1, 0, 1, 1, 1], np.float32)

    def digest():
        mesh = create_mesh({"nodes": 4, "model": 2})
        _, p, _, _ = _run_lm_engine(n, mesh, "fedavg", xs, ys, w, n_rounds=2)
        return b"".join(leaf.tobytes() for leaf in _leaves(p))

    assert digest() == digest()


def test_2d_mesh_donation_report_clean():
    """ISSUE-15 satellite: buffer donation stays a verified contract
    on 2D programs — every donated state leaf aliases an output in the
    lowering AND the compiled HLO (no staging copy of the sharded
    model state)."""
    n = 4
    xs, ys = _lm_data(n)
    mesh = create_mesh({"nodes": 2, "model": 4})
    eng = FederationEngine(_lm(), n, mesh=mesh, seed=0, learning_rate=0.05)
    p = eng.init_params((16,))
    dx, dy = eng.shard_data(xs, ys)
    report = eng.donation_report(p, dx, dy, n_rounds=2)
    assert report["clean"], report


def test_2d_mesh_device_wire_codec_parity():
    """ENGINE_WIRE_CODEC on a 2D mesh: the in-program quantize
    round-trip partitions over the model shards but keeps its per-leaf
    GLOBAL scale (max is exact under any partitioning — host-codec
    bit semantics), so the quantized 2D run matches the quantized
    single-device run within accumulation tolerance."""
    n = 8
    xs, ys = _lm_data(n)
    snap = Settings.ENGINE_WIRE_CODEC
    Settings.ENGINE_WIRE_CODEC = "quant8"
    try:
        mesh = create_mesh({"nodes": 4, "model": 2})
        _, p1, l1, _ = _run_lm_engine(n, None, "fedavg", xs, ys, None)
        _, p2, l2, _ = _run_lm_engine(n, mesh, "fedavg", xs, ys, None)
        for a, b in zip(_leaves(p1), _leaves(p2)):
            np.testing.assert_allclose(a, b, atol=5e-4)
        np.testing.assert_allclose(
            np.asarray(l1), np.asarray(l2), atol=5e-4
        )
    finally:
        Settings.ENGINE_WIRE_CODEC = snap


def test_2d_mesh_telemetry_carry():
    """ENGINE_TELEMETRY on a 2D mesh: the carry fans out with sane
    values and the model outputs stay byte-identical to the
    untelemetered 2D program (read-only carry, as on 1D meshes)."""
    n = 8
    xs, ys = _lm_data(n)
    mesh = create_mesh({"nodes": 4, "model": 2})
    snap = Settings.ENGINE_TELEMETRY

    def run(tele):
        Settings.ENGINE_TELEMETRY = tele
        eng = FederationEngine(
            _lm(), n, mesh=mesh, seed=0, learning_rate=0.05
        )
        p = eng.init_params((16,))
        dx, dy = eng.shard_data(xs, ys)
        p, losses = eng.run_rounds(p, dx, dy, n_rounds=2)
        return b"".join(leaf.tobytes() for leaf in _leaves(p))

    try:
        from tpfl.management.telemetry import metrics

        off = run(False)
        on = run(True)
        assert off == on
        folded = metrics.fold()
        rounds = [
            v for k, v in folded["counters"].items()
            if k[0] == "tpfl_engine_rounds_total"
        ]
        assert rounds and sum(rounds) >= 2
    finally:
        Settings.ENGINE_TELEMETRY = snap


def test_model_axis_one_mesh_lowers_byte_identical_to_1d():
    """HLO pin: an explicit nodes=8 x model=1 mesh takes the manual
    shard_map route and lowers the program of the 1D nodes=8 mesh — the
    2D (GSPMD) machinery engages only past model=1 (SHARD_MODEL=1
    default semantics). The raw texts cannot be equal: Shardy prints the
    mesh (``sdy.mesh @mesh = <["nodes"=8, "model"=1]>``) and repeats its
    axes in ``sdy.manual_computation``'s ``manual_axes``. So the size-1
    axis is taken out of those two places and everything else — every
    operation of the round body — must match to the byte."""
    n = 8
    xs, ys = _data(n)

    def lowered(mesh):
        eng = FederationEngine(_mlp(), n, mesh=mesh, seed=0)
        fn = eng.program(
            "plain", 1, 2, 1, donate=False,
            model_axes=eng.model_axes, layout=eng.layout.name,
        )
        p = eng.init_params((28, 28))
        dx, dy = eng.shard_data(xs, ys)
        low = fn.lower(p, {}, {}, {}, dx, dy, eng.pad_weights(None), eng.valid)
        return low.as_text()

    one_d = lowered(create_mesh({"nodes": 8}))
    two_d = lowered(create_mesh({"nodes": 8, "model": 1}))
    # The 2D GSPMD route has no manual computation at all.
    assert "sdy.manual_computation" in one_d
    assert "sdy.manual_computation" in two_d
    assert '"model"' not in one_d
    without_axis = two_d.replace(', "model"=1', "").replace(', "model"}', "}")
    assert '"model"' not in without_axis
    assert without_axis == one_d


def test_auto_mesh_resolves_shard_model():
    """SHARD_MODEL=2 over 8 devices -> a 4x2 nodes x model auto mesh;
    a non-dividing value is an explicit error, not a silent fallback."""
    Settings.SHARD_NODES = True
    Settings.SHARD_DEVICES = 0
    Settings.SHARD_MODEL = 2
    try:
        eng = FederationEngine(_mlp(), 8, mesh="auto", seed=0)
        assert eng.mesh is not None
        assert eng.mesh.shape == {"nodes": 4, "model": 2}
        assert eng.model_axes == 2
        Settings.SHARD_MODEL = 3
        with pytest.raises(ValueError, match="SHARD_MODEL"):
            FederationEngine(_mlp(), 8, mesh="auto", seed=0)
    finally:
        Settings.SHARD_NODES = False
        Settings.SHARD_DEVICES = 0
        Settings.SHARD_MODEL = 1


def test_spec_layout_policy():
    """The per-leaf layout policy: transformer embeddings/QKV/FFN
    shard on the model axis, LayerNorm and non-dividing dims ride
    replicated; MLP resolves to the replicated layout by default."""
    lay = transformer_layout()
    assert lay.leaf_dims(
        "Embed_0/embedding", (64, 32), 2
    ) == ("model", None)
    assert lay.leaf_dims(
        "TransformerBlock_0/Dense_0/kernel", (32, 96), 2
    ) == (None, "model")
    assert lay.leaf_dims(
        "TransformerBlock_0/Dense_1/kernel", (32, 32), 2
    ) == ("model", None)
    assert lay.leaf_dims(
        "TransformerBlock_0/LayerNorm_0/scale", (32,), 2
    ) == (None,)
    # Non-dividing named dim falls back to replicated.
    assert lay.leaf_dims("Embed_0/embedding", (63, 32), 2) == (None, None)
    # Axis size 1: everything replicated regardless of rules.
    assert lay.leaf_dims("Embed_0/embedding", (64, 32), 1) == (None, None)
    assert layout_for_module(_mlp()).name == "replicated"
    assert layout_for_module(_lm()).name == "transformer"
    assert isinstance(layout_for_module(_mlp(), "transformer"), SpecLayout)
    with pytest.raises(ValueError, match="unknown model-axis layout"):
        layout_for_module(_mlp(), "bogus")


def test_stacked_model_shardings_specs():
    """stacked_model_shardings prepends the node axis and applies the
    layout's model dims per leaf."""
    from jax.sharding import PartitionSpec

    mesh = create_mesh({"nodes": 4, "model": 2})
    tree = {
        "Embed_0": {"embedding": np.zeros((4, 64, 32), np.float32)},
        "LayerNorm_0": {"scale": np.zeros((4, 32), np.float32)},
    }
    sh = stacked_model_shardings(mesh, tree, transformer_layout())
    assert sh["Embed_0"]["embedding"].spec == PartitionSpec(
        "nodes", "model", None
    )
    assert sh["LayerNorm_0"]["scale"].spec == PartitionSpec("nodes", None)


def test_padding_helpers_2d_aware():
    """ISSUE-15 satellite: the padding helpers key off the NODE axis
    size, never the device count — a 4x2 mesh pads node counts to
    multiples of 4, and shard_stacked splits rows over nodes only."""
    mesh = create_mesh({"nodes": 4, "model": 2})
    assert padded_node_count(6, mesh) == 8
    assert padded_node_count(4, mesh) == 4
    assert padded_node_count(9, mesh) == 12
    w = pad_node_weights(np.ones(6, np.float32), padded_node_count(6, mesh))
    np.testing.assert_array_equal(np.asarray(w), [1, 1, 1, 1, 1, 1, 0, 0])
    placed = shard_stacked(mesh, {"x": np.ones((6, 4), np.float32)})["x"]
    assert placed.shape == (8, 4)
    # Rows shard over the 4-way node axis; the model axis replicates:
    # each of the 8 devices holds 8/4 = 2 rows, full feature width.
    assert placed.addressable_shards[0].data.shape == (2, 4)


# --- aux (BatchNorm) path over the mesh ----------------------------------

def _bn_cnn():
    import flax.linen as nn

    class BnCnn(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            if x.ndim == 3:
                x = x[..., None]
            x = nn.Conv(4, (3, 3))(x)
            x = nn.relu(
                nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
            )
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(10)(x)

    return BnCnn()


@pytest.mark.parametrize("aux_mode", ["mean", "local"])
def test_sharded_aux_round_matches_single_device(aux_mode):
    n = 8
    xs, ys = _data(n, nb=1, bs=4)
    w = np.asarray([1, 1, 0, 1, 0, 1, 1, 0], np.float32)

    def run(mesh):
        eng = FederationEngine(
            _bn_cnn(), n, mesh=mesh, seed=0, learning_rate=0.05,
            aux_mode=aux_mode,
        )
        p, a = eng.init_state((28, 28))
        dx, dy = eng.shard_data(xs, ys)
        p, a, losses = eng.round(p, dx, dy, weights=w, aux=a)
        return _leaves(p) + _leaves(a)

    for got, want in zip(run(create_mesh({"nodes": 8})), run(None)):
        np.testing.assert_allclose(got, want, atol=2e-6)


# --- observatory / round-profiler wiring over the engine seams -----------


def test_engine_profiling_seams():
    """PR-6 observatory coverage over the engine: the wrapped program
    registers a recompile-detection signature, the dispatch window
    lands in the round profiler (one `dispatch` + `train` attribution
    per WINDOW under the engine's node label), and the program cache
    emits hit/miss events."""
    from tpfl.management import profiling

    Settings.PROFILING_ENABLED = True
    profiling.rounds.reset()
    profiling.observatory.reset()
    try:
        n = 8
        xs, ys = _data(n, nb=1, bs=4)
        eng = FederationEngine(_mlp(), n, mesh=create_mesh({"nodes": 8}), seed=0)
        p = eng.init_params((28, 28))
        dx, dy = eng.shard_data(xs, ys)
        p, _ = eng.run_rounds(p, dx, dy, n_rounds=2)
        p, _ = eng.run_rounds(p, dx, dy, n_rounds=2)

        sigs = profiling.observatory.signature_counts()
        engine_keys = [k for k in sigs if k.startswith("engine_round:plain")]
        assert engine_keys and sigs[engine_keys[0]] == 1  # no recompiles
        records = profiling.rounds.attribution()
        mine = [r for r in records if r["node"].startswith("engine:")]
        assert len(mine) == 2  # one attribution record per WINDOW
        for rec in mine:
            assert rec["parts"]["dispatch"] >= 0.0
            assert rec["parts"]["train"] >= 0.0
            assert rec["coverage"] >= 0.95
    finally:
        Settings.PROFILING_ENABLED = False
        profiling.rounds.reset()
        profiling.observatory.reset()


def test_run_rounds_accepts_replicated_committed_inputs():
    """FederationLearner re-stacks the single global model each protocol
    round, so its stacked inputs arrive COMMITTED as replicated on the
    mesh — run_rounds must reshard them onto the node axis (device_put)
    rather than refuse like raw pjit in_shardings do."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = create_mesh({"nodes": 8})
    eng = FederationEngine(_mlp(), 4, mesh=mesh, seed=0)
    glob = jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf[0]), eng.unpad(eng.init_params((28, 28)))
    )
    restacked = jax.device_put(
        eng.broadcast_params(glob), NamedSharding(mesh, PartitionSpec())
    )
    xs, ys = _data(4, nb=1, bs=4)
    dx, dy = eng.shard_data(xs, ys)
    p, losses = eng.run_rounds(restacked, dx, dy, n_rounds=2)
    assert np.isfinite(np.asarray(losses)).all()
    leaf = jax.tree_util.tree_leaves(p)[0]
    assert not leaf.sharding.is_fully_replicated
