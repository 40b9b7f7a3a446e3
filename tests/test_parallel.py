"""TPU execution layer tests — run on the 8-device virtual CPU mesh
(conftest). Checks: mesh construction, vmapped federation correctness
vs the sequential aggregator path, mask semantics, sharded trainer."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpfl.learning.dataset import synthetic_mnist, RandomIIDPartitionStrategy
from tpfl.models import MLP
from tpfl.parallel import ShardedTrainer, VmapFederation, create_mesh


def test_create_mesh_shapes():
    m = create_mesh({"nodes": 8})
    assert m.shape == {"nodes": 8}
    m2 = create_mesh({"dp": 2, "fsdp": -1})
    assert m2.shape == {"dp": 2, "fsdp": 4}
    with pytest.raises(ValueError):
        create_mesh({"nodes": 3})


def _node_data(n_nodes, n_batches=4, bs=16):
    ds = synthetic_mnist(n_train=n_nodes * n_batches * bs, n_test=64, seed=0, noise=0.4)
    parts = ds.generate_partitions(n_nodes, RandomIIDPartitionStrategy, seed=0)
    xs, ys = [], []
    for p in parts:
        b = p.export(batch_size=bs)
        x, y = b.stacked(num_batches=n_batches)
        xs.append(x)
        ys.append(y)
    return np.stack(xs), np.stack(ys)


def test_vmap_federation_trains_and_averages():
    n = 8
    mesh = create_mesh({"nodes": n})
    fed = VmapFederation(MLP(hidden_sizes=(32,), compute_dtype=jnp.float32), n, mesh=mesh)
    params = fed.init_params((28, 28))
    xs, ys = _node_data(n)
    xs, ys = fed.shard_data(xs, ys)

    # Initial params identical across nodes.
    leaf0 = jax.tree_util.tree_leaves(params)[0]
    np.testing.assert_allclose(np.asarray(leaf0[0]), np.asarray(leaf0[1]))

    losses0 = None
    for r in range(3):
        params, losses = fed.round(params, xs, ys, epochs=1)
        if losses0 is None:
            losses0 = np.asarray(losses).mean()
    # After aggregation all nodes share the model again.
    leaf = jax.tree_util.tree_leaves(params)[0]
    np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[-1]))
    assert np.asarray(losses).mean() < losses0

    _, accs = fed.evaluate(params, xs, ys)
    assert np.asarray(accs).mean() > 0.5


def test_vmap_federation_mask_excludes_nodes():
    n = 4
    fed = VmapFederation(MLP(hidden_sizes=(16,), compute_dtype=jnp.float32), n)
    params = fed.init_params((28, 28))
    xs, ys = _node_data(n, n_batches=2, bs=8)

    # Poison node 3's data with huge values; mask it out of FedAvg.
    xs_p = np.array(xs)
    xs_p[3] = 1e6
    weights = np.array([1.0, 1.0, 1.0, 0.0], np.float32)
    params, _ = fed.round(params, jnp.asarray(xs_p), jnp.asarray(ys), weights=weights)
    leaves = jax.tree_util.tree_leaves(params)
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in leaves)


def test_vmap_federation_matches_manual_fedavg():
    """The one-program federation must equal per-node training + manual
    weighted average (same data, same init, same optimizer)."""
    n = 2
    fed = VmapFederation(
        MLP(hidden_sizes=(16,), compute_dtype=jnp.float32), n, learning_rate=0.1
    )
    params = fed.init_params((28, 28))
    xs, ys = _node_data(n, n_batches=2, bs=8)
    out, _ = fed.round(params, jnp.asarray(xs), jnp.asarray(ys), epochs=1)

    # Manual: train each node separately with the same batches.
    import optax

    module = MLP(hidden_sizes=(16,), compute_dtype=jnp.float32)
    opt = optax.sgd(0.1, momentum=0.9)
    variables = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)), train=False)
    manual = []
    for i in range(n):
        p = variables["params"]
        o = opt.init(p)
        for b in range(xs.shape[1]):
            x, y = jnp.asarray(xs[i, b]), jnp.asarray(ys[i, b])

            def loss_of(pp):
                logits = module.apply({"params": pp}, x, train=False)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).mean()

            _, grads = jax.value_and_grad(loss_of)(p)
            updates, o = opt.update(grads, o, p)
            p = optax.apply_updates(p, updates)
        manual.append(p)
    avg = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, *manual)
    for got, want in zip(
        jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(avg)
    ):
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(want), rtol=2e-4, atol=2e-5
        )


def test_vmap_federation_scaffold_matches_callback_math():
    """The vectorized SCAFFOLD round: (a) with zero control variates the
    params equal the plain FedAvg round (corrections are zero on round
    one), and (b) the post-round variates equal the ScaffoldCallback's
    Option-II hand math c_i+ = (x - y_i)/(K·lr) with c = mean(c_i+)
    (callbacks.py:105-124, aggregators/scaffold.py server update)."""
    n, lr = 2, 0.1
    kwargs = dict(learning_rate=lr, seed=0)
    mlp = lambda: MLP(hidden_sizes=(16,), compute_dtype=jnp.float32)
    fed_avg = VmapFederation(mlp(), n, **kwargs)
    fed_sc = VmapFederation(mlp(), n, algorithm="scaffold", **kwargs)
    xs, ys = _node_data(n, n_batches=2, bs=8)
    xs, ys = jnp.asarray(xs), jnp.asarray(ys)

    # round() donates its params/state buffers: give each federation
    # its own init (seed-identical).
    want, _ = fed_avg.round(fed_avg.init_params((28, 28)), xs, ys, epochs=1)
    params = fed_sc.init_params((28, 28))
    state = fed_sc.init_scaffold_state(params)
    got, _aux, (c_locals, c_global), _ = fed_sc.round(
        params, xs, ys, epochs=1, scaffold_state=state
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    # Hand math: per-node trained params via the same local SGD.
    import optax

    module = mlp()
    opt = optax.sgd(lr, momentum=0.9)
    variables = module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)), train=False
    )
    k_steps = xs.shape[1]  # 1 epoch x n_batches
    scale = 1.0 / (k_steps * lr)
    c_manual = []
    for i in range(n):
        p = variables["params"]
        o = opt.init(p)
        for b in range(xs.shape[1]):

            def loss_of(pp):
                logits = module.apply({"params": pp}, xs[i, b], train=False)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, ys[i, b]
                ).mean()

            _, grads = jax.value_and_grad(loss_of)(p)
            updates, o = opt.update(grads, o, p)
            p = optax.apply_updates(p, updates)
        c_manual.append(
            jax.tree_util.tree_map(
                lambda x0, y_: scale * (x0 - y_), variables["params"], p
            )
        )
    for i in range(n):
        for got_c, want_c in zip(
            jax.tree_util.tree_leaves(c_locals),
            jax.tree_util.tree_leaves(c_manual[i]),
        ):
            np.testing.assert_allclose(
                np.asarray(got_c[i]), np.asarray(want_c),
                rtol=2e-4, atol=1e-5,
            )
    c_mean = jax.tree_util.tree_map(
        lambda a, b: (a + b) / 2, *c_manual
    )
    for got_c, want_c in zip(
        jax.tree_util.tree_leaves(c_global),
        jax.tree_util.tree_leaves(c_mean),
    ):
        np.testing.assert_allclose(
            np.asarray(got_c), np.asarray(want_c), rtol=2e-4, atol=1e-5
        )


def test_vmap_federation_scaffold_partial_participation():
    """Unelected nodes neither move the aggregate nor advance their
    control variate; the server variate scales by |S|/N."""
    n = 4
    fed = VmapFederation(
        MLP(hidden_sizes=(16,), compute_dtype=jnp.float32), n,
        algorithm="scaffold", learning_rate=0.1,
    )
    params = fed.init_params((28, 28))
    xs, ys = _node_data(n, n_batches=2, bs=8)
    weights = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    state = fed.init_scaffold_state(params)
    params, _aux, (c_locals, c_global), _ = fed.round(
        params, jnp.asarray(xs), jnp.asarray(ys), weights=weights,
        scaffold_state=state,
    )
    for leaf in jax.tree_util.tree_leaves(c_locals):
        leaf = np.asarray(leaf)
        assert np.abs(leaf[:2]).max() > 0  # elected advanced
        np.testing.assert_array_equal(leaf[2:], 0)  # unelected frozen
    # Across further rounds: unelected variates STAY frozen, elected
    # ones keep moving, the diffused model stays identical across
    # nodes, and everything stays finite (the correction loop is
    # stable). (Protocol-path SCAFFOLD convergence is e2e-tested in
    # test_node.py; at K=2 steps on noise data per-round loss is not
    # monotone — the variates are 1/(K·lr)-scaled.)
    state = (c_locals, c_global)
    for _ in range(2):
        params, _aux, state, losses = fed.round(
            params, jnp.asarray(xs), jnp.asarray(ys), weights=weights,
            scaffold_state=state,
        )
    for leaf in jax.tree_util.tree_leaves(state[0]):
        leaf = np.asarray(leaf)
        assert np.isfinite(leaf).all()
        np.testing.assert_array_equal(leaf[2:], 0)
    for leaf in jax.tree_util.tree_leaves(params):
        leaf = np.asarray(leaf)
        assert np.isfinite(leaf).all()
        np.testing.assert_allclose(leaf[0], leaf[-1])  # diffused
    assert np.isfinite(np.asarray(losses)).all()


def test_vmap_federation_fedprox_pulls_toward_anchor():
    """FedProx: a large mu keeps the round's aggregate closer to the
    round-start weights than mu→0 (same data, same steps)."""

    def dist(fed):
        params = fed.init_params((28, 28))
        # Snapshot before round() donates the buffers — np.array, not
        # np.asarray: asarray is a zero-copy VIEW of the CPU device
        # buffer, which an in-place donating executable overwrites.
        p0 = [np.array(leaf) for leaf in jax.tree_util.tree_leaves(params)]
        xs, ys = _node_data(2, n_batches=2, bs=8)
        out, _ = fed.round(params, jnp.asarray(xs), jnp.asarray(ys))
        sq = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(out), p0):
            sq += float(np.sum((np.asarray(a[0]) - b[0]) ** 2))
        return sq

    mk = lambda **kw: VmapFederation(
        MLP(hidden_sizes=(16,), compute_dtype=jnp.float32), 2,
        learning_rate=0.1, **kw,
    )
    d_avg = dist(mk())
    d_prox = dist(mk(algorithm="fedprox", prox_mu=10.0))
    assert d_prox < d_avg * 0.9, (d_prox, d_avg)


def test_sharded_trainer_dp_and_fsdp():
    mesh = create_mesh({"dp": 8})
    for fsdp in (False, True):
        tr = ShardedTrainer(
            MLP(hidden_sizes=(64,), compute_dtype=jnp.float32),
            mesh,
            fsdp=fsdp,
            learning_rate=0.1,
        )
        params, opt_state = tr.init((28, 28))
        ds = synthetic_mnist(n_train=256, n_test=32, seed=0, noise=0.4)
        b = ds.export(batch_size=64)
        x, y = next(iter(b))
        x, y = tr.shard_batch(x, y)
        losses = []
        for _ in range(5):
            params, opt_state, loss = tr.train_step(params, opt_state, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        if fsdp:
            # At least one leaf actually sharded over dp.
            shardings = [
                leaf.sharding.spec
                for leaf in jax.tree_util.tree_leaves(params)
            ]
            assert any(s != jax.sharding.PartitionSpec() for s in shardings)


# --- mutable collections (BatchNorm) through the TPU layer -----------------


def _bn_cnn():
    """Tiny BatchNorm'd conv net (the ResNet18 aux pattern, zoo.py:94,
    cheap enough for the 8-device CPU mesh)."""
    import flax.linen as nn

    class BnCnn(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            if x.ndim == 3:
                x = x[..., None]
            x = nn.Conv(8, (3, 3))(x)
            x = nn.relu(
                nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
            )
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(10)(x)

    return BnCnn()


def test_vmap_federation_batchnorm_round():
    n = 8
    mesh = create_mesh({"nodes": n})
    fed = VmapFederation(_bn_cnn(), n, mesh=mesh, learning_rate=0.05)
    params, aux = fed.init_state((28, 28))
    assert "batch_stats" in aux
    xs, ys = _node_data(n, n_batches=2, bs=8)
    xs, ys = fed.shard_data(xs, ys)
    # Owning snapshot (np.array): round() donates aux, and np.asarray
    # is a zero-copy view of the donated CPU buffer.
    aux0 = jax.tree_util.tree_map(np.array, aux)

    new_params, new_aux, losses = fed.round(params, xs, ys, epochs=1, aux=aux)
    assert losses.shape == (n,)
    assert np.all(np.isfinite(np.asarray(losses)))
    # Stats actually moved (train=True ran BN in batch-stats mode).
    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        aux0,
        jax.tree_util.tree_map(np.asarray, new_aux),
    )
    assert max(jax.tree_util.tree_leaves(moved)) > 0
    # aux_mode="mean" (default): every node holds identical stats.
    for leaf in jax.tree_util.tree_leaves(new_aux):
        leaf = np.asarray(leaf)
        np.testing.assert_allclose(leaf, np.broadcast_to(leaf[:1], leaf.shape), atol=1e-6)
    # And identical params (full diffusion).
    for leaf in jax.tree_util.tree_leaves(new_params):
        leaf = np.asarray(leaf)
        np.testing.assert_allclose(leaf, np.broadcast_to(leaf[:1], leaf.shape), atol=1e-6)
    # evaluate with aux works.
    loss_e, acc_e = fed.evaluate(new_params, xs, ys, aux=new_aux)
    assert np.all(np.isfinite(np.asarray(loss_e)))


def test_vmap_federation_fedbn_keeps_local_stats():
    n = 4
    fed = VmapFederation(_bn_cnn(), n, learning_rate=0.05, aux_mode="local")
    params, aux = fed.init_state((28, 28))
    xs, ys = _node_data(n, n_batches=2, bs=8)
    _, new_aux, _ = fed.round(params, jnp.asarray(xs), jnp.asarray(ys), aux=aux)
    # Different nodes saw different data -> at least one stats leaf differs
    # across the node axis (FedBN: stats stay private).
    diffs = [
        float(np.abs(np.asarray(l) - np.asarray(l)[:1]).max())
        for l in jax.tree_util.tree_leaves(new_aux)
    ]
    assert max(diffs) > 0


def test_init_params_rejects_bn_module():
    fed = VmapFederation(_bn_cnn(), 2)
    with pytest.raises(ValueError, match="init_state"):
        fed.init_params((28, 28))


def test_sharded_trainer_resnet18_with_aux():
    from tpfl.models import ResNet18

    mesh = create_mesh({"dp": 8})
    tr = ShardedTrainer(
        ResNet18(out_channels=10, stage_sizes=(1, 1), compute_dtype=jnp.float32),
        mesh,
        fsdp=False,
        learning_rate=0.05,
    )
    params, aux, opt_state = tr.init_with_aux((16, 16, 3))
    assert "batch_stats" in aux
    rng = np.random.default_rng(0)
    x, y = rng.random((16, 16, 16, 3), np.float32), rng.integers(0, 10, 16)
    x, y = tr.shard_batch(x, jnp.asarray(y, jnp.int32))
    losses = []
    for _ in range(2):
        params, aux, opt_state, loss = tr.train_step_with_aux(
            params, aux, opt_state, x, y
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses))


def test_sharded_trainer_init_rejects_bn_module():
    mesh = create_mesh({"dp": 8})
    tr = ShardedTrainer(_bn_cnn(), mesh)
    with pytest.raises(ValueError, match="init_with_aux"):
        tr.init((28, 28))


def test_fedbn_mask_keeps_nonparticipant_stats():
    n = 4
    fed = VmapFederation(_bn_cnn(), n, learning_rate=0.05, aux_mode="local")
    params, aux = fed.init_state((28, 28))
    xs, ys = _node_data(n, n_batches=2, bs=8)
    weights = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    # Owning snapshot (np.array): round() donates aux, and np.asarray
    # is a zero-copy view of the donated CPU buffer.
    aux0 = jax.tree_util.tree_map(np.array, aux)
    _, new_aux, _ = fed.round(
        params, jnp.asarray(xs), jnp.asarray(ys), weights=weights, aux=aux
    )
    for old, new in zip(
        jax.tree_util.tree_leaves(aux0), jax.tree_util.tree_leaves(new_aux)
    ):
        new = np.asarray(new)
        # Non-participants (w=0): stats unchanged.
        np.testing.assert_array_equal(new[2:], old[2:])
        # Participants: stats moved.
        assert np.abs(new[:2] - old[:2]).max() > 0


def test_round_uniform_api_with_empty_aux():
    """init_state -> round(aux=...) works for aux-free modules too
    (aux={} still takes the 3-tuple path)."""
    import jax.numpy as jnp2

    n = 2
    fed = VmapFederation(MLP(hidden_sizes=(16,), compute_dtype=jnp.float32), n)
    params, aux = fed.init_state((28, 28))
    assert aux == {}
    xs, ys = _node_data(n, n_batches=2, bs=8)
    p2, a2, losses = fed.round(params, jnp.asarray(xs), jnp.asarray(ys), aux=aux)
    assert a2 == {} and losses.shape == (n,)
    loss_e, acc_e = fed.evaluate(p2, jnp.asarray(xs), jnp.asarray(ys), aux=a2)
    assert np.all(np.isfinite(np.asarray(loss_e)))


def test_federation_learner_hierarchical():
    """BASELINE config 5 shape: 2 protocol 'hosts' x 4 local vmapped
    nodes each — the outer gossip protocol runs 2 nodes while 8 logical
    nodes train; hosts converge and agree."""
    from tpfl.communication.memory import clear_registry
    from tpfl.learning.dataset import synthetic_mnist
    from tpfl.models import create_model
    from tpfl.node import Node
    from tpfl.parallel import FederationLearner
    from tpfl.utils import check_equal_models, wait_convergence, wait_to_finish

    clear_registry()
    ds = synthetic_mnist(n_train=1600, n_test=320, seed=0, noise=0.4)
    shards = ds.generate_partitions(2, RandomIIDPartitionStrategy, seed=0)
    nodes = []
    for i in range(2):
        model = create_model("mlp", (28, 28), seed=7, hidden_sizes=(32,))
        learner = FederationLearner(
            n_local_nodes=4,
            local_rounds=2,
            learning_rate=0.1,
            batch_size=25,
            seed=i,
        )
        nodes.append(
            Node(model, shards[i], addr=f"slice-{i}", learner=learner)
        )
    for nd in nodes:
        nd.start()
    try:
        nodes[0].connect(nodes[1].addr)
        wait_convergence(nodes, 1, wait=10)
        nodes[0].set_start_learning(rounds=2, epochs=1)
        wait_to_finish(nodes, timeout=240)
        check_equal_models(nodes)
        # 8 logical nodes trained; outer protocol only saw 2.
        m = nodes[0].learner.evaluate()
        assert m["test_metric"] > 0.5, m
    finally:
        for nd in nodes:
            nd.stop()
        clear_registry()


# --- sequence parallelism: ring attention --------------------------------


def _dense_attention(q, k, v, causal):
    import jax as _jax

    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        S = q.shape[1]
        mask = np.tril(np.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = _jax.nn.softmax(s, axis=-1)
    return jnp.moveaxis(jnp.einsum("bhqk,bkhd->bhqd", p, v), 1, 2)


@pytest.mark.parametrize("impl", ["flash", "xla"])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal, impl):
    from tpfl.parallel.ring_attention import (
        blockwise_attention,
        make_ring_attention,
    )

    rng = np.random.default_rng(0)
    B, S, H, D = 2, 64, 4, 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        for _ in range(3)
    )
    want = _dense_attention(q, k, v, causal)
    got_block = blockwise_attention(q, k, v, causal=causal, block_size=16)
    np.testing.assert_allclose(np.asarray(got_block), np.asarray(want), atol=2e-5)
    mesh = create_mesh({"sp": 8})
    # impl pinned: the default is "auto" (xla off-TPU), so flash-ring
    # exactness on the CPU suite must ask for the kernel explicitly.
    ring = make_ring_attention(mesh, causal=causal, impl=impl)
    got_ring = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(got_ring), np.asarray(want), atol=2e-5)


def test_ring_attention_grads_flow():
    """Training through the ring: grads propagate through ppermute
    (sequence-parallel backprop)."""
    from jax.sharding import PartitionSpec

    from tpfl.parallel.compat import shard_map
    from tpfl.parallel.ring_attention import ring_attention

    mesh = create_mesh({"sp": 8})
    rng = np.random.default_rng(1)
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        for _ in range(3)
    )
    spec = PartitionSpec(None, "sp", None, None)
    from functools import partial

    fn = shard_map(
        partial(ring_attention, axis_name="sp", causal=True, impl="flash"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, True) ** 2)

    gd = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_transformer_lm_trains():
    """The long-context zoo tier: a tiny causal LM fits a repeating
    sequence (loss drops) with the standard learner machinery."""
    import optax

    from tpfl.models import create_model

    model = create_model(
        "transformer_lm", (32,), seed=0,
        vocab=17, dim=32, heads=2, n_layers=1,
    )
    module = model.module
    params = model.get_parameters()
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 16, (4, 33)), jnp.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        def loss_fn(p):
            logits = module.apply({"params": p}, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    losses = []
    for _ in range(30):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses[::10]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_dense(causal):
    """Pallas flash kernel (interpret mode on CPU: exact f32) equals
    dense attention; on TPU the same kernel compiles natively and
    handles 32k sequences in VMEM-bounded memory."""
    from tpfl.parallel.flash_kernel import flash_attention

    rng = np.random.default_rng(3)
    B, S, H, D = 2, 256, 2, 64
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        for _ in range(3)
    )
    want = _dense_attention(q, k, v, causal)
    got = flash_attention(q, k, v, causal=causal, block=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_kernel_unaligned_causal():
    """Sequence not a block multiple: causal mask excludes pad keys."""
    from tpfl.parallel.flash_kernel import flash_attention

    rng = np.random.default_rng(4)
    B, S, H, D = 1, 100, 2, 32
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        for _ in range(3)
    )
    want = _dense_attention(q, k, v, True)
    got = flash_attention(q, k, v, causal=True, block=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_gradients_match_blockwise(causal):
    """The Pallas kernel's custom VJP (recompute-based flash backward)
    produces the same dQ/dK/dV as autodiff through the XLA blockwise
    path — so training through the kernel is exact, not just serving."""
    from tpfl.parallel.flash_kernel import flash_attention
    from tpfl.parallel.ring_attention import blockwise_attention

    rng = np.random.default_rng(5)
    B, S, H, D = 2, 256, 2, 64
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        for _ in range(3)
    )
    cot = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=causal, block=128), cot)

    def f_ref(q, k, v):
        return jnp.vdot(
            blockwise_attention(q, k, v, causal=causal, block_size=128), cot
        )

    g_kernel = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_kernel, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=3e-5, err_msg=f"d{name}"
        )


def test_flash_kernel_gradients_unaligned_causal():
    """Backward with pad rows (S=100, block=64): pad-key/query grads
    vanish and real grads equal the blockwise path's."""
    from tpfl.parallel.flash_kernel import flash_attention
    from tpfl.parallel.ring_attention import blockwise_attention

    rng = np.random.default_rng(6)
    B, S, H, D = 1, 100, 2, 32
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        for _ in range(3)
    )

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block=64) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True) ** 2)

    g_kernel = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_kernel, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=3e-5, err_msg=f"d{name}"
        )


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """``blockwise_attention``'s TPU branch on the CPU: the one seam the
    selection goes through says "a TPU", and the kernels run in Pallas's
    emulator."""
    from tpfl.parallel import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "pallas_interpret", lambda interpret: True)


def _attention_and_gradients(q, k, v, cot, silos: bool, **kw):
    """(out, dq, dk, dv) of ``blockwise_attention`` as traced NOW (a fresh
    function each call: jit would hand back the other path's program)."""
    from tpfl.parallel.ring_attention import blockwise_attention

    def both(q, k, v, cot):
        def loss(q, k, v):
            out = blockwise_attention(q, k, v, **kw)
            return jnp.vdot(out.astype(jnp.float32), cot), out

        grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    return jax.jit(jax.vmap(both) if silos else both)(q, k, v, cot)


@pytest.mark.parametrize(
    "hq, hkv, d, dv, s, block, causal, silos, dtype, window",
    [
        # GPT-2's call: equal heads of 64 (two a program instance), two
        # blocks of 512 -> three visible pairs, one skipped.
        (2, 2, 64, 64, 1024, 512, True, False, jnp.float32, None),
        # SambaY's: two query heads a key head as rows, a value twice as
        # wide as the keys (its own lane tiles, the keys' lanes masked).
        (4, 2, 64, 128, 512, 256, True, False, jnp.float32, None),
        # S not a multiple of the block: padded rows and keys, s_len.
        (2, 2, 64, 64, 600, 256, True, False, jnp.float32, None),
        # ... not causal: the last key block masks its padding.
        (2, 2, 64, 64, 300, 128, False, False, jnp.float32, None),
        # One 128-wide head a program instance; a scale (128 ** -0.5) that
        # is no power of two multiplies the scores, not an operand.
        (2, 1, 128, 128, 256, 128, True, False, jnp.float32, None),
        # Heads no 128 lanes hold: every head in one instance.
        (4, 2, 8, 16, 256, 128, True, False, jnp.float32, None),
        # Under the engine's vmap over silos (one more grid dimension).
        (4, 2, 64, 128, 256, 128, True, True, jnp.float32, None),
        # bf16 as the cells run it.
        (4, 2, 64, 128, 512, 256, True, True, jnp.bfloat16, None),
        # --- a band (``window``): the sweep covers the blocks it reaches ---
        # Mellum 2's banded call, scaled down: 8 query heads a key head
        # of 128, a window of 4 blocks -> five pairs a query block, the
        # diagonal and the far edge masked, the three between not.
        (8, 1, 128, 128, 1024, 128, True, False, jnp.float32, 512),
        # A window that is no multiple of the block: TWO far pairs masked.
        (2, 2, 64, 64, 768, 128, True, False, jnp.float32, 300),
        # A window shorter than one block: every pair masked, the
        # diagonal by both edges.
        (2, 2, 64, 64, 512, 128, True, False, jnp.float32, 50),
        # A padded sequence: the sweep's last steps leave the sequence.
        (2, 2, 64, 64, 600, 128, True, False, jnp.float32, 256),
        # SambaY's sliding window: two query heads a key head, a value
        # width of its own, a window of one block (both pairs masked).
        (4, 2, 64, 128, 768, 256, True, False, jnp.float32, 256),
        # Under the engine's vmap over silos.
        (4, 2, 64, 128, 512, 128, True, True, jnp.float32, 200),
        # bf16 as the cell runs it.
        (8, 1, 128, 128, 1024, 128, True, True, jnp.bfloat16, 512),
        # --- grouped query heads as LANES of the q / out / dO / dq blocks ---
        # Mellum 2's full layer, scaled down: 8 query heads a key head of
        # 128 lie on 1024 lanes of a block and are stacked in VMEM.
        (8, 1, 128, 128, 512, 128, True, False, jnp.float32, None),
        (8, 1, 128, 128, 512, 256, True, True, jnp.bfloat16, None),
        # ZAYA1's: two key heads (two head blocks of the grid), 4 query
        # heads each — with a band, without, and not causal over a padded
        # sequence (the last key block masks its padding).
        (8, 2, 128, 128, 512, 128, True, False, jnp.float32, None),
        (8, 2, 128, 128, 768, 128, True, False, jnp.float32, 300),
        (8, 2, 128, 128, 300, 128, False, False, jnp.float32, None),
        (8, 2, 128, 128, 512, 256, True, False, jnp.bfloat16, None),
        (8, 2, 128, 128, 768, 256, True, True, jnp.bfloat16, 256),
        # 64-wide query heads (SambaY's two a key head, two key heads an
        # instance) are rolled onto their key head's lanes: bf16 too, and
        # four a key head.
        (4, 2, 64, 128, 768, 256, True, False, jnp.bfloat16, 256),
        (8, 2, 64, 64, 512, 128, True, False, jnp.float32, None),
    ],
)
def test_blockwise_attention_kernels_match_the_xla_loop(
    monkeypatch, hq, hkv, d, dv, s, block, causal, silos, dtype, window
):
    """The Pallas kernels behind ``blockwise_attention`` on a TPU against
    its XLA block loop: the output and all three gradients. float32 to
    float32 tolerance (the same arithmetic, the matmuls' own summation
    order apart). bf16 to 1% in norm: the LOOP rounds its scores to bf16
    (``ring_attention._scores``: faster in XLA), the kernel reads the
    float32 accumulator, so they differ by the rounding of one tile —
    and the kernel is the closer of the two to the float32 result."""
    from tpfl.parallel import compat

    lead = (2,) if silos else ()
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q32 = jax.random.normal(keys[0], (*lead, 2, s, hq, d))
    k32 = jax.random.normal(keys[1], (*lead, 2, s, hkv, d))
    v32 = jax.random.normal(keys[2], (*lead, 2, s, hkv, dv))
    cot = jax.random.normal(keys[3], (*lead, 2, s, hq, dv))
    q, k, v = (x.astype(dtype) for x in (q32, k32, v32))
    kw = dict(causal=causal, block_size=block, window=window)
    loop = _attention_and_gradients(q, k, v, cot, silos, **kw)
    exact = _attention_and_gradients(q32, k32, v32, cot, silos, **kw)
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    monkeypatch.setattr(compat, "pallas_interpret", lambda interpret: True)
    kernels = _attention_and_gradients(q, k, v, cot, silos, **kw)
    # (a case whose blocks the selection refused would compare the loop
    # with itself)
    from tpfl.parallel.ring_attention import blockwise_attention

    one = (x[0] if silos else x for x in (q, k, v))
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda q, k, v: blockwise_attention(q, k, v, **kw)
    )(*one))

    def off(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    for name, got, want, true in zip(("out", "dq", "dk", "dv"), kernels, loop, exact):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, atol=3e-5, err_msg=name)
        else:
            assert off(got, want) < 1e-2, (name, off(got, want))
            assert off(got, true) < 1.1 * off(want, true), name


def _dense_band(q, k, v, window):
    """Float32 softmax over the whole masked ``[S, S]`` score matrix."""
    groups = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, groups, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    apart = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None]
    scores = jnp.where((apart >= 0) & (apart < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize(
    "hq, hkv, d, dv",
    [
        (4, 2, 64, 128),  # SambaY's: 64-wide query heads rolled in VMEM
        (8, 1, 128, 128),  # Mellum 2's: 8 query heads a key head as lanes
        (8, 2, 128, 128),  # ZAYA1's: 4 a key head, two head blocks
    ],
)
def test_banded_kernels_match_a_dense_masked_softmax(kernels_on_cpu, hq, hkv, d, dv):
    """The band inside the kernels against plain dense attention under
    the mask ``0 <= q - k < window``, output and gradients: grouped
    heads, a padded sequence, a window that cuts two far blocks."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(keys[0], (2, 700, hq, d))
    k = jax.random.normal(keys[1], (2, 700, hkv, d))
    v = jax.random.normal(keys[2], (2, 700, hkv, dv))
    cot = jax.random.normal(keys[3], (2, 700, hq, dv))
    got = _attention_and_gradients(
        q, k, v, cot, False, causal=True, block_size=128, window=300
    )

    def loss(q, k, v):
        out = _dense_band(q, k, v, 300)
        return jnp.vdot(out, cot), out

    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, (out, *grads)):
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=name)


#: float64 sums of (out, dq, dk, dv) of the call below at the commit
#: before the band went into the kernels (PR 32's tree, the emulator).
PARENT_SUMS = [
    -134.0277310088277, 69.49734580801123, 0.17355041205883026,
    141.93218785896897,
]


@pytest.mark.parametrize("window", [None, 600, 4096])
def test_a_band_as_long_as_the_sequence_is_the_causal_call(kernels_on_cpu, window):
    """With ``window=None``, or a window that no query's band leaves
    (>= the sequence), the kernels' results are the causal call's bit
    for bit — and the causal call's are the parent's: its kernels are
    pinned below to the values the commit before the band gave."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, cot = (
        jax.random.normal(key, (1, 600, 2, 64)).astype(jnp.bfloat16)
        for key in keys
    )
    kw = dict(causal=True, block_size=256)
    causal = _attention_and_gradients(q, k, v, cot, False, **kw)
    banded = _attention_and_gradients(q, k, v, cot, False, window=window, **kw)
    for name, a, b in zip(("out", "dq", "dk", "dv"), banded, causal):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name
        )
    sums = [float(np.asarray(x, np.float64).sum()) for x in causal]
    assert sums == PARENT_SUMS


@pytest.mark.parametrize(
    "window, block, n_blocks, reach, edge",
    [
        # Mellum 2's band: pairs 0 and 4 blocks apart are masked, 1-3 not.
        (1024, 256, 32, 4, 4),
        (1024, 128, 64, 8, 8),
        # No multiple of the block: the far edge cuts TWO pairs (2 and 3).
        (600, 256, 32, 3, 2),
        # Shorter than a block: the diagonal is cut by both edges.
        (50, 128, 8, 1, 0),
        (1, 128, 8, 0, 0),
        # SambaY's: one block. A band longer than the sequence's blocks.
        (512, 512, 16, 1, 1),
        (4000, 256, 4, 3, 15),
    ],
)
def test_band_plan_sweeps_the_blocks_the_band_reaches(
    window, block, n_blocks, reach, edge
):
    """The kernels' static plan of a band: a sweep of ``reach + 1`` steps
    (the XLA loop's ``_band_reach``, within the sequence), masks from
    ``edge`` blocks apart — checked against the mask itself."""
    from tpfl.parallel import flash_kernel
    from tpfl.parallel.ring_attention import _band_reach

    x = jax.ShapeDtypeStruct((1, n_blocks * block, 2, 64), jnp.float32)
    plan = flash_kernel._plan(x, x, True, block, x.shape[1], 1, window)
    assert (plan.reach, plan.edge, plan.sweep) == (reach, edge, reach + 1)
    assert plan.reach == min(n_blocks - 1, _band_reach(block, window))
    apart = np.arange(block)[:, None] - np.arange(block)[None]  # q - k
    for blocks in range(n_blocks):
        seen = (apart + blocks * block >= 0) & (apart + blocks * block < window)
        assert seen.any() == (blocks <= _band_reach(block, window)), blocks
        if blocks:
            assert (not seen.all()) == (blocks >= edge), blocks
    assert flash_kernel._plan(x, x, True, block, x.shape[1], 1).sweep == n_blocks


def test_blockwise_attention_kernels_are_the_tpu_branch_with_a_band_too(kernels_on_cpu):
    """What the code can observe selects the path: on a TPU the block
    loop is two ``pallas_call`` s (forward; ONE backward sweep), with a
    band (``window``) too; at blocks the kernels cannot tile it stays
    the XLA loop — and elsewhere (every other test of this file) too."""
    from tpfl.parallel.ring_attention import blockwise_attention

    def kernels(s, **kw):
        x = jnp.zeros((1, s, 2, 64), jnp.bfloat16)
        text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            blockwise_attention(q, k, v, causal=True, **kw).astype(jnp.float32)
        ), argnums=(0, 1, 2)))(x, x, x))
        return text.count("pallas_call")

    assert kernels(1024) == 2
    assert kernels(1024, window=256) == 2
    assert kernels(128) == 2  # one block as long as the sequence
    # Only what was compiled for a TPU: a short sequence that is one
    # block of its own, unaligned length stays with the loop, as before
    # the kernels; so do blocks that are no lane tiles, or too tall.
    assert kernels(100) == 0
    assert kernels(120, block_size=40) == 0
    assert kernels(4096, block_size=2048) == 0


def _kernel_calls(fn, *args) -> list:
    """The ``pallas_call`` equations of ``fn``'s jaxpr, through its
    ``custom_vjp`` and ``jit`` calls."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize(
    "hq, hkv, d, block, q_block, dq_block",
    [
        # Equal heads (GPT-2; ``flash_attention``; the ring's inner): the
        # kernel call it was before grouped heads became lanes — a block
        # of ``block`` rows x two 64-wide heads, dq for the whole
        # sequence.
        (12, 12, 64, 512, (None, 512, 128), (None, 1024, 128)),
        # Grouped heads: ``block`` rows still, the key head's query
        # heads side by side on the lanes (Mellum 2, ZAYA1, SambaY).
        (32, 4, 128, 256, (None, 256, 1024), (None, 1024, 1024)),
        (8, 2, 128, 512, (None, 512, 512), (None, 1024, 512)),
        (40, 20, 64, 512, (None, 512, 256), (None, 1024, 256)),
    ],
)
def test_kernels_take_query_heads_where_the_projection_left_them(
    kernels_on_cpu, hq, hkv, d, block, q_block, dq_block
):
    """No operand of the kernels is transposed on its way in or out:
    q, dO, out and dq are ``[B, S, Hq * D]`` — the free reshape of what
    the projections and rotary hold — whatever the groups; k, v, dk and
    dv ``[B, S, Hkv * D]``; only lse and delta, ``[B, S, Hq]`` float32,
    are in the kernels' own row order."""
    from tpfl.parallel.ring_attention import blockwise_attention

    q = jnp.zeros((1, 1024, hq, d), jnp.bfloat16)
    k = jnp.zeros((1, 1024, hkv, d), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *x: jnp.sum(blockwise_attention(
            *x, causal=True, block_size=block
        ).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    from tpfl.parallel import flash_kernel

    calls = {eqn.params["name"]: eqn for eqn in _kernel_calls(grads, q, k, k)}
    forward, backward = calls[flash_kernel.FORWARD], calls[flash_kernel.BACKWARD]
    # (grouped heads of whole lane tiles: delta by a third, small kernel)
    assert len(calls) == 2 + (hq != hkv and d % 128 == 0)

    def blocks(eqn):
        return [tuple(
            None if type(n).__name__ in ("Squeezed", "Mapped") else int(getattr(n, "block_size", n))
            for n in m.block_shape
        ) for m in eqn.params["grid_mapping"].block_mappings]

    wide, narrow = (1, 1024, hq * d), (1, 1024, hkv * d)
    assert [v.aval.shape for v in forward.invars] == [wide, narrow, narrow]
    assert forward.outvars[0].aval.shape == wide
    assert [v.aval.shape for v in backward.invars[:4]] == [wide, narrow, narrow, wide]
    assert [v.aval.shape for v in backward.outvars] == [wide, narrow, narrow]
    assert blocks(forward)[0] == blocks(forward)[3] == q_block
    assert blocks(backward)[0] == blocks(backward)[3] == q_block
    assert blocks(backward)[6] == dq_block
    assert forward.params["grid_mapping"].grid == backward.params["grid_mapping"].grid


def test_delta_rows_are_in_the_kernels_row_order():
    """``flash_kernel.delta_rows`` gives ``rowsum(dO * O)`` of the
    natural ``[B, S, Hq, Dv]`` arrays in the order the block loop keeps
    its scalars in — what the XLA loop computes on grouped rows."""
    from tpfl.parallel import flash_kernel
    from tpfl.parallel.ring_attention import _grouped_rows, _natural_rows

    do, out = jax.random.normal(jax.random.PRNGKey(5), (2, 2, 512, 8, 16))
    got = flash_kernel.delta_rows(do, out, 128, 4)
    rows = [_grouped_rows(x, 128, 4) for x in (do, out)]
    want = jnp.moveaxis(jnp.sum(rows[0] * rows[1], axis=-1), 1, 2)
    assert got.shape == (2, 2, 4 * 512)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(
        flash_kernel.delta_rows(do, out), jnp.moveaxis(jnp.sum(do * out, -1), 1, 2)
    )
    np.testing.assert_array_equal(_natural_rows(rows[0], 128, 4), do)
    # Heads of whole lane tiles: the small kernel, from lanes.
    do, out = jax.random.normal(jax.random.PRNGKey(6), (2, 1, 256, 8, 128))
    calls = _kernel_calls(
        lambda *x: flash_kernel.delta_rows(*x, 128, 4, interpret=True), do, out
    )
    assert [v.aval.shape for v in calls[0].invars] == [(1, 256, 1024)] * 2
    got = flash_kernel.delta_rows(do, out, 128, 4, interpret=True)
    rows = [_grouped_rows(x, 128, 4) for x in (do, out)]
    want = jnp.moveaxis(jnp.sum(rows[0] * rows[1], axis=-1), 1, 2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ring_auto_takes_the_kernels_only_at_blocks_they_tile(kernels_on_cpu):
    """``ring_attention(impl="auto")`` on a TPU: the flash inner where
    the local block is whole lane tiles, the einsum inner where it is
    not (a 100-token shard would be one 100-row block: never compiled
    for a TPU)."""
    from tpfl.parallel.ring_attention import make_ring_attention

    ring = make_ring_attention(create_mesh({"sp": 8}), causal=True)

    def kernels(s):
        x = jnp.zeros((1, s, 2, 64), jnp.bfloat16)
        return str(jax.make_jaxpr(ring)(x, x, x)).count("pallas_call")

    assert kernels(8 * 128) > 0 and kernels(8 * 100) == 0


def test_attention_kernels_take_the_fewest_heads_with_whole_lane_tiles():
    from tpfl.parallel import flash_kernel

    heads = flash_kernel._heads_per_instance
    assert heads(12, 64, 64) == 2  # GPT-2: a head pair is one 128-lane tile
    assert heads(20, 64, 128) == 2  # SambaY: the keys decide
    assert heads(8, 128, 128) == 1
    assert heads(3, 64, 64) == 3  # no pair divides three heads: all of them
    assert heads(4, 8, 16) == 4
    tiles = flash_kernel.tiles
    assert tiles((1, 1024, 12, 64), 64, 512) and tiles((1, 128, 2, 64), 64, 128)
    # One block of an unaligned length, 40-row blocks: no lane tiles.
    assert not tiles((1, 100, 2, 64), 64, 100)
    assert not tiles((1, 120, 2, 64), 64, 40)
    # A pair's float32 tile: [1024, 1024] is the largest compiled.
    assert tiles((1, 8192, 8, 128), 128, 1024)
    assert not tiles((1, 8192, 8, 128), 128, 2048)
    assert not tiles((1, 8192, 20, 64), 128, 1024, groups=2)
    # The backward keeps a head block's dq for the whole sequence in
    # VMEM, float32 scratch and double-buffered output block: 8 + 8 MB
    # in the SambaY cell, too much at a million tokens, and float32
    # gradients (the ring's; float32 inputs) reach the limit sooner.
    assert tiles((1, 8192, 20, 64), 128, 512, groups=2)
    assert not tiles((1, 1 << 20, 20, 64), 128, 512, groups=2)
    assert tiles((1, 32768, 20, 64), 128, 512, groups=2)
    assert not tiles((1, 32768, 20, 64), 128, 512, groups=2, grad_bytes=4)


def test_transformer_lm_with_ring_attention_seam():
    """TransformerLM's attention_fn seam: the same model computes
    matching logits with default blockwise attention and with
    sequence-parallel ring attention over the 8-device mesh."""
    from tpfl.models import create_model
    from tpfl.parallel import make_ring_attention

    model = create_model(
        "transformer_lm", (64,), seed=0, vocab=32, dim=32, heads=2,
        n_layers=1,
    )
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 31, (2, 64)), jnp.int32)
    base = model.module.apply({"params": model.get_parameters()}, tokens)

    mesh = create_mesh({"sp": 8})
    ring = make_ring_attention(mesh, causal=True, impl="flash")
    # The closure plugs in directly: it validates the causal kwarg the
    # block passes, so a causality mismatch raises instead of silently
    # attending the wrong way.
    ring_module = type(model.module)(
        vocab=32, dim=32, heads=2, n_layers=1, attention_fn=ring,
    )
    ringed = ring_module.apply({"params": model.get_parameters()}, tokens)
    # bf16-honest tolerance: the model computes in bf16, and the two
    # attention inners round at different points — blockwise's score
    # einsum on bf16 inputs yields bf16 scores, the flash-ring kernel
    # keeps scores f32 (strictly more accurate) — so logits agree to
    # bf16 resolution, not f32. The f32 exactness of the ring itself
    # is pinned by test_ring_attention_matches_dense (atol 2e-5).
    np.testing.assert_allclose(
        np.asarray(ringed), np.asarray(base), atol=4e-2
    )
    with pytest.raises(ValueError, match="causal"):
        make_ring_attention(mesh, causal=False)(
            jnp.zeros((1, 8, 1, 8)), jnp.zeros((1, 8, 1, 8)),
            jnp.zeros((1, 8, 1, 8)), causal=True,
        )


def test_transformer_lm_trains_with_ring_attention():
    """The long-context stack TRAINS sequence-parallel: gradient steps
    through ring attention on the sp mesh match the single-device
    blockwise model step for step."""
    import optax

    from tpfl.models import TransformerLM, create_model
    from tpfl.parallel import make_ring_attention

    model = create_model(
        "transformer_lm", (64,), seed=0, vocab=32, dim=32, heads=2,
        n_layers=1, compute_dtype=jnp.float32,
    )
    params0 = model.get_parameters()
    mesh = create_mesh({"sp": 8})
    ring_mod = TransformerLM(
        vocab=32, dim=32, heads=2, n_layers=1,
        compute_dtype=jnp.float32,
        attention_fn=make_ring_attention(mesh, causal=True, impl="flash"),
    )
    base_mod = TransformerLM(
        vocab=32, dim=32, heads=2, n_layers=1, compute_dtype=jnp.float32
    )

    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 31, (2, 64)), jnp.int32)

    def make_step(mod):
        tx = optax.sgd(0.1)

        def loss_of(p):
            logits = mod.apply({"params": p}, tokens, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], tokens[:, 1:]
            ).mean()

        @jax.jit
        def step(p, o):
            loss, g = jax.value_and_grad(loss_of)(p)
            u, o = tx.update(g, o, p)
            return optax.apply_updates(p, u), o, loss

        return step, tx.init(params0)

    ring_step, ring_opt = make_step(ring_mod)
    base_step, base_opt = make_step(base_mod)
    rp, bp = params0, params0
    ring_losses, base_losses = [], []
    for _ in range(3):
        rp, ring_opt, rl = ring_step(rp, ring_opt)
        bp, base_opt, bl = base_step(bp, base_opt)
        ring_losses.append(float(rl))
        base_losses.append(float(bl))
    np.testing.assert_allclose(ring_losses, base_losses, rtol=1e-4)
    assert ring_losses[-1] < ring_losses[0]
    for g, w in zip(
        jax.tree_util.tree_leaves(rp), jax.tree_util.tree_leaves(bp)
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=5e-4
        )


def test_composed_dp_sp_mesh_train_step():
    """Axes compose: one mesh with dp x sp, batch sharded over dp,
    ring attention over sp, one jitted train step executes and the
    loss is finite."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from tpfl.models import TransformerLM
    from tpfl.parallel import make_ring_attention

    mesh = create_mesh({"dp": 2, "sp": 4})
    mod = TransformerLM(
        vocab=32, dim=32, heads=2, n_layers=1,
        compute_dtype=jnp.float32,
        attention_fn=make_ring_attention(
            mesh, axis_name="sp", causal=True, impl="flash"
        ),
    )
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, 31, (4, 32)), jnp.int32)
    params = mod.init(jax.random.PRNGKey(0), tokens[:1], train=False)["params"]
    tx = optax.sgd(0.1)
    opt = tx.init(params)
    tokens = jax.device_put(
        tokens, NamedSharding(mesh, PartitionSpec("dp", "sp"))
    )

    @jax.jit
    def step(p, o, t):
        def loss_of(pp):
            logits = mod.apply({"params": pp}, t, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], t[:, 1:]
            ).mean()

        loss, g = jax.value_and_grad(loss_of)(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    params, opt, loss = step(params, opt, tokens)
    assert np.isfinite(float(loss))


def test_pipeline_parallel_matches_sequential():
    """GPipe-style pipeline over a pp axis: microbatched, stage-sharded
    params, activations ppermuted down the pipe — exactly equal to the
    sequential stack."""
    from tpfl.parallel.pipeline import make_pipeline

    rng = np.random.default_rng(0)
    L, D = 8, 16
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32),
        "w2": jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32),
    }

    def block_fn(p, x):
        return x + jnp.tanh(x @ p["w1"]) @ p["w2"]

    mesh = create_mesh({"pp": 4}, devices=jax.devices()[:4])
    pipe = make_pipeline(mesh, block_fn, n_layers=L)
    micro = jnp.asarray(rng.normal(size=(6, 4, D)), jnp.float32)
    got = pipe(params, micro)

    def ref(x):
        for layer in range(L):
            x = block_fn(
                jax.tree_util.tree_map(lambda p: p[layer], params), x
            )
        return x

    want = jnp.stack([ref(micro[i]) for i in range(6)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # Params are genuinely stage-sharded: the layer axis splits over pp
    # (each stage holds L/n layers - the memory win the module claims).
    from jax.sharding import NamedSharding, PartitionSpec

    placed = jax.device_put(
        params["w1"], NamedSharding(mesh, PartitionSpec("pp"))
    )
    assert placed.addressable_shards[0].data.shape == (L // 4, D, D)
    # Layer counts that don't divide the stage count are rejected.
    with pytest.raises(ValueError, match="split"):
        make_pipeline(mesh, block_fn, n_layers=6)
    # Mixed precision: bf16 microbatches through f32 params trace fine.
    got_bf16 = pipe(params, micro.astype(jnp.bfloat16))
    assert got_bf16.dtype == jnp.bfloat16


def test_pipeline_training_matches_sequential():
    """The pipeline TRAINS: grads through the scan-based schedule (the
    backward GPipe pass — reverse-ring ppermute of cotangents) are
    exactly the sequential stack's, and a short training loop produces
    identical params and decreasing loss."""
    import optax

    from tpfl.parallel.pipeline import make_pipeline_trainer

    rng = np.random.default_rng(1)
    L, D, n_micro, mb = 8, 16, 6, 4
    params = {
        "w1": jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32),
        "w2": jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32),
    }

    def block_fn(p, x):
        return x + jnp.tanh(x @ p["w1"]) @ p["w2"]

    def loss_fn(outputs, targets):
        return jnp.mean((outputs - targets) ** 2)

    micro = jnp.asarray(rng.normal(size=(n_micro, mb, D)), jnp.float32)
    targets = jnp.asarray(rng.normal(size=(n_micro, mb, D)), jnp.float32)

    mesh = create_mesh({"pp": 4}, devices=jax.devices()[:4])
    init, step = make_pipeline_trainer(
        mesh, block_fn, n_layers=L, loss_fn=loss_fn, learning_rate=0.05
    )

    # Sequential twin: same blocks, same loss, same optimizer.
    def seq_loss(p, x, t):
        def one(h, layer):
            lp = jax.tree_util.tree_map(lambda q: q[layer], p)
            return block_fn(lp, h)

        out = x
        for layer in range(L):
            out = one(out, layer)
        return loss_fn(out, t)

    sgd = optax.sgd(0.05)
    seq_params = params
    seq_opt = sgd.init(seq_params)

    pp_params, pp_opt = init(params)
    seq_losses, pp_losses = [], []
    for _ in range(5):
        loss_s, grads_s = jax.value_and_grad(seq_loss)(
            seq_params, micro, targets
        )
        upd, seq_opt = sgd.update(grads_s, seq_opt, seq_params)
        seq_params = optax.apply_updates(seq_params, upd)
        seq_losses.append(float(loss_s))

        pp_params, pp_opt, loss_p = step(pp_params, pp_opt, micro, targets)
        pp_losses.append(float(loss_p))

    np.testing.assert_allclose(pp_losses, seq_losses, rtol=1e-5)
    assert pp_losses[-1] < pp_losses[0]  # it actually learns
    for k in ("w1", "w2"):
        np.testing.assert_allclose(
            np.asarray(pp_params[k]), np.asarray(seq_params[k]), atol=1e-5
        )


def test_moe_expert_parallel_routing():
    """Expert parallelism over ep: top-1 routing with all_to_all
    dispatch — every kept token is processed by exactly the expert its
    router chose; over-capacity tokens take the residual passthrough."""
    from tpfl.parallel.moe import make_moe_layer

    n, t_per, dim = 8, 16, 8
    mesh = create_mesh({"ep": n})
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n * t_per, dim)).astype(np.float32)
    want_expert = rng.integers(0, n, n * t_per)
    x[:, 0] = want_expert  # feature 0 encodes the desired expert

    scales = jnp.arange(1, n + 1, dtype=jnp.float32).reshape(n, 1, 1)
    layer = make_moe_layer(
        mesh,
        expert_fn=lambda p, toks: toks * p["scale"],
        router_fn=lambda toks: toks[:, 0].astype(jnp.int32),
        capacity=t_per,
    )
    out = np.asarray(layer({"scale": scales}, jnp.asarray(x)))
    expected = x * (want_expert[:, None] + 1)
    np.testing.assert_allclose(out, expected, atol=1e-5)

    # Tight capacity: dropped tokens pass through unchanged.
    layer1 = make_moe_layer(
        mesh,
        expert_fn=lambda p, toks: toks * p["scale"],
        router_fn=lambda toks: toks[:, 0].astype(jnp.int32),
        capacity=1,
    )
    out1 = np.asarray(layer1({"scale": scales}, jnp.asarray(x)))
    processed = np.isclose(out1, expected).all(axis=1)
    passthrough = np.isclose(out1, x).all(axis=1)
    assert (processed | passthrough).all()
    assert passthrough.sum() > 0  # capacity actually bit


def test_moe_trains_end_to_end_with_balanced_experts():
    """The MoE TRAINS: router + experts learn a task only a routed
    mixture can solve (4 clusters, each needing a different linear
    map), router params receive gradients, and the aux load-balance
    loss drives expert traffic toward uniform."""
    import optax

    from tpfl.parallel.moe import make_moe_train_layer

    n, dim, t_per = 4, 8, 32
    mesh = create_mesh({"ep": n}, devices=jax.devices()[:n])
    rng = np.random.default_rng(0)

    # 4 well-separated clusters; target = cluster-specific linear map.
    centers = rng.normal(0, 4.0, (n, dim)).astype(np.float32)
    maps = rng.normal(0, 1.0, (n, dim, dim)).astype(np.float32)
    cluster = rng.integers(0, n, n * t_per)
    x = (centers[cluster] + rng.normal(0, 0.3, (n * t_per, dim))).astype(
        np.float32
    )
    y_true = np.einsum("td,tdk->tk", x, maps[cluster]).astype(np.float32)

    layer = make_moe_train_layer(
        mesh,
        expert_fn=lambda p, toks: toks @ p["w"],
        capacity=2 * t_per,
        k=2,
    )
    params = {
        "router": jnp.asarray(rng.normal(0, 0.1, (dim, n)), jnp.float32),
        "experts": {
            "w": jnp.asarray(rng.normal(0, 0.3, (n, dim, dim)), jnp.float32)
        },
    }

    xj, yj = jnp.asarray(x), jnp.asarray(y_true)

    def loss_of(p):
        out, aux = layer(p, xj)
        return jnp.mean((out - yj) ** 2) + 0.01 * aux, aux

    opt = optax.adam(3e-2)
    opt_state = opt.init(params)
    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    (l0, aux0), g0 = grad_fn(params)
    # Router genuinely receives gradients through the top-k combine.
    assert float(jnp.abs(g0["router"]).sum()) > 0
    losses, auxes = [], []
    p = params
    for _ in range(60):
        (loss, aux), grads = grad_fn(p)
        updates, opt_state = opt.update(grads, opt_state, p)
        p = optax.apply_updates(p, updates)
        losses.append(float(loss))
        auxes.append(float(aux))
    assert losses[-1] < 0.3 * losses[0], losses[::10]
    # Aux loss ends near its uniform-load minimum of 1.0.
    assert auxes[-1] < 1.5, auxes[::10]
    # Expert traffic (top-1 fractions) is not collapsed onto one expert.
    logits = x @ np.asarray(p["router"])
    top1 = logits.argmax(-1)
    frac = np.bincount(top1, minlength=n) / len(top1)
    assert frac.max() < 0.8, frac


def test_moe_rejects_mismatched_experts_and_drops_invalid_routes():
    from tpfl.parallel.moe import make_moe_layer

    n = 8
    mesh = create_mesh({"ep": n})
    layer = make_moe_layer(
        mesh,
        expert_fn=lambda p, toks: toks * p["scale"],
        router_fn=lambda toks: toks[:, 0].astype(jnp.int32),
        capacity=4,
    )
    with pytest.raises(ValueError, match="leading dim"):
        layer({"scale": jnp.ones((16, 1, 1))}, jnp.zeros((16, 4)))
    # Out-of-range router ids pass through, never clamped to an expert.
    x = np.ones((16, 4), np.float32)
    x[:, 0] = 99  # invalid expert everywhere
    out = np.asarray(layer({"scale": 2 * jnp.ones((n, 1, 1))}, jnp.asarray(x)))
    np.testing.assert_array_equal(out, x)


# --- per-node conv backward lowering (tpfl.models.zoo.conv_fwd_style) ---


def test_conv_fwd_style_grads_match_autodiff():
    """conv_fwd_style: backward convs reformulated as forward-style
    convs must produce the SAME gradients as plain autodiff through
    lax.conv — including under vmap over a nodes axis (the federation
    composition)."""
    from tpfl.models.zoo import conv_fwd_style

    rng = np.random.default_rng(0)
    ref = lambda x, w: jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    for shape in [(2, 8, 8, 3, 5), (2, 6, 10, 7, 4)]:
        B, H, W, Cin, Cout = shape
        x = jnp.asarray(rng.normal(size=(B, H, W, Cin)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(3, 3, Cin, Cout)), jnp.float32)
        gx_k, gw_k = jax.grad(
            lambda a, b: jnp.sum(conv_fwd_style(a, b) ** 2), argnums=(0, 1)
        )(x, w)
        gx_r, gw_r = jax.grad(
            lambda a, b: jnp.sum(ref(a, b) ** 2), argnums=(0, 1)
        )(x, w)
        np.testing.assert_allclose(
            np.asarray(gx_k), np.asarray(gx_r), rtol=1e-5, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(gw_k), np.asarray(gw_r), rtol=1e-5, atol=1e-4
        )

    # vmapped (per-node weights) — the VmapFederation composition
    n = 3
    xs = jnp.asarray(rng.normal(size=(n, 2, 8, 8, 8)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(n, 3, 3, 8, 4)), jnp.float32)
    gk = jax.grad(lambda ws: jnp.sum(
        jax.vmap(conv_fwd_style)(xs, ws) ** 2))(ws)
    gr = jax.grad(lambda ws: jnp.sum(jax.vmap(ref)(xs, ws) ** 2))(ws)
    np.testing.assert_allclose(
        np.asarray(gk), np.asarray(gr), rtol=1e-5, atol=1e-4
    )


def test_cnn_conv_impls_share_param_tree_and_forward():
    """CNN conv_impl variants must be drop-in interchangeable: same
    param tree (paths+shapes), same init values, same forward — and the
    same gradients: ``fwd_bwd``'s reformulated backward against plain
    autodiff through ``nn.Conv``."""
    from tpfl.models import CNN

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, (2,)))
    outs, trees, grads = [], [], []
    for impl in ("fwd_bwd", "xla"):
        m = CNN(out_channels=10, conv_impl=impl, compute_dtype=jnp.float32)
        v = m.init(jax.random.PRNGKey(7), x, train=False)
        trees.append(jax.tree_util.tree_structure(v["params"]))
        outs.append(m.apply(v, x, train=False))

        def loss(params, m=m):
            logits = m.apply({"params": params}, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        grads.append(jax.grad(loss)(v["params"]))
    assert trees[0] == trees[1]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]), atol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads[0]), jax.tree_util.tree_leaves(grads[1])
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        )


def test_cnn_conv_impl_pallas_is_gone():
    """The Pallas conv lost its only measurement and went (PR 29): the
    value raises, naming the two that remain."""
    from tpfl.models import CNN

    x = jnp.zeros((1, 32, 32, 3), jnp.float32)
    with pytest.raises(ValueError, match="fwd_bwd.*xla"):
        CNN(conv_impl="pallas").init(jax.random.PRNGKey(0), x, train=False)
