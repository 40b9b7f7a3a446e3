"""Each configuration's PLAIN REFERENCE against ``jax.grad`` of the zoo
module, at a tiny size in float32 on the CPU: logits, loss, gradients
and (ResNet) the moved BatchNorm statistics. The references share no
code with ``tpfl.models``; this is where the two meet."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from toy import toy_cell

CELLS = ["resnet18_sync_long", "gpt2s_silo_1chip"]


def _setup(name):
    cell = toy_cell(name)
    module = cell.model.build_module(cell.config)
    xs, ys = cell.model.make_data(jax.random.PRNGKey(7), cell.config, cell.traffic)
    x, y = xs[0, 0], ys[0, 0]
    variables = module.init(jax.random.PRNGKey(1), x[:1], train=False)
    params = variables["params"]
    aux = {k: v for k, v in variables.items() if k != "params"}
    if aux:
        # Statistics away from their initial 0/1, so a reference that
        # ignored the running values could not pass.
        aux = jax.tree_util.tree_map(lambda v: v + 0.25, aux)
    return cell, module, params, aux, x, y


def _zoo_loss(module, params, aux, x, y):
    if aux:
        logits, new_aux = module.apply(
            {"params": params, **aux}, x, train=True, mutable=list(aux)
        )
    else:
        logits, new_aux = module.apply({"params": params}, x, train=True), {}
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    return loss, (logits, new_aux)


def _max_rel(a, b):
    flat_a, flat_b = (jax.tree_util.tree_leaves(t) for t in (a, b))
    assert len(flat_a) == len(flat_b)
    return max(
        float(jnp.abs(u - v).max() / jnp.maximum(jnp.abs(v).max(), 1e-12))
        for u, v in zip(flat_a, flat_b)
    )


@pytest.mark.parametrize("name", CELLS)
def test_reference_forward_loss_and_gradients_match_the_zoo(name):
    cell, module, params, aux, x, y = _setup(name)
    (loss, (logits, new_aux)), grads = jax.value_and_grad(
        lambda p: _zoo_loss(module, p, aux, x, y), has_aux=True
    )(params)

    with jax.default_matmul_precision("highest"):
        ref_logits, ref_aux = cell.model.reference_forward(
            cell.config, params, aux, x
        )

        def ref_loss(p):
            out, _ = cell.model.reference_forward(cell.config, p, aux, x)
            return optax.softmax_cross_entropy_with_integer_labels(out, y).mean()

        ref_value, ref_grads = jax.value_and_grad(ref_loss)(params)

    np.testing.assert_allclose(ref_logits, logits, rtol=1e-4, atol=1e-5)
    assert float(ref_value) == pytest.approx(float(loss), rel=1e-5)
    # Same tree (every leaf has a gradient), same values.
    assert jax.tree_util.tree_structure(ref_grads) == jax.tree_util.tree_structure(grads)
    assert _max_rel(ref_grads, grads) < 1e-4
    if aux:
        assert _max_rel(ref_aux, new_aux) < 1e-5


@pytest.mark.parametrize("name", CELLS)
def test_reference_round_is_momentum_sgd_then_a_weighted_mean(name):
    """``reference_round`` against the same round spelt with optax and
    the zoo module: two local steps a node, uneven weights."""
    cell, module, params, aux, _, _ = _setup(name)
    traffic = dict(cell.traffic, nodes=2, local_batches=2)
    xs, ys = cell.model.make_data(jax.random.PRNGKey(9), cell.config, traffic)
    weights, lr = jnp.asarray([1.0, 3.0]), 0.05
    losses, folded, folded_aux = cell.model.reference_round(
        cell.config, params, aux, xs, ys, weights, lr
    )

    tx = optax.sgd(lr, momentum=0.9)
    trained, stats, want_losses = [], [], []
    for node in range(2):
        p, a, opt, seen = params, aux, tx.init(params), []
        for batch in range(2):
            (loss, (_, a)), g = jax.value_and_grad(
                lambda pp: _zoo_loss(module, pp, a, xs[node, batch], ys[node, batch]),
                has_aux=True,
            )(p)
            upd, opt = tx.update(g, opt, p)
            p = optax.apply_updates(p, upd)
            seen.append(loss)
        trained.append(p)
        stats.append(a)
        want_losses.append(jnp.mean(jnp.stack(seen)))
    mean = lambda *leaves: 0.25 * leaves[0] + 0.75 * leaves[1]  # noqa: E731
    np.testing.assert_allclose(losses, jnp.stack(want_losses), rtol=1e-5)
    assert _max_rel(folded, jax.tree_util.tree_map(mean, *trained)) < 1e-4
    if aux:
        assert _max_rel(folded_aux, jax.tree_util.tree_map(mean, *stats)) < 1e-4
