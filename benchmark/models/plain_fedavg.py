"""The federated part of every plain reference: local SGD with momentum,
then the weighted mean — in straightforward float32 ``jax.numpy``, shared
by the configurations' reference files (each brings its own forward pass
and loss). Independent of ``tpfl``: written from FedAvg (McMahan et al.
2017) and the heavy-ball update ``t <- g + m t; p <- p - lr t``."""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

SGD_MOMENTUM = 0.9


def plain_fedavg_round(
    loss_fn: Callable, params: Any, aux: Any, xs: Any, ys: Any,
    weights: Any, lr: float,
) -> tuple:
    """One federated round from ONE global model. ``loss_fn(params, aux,
    x, y) -> (loss, new_aux)``; ``xs``/``ys`` are ``[nodes, batches,
    ...]``. Every node takes one step per batch (momentum starts at zero
    each round, its loss is the mean of its batches' losses before each
    step), then models and ``aux`` are averaged with ``weights / sum``.
    Folded node by node, so no more than two models are held beside the
    global one. Returns (per-node losses [n], folded params, folded
    aux)."""
    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        wnorm = jnp.asarray(weights, jnp.float32)
        wnorm = wnorm / jnp.sum(wnorm)
        zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)  # noqa: E731
        losses, folded, folded_aux = [], zeros(params), zeros(aux)
        for node in range(xs.shape[0]):
            p, a, trace, node_losses = params, aux, zeros(params), []
            for batch in range(xs.shape[1]):
                (loss, a), g = grad(p, a, xs[node, batch], ys[node, batch])
                trace = jax.tree_util.tree_map(
                    lambda t, gg: gg + SGD_MOMENTUM * t, trace, g
                )
                p = jax.tree_util.tree_map(lambda pp, t: pp - lr * t, p, trace)
                node_losses.append(loss)
            losses.append(jnp.mean(jnp.stack(node_losses)))
            add = lambda acc, leaf, w=wnorm[node]: acc + w * leaf  # noqa: E731
            folded = jax.tree_util.tree_map(add, folded, p)
            folded_aux = jax.tree_util.tree_map(add, folded_aux, a)
        return jnp.stack(losses), folded, folded_aux
