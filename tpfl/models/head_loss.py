"""A vocabulary projection that owns its training loss.

``head_cross_entropy(hidden, kernel, bias, targets)`` is the mean token
cross-entropy of ``softmax(hidden @ kernel + bias)`` against integer
``targets``: the same mathematics, in the same precisions, as
``nn.Dense(dtype=hidden.dtype)`` followed by ``.astype(float32)`` and
``optax.softmax_cross_entropy_with_integer_labels(...).mean()``, as ONE
``jax.custom_vjp`` operation. Written as two (a head that returns
logits, a loss that takes them) the TPU compiler runs five passes over
the ``[tokens, vocab]`` logits, two of them with the matrix unit idle:
the bias gradient as a reduction of its own, and a float32 copy of the
logits kept for the label gather. Here every pass over
``[tokens, vocab]`` carries a matmul:

- forward: logits in the compute dtype, max / exp / sum / log in
  float32, the label's logit by compare-and-select on an ``iota`` (no
  gather, so no float32 logits are materialised); the residuals are
  the operands, the logits in the compute dtype and ``lse``;
- backward: ``dlogits = (exp(l - lse) - onehot) * g / n_tokens`` cast
  to the compute dtype once, as autodiff casts it; the kernel and the
  input gradients as today's two matmuls; the BIAS gradient as a third
  small matmul (a block of ones against ``dlogits``) instead of a
  reduction. Precision only goes up: the kernel and bias gradients
  leave the matrix unit's float32 accumulators without the rounding
  to the compute dtype that autodiff of a bf16 ``Dense`` inserts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: Rows of ones the bias gradient's matmul contracts ``dlogits`` with:
#: one sublane tile, the smallest left operand the matrix unit takes
#: without padding. Row 0 of the product is the gradient.
_ONES_ROWS = 8


def _logits(hidden, kernel, bias):
    """``nn.Dense(dtype=hidden.dtype)``'s forward: operands and result
    in the compute dtype."""
    dtype = hidden.dtype
    y = lax.dot_general(
        hidden, kernel.astype(dtype),
        (((hidden.ndim - 1,), (0,)), ((), ())),
    )
    return y if bias is None else y + bias.astype(dtype)


def _is_label(logits, targets):
    """``[..., vocab]`` bool: the position of each token's target."""
    vocab = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return vocab == targets[..., None].astype(jnp.int32)


@jax.custom_vjp
def head_cross_entropy(hidden, kernel, bias, targets):
    """Mean over tokens of the cross-entropy of the vocabulary
    projection ``hidden [..., d] @ kernel [d, V] + bias [V]`` (computed
    in ``hidden.dtype``; ``bias`` may be None) against integer
    ``targets [...]``; a float32 scalar. Differentiable in ``hidden``,
    ``kernel`` and ``bias``."""
    return _forward(hidden, kernel, bias, targets)[0]


# Not a ``tpfl.*`` scope (those are the round body's legs, which the
# benchmark sums): only a name by which a trace finds these operations.
@jax.named_scope("head_cross_entropy")
def _forward(hidden, kernel, bias, targets):
    logits = _logits(hidden, kernel, bias)
    lf = logits.astype(jnp.float32)
    # optax's order of operations, so the value is bit-equal to it:
    # shift by the row max, then log-sum-exp less the shifted label.
    top = jnp.max(lf, axis=-1)
    log_norm = jnp.log(jnp.sum(jnp.exp(lf - top[..., None]), axis=-1))
    label = jnp.sum(jnp.where(_is_label(lf, targets), lf, 0.0), axis=-1)
    loss = jnp.mean(log_norm - (label - top))
    return loss, (hidden, kernel, bias, logits, targets, top + log_norm)


@jax.named_scope("head_cross_entropy")
def _backward(residuals, g):
    hidden, kernel, bias, logits, targets, lse = residuals
    dtype = hidden.dtype
    lf = logits.astype(jnp.float32)
    probs = jnp.exp(lf - lse[..., None])
    onehot = _is_label(lf, targets).astype(jnp.float32)
    dlogits = ((probs - onehot) * (g / targets.size)).astype(dtype)
    tokens = tuple(range(hidden.ndim - 1))
    d_kernel = lax.dot_general(
        hidden, dlogits, ((tokens, tokens), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d_hidden = lax.dot_general(
        dlogits, kernel.astype(dtype),
        (((dlogits.ndim - 1,), (1,)), ((), ())),
    )
    if bias is None:
        return d_hidden, d_kernel.astype(kernel.dtype), None, None
    # The bias gradient on the matrix unit, accumulated in float32.
    # HIGHEST costs nothing on bf16 operands (ones and dlogits are exact
    # in one pass) and keeps a float32 compute dtype at a float32 sum.
    ones = jnp.ones((*dlogits.shape[:-1], _ONES_ROWS), dtype)
    d_bias = lax.dot_general(
        ones, dlogits, ((tokens, tokens), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )[0]
    return (
        d_hidden, d_kernel.astype(kernel.dtype), d_bias.astype(bias.dtype),
        None,
    )


head_cross_entropy.defvjp(_forward, _backward)
