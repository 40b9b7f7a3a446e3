"""Selective scan — the recurrence of a Mamba-1 state-space layer.

Gu & Dao 2023 ("Mamba: Linear-Time Sequence Modeling with Selective
State Spaces"), per channel ``d`` of ``D`` and state ``n`` of ``N``:

    h_t = exp(delta_t[d] * A[d, n]) * h_{t-1} + delta_t[d] * c_t[d] * B_t[n]
    s_t[d] = sum_n C_t[n] * h_t[d, n] + Dskip[d] * c_t[d],     h_{-1} = 0

``A`` depends on channel AND state, so there is no matmul form: the work
is element-wise on ``S x D x N`` state elements (671 M a sequence at
S = 8192, D = 5120, N = 16), and all ``[S, D, N]`` states in float32
would be 2.7 GB a sequence a layer. :func:`selective_scan` is one
``jax.custom_vjp`` operation that never holds them. In plain XLA:

- the sequence is cut into CHUNKS of ``chunk`` tokens; a ``lax.scan``
  over chunks carries the ``[D, N]`` float32 state, and inside a chunk a
  second ``lax.scan`` steps token by token, so only one chunk's states
  (``chunk x D x N`` float32: 21 MB at 64) ever exist;
- the forward keeps the inputs and each chunk's STARTING state
  (``S / chunk x D x N``); the backward walks the chunks in reverse,
  recomputes a chunk's states from its starting state, and gets the
  states' adjoints from THE SAME routine: the adjoint recurrence
  ``dh_t = exp(delta_{t+1} A) dh_{t+1} + g_t (x) C_t`` is this
  recurrence on the reversed tokens with the decays shifted by one.
  All six gradients are then element-wise products of the two,
  reduced.

(Measured on the v5e and dropped, PERF.md §6 PR 27: sub-chunks stepping
in lockstep with a correction pass, 1.3x to 2.6x slower at every size
tried; an associative scan inside the chunk, 6x slower.)

On a TPU the same operation runs as two Pallas kernels instead
(:mod:`tpfl.parallel.scan_kernel`: the state is a loop carry in vector
registers, so HBM sees only inputs and outputs); the XLA form above is
what every other backend runs, and what the kernels are tested against.

Everything inside is float32 (``delta``, ``A``, the state, the sums),
whatever the dtype of ``c``; the result is cast back to ``c.dtype``.
Works under ``jax.vmap`` (the engine's vmap over silos batches every
loop, and the kernels' grid). Named ``ssm_scan`` in a trace, forward
and backward, either way.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from tpfl.parallel import compat, scan_kernel

F32 = jnp.float32
#: Tokens a chunk: the states held at once. Chosen on the v5e at
#: D = 5120, N = 16 (PERF.md §6, PR 27: 32 and 64 within 2%, 128 2x slower).
CHUNK = 64


def _chunk_states(h0, delta, u, bmat, a):
    """Every state of one chunk, ``[T, D, N]`` float32: ``delta`` and ``u``
    are ``[T, D]``, ``bmat`` ``[T, N]``, ``a`` ``[D, N]``, ``h0 [D, N]`` the
    state before the chunk's first token; the recurrence is
    ``h_t = exp(delta_t a) h_{t-1} + u_t (x) bmat_t``."""

    def step(h, xs):
        dt, ut, bt = xs
        h = jnp.exp(dt[:, None] * a) * h + ut[:, None] * bt[None, :]
        return h, h

    return lax.scan(step, h0, (delta, u, bmat))[1]


def _chunks(x, chunk: int):
    """``[S, F] -> [S / chunk, chunk, F]`` float32."""
    return x.astype(F32).reshape(-1, chunk, x.shape[-1])


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(c, delta, a, bmat, cmat, dskip, chunk: int):
    return _forward(c, delta, a, bmat, cmat, dskip, chunk)[0]


@jax.named_scope("ssm_scan")
def _forward(c, delta, a, bmat, cmat, dskip, chunk):
    def one_chunk(h, xs):
        cc, dl, bl, cl = xs
        states = _chunk_states(h, dl, dl * cc, bl, a)
        y = jnp.sum(states * cl[:, None, :], axis=-1) + dskip * cc
        return states[-1], (y, h)

    _, (y, starts) = lax.scan(
        one_chunk, jnp.zeros(a.shape, F32),
        tuple(_chunks(x, chunk) for x in (c, delta, bmat, cmat)),
    )
    y = y.reshape(c.shape).astype(c.dtype)
    return y, (c, delta, a, bmat, cmat, dskip, starts)


@jax.named_scope("ssm_scan")
def _backward(chunk, residuals, g):
    c, delta, a, bmat, cmat, dskip, starts = residuals
    # The decay that carries token t+1's adjoint back to token t.
    delta_next = jnp.concatenate([delta[1:], jnp.zeros_like(delta[:1])])

    def one_chunk(carry, xs):
        dh_next, d_a = carry
        cc, dl, dnl, bl, cl, gl, h0 = xs
        ul = dl * cc
        states = _chunk_states(h0, dl, ul, bl, a)
        dh = _chunk_states(dh_next, dnl[::-1], gl[::-1], cl[::-1], a)[::-1]
        # dh * (a_t h_{t-1}): what the decay's gradient multiplies.
        w = dh * (states - ul[..., None] * bl[:, None, :])
        d_u = jnp.sum(dh * bl[:, None, :], axis=-1)
        d_delta = jnp.sum(w * a, axis=-1) + d_u * cc
        d_c = d_u * dl + dskip * gl
        d_b = jnp.sum(dh * ul[..., None], axis=1)
        d_cmat = jnp.sum(states * gl[..., None], axis=1)
        d_a = d_a + jnp.sum(w * dl[..., None], axis=0)
        return (dh[0], d_a), (d_c, d_delta, d_b, d_cmat)

    zeros = jnp.zeros(a.shape, F32)
    (_, d_a), grads = lax.scan(
        one_chunk, (zeros, zeros),
        (*(_chunks(x, chunk) for x in (c, delta, delta_next, bmat, cmat, g)),
         starts),
        reverse=True,
    )
    d_skip = jnp.sum(g.astype(F32) * c.astype(F32), axis=0)
    return tuple(
        d.reshape(x.shape).astype(x.dtype)
        for d, x in zip((*grads[:2], d_a, *grads[2:], d_skip), residuals)
    )


_scan.defvjp(_forward, _backward)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_scan(c, delta, a, bmat, cmat, dskip, tokens: int, interpret: bool):
    return _kernel_forward(c, delta, a, bmat, cmat, dskip, tokens, interpret)[0]


@jax.named_scope("ssm_scan")
def _kernel_forward(c, delta, a, bmat, cmat, dskip, tokens, interpret):
    y, starts = scan_kernel.scan_forward(
        c, delta, a, bmat, cmat, dskip, tokens, interpret
    )
    return y.astype(c.dtype), (c, delta, a, bmat, cmat, dskip, starts)


@jax.named_scope("ssm_scan")
def _kernel_backward(tokens, interpret, residuals, g):
    *inputs, starts = residuals
    grads = scan_kernel.scan_backward(*inputs, starts, g, tokens, interpret)
    return tuple(d.astype(x.dtype) for d, x in zip(grads, inputs))


_kernel_scan.defvjp(_kernel_forward, _kernel_backward)


def selective_scan(
    c: jnp.ndarray,
    delta: jnp.ndarray,
    a: jnp.ndarray,
    bmat: jnp.ndarray,
    cmat: jnp.ndarray,
    dskip: jnp.ndarray,
    impl: str = "auto",
    chunk: int = CHUNK,
) -> jnp.ndarray:
    """``s`` of the recurrence in the module's docstring. ``c``,
    ``delta``: ``[..., S, D]``; ``a``: ``[D, N]`` (negative);
    ``bmat``, ``cmat``: ``[..., S, N]``; ``dskip``: ``[D]``. Returns
    ``[..., S, D]`` in ``c.dtype``; differentiable in all six.

    ``impl="auto"`` runs the Pallas kernels on a TPU and the XLA form
    elsewhere (``"kernel"`` / ``"xla"`` force one; the kernels off a TPU
    run in Pallas's emulator, for tests). XLA form: ``chunk`` tokens'
    states are held at once. Any ``S`` and ``D``: tokens and channels are
    padded with ones that change nothing."""
    if impl not in ("auto", "kernel", "xla"):
        raise ValueError(
            f"selective_scan impl must be 'auto', 'kernel' or 'xla'; got {impl!r}"
        )
    if impl == "auto":
        impl = "kernel" if compat.on_tpu() else "xla"
    s, d = c.shape[-2:]
    if impl == "kernel":
        tokens = scan_kernel.TOKENS
        pad_d = -d % scan_kernel.CHANNEL_BLOCK
        scan = partial(
            _kernel_scan, tokens=tokens, interpret=compat.pallas_interpret(None)
        )
    else:
        tokens = min(chunk, s)
        pad_d = 0
        scan = partial(_scan, chunk=tokens)
    pad_s = -s % tokens
    if pad_d:
        # Channels that stay at zero (a's padding only has to be finite).
        channels = lambda x: jnp.pad(  # noqa: E731
            x, [(0, 0)] * (x.ndim - 1) + [(0, pad_d)]
        )
        c, delta, dskip = channels(c), channels(delta), channels(dskip)
        a = jnp.pad(a, ((0, pad_d), (0, 0)), constant_values=-1.0)

    def one_sequence(c, delta, bmat, cmat):
        if pad_s:
            # delta = 0 and c = 0: the state passes through unchanged.
            c, delta, bmat, cmat = (
                jnp.pad(x, ((0, pad_s), (0, 0))) for x in (c, delta, bmat, cmat)
            )
        return scan(c, delta, a, bmat, cmat, dskip)[:s, :d]

    fn = one_sequence
    for _ in c.shape[:-2]:
        fn = jax.vmap(fn)
    return fn(c, delta, bmat, cmat)
