"""The selective scan as Pallas TPU kernels — the state never leaves
the chip's vector memory.

:mod:`tpfl.parallel.selective_scan` in plain XLA moves a chunk's
``[chunk, D, N]`` states through HBM several times (a ``while`` loop's
carry lives in HBM): ~1% of the recurrence's bytes roofline on a v5e
(PERF.md §5, PR 27). Here the state of 1024 channels x N is a loop
carry in vector registers and HBM sees only the inputs and outputs.

Layout. A token's ``D`` channels are cut into blocks of 1024 = one
``(8, 128)`` float32 tile; the state of a block is ``N`` such tiles.
``B_t[n]`` and ``C_t[n]`` are SCALARS of the recurrence (one per token
and state), so they live in scalar memory and multiply a tile as
scalars — there is no transpose or lane broadcast anywhere. Grid:
``(channel blocks, chunks of T tokens)``; the chunk axis is sequential
and carries the state in scratch.

- forward: per token ``h_n = exp(delta a_n) h_n + (delta c) B[t, n]``,
  ``y += h_n C[t, n]``; writes ``y`` and each chunk's STARTING state;
- backward: chunks in reverse. A chunk's states are recomputed from
  its starting state into a ``[T + 1, N, 8, 128]`` scratch, then the
  tokens are walked backwards with the adjoint as the carry. The
  channel sums of ``dB`` and ``dC`` are reduced over sublanes only and
  leave as 128-lane partials (``[S, N, 128]`` a channel block); XLA
  finishes them, a full cross-lane reduction a token and state being
  the one thing this loop cannot afford.

Everything is float32 inside. ``jax.vmap`` batches the kernels by a
leading grid axis (the engine's vmap over silos, the batch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
SUBLANES, LANES = 8, 128
CHANNEL_BLOCK = SUBLANES * LANES
#: Tokens a grid step. The backward's recomputed states take
#: ``(T + 1) x N x 4 KiB`` of vector memory (8.1 MiB at 128, N = 16).
TOKENS = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile_spec(rows: int, index_map):
    """``rows`` leading entries of an array whose last two dimensions are
    ``[D / 128, 128]``, one channel block (8 x 128) of them."""
    return pl.BlockSpec((rows, SUBLANES, LANES), index_map)


def _forward_kernel(
    c_ref, delta_ref, at_ref, dskip_ref, b_ref, cm_ref, y_ref, start_ref,
    h_scr, *, tokens: int, states: int,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    start_ref[0] = h_scr[...]
    dskip = dskip_ref[0]

    def token(t, h):
        dt, ct = delta_ref[t], c_ref[t]
        u, y, new = dt * ct, dskip * ct, []
        for n in range(states):
            hn = jnp.exp(dt * at_ref[n]) * h[n] + u * b_ref[t, n]
            y = y + hn * cm_ref[t, n]
            new.append(hn)
        y_ref[t] = y
        return tuple(new)

    h = lax.fori_loop(
        0, tokens, token, tuple(h_scr[n] for n in range(states))
    )
    for n in range(states):
        h_scr[n] = h[n]


def _backward_kernel(
    c_ref, delta_ref, at_ref, dskip_ref, b_ref, cm_ref, g_ref, start_ref,
    dnext_ref,
    dc_ref, ddelta_ref, db_ref, dcm_ref, da_ref,
    hs_scr, dh_scr, da_scr, *, tokens: int, states: int,
):
    first = pl.program_id(1) == 0  # the LAST chunk of the sequence

    @pl.when(first)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_scr[...] = jnp.zeros_like(da_scr)

    # Recompute: hs[t + 1] is the state after token t, hs[0] before it.
    hs_scr[0] = start_ref[0]

    def recompute(t, h):
        dt = delta_ref[t]
        u, new = dt * c_ref[t], []
        for n in range(states):
            hn = jnp.exp(dt * at_ref[n]) * h[n] + u * b_ref[t, n]
            hs_scr[t + 1, n] = hn
            new.append(hn)
        return tuple(new)

    lax.fori_loop(
        0, tokens, recompute, tuple(start_ref[0, n] for n in range(states))
    )
    dskip = dskip_ref[0]

    def token(i, carry):
        """``carry[n]``: the adjoint that reaches h_t from token t + 1 on,
        NOT yet decayed; ``dn``: the delta of token t + 1."""
        t = tokens - 1 - i
        dn, arriving = carry
        dt, ct, g = delta_ref[t], c_ref[t], g_ref[t]
        u = dt * ct
        d_u = jnp.zeros_like(u)
        d_dt = jnp.zeros_like(u)
        new = []
        for n in range(states):
            a_row = at_ref[n]
            dh = jnp.exp(dn * a_row) * arriving[n] + g * cm_ref[t, n]
            h_now, h_before = hs_scr[t + 1, n], hs_scr[t, n]
            w = dh * jnp.exp(dt * a_row) * h_before
            d_dt = d_dt + w * a_row
            da_scr[n] = da_scr[n] + w * dt
            d_u = d_u + dh * b_ref[t, n]
            db_ref[t, n:n + 1, :] = jnp.sum(dh * u, axis=0, keepdims=True)
            dcm_ref[t, n:n + 1, :] = jnp.sum(g * h_now, axis=0, keepdims=True)
            new.append(dh)
        dc_ref[t] = d_u * dt + dskip * g
        ddelta_ref[t] = d_dt + d_u * ct
        return dt, tuple(new)

    dn, dh = lax.fori_loop(
        0, tokens, token,
        (dnext_ref[0], tuple(dh_scr[n] for n in range(states))),
    )
    for n in range(states):
        dh_scr[n] = dh[n]
    da_ref[...] = da_scr[...]


def _shapes(c, a, tokens):
    s, d = c.shape
    n = a.shape[1]
    if d % CHANNEL_BLOCK or s % tokens:
        raise ValueError(
            f"scan kernel: {d} channels must be a multiple of "
            f"{CHANNEL_BLOCK} and {s} tokens of {tokens} (the caller pads)"
        )
    return s, d, n, d // CHANNEL_BLOCK, s // tokens


def _tiles(x):
    """``[..., D] -> [..., D / 128, 128]`` float32 (a free reshape)."""
    return x.astype(F32).reshape(*x.shape[:-1], -1, LANES)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def scan_forward(c, delta, a, bmat, cmat, dskip, tokens: int, interpret: bool):
    """One sequence: ``c, delta [S, D]``, ``a [D, N]``, ``bmat, cmat
    [S, N]``, ``dskip [D]`` -> ``(y [S, D] float32, chunk-start states
    [S / tokens, N, D / 128, 128])``."""
    s, d, n, blocks, chunks = _shapes(c, a, tokens)
    smem = pl.BlockSpec(
        (tokens, n), lambda i, j: (j, 0), memory_space=pltpu.SMEM
    )
    y, starts = pl.pallas_call(
        functools.partial(_forward_kernel, tokens=tokens, states=n),
        out_shape=[
            jax.ShapeDtypeStruct((s, d // LANES, LANES), F32),
            jax.ShapeDtypeStruct((chunks, n, d // LANES, LANES), F32),
        ],
        grid=(blocks, chunks),
        in_specs=[
            _tile_spec(tokens, lambda i, j: (j, i, 0)),
            _tile_spec(tokens, lambda i, j: (j, i, 0)),
            _tile_spec(n, lambda i, j: (0, i, 0)),
            _tile_spec(1, lambda i, j: (0, i, 0)),
            smem, smem,
        ],
        out_specs=[
            _tile_spec(tokens, lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, n, SUBLANES, LANES), lambda i, j: (j, 0, i, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((n, SUBLANES, LANES), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_forward",
    )(
        _tiles(c), _tiles(delta), _tiles(a.T), _tiles(dskip[None]),
        bmat.astype(F32), cmat.astype(F32),
    )
    return y.reshape(s, d), starts


def scan_backward(
    c, delta, a, bmat, cmat, dskip, starts, g, tokens: int, interpret: bool
):
    """The six gradients (float32) for one sequence, from the forward's
    inputs, its chunk-start states and the result's gradient ``g``."""
    s, d, n, blocks, chunks = _shapes(c, a, tokens)
    last = chunks - 1
    rev = lambda i, j: (last - j, i, 0)  # noqa: E731
    smem = pl.BlockSpec(
        (tokens, n), lambda i, j: (last - j, 0), memory_space=pltpu.SMEM
    )
    # The delta of the token after each chunk's last (0 after the end).
    after = jnp.concatenate([delta[tokens::tokens], jnp.zeros_like(delta[:1])])
    partial = jax.ShapeDtypeStruct((blocks, s, n, LANES), F32)
    partial_spec = pl.BlockSpec(
        (None, tokens, n, LANES), lambda i, j: (i, last - j, 0, 0)
    )
    dc, ddelta, db, dcm, da = pl.pallas_call(
        functools.partial(_backward_kernel, tokens=tokens, states=n),
        out_shape=[
            jax.ShapeDtypeStruct((s, d // LANES, LANES), F32),
            jax.ShapeDtypeStruct((s, d // LANES, LANES), F32),
            partial, partial,
            jax.ShapeDtypeStruct((n, d // LANES, LANES), F32),
        ],
        grid=(blocks, chunks),
        in_specs=[
            _tile_spec(tokens, rev), _tile_spec(tokens, rev),
            _tile_spec(n, lambda i, j: (0, i, 0)),
            _tile_spec(1, lambda i, j: (0, i, 0)),
            smem, smem,
            _tile_spec(tokens, rev),
            pl.BlockSpec(
                (1, n, SUBLANES, LANES), lambda i, j: (last - j, 0, i, 0)
            ),
            _tile_spec(1, rev),
        ],
        out_specs=[
            _tile_spec(tokens, rev), _tile_spec(tokens, rev),
            partial_spec, partial_spec,
            _tile_spec(n, lambda i, j: (0, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((tokens + 1, n, SUBLANES, LANES), F32),
            pltpu.VMEM((n, SUBLANES, LANES), F32),
            pltpu.VMEM((n, SUBLANES, LANES), F32),
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="ssm_scan_backward",
    )(
        _tiles(c), _tiles(delta), _tiles(a.T), _tiles(dskip[None]),
        bmat.astype(F32), cmat.astype(F32), _tiles(g), starts, _tiles(after),
    )
    d_skip = jnp.sum(g.astype(F32) * c.astype(F32), axis=0)
    return (
        dc.reshape(s, d), ddelta.reshape(s, d), da.reshape(n, d).T,
        jnp.sum(db, axis=(0, 3)), jnp.sum(dcm, axis=(0, 3)), d_skip,
    )
