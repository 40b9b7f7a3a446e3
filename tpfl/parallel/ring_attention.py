"""Ring attention — sequence/context parallelism over a mesh axis.

Green-field TPU capability (SURVEY §5.7: the reference has no attention
models and no sequence parallelism of any kind). Long sequences shard
over a ``sp`` mesh axis: every device holds one block of Q, K and V;
K/V blocks rotate around the ring with ``jax.lax.ppermute`` (one hop
per step, riding ICI) while each device accumulates its Q block's
attention with a numerically-stable online softmax (the
log-sum-exp-carrying accumulation of Liu et al. 2023 "Ring Attention
with Blockwise Transformers" / Milakov & Gimelshein 2018). No device
ever materializes the full [S, S] score matrix or the full K/V.

Memory per device: O(S/n · d) activations; the default flash inner
(``impl="flash"``) keeps score tiles in VMEM (Pallas kernel per ring
step, ring-level recompute VJP — see the flash-ring notes below), the
``impl="xla"`` fallback materializes O((S/n)²) scores per step. A 128k
sequence on 8 devices attends with 16k-sized local blocks either way.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpfl.parallel import compat
from tpfl.parallel.compat import shard_map


def _mm(spec: str, a, b):
    """A tile matmul on its operands in the dtype they arrive in, with a
    float32 accumulator (``flash_kernel._dot``'s rule): float32 copies of
    bf16 operands buy no precision — at default precision the MXU
    multiplies float32 operands in bf16 anyway. float32 operands (the
    exactness tests) compute in float32 throughout."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _scores(q, k):
    """``q k^T`` of a block pair as float32, ROUNDED to the inputs' dtype
    on its way (the einsum's own output): on the v5e a bf16 score tile
    written and read back beats the float32 accumulator taken directly —
    2.4x on the forward at GPT-2's shapes (PERF.md §6, PR 28), where a
    float32 tile over four silos no longer fits the fast memory."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)


def _block_attend(q, k, v, acc, row_max, denom, mask):
    """Fold one K/V block into the running (acc, row_max, denom).

    q: [B, Lq, H, D], k/v: [B, Lk, H, D]; mask: [Lq, Lk] boolean or
    None. Online softmax: rescale previous accumulators by
    exp(old_max - new_max), add this block's exp-weighted values. P is
    rounded to the values' dtype for its matmul alone (the denominator
    sums the float32 P) — the standard flash recipe.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1])
    # [B, H, Lq, Lk]
    scores = _scores(q, k) * scale
    if mask is not None:
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    block_max = jnp.max(scores, axis=-1)  # [B, H, Lq]
    new_max = jnp.maximum(row_max, block_max)
    # exp(-inf - -inf) guards: rows with no visible keys yet keep -inf.
    correction = jnp.exp(jnp.where(row_max == -jnp.inf, -jnp.inf, row_max - new_max))
    p = jnp.exp(scores - new_max[..., None])  # [B, H, Lq, Lk]
    p = jnp.where(jnp.isnan(p), 0.0, p)  # -inf - -inf rows
    acc = acc * correction[..., None] + _mm(
        "bhqk,bkhd->bhqd", p.astype(v.dtype), v
    )
    denom = denom * correction + jnp.sum(p, axis=-1)
    return acc, new_max, denom


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block_size: Optional[int] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Single-device flash-style attention, blocked over BOTH queries
    and keys: peak score memory is O(block²) per (batch, head), never
    O(S²) or O(S·block). The causal inner loop's trip count is the
    query block index + 1, so fully-masked future K/V blocks are never
    computed (≈2× fewer FLOPs). q: [B, S, Hq, D], k: [B, S, Hkv, D],
    v: [B, S, Hkv, Dv] -> [B, S, Hq, Dv].

    **Grouped key/value heads**: ``Hq`` may be a multiple of ``Hkv``;
    query head ``h`` reads key/value head ``h // (Hq // Hkv)``. A
    group's query heads meet their key/value head's block together, as
    extra query ROWS of one tile, so the block loop and its matmuls are
    the equal-head ones with taller tiles: the kernels stack them in
    VMEM from where they lie, lanes of ``[B, S, Hq * D]``; the XLA loop
    lays them side by side in HBM first (``_grouped_rows``, a
    transpose). **Value width**: ``Dv`` need
    not be ``D``. **Band**: ``window`` (causal only) keeps the keys with
    ``0 <= q - k < window``; key blocks wholly outside the band are
    skipped on both sides. ``k`` / ``v`` may come from anywhere (another
    layer's projections): nothing here assumes they were made with ``q``.

    Differentiable with a RECOMPUTE backward (``jax.custom_vjp``): the
    forward banks only the output and per-row logsumexp; the backward
    re-derives P = exp(S - lse) block by block in ONE sweep that visits
    each visible block pair once and feeds dq, dk and dv from the same
    P and dS (FlashAttention-2's VJP). Reverse-mode through the
    forward's scan would instead stash O(S·block) score residuals per
    step, which at 32k tokens produced a program the TPU compiler could
    not build (pre-PR-1, not re-measured).

    **Two forms of the one block loop**, chosen by what can be observed
    (``_kernels``): on a TPU, at blocks that are whole 128-lane tiles
    (``flash_kernel.tiles``: only what was compiled for a TPU), it runs
    as the Pallas kernels of :mod:`tpfl.parallel.flash_kernel`, with a
    band too — a pair's score tile, P, dP, dS and the running
    accumulators stay in VMEM; no operand is transposed or padded.
    Everywhere else — a short sequence that is one block of its own
    unaligned length too — it is the XLA loop below
    (``_blockwise_fwd_core`` / ``_blockwise_vjp_bwd``, a few key heads a
    backward step), which is also what the kernels are tested against.
    Both run under the named scope ``block_attention`` inside ONE
    ``custom_vjp`` that takes q and returns out and dq in the layout the
    caller holds them in; padding, the row order of the banked lse and
    what else the forward banks are the same.

    **The block** a call that names none gets is the largest of 512 /
    256 / 128 that the kernels admit for its shapes
    (``_default_block``): a function of shapes alone, the same on every
    backend.

    The tile matmuls (``P V``, ``dO V^T``, ``dS K``, ``dS^T Q``,
    ``P^T dO``) read P and dS rounded to the inputs' dtype beside q, k,
    v and dO as they arrive, and accumulate in float32; the running max,
    denominator, ``acc``, lse and delta are float32. float32 inputs
    compute in float32 throughout."""
    s, hq = q.shape[1:3]
    hkv = k.shape[2]
    groups = hq // hkv
    if groups * hkv != hq or v.shape[2] != hkv:
        raise ValueError(
            f"query heads ({hq}) must be a multiple of the key heads "
            f"({hkv}), and values must have the keys' heads ({v.shape[2]})"
        )
    if window is not None and not causal:
        raise ValueError("a band (window) is causal: pass causal=True")
    if window is not None and window >= s:
        window = None  # every key behind a query is inside the band
    block = block_size or _default_block(s, k, v, groups)
    n_blocks = -(-s // block)
    pad = n_blocks * block - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return _blockwise(q, k, v, causal, block, s, groups, window)[:, :s]


def _grouped_rows(x, block: int, groups: int):
    """``[B, nb * block, H * G, D] -> [B, nb * G * block, H, D]``: a key
    head's ``G`` query heads side by side as extra ROWS of its block (a
    transpose: the XLA loop's layout alone)."""
    if groups == 1:
        return x
    b, sp, hq, d = x.shape
    x = x.reshape(b, sp // block, block, hq // groups, groups, d)
    return jnp.moveaxis(x, 4, 2).reshape(b, -1, hq // groups, d)


def _natural_rows(x, block: int, groups: int):
    """``_grouped_rows`` undone."""
    if groups == 1:
        return x
    b, _, h, d = x.shape
    x = x.reshape(b, -1, groups, block, h, d)
    return jnp.moveaxis(x, 2, 4).reshape(b, -1, h * groups, d)


def _bw_mask(q_idx, k_idx, s_len: int, causal: bool, window=None):
    mask = jnp.broadcast_to(
        k_idx[None, :] < s_len, (q_idx.shape[0], k_idx.shape[0])
    )
    if causal:
        mask = mask & (q_idx[:, None] >= k_idx[None, :])
    if window is not None:
        mask = mask & (q_idx[:, None] - k_idx[None, :] < window)
    return mask


def _band_reach(block: int, window: int) -> int:
    """How many key blocks behind its own a query block's band reaches:
    the nearest pair of rows of blocks i and j is
    ``(i - j - 1) * block + 1`` apart."""
    return -(-(window - 1) // block)


def _in_band(pred, i, j, block: int, window):
    """``pred`` (block j is not in query block i's future) narrowed to
    the key blocks that reach into the band."""
    if window is None:
        return pred
    return pred & (i - j <= _band_reach(block, window))


def _query_rows(local_idx, groups: int):
    """Sequence offsets of a query block's rows: a group's heads lie
    side by side as ``groups`` runs of the block's positions."""
    return jnp.tile(local_idx, groups) if groups > 1 else local_idx


def _kernels(k, v, block: int, groups: int) -> bool:
    """Whether the block loop runs as the Pallas kernels of
    :mod:`tpfl.parallel.flash_kernel`: on a TPU, at the shapes they were
    compiled for."""
    if not compat.on_tpu():
        return False
    from tpfl.parallel import flash_kernel

    return flash_kernel.tiles(
        k.shape, v.shape[-1], block, groups, k.dtype.itemsize
    )


def _default_block(s: int, k, v, groups: int) -> int:
    """The block of a call that names none: the largest of 512 / 256 /
    128 (a shorter sequence is one block) that the kernels admit for the
    call's shapes (``flash_kernel.tiles``), 512 where they admit none. A
    function of shapes alone, so the same on every backend: with 8 query
    heads a key head of 128 a 512-block's float32 tile is 8 MB and the
    block is 256; equal heads and pairs of heads keep 512."""
    from tpfl.parallel import flash_kernel

    for block in (512, 256, 128):
        block = min(s, block)
        padded = (k.shape[0], -(-s // block) * block, *k.shape[2:])
        if flash_kernel.tiles(
            padded, v.shape[-1], block, groups, k.dtype.itemsize
        ):
            return block
    return min(s, 512)


@jax.named_scope("block_attention")
def _blockwise_fwd_core(
    q, k, v, causal: bool, block: int, s_len: int, groups: int = 1,
    window=None,
):
    """Padded k [B, nb·block, H, D], v [.., Dv] and q [B, nb·block, H·G,
    D] -> (out [B, nb·block, H·G, Dv], lse [B, H, nb·rows]) with ``rows
    = groups·block`` query rows a block: lse is in the block loop's own
    row order, a key head's ``groups`` query heads one after the other
    within a block. The kernels read q's heads where they lie, as lanes;
    the XLA loop lays them side by side as rows first
    (``_grouped_rows``). lse rows with no visible key get +LARGE so the
    backward's exp(s - lse) is exactly 0 for them."""
    b, sp, h, d = k.shape
    d_v = v.shape[-1]
    n_blocks = sp // block
    if _kernels(k, v, block, groups):
        from tpfl.parallel import flash_kernel

        return flash_kernel.attention_forward(
            q, k, v, causal=causal, block=block, s_len=s_len, groups=groups,
            window=window, interpret=compat.pallas_interpret(None),
        )
    q = _grouped_rows(q, block, groups)
    rows = groups * block
    qb = q.reshape(b, n_blocks, rows, h, d)
    kb = k.reshape(b, n_blocks, block, h, d)
    vb = v.reshape(b, n_blocks, block, h, d_v)
    local_idx = jnp.arange(block)
    q_local = _query_rows(local_idx, groups)

    def per_q_block(i):
        q_i = qb[:, i]
        q_idx = i * block + q_local

        def body(j, carry):
            def attend(c):
                k_j = jax.lax.dynamic_index_in_dim(kb, j, axis=1, keepdims=False)
                v_j = jax.lax.dynamic_index_in_dim(vb, j, axis=1, keepdims=False)
                k_idx = j * block + local_idx
                mask = _bw_mask(q_idx, k_idx, s_len, causal, window)
                return _block_attend(q_i, k_j, v_j, *c, mask)

            if causal:
                # Blocks above the diagonal (and, with a band, those
                # wholly behind it) are fully masked: cond skips their
                # compute at runtime.
                visible = _in_band(j <= i, i, j, block, window)
                return jax.lax.cond(visible, attend, lambda c: c, carry)
            return attend(carry)

        acc = jnp.zeros((b, h, rows, d_v), jnp.float32)
        row_max = jnp.full((b, h, rows), -jnp.inf, jnp.float32)
        denom = jnp.zeros((b, h, rows), jnp.float32)
        acc, row_max, denom = jax.lax.fori_loop(
            0, n_blocks, body, (acc, row_max, denom)
        )
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        lse = jnp.where(
            denom > 0, row_max + jnp.log(jnp.maximum(denom, 1e-30)), 1e30
        )  # [B, H, block]
        return jnp.moveaxis(out, 1, 2), lse  # [B, block, H, D], [B,H,block]

    blocks, lses = jax.lax.map(per_q_block, jnp.arange(n_blocks))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, n_blocks * rows, h, d_v)
    # lses: [nb, B, H, rows] -> [B, H, nb, rows] -> [B, H, S']
    lse = jnp.moveaxis(lses, 0, 2).reshape(b, h, n_blocks * rows)
    return _natural_rows(out.astype(q.dtype), block, groups), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _blockwise(
    q, k, v, causal: bool, block: int, s_len: int, groups: int = 1,
    window=None,
):
    out, _ = _blockwise_fwd_core(q, k, v, causal, block, s_len, groups, window)
    return out


def _blockwise_vjp_fwd(q, k, v, causal, block, s_len, groups, window):
    out, lse = _blockwise_fwd_core(
        q, k, v, causal, block, s_len, groups, window
    )
    return out, (q, k, v, out, lse)


# A step of the backward works on float32 score tiles [B, heads, rows,
# block] of one block pair — S / P, dP and dS together, where the
# two-sweep form it replaced held one at a time. Measured on the v5e
# (PERF.md §6, PR 28): a step whose tiles outgrow the chip's fast memory
# sends dS through HBM, and one sweep then LOSES to two (GPT-2's cell,
# all twelve heads a step: 5.25 ms a call against 4.76; three heads a
# step: 4.03). So a step takes the most key heads whose float32 tile is
# within this many bytes. That is the tile this function can see: under
# the engine's vmap it is as many times larger as the chip holds silos.
_STEP_TILE_BYTES = 12 << 20


def _heads_per_step(b: int, h: int, rows: int, block: int) -> int:
    """The most key heads, a divisor of ``h``, whose float32 score tile
    ``[b, heads, rows, block]`` is within ``_STEP_TILE_BYTES`` (one head
    where none is)."""
    head_bytes = 4 * b * rows * block
    return max(
        n for n in range(1, h + 1)
        if h % n == 0 and (n == 1 or n * head_bytes <= _STEP_TILE_BYTES)
    )


@jax.named_scope("block_attention")
def _blockwise_vjp_bwd(causal, block, s_len, groups, window, res, g):
    """dq, dk, dv of padded q / k / v (``_blockwise_fwd_core``'s shapes)
    from the forward's ``out`` and ``lse`` and the cotangent ``g``: the
    kernels' backward where ``_kernels`` takes the shape, else, on
    grouped rows, a flash-style recompute backward in ONE sweep
    (FlashAttention-2's, at the XLA level). Each visible (query block i, key block j) pair is
    visited once, ``_heads_per_step`` key heads a step (heads never mix):
    S, P = exp(S - lse), dP and dS are computed once and feed all three of
    dQ, dK, dV — 5 tile matmuls a step. The outer loop runs over key
    blocks and carries ``dk_j`` / ``dv_j``; ``dq`` grows in its whole
    float32 buffer by slice-add. Peak transient is O(block²) per (batch,
    head) — no stored residuals, and no array the size of q / k / v
    besides the three gradients.

    The inner loop's BOUNDS are the causal / band limits, so invisible
    pairs are never entered and the ``dq`` buffer never passes through a
    ``cond`` (whose identity branch would copy it). Nothing
    differentiates through this function, so dynamic bounds are free."""
    q, k, v, out, lse = res
    b, sp, h, d = k.shape
    d_v = v.shape[-1]
    n_blocks = sp // block
    rows = groups * block
    if _kernels(k, v, block, groups):
        from tpfl.parallel import flash_kernel

        interpret = compat.pallas_interpret(None)
        delta = flash_kernel.delta_rows(g, out, block, groups, interpret)
        return flash_kernel.attention_backward(
            q, k, v, g, lse, delta, causal=causal, block=block, s_len=s_len,
            groups=groups, window=window, interpret=interpret,
        )
    q, out, g = (_grouped_rows(x, block, groups) for x in (q, out, g))
    delta = jnp.moveaxis(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1),
        1, 2,
    )  # [B, H, S']
    heads = _heads_per_step(b, h, rows, block)
    chunks = h // heads
    scale = 1.0 / jnp.sqrt(d)
    qb = q.reshape(b, n_blocks, rows, h, d)
    kb = k.reshape(b, n_blocks, block, h, d)
    vb = v.reshape(b, n_blocks, block, h, d_v)
    gb = g.reshape(b, n_blocks, rows, h, d_v)
    lse_b = lse.reshape(b, h, n_blocks, rows)
    delta_b = delta.reshape(b, h, n_blocks, rows)
    local_idx = jnp.arange(block)
    q_local = _query_rows(local_idx, groups)
    block_of = partial(jax.lax.dynamic_index_in_dim, keepdims=False)
    heads_of = partial(jax.lax.dynamic_slice_in_dim, slice_size=heads)
    put_heads = jax.lax.dynamic_update_slice_in_dim

    def key_block(dq, j):
        k_j = block_of(kb, j, axis=1)
        v_j = block_of(vb, j, axis=1)
        k_idx = j * block + local_idx

        def step(t, carry):
            dq, dk, dv = carry
            i, h0 = jax.lax.div(t, chunks), jax.lax.rem(t, chunks) * heads
            q_i = heads_of(block_of(qb, i, axis=1), h0, axis=2)
            g_i = heads_of(block_of(gb, i, axis=1), h0, axis=2)
            k_c = heads_of(k_j, h0, axis=2)
            v_c = heads_of(v_j, h0, axis=2)
            lse_i = heads_of(block_of(lse_b, i, axis=2), h0, axis=1)
            delta_i = heads_of(block_of(delta_b, i, axis=2), h0, axis=1)
            s_ij = _scores(q_i, k_c) * scale
            mask = _bw_mask(i * block + q_local, k_idx, s_len, causal, window)
            p = jnp.where(
                mask[None, None], jnp.exp(s_ij - lse_i[..., None]), 0.0
            )
            dp = _mm("bqhd,bkhd->bhqk", g_i, v_c)
            ds = p * (dp - delta_i[..., None]) * scale
            dv_c = _mm("bhqk,bqhd->bkhd", p.astype(g.dtype), g_i)
            dk_c = _mm("bhqk,bqhd->bkhd", ds.astype(q.dtype), q_i)
            dq_i = _mm("bhqk,bkhd->bqhd", ds.astype(k.dtype), k_c)
            dv = put_heads(dv, dv_c + heads_of(dv, h0, axis=2), h0, axis=2)
            dk = put_heads(dk, dk_c + heads_of(dk, h0, axis=2), h0, axis=2)
            at = (0, i, 0, h0, 0)
            dq_i = dq_i[:, None] + jax.lax.dynamic_slice(
                dq, at, (b, 1, rows, heads, d)
            )
            return jax.lax.dynamic_update_slice(dq, dq_i, at), dk, dv

        # Query blocks that see key block j: from the diagonal down to
        # the band's reach; each in ``chunks`` steps.
        first = j if causal else 0
        last = (
            n_blocks if window is None
            else jnp.minimum(n_blocks, j + _band_reach(block, window) + 1)
        )
        dk = jnp.zeros((b, block, h, d), jnp.float32)
        dv = jnp.zeros((b, block, h, d_v), jnp.float32)
        dq, dk, dv = jax.lax.fori_loop(
            first * chunks, last * chunks, step, (dq, dk, dv)
        )
        return dq, (dk, dv)

    dq = jnp.zeros((b, n_blocks, rows, h, d), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(key_block, dq, jnp.arange(n_blocks))

    def unblk(x):
        return jnp.moveaxis(x, 0, 1).reshape(b, -1, *x.shape[3:])

    return (
        _natural_rows(dq.reshape(q.shape).astype(q.dtype), block, groups),
        unblk(dk).astype(k.dtype),
        unblk(dv).astype(v.dtype),
    )


_blockwise.defvjp(_blockwise_vjp_fwd, _blockwise_vjp_bwd)


def _ring_xla(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
) -> jnp.ndarray:
    """Pure-XLA ring inner (einsum over full local blocks): the
    reference implementation the flash ring is tested against, and the
    fallback when the Pallas path is unavailable. Differentiated by
    reverse-mode through the scan (stores per-step score residuals —
    fine at test scale, the flash ring's recompute VJP avoids it)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape

    q_pos = my * lq + jnp.arange(lq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        acc, row_max, denom, kt, vt = carry
        # At step t we hold the block that started on device (my - t).
        src = (my - t) % n
        k_pos = src * lq + jnp.arange(lq)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]

            def attend(c):
                return _block_attend(q, kt, vt, *c, mask)

            # Blocks entirely in the future are fully masked: cond
            # skips their einsums at runtime (~2x fewer FLOPs on
            # average) and stays differentiable.
            acc, row_max, denom = jax.lax.cond(
                src <= my, attend, lambda c: c, (acc, row_max, denom)
            )
        else:
            acc, row_max, denom = _block_attend(
                q, kt, vt, acc, row_max, denom, None
            )
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        return acc, row_max, denom, kt, vt

    acc = jnp.zeros((b, h, lq, d), jnp.float32)
    row_max = jnp.full((b, h, lq), -jnp.inf, jnp.float32)
    denom = jnp.zeros((b, h, lq), jnp.float32)
    acc, row_max, denom, _, _ = jax.lax.fori_loop(
        0, n, body, (acc, row_max, denom, k, v)
    )
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B, Lq, H, D]


# --- flash ring: Pallas flash kernel per ring step, recompute VJP ---
#
# Forward: each ring step attends the local Q block to the rotating
# K/V block with the Pallas forward kernel (flash_kernel.flash_block_fwd:
# the kernels blockwise_attention runs on a TPU, with a float32 output),
# and steps are merged by logsumexp:
#   lse' = logaddexp(lse, lse_t);  o' = o·e^{lse-lse'} + o_t·e^{lse_t-lse'}
# which is exactly the online-softmax accumulation at block
# granularity. Only (out, lse) carry across steps — no O(lq²) score
# memory at the XLA level.
#
# Backward (jax.custom_vjp): banks just (q, k, v, out, lse); recomputes
# per-step gradients with the one-sweep backward kernel fed the GLOBAL
# lse/delta (flash_kernel.flash_block_bwd), re-rotating K/V around the
# ring. dK/dV contributions accumulate in buffers that rotate WITH
# their K/V block, so after the full circle each block's gradient
# arrives back at its owner — the Liu et al. ring backward, with the
# inner math on the MXU. Residual memory is O(local block), where
# reverse-mode through the forward scan would stash O(n·block²).


def _ring_merge(o, lse, o_t, lse_t):
    """Fold one ring step's (o_t, lse_t) into the running (o, lse).
    o/o_t: [B, Lq, H, D] (o f32); lse/lse_t: [B, H, Lq] f32."""
    new = jnp.logaddexp(lse, lse_t)
    a = jnp.moveaxis(jnp.exp(lse - new), 1, 2)[..., None]
    b_ = jnp.moveaxis(jnp.exp(lse_t - new), 1, 2)[..., None]
    return o * a + o_t.astype(jnp.float32) * b_, new


def _ring_flash_fwd_core(q, k, v, axis_name, causal, block, interpret):
    from tpfl.parallel.flash_kernel import flash_block_fwd

    n = jax.lax.psum(1, axis_name)
    # axis_index only when the causal masking actually consumes it: the
    # non-causal ring otherwise lowers a DEAD partition-id op inside
    # the (un-DCE'd) custom_vjp call jaxpr, and XLA's SPMD sharding
    # propagation — which flows from USERS — never marks a user-less
    # instruction {manual}, so the partitioner rejects the whole
    # sharded program ("PartitionId instruction is not supported").
    my = jax.lax.axis_index(axis_name) if causal else None
    b, lq, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(c, kt, vt, diag):
        o_t, lse_t = flash_block_fwd(
            q, kt, vt, causal=diag, block=block, interpret=interpret
        )
        return _ring_merge(*c, o_t, lse_t)

    def body(t, carry):
        o, lse, kt, vt = carry
        if causal:
            src = (my - t) % n
            # Diagonal step: causal within the block. Earlier blocks:
            # full attention. Future blocks: skipped at runtime.
            o, lse = jax.lax.cond(
                src == my,
                lambda c: attend(c, kt, vt, True),
                lambda c: jax.lax.cond(
                    src < my,
                    lambda cc: attend(cc, kt, vt, False),
                    lambda cc: cc,
                    c,
                ),
                (o, lse),
            )
        else:
            o, lse = attend((o, lse), kt, vt, False)
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        return o, lse, kt, vt

    o = jnp.zeros((b, lq, h, d), jnp.float32)
    lse = jnp.full((b, h, lq), -jnp.inf, jnp.float32)
    o, lse, _, _ = jax.lax.fori_loop(0, n, body, (o, lse, k, v))
    return o.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name: str, causal: bool, block: int,
                interpret: bool):
    out, _ = _ring_flash_fwd_core(q, k, v, axis_name, causal, block, interpret)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, block, interpret):
    out, lse = _ring_flash_fwd_core(
        q, k, v, axis_name, causal, block, interpret
    )
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, block, interpret, res, g):
    from tpfl.parallel.flash_kernel import flash_block_bwd

    q, k, v, out, lse = res
    n = jax.lax.psum(1, axis_name)
    # Same dead-partition-id guard as the forward core above.
    my = jax.lax.axis_index(axis_name) if causal else None
    perm = [(i, (i + 1) % n) for i in range(n)]
    g32 = g.astype(jnp.float32)
    delta = jnp.einsum(
        "bshd,bshd->bhs", g32, out.astype(jnp.float32)
    )  # [B, H, Lq]

    def contrib(kt, vt, diag):
        return flash_block_bwd(
            q, kt, vt, g, lse, delta, causal=diag, block=block,
            interpret=interpret,
        )

    def add(c, kt, vt, diag):
        dq, dkt, dvt = c
        dq_c, dk_c, dv_c = contrib(kt, vt, diag)
        return (
            dq + dq_c.astype(jnp.float32),
            dkt + dk_c.astype(jnp.float32),
            dvt + dv_c.astype(jnp.float32),
        )

    def body(t, carry):
        dq, kt, vt, dkt, dvt = carry
        if causal:
            src = (my - t) % n
            dq, dkt, dvt = jax.lax.cond(
                src == my,
                lambda c: add(c, kt, vt, True),
                lambda c: jax.lax.cond(
                    src < my,
                    lambda cc: add(cc, kt, vt, False),
                    lambda cc: cc,
                    c,
                ),
                (dq, dkt, dvt),
            )
        else:
            dq, dkt, dvt = add((dq, dkt, dvt), kt, vt, False)
        # dK/dV accumulators rotate WITH their block: after the full
        # circle each block's gradient is back at its owner.
        kt = jax.lax.ppermute(kt, axis_name, perm)
        vt = jax.lax.ppermute(vt, axis_name, perm)
        dkt = jax.lax.ppermute(dkt, axis_name, perm)
        dvt = jax.lax.ppermute(dvt, axis_name, perm)
        return dq, kt, vt, dkt, dvt

    dq = jnp.zeros(q.shape, jnp.float32)
    dkv = jnp.zeros(k.shape, jnp.float32)
    dq, _, _, dk, dv = jax.lax.fori_loop(
        0, n, body, (dq, k, v, dkv, dkv)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    impl: str = "auto",
    block: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention INSIDE shard_map: q/k/v are the
    LOCAL sequence blocks [B, S/n, H, D] of a sequence sharded over
    ``axis_name``; K/V rotate the ring via ppermute. Returns the local
    output block.

    ``impl="auto"`` (default) picks the Pallas flash kernel per ring
    step with a ring-level recompute VJP (see module notes above) on
    TPU at local blocks the kernels take (``flash_kernel.tiles``: whole
    128-lane tiles), and the plain einsum inner elsewhere — Pallas
    interpret mode is an emulator, orders of magnitude slower than XLA
    at real sequence lengths, so non-TPU backends must not land on it
    by default. ``impl="flash"`` forces the kernel (interpret-mode off
    TPU — for exactness tests); ``impl="xla"`` forces the einsum inner
    (identical math)."""
    if impl not in ("auto", "flash", "xla"):
        # Explicit rejection — an unknown impl silently falling through
        # to the flash kernel would run interpret-mode Pallas off-TPU
        # (orders of magnitude slower) with no hint why.
        raise ValueError(
            f"ring_attention impl must be one of 'auto', 'flash', 'xla'; "
            f"got {impl!r}"
        )
    if impl == "auto":
        from tpfl.parallel import flash_kernel

        # A ring step's gradients leave the kernel in float32.
        fits = flash_kernel.tiles(
            k.shape, v.shape[-1],
            flash_kernel.ring_block_size(k.shape[1], block), grad_bytes=4,
        )
        impl = "flash" if compat.on_tpu() and fits else "xla"
    if impl == "xla":
        return _ring_xla(q, k, v, axis_name, causal)
    return _ring_flash(
        q, k, v, axis_name, causal, block,
        compat.pallas_interpret(interpret),
    )


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = False,
    impl: str = "auto",
    block: int = 1024,
):
    """shard_map-wrapped ring attention: takes GLOBAL [B, S, H, D]
    arrays sharded (or shardable) over ``axis_name`` on the sequence
    dimension, returns the global output with the same sharding."""
    if impl not in ("auto", "flash", "xla"):
        # Validate at build time, not inside the traced shard_map body,
        # so the error surfaces where the bad argument was written.
        raise ValueError(
            f"make_ring_attention impl must be one of 'auto', 'flash', "
            f"'xla'; got {impl!r}"
        )
    spec = PartitionSpec(None, axis_name, None, None)

    fn = shard_map(
        partial(
            ring_attention,
            axis_name=axis_name,
            causal=causal,
            impl=impl,
            block=block,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )

    baked_causal = causal
    sharding = NamedSharding(mesh, spec)
    jitted = jax.jit(fn)

    def apply(q, k, v, causal: Optional[bool] = None):
        # Causality is baked into the compiled program; accepting (and
        # validating) the kwarg lets this closure plug directly into
        # TransformerBlock's ``attention_fn(q, k, v, causal=...)`` seam
        # without silently attending the wrong way.
        if causal is not None and causal != baked_causal:
            raise ValueError(
                f"make_ring_attention was built with causal="
                f"{baked_causal}, called with causal={causal}"
            )
        return jitted(
            jax.device_put(q, sharding),
            jax.device_put(k, sharding),
            jax.device_put(v, sharding),
        )

    return apply
