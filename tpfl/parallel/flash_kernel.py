"""Flash attention as a Pallas TPU kernel — the hot-op fast path.

The pure-XLA :func:`tpfl.parallel.ring_attention.blockwise_attention`
is correct and fuses well; this kernel goes further: the online-softmax
accumulators for one query block live in VMEM scratch across the whole
K/V sweep (K/V stream through VMEM one block at a time — sequence
length is bounded by HBM, not by the ~16 MB VMEM), and the score
matmuls run on the MXU.

Grid: (batch·heads, query blocks, key blocks) — TPU executes the last
grid dimension sequentially on the same core, so scratch carries the
running (acc, max, denom) between key blocks; the first key block
initializes them and the last one writes the output block. Causal
programs above the diagonal skip all work via ``pl.when``.

Training: ``flash_attention`` carries a ``jax.custom_vjp`` with the
standard recompute-based flash backward (Dao et al.): the forward
additionally banks the per-query logsumexp L; the backward recomputes
P = exp(S - L) tile by tile and runs two kernels — dQ (query-block
grid, key sweep) and dK/dV (key-block grid, query sweep) — all matmuls
on the MXU, no S-sized tensor ever materialized in HBM.

``flash_attention`` interprets on CPU (tests) and compiles on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpfl.parallel import compat

_NEG_INF = -1e30  # large-negative instead of -inf: exp() stays exact, no NaNs


def _mm(a, b, dims):
    """MXU matmul at the operands' NATIVE dtype with f32 accumulation.
    bf16 inputs run the MXU at full rate; upcasting them to f32 first
    (the r4 kernels did) runs every score/grad matmul at the f32 rate —
    several times slower — for precision the f32 accumulator already
    provides. f32 inputs (exactness tests) still compute fully in f32."""
    if a.dtype != b.dtype:  # ring bwd: f32 cotangents, bf16 operands
        wide = jnp.promote_types(a.dtype, b.dtype)
        a, b = a.astype(wide), b.astype(wide)
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


def _lowp(ref):
    """The dtype f32 intermediates must be cast back to before feeding
    the next matmul: the ref's native dtype when it is low-precision
    (bf16 path — the standard flash recipe rounds P/dS to bf16), f32
    otherwise."""
    return ref.dtype if ref.dtype == jnp.bfloat16 else jnp.float32


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, block: int, causal: bool, scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = (qi >= ki) if causal else (ki >= 0)

    @pl.when(run)
    def _attend():
        # Native-dtype operands on the MXU, f32 scores out (_mm); the
        # scale folds into the f32 scores, not the (possibly bf16) q.
        s = _mm(q_ref[0], k_ref[0], ((1,), (1,))) * scale  # [block, block]
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0
            )
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:, :1]  # [block, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc_ref[:] = acc_ref[:] * corr + _mm(
            p.astype(_lowp(v_ref)), v_ref[0], ((1,), (0,))
        )
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # Per-query logsumexp (the flash backward's softmax residual),
        # broadcast across the 8-lane trailing dim — mosaic requires
        # block dims (8k, 128m) or dims equal to the array's, so scalar
        # rows are stored 8 lanes wide (see _flash_fwd_impl).
        lse = m_ref[:, :1] + jnp.log(jnp.maximum(l_ref[:, :1], 1e-30))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, dq_acc_ref,
    *, block: int, causal: bool, scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    run = (qi >= ki) if causal else (ki >= 0)

    @pl.when(run)
    def _accumulate():
        s = _mm(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0
            )
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [blkq, blkk]
        dp = _mm(do_ref[0], v_ref[0], ((1,), (1,)))
        ds = p * (dp - dd_ref[0][:, :1])
        dq_acc_ref[:] += _mm(
            ds.astype(_lowp(k_ref)), k_ref[0], ((1,), (0,))
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, block: int, causal: bool, scale: float,
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    run = (qi >= ki) if causal else (qi >= 0)

    @pl.when(run)
    def _accumulate():
        s = _mm(q_ref[0], k_ref[0], ((1,), (1,))) * scale
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0
            )
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [blkq, blkk]
        # dV_j += P^T @ dO
        pl_ = p.astype(_lowp(do_ref))
        dv_acc_ref[:] += _mm(pl_, do_ref[0], ((0,), (0,)))
        dp = _mm(do_ref[0], v_ref[0], ((1,), (1,)))
        ds = p * (dp - dd_ref[0][:, :1])
        # dK_j += scale · dS^T @ Q — scale applied at finalize (the
        # f32 accumulator), not to the native-dtype q operand.
        dk_acc_ref[:] += _mm(
            ds.astype(_lowp(q_ref)), q_ref[0], ((0,), (0,))
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _prep(x, b, h, s, d, s_pad, d_pad):
    x = jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)  # [BH, S, D]
    return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, d_pad - d)))


def _unprep(x, b, h, s, d):
    x = x[:, :s, :d].reshape(b, h, s, d)
    return jnp.moveaxis(x, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, block: int, interpret: bool):
    out, _ = _flash_fwd_impl(q, k, v, causal, block, interpret)
    return out


def _flash_fwd_impl(q, k, v, causal, block, interpret, out_dtype=None):
    b, s, h, d = q.shape
    blk = min(block, s)
    s_pad = -(-s // blk) * blk
    d_pad = -(-d // 128) * 128
    qp = _prep(q, b, h, s, d, s_pad, d_pad)
    kp = _prep(k, b, h, s, d, s_pad, d_pad)
    vp = _prep(v, b, h, s, d, s_pad, d_pad)
    nblk = s_pad // blk
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block=blk, causal=causal, scale=1.0 / (d**0.5)
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_pad, d_pad), out_dtype or q.dtype),
            # lse rows are stored 8 lanes wide (col 0 meaningful): a
            # (1, blk) block of a 2-D array violates mosaic's (8, 128)
            # tiling rule on real TPUs.
            jax.ShapeDtypeStruct((b * h, s_pad, 8), jnp.float32),
        ],
        grid=(b * h, nblk, nblk),
        in_specs=[
            pl.BlockSpec((1, blk, d_pad), lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((1, blk, d_pad), lambda bhi, qi, ki: (bhi, ki, 0)),
            pl.BlockSpec((1, blk, d_pad), lambda bhi, qi, ki: (bhi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, d_pad), lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((1, blk, 8), lambda bhi, qi, ki: (bhi, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, d_pad), jnp.float32),  # acc
            pltpu.VMEM((blk, 128), jnp.float32),  # running max (col 0)
            pltpu.VMEM((blk, 128), jnp.float32),  # running denom (col 0)
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return _unprep(out, b, h, s, d), lse


def _flash_fwd(q, k, v, causal, block, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, block, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_kernels(qp, kp, vp, dop, lse, dd, causal, blk, d_pad,
                       interpret, dtypes):
    """The two flash backward pallas calls over PREPPED operands
    ([BH, S_pad, D_pad]; lse/dd 8-lane wide [BH, S_pad, 8] f32).
    Shared by the standalone VJP and the ring backward (which supplies
    a GLOBAL lse/delta covering all ring steps)."""
    bh, s_pad, _ = qp.shape
    nblk = s_pad // blk
    d = dtypes["d"]
    scale = 1.0 / (d**0.5)

    qkv_spec = pl.BlockSpec((1, blk, d_pad), lambda bhi, i, j: (bhi, i, 0))
    kv_of_j = pl.BlockSpec((1, blk, d_pad), lambda bhi, i, j: (bhi, j, 0))
    row_of_i = pl.BlockSpec((1, blk, 8), lambda bhi, i, j: (bhi, i, 0))
    row_of_j = pl.BlockSpec((1, blk, 8), lambda bhi, i, j: (bhi, j, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block=blk, causal=causal, scale=scale),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d_pad), dtypes["q"]),
        grid=(bh, nblk, nblk),  # (BH, query block, key sweep)
        in_specs=[qkv_spec, kv_of_j, kv_of_j, qkv_spec, row_of_i, row_of_i],
        out_specs=qkv_spec,
        scratch_shapes=[pltpu.VMEM((blk, d_pad), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lse, dd)

    q_of_j = pl.BlockSpec((1, blk, d_pad), lambda bhi, i, j: (bhi, j, 0))
    kv_of_i = pl.BlockSpec((1, blk, d_pad), lambda bhi, i, j: (bhi, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block=blk, causal=causal, scale=scale),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d_pad), dtypes["k"]),
            jax.ShapeDtypeStruct((bh, s_pad, d_pad), dtypes["v"]),
        ],
        grid=(bh, nblk, nblk),  # (BH, key block, query sweep)
        in_specs=[q_of_j, kv_of_i, kv_of_i, q_of_j, row_of_j, row_of_j],
        out_specs=[kv_of_i, kv_of_i],
        scratch_shapes=[
            pltpu.VMEM((blk, d_pad), jnp.float32),
            pltpu.VMEM((blk, d_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lse, dd)
    return dq, dk, dv


def _flash_bwd(causal, block, interpret, res, dout):
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    blk = min(block, s)
    s_pad = -(-s // blk) * blk
    d_pad = -(-d // 128) * 128

    qp = _prep(q, b, h, s, d, s_pad, d_pad)
    kp = _prep(k, b, h, s, d, s_pad, d_pad)
    vp = _prep(v, b, h, s, d, s_pad, d_pad)
    dop = _prep(dout, b, h, s, d, s_pad, d_pad)
    op = _prep(out, b, h, s, d, s_pad, d_pad)
    # D_i = rowsum(dO * O) — the softmax-derivative correction term.
    # Stored 8 lanes wide like lse (mosaic tiling rule).
    dd = jnp.sum(dop.astype(jnp.float32) * op.astype(jnp.float32), axis=-1)
    dd = jnp.broadcast_to(dd[..., None], (*dd.shape, 8))
    # lse pad rows: 0 is safe — their dO rows are zero, so every term
    # they touch (p * 0, ds * 0) vanishes before it reaches real rows.

    dq, dk, dv = _flash_bwd_kernels(
        qp, kp, vp, dop, lse, dd, causal, blk, d_pad, interpret,
        {"q": q.dtype, "k": k.dtype, "v": v.dtype, "d": d},
    )
    return (
        _unprep(dq, b, h, s, d),
        _unprep(dk, b, h, s, d),
        _unprep(dv, b, h, s, d),
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def ring_block_size(s: int, block: int) -> int:
    """Largest kernel block ≤ ``block`` that tiles ``s`` exactly — ring
    steps need s_pad == s (an off-diagonal ring step is FULL attention;
    unmasked pad keys would corrupt it). Multiples of 8 keep mosaic's
    (8, 128) tiling rule; if none divides, a single s-sized block
    (block dims equal to array dims) is always legal."""
    if s <= block:
        return s
    blk = (min(block, s) // 8) * 8
    while blk >= 8 and s % blk:
        blk -= 8
    return blk if blk >= 8 and s % blk == 0 else s


def _rows_to_lanes(x: jnp.ndarray) -> jnp.ndarray:
    """[B, H, S] f32 per-row scalars -> the kernels' 8-lane-wide
    [BH, S, 8] layout (mosaic tiling rule, see _fwd_kernel)."""
    b, h, s = x.shape
    x = x.reshape(b * h, s).astype(jnp.float32)
    return jnp.broadcast_to(x[..., None], (b * h, s, 8))


def flash_block_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool,
    block: int = 1024,
    interpret: bool | None = None,
):
    """One flash forward over a (q-block, kv-block) pair, returning
    ``(out, lse)`` with lse as [B, H, S] f32 — the building block of
    ring attention's per-step inner (the ring merges steps by
    logsumexp, so it needs the softmax residual, not just the output).
    Not differentiable on its own: the ring defines its own VJP."""
    interpret = compat.pallas_interpret(interpret)
    b, s, h, d = q.shape
    blk = ring_block_size(s, block)
    # f32 out: the ring merges steps at f32 — a per-step downcast to
    # q.dtype would round every block before the logsumexp rescale.
    out, lse8 = _flash_fwd_impl(
        q, k, v, causal, blk, interpret, out_dtype=jnp.float32
    )
    lse = lse8[:, :s, 0].reshape(b, h, s)
    return out, lse


def flash_block_bwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    do: jnp.ndarray,
    lse: jnp.ndarray,
    delta: jnp.ndarray,
    causal: bool,
    block: int = 1024,
    interpret: bool | None = None,
):
    """Flash backward for one (q-block, kv-block) pair with EXTERNAL
    softmax residuals: ``lse``/``delta`` are [B, H, S] f32 computed
    over the FULL attention row (all ring steps), so per-step
    contributions recomputed here sum exactly to the global gradient.
    Returns (dq, dk, dv) in the operands' dtypes."""
    interpret = compat.pallas_interpret(interpret)
    b, s, h, d = q.shape
    blk = ring_block_size(s, block)
    d_pad = -(-d // 128) * 128
    qp = _prep(q, b, h, s, d, s, d_pad)
    kp = _prep(k, b, h, s, d, s, d_pad)
    vp = _prep(v, b, h, s, d, s, d_pad)
    dop = _prep(do, b, h, s, d, s, d_pad)
    # f32 grads out: per-step contributions sum in the ring's f32
    # accumulators; rounding each to the operand dtype first would
    # compound across steps.
    dq, dk, dv = _flash_bwd_kernels(
        qp, kp, vp, dop, _rows_to_lanes(lse), _rows_to_lanes(delta),
        causal, blk, d_pad, interpret,
        {"q": jnp.float32, "k": jnp.float32, "v": jnp.float32, "d": d},
    )
    return (
        _unprep(dq, b, h, s, d),
        _unprep(dk, b, h, s, d),
        _unprep(dv, b, h, s, d),
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas flash attention, differentiable. q/k/v: [B, S, H, D] ->
    [B, S, H, D]. Backward is the recompute-based flash VJP (two Pallas
    kernels); gradients match the XLA blockwise path (tested).

    ``block``: 1024 on v5e for H=8, D=128 (the [block, block] f32
    score tile then fills VMEM well; 2048 exceeds it and fails to
    compile; its rates are pre-PR-1, not re-measured:
    ``docs/perf_attention.md``). Shorter sequences are clamped to
    ``min(block, S)``.

    Non-causal with a sequence that doesn't divide ``block`` falls back
    to the XLA blockwise path (pad keys would need extra masking; the
    causal mask already excludes the high-position pad keys)."""
    interpret = compat.pallas_interpret(interpret)
    b, s, h, d = q.shape
    blk = min(block, s)
    s_pad = -(-s // blk) * blk
    if not causal and s_pad != s:
        from tpfl.parallel.ring_attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=False, block_size=blk)
    return _flash(q, k, v, causal, block, interpret)
