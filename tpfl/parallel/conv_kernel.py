"""Per-node 3×3 convolution with a Pallas TPU backward — the hot op of
the vmapped federation round.

Why this exists: ``VmapFederation`` trains N nodes' DISTINCT conv
weights in one program. XLA lowers the vmapped conv FORWARD well
(grouped conv, measured ~27% MFU on the bench CNN), but its backward —
the weight gradient (a ``batch_group_count`` conv) and the input
gradient (a grouped transposed conv) — dominates the round at <11% MFU:
measured on one v5e chip, the 100-node CNN train step spends 2.95 ms in
the forward and ~19 ms in the backward. GEMM reformulations at the XLA
level (im2col / ``dot_general`` with a batch dim) are WORSE (58-89 ms):
XLA's batched-GEMM lowering cannot pipeline these shapes.

So: keep XLA's forward, replace only the backward with two Pallas
kernels that stream images through VMEM and feed the MXU with im2col
GEMMs built in-kernel (patches never touch HBM):

- ``dW = patches(x)^T @ dout`` — per (node, image-block) grid step the
  kernel zero-pads the image block in VMEM scratch, concatenates the
  kh·kw shifted slices into a ``[bb·H·W, kh·kw·Cin]`` patch matrix,
  and accumulates ``[kh·kw·Cin, Cout]`` partials in the revisited
  float32 output block (grid's minor dimension sweeps image blocks, so
  the accumulator lives in VMEM across the sweep).
- ``dx = patches(dout) @ rot180(w)^T`` — the transposed conv expressed
  the same way: halo-pad dout in scratch, im2col, one MXU GEMM per
  block, output written once.

The public entry is :class:`NodeConv`, a drop-in for ``nn.Conv`` with
the SAME param layout (kernel ``[kh, kw, Cin, Cout]``, bias
``[Cout]``) and the IDENTICAL forward (same ``lax.conv_general_dilated``
call — only gradient lowering changes). It vmaps: under ``jax.vmap``
the pallas grid gains the node dimension, which is exactly the
federation use. Reference seam being replaced: the per-process Ray
actor fits (``simulation/actor_pool.py:39-66``) — here the whole
N-node round is one XLA program and this kernel is its backward.

Restrictions (asserted): stride 1, SAME padding, odd square kernel —
what the zoo CNN uses. Layers with fewer than ``_MIN_LANE_CHANNELS``
input channels (an RGB stem) take the forward-style XLA backward
instead of the kernels. Interprets on CPU (tests), compiles on TPU.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpfl.parallel import compat

_DN = ("NHWC", "HWIO", "NHWC")

#: Narrowest input-channel count the Pallas backward takes. The kernels
#: block ``[bb, H, W, C]`` with C on the 128-wide lane axis, so HBM
#: operands are padded to 128/C times their size: at C=3 (an RGB stem)
#: that is 42x — the chip's compiler refused the 100-node bench CNN's
#: round program for it (19.8 GB of 15.75 GB HBM, PR 21 rehearsal).
#: Narrower layers take the forward-style XLA backward (identical math).
_MIN_LANE_CHANNELS = 8


def _pick_bb(b: int, h: int, w: int, cin: int, cout: int, k: int,
             itemsize: int) -> int:
    """Images per grid step: bound the in-kernel patch matrix to ~2.5 MB
    of VMEM ([bb·h·w, k²·max(cin,cout)] at the input itemsize) and
    divide the batch."""
    budget = 2_500_000
    per_img = h * w * k * k * max(cin, cout) * itemsize
    bb = max(1, min(b, budget // max(per_img, 1)))
    while b % bb:
        bb -= 1
    return bb


def _build_patches(pad_ref, patch_ref, bb: int, h: int, w: int, k: int,
                   c: int):
    """Write the im2col matrix of the zero-haloed ``pad_ref`` into
    ``patch_ref`` ([bb, h, w, k²·c]); channel index is (di·k+dj)·c + ci.
    Stores (not concat): Mosaic relayouts the shifted slices on store,
    where a concat of offset-mismatched vectors fails to compile."""
    for di in range(k):
        for dj in range(k):
            idx = di * k + dj
            patch_ref[:, :, :, idx * c:(idx + 1) * c] = (
                pad_ref[:, di:di + h, dj:dj + w, :]
            )


def _dw_kernel(x_ref, g_ref, dw_ref, pad_ref, patch_ref, *, bb, h, w, k,
               cin, cout):
    bi = pl.program_id(0)
    r = k // 2

    @pl.when(bi == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    pad_ref[:] = jnp.zeros_like(pad_ref)
    pad_ref[:, r:r + h, r:r + w, :] = x_ref[:]
    _build_patches(pad_ref, patch_ref, bb, h, w, k, cin)
    pm = patch_ref[:].reshape(bb * h * w, k * k * cin)
    gm = g_ref[:].reshape(bb * h * w, cout)
    # MXU: contract the big M dim; accumulate f32 across image blocks.
    dw_ref[:] += lax.dot_general(
        pm, gm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dx_kernel(g_ref, wrot_ref, dx_ref, pad_ref, patch_ref, *, bb, h, w,
               k, cin, cout):
    r = k // 2
    pad_ref[:] = jnp.zeros_like(pad_ref)
    pad_ref[:, r:r + h, r:r + w, :] = g_ref[:]
    _build_patches(pad_ref, patch_ref, bb, h, w, k, cout)
    pm = patch_ref[:].reshape(bb * h * w, k * k * cout)
    dx = lax.dot_general(
        pm, wrot_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dx_ref[:] = dx.reshape(bb, h, w, cin).astype(dx_ref.dtype)


def _conv_fwd_op(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=_DN
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def node_conv(x: jnp.ndarray, w: jnp.ndarray, interpret: Optional[bool] = None):
    """3×3/SAME/stride-1 conv [B,H,W,Cin]·[k,k,Cin,Cout] -> [B,H,W,Cout]
    with XLA forward and Pallas backward. Vmappable over a leading node
    axis on both operands."""
    return _conv_fwd_op(x, w)


def _nc_fwd(x, w, interpret):
    return _conv_fwd_op(x, w), (x, w)


def _nc_bwd(interpret, res, g):
    x, w = res
    if x.shape[-1] < _MIN_LANE_CHANNELS:
        return _fs_bwd(res, g)
    interpret = compat.pallas_interpret(interpret)
    b, h, w_, cin = x.shape
    k, k2, _, cout = w.shape
    assert k == k2 and k % 2 == 1, "NodeConv: odd square kernels only"
    g = g.astype(x.dtype)
    bb = _pick_bb(b, h, w_, cin, cout, k, jnp.dtype(x.dtype).itemsize)
    grid = (b // bb,)
    halo = k - 1

    dw = pl.pallas_call(
        functools.partial(
            _dw_kernel, bb=bb, h=h, w=w_, k=k, cin=cin, cout=cout
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, h, w_, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((bb, h, w_, cout), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (k * k * cin, cout), lambda i: (0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((k * k * cin, cout), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bb, h + halo, w_ + halo, cin), x.dtype),
            pltpu.VMEM((bb, h, w_, k * k * cin), x.dtype),
        ],
        interpret=interpret,
    )(x, g)
    # [k²·cin, cout] with channel index (di·k+dj)·cin + ci -> flax HWIO.
    dw = dw.reshape(k, k, cin, cout).astype(w.dtype)

    # dx = conv_T(g, w): patches(g) @ rot180(w)^T, built as a [k²·cout,
    # cin] matrix whose row index matches _patches' channel order.
    wrot = jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2).reshape(
        k * k * cout, cin
    )
    dx = pl.pallas_call(
        functools.partial(
            _dx_kernel, bb=bb, h=h, w=w_, k=k, cin=cin, cout=cout
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, h, w_, cout), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((k * k * cout, cin), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, h, w_, cin), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, w_, cin), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bb, h + halo, w_ + halo, cout), g.dtype),
            pltpu.VMEM((bb, h, w_, k * k * cout), g.dtype),
        ],
        interpret=interpret,
    )(g, wrot)
    return dx, dw


node_conv.defvjp(_nc_fwd, _nc_bwd)


@jax.custom_vjp
def conv_fwd_style(x: jnp.ndarray, w: jnp.ndarray):
    """Same conv as :func:`node_conv`, but with BOTH backward passes
    expressed as ordinary FORWARD convolutions at the XLA level:

    - ``dx = conv_SAME(dout, rot180(w) io-swapped)`` — the standard
      transposed-conv identity for stride 1 / SAME / odd kernels;
    - ``dW = conv(x, dout)`` with dimension numbers ``CHWN/IHWO/HWNC``
      (Cin as the conv batch, the real batch as the contraction
      feature, dout as a big-window kernel).

    Why: JAX's built-in conv transpose rules emit
    ``batch_group_count``/grouped-transpose convolutions that, once
    vmapped over a nodes axis, lower ~6x slower than forward-style
    grouped convs on TPU (measured on the bench CNN: 22.0 -> 21.1 ms
    per 100-node step, and the dW/dx ops individually 4.5-6.6 ms ->
    forward-conv class). Gradients are numerically IDENTICAL to the
    autodiff path (same conv op, exact — tested).

    Restrictions: stride 1, SAME padding, odd square kernel."""
    return _conv_fwd_op(x, w)


def _fs_fwd(x, w):
    return _conv_fwd_op(x, w), (x, w)


def _fs_bwd(res, g):
    x, w = res
    g = g.astype(x.dtype)
    k = w.shape[0]
    assert k == w.shape[1] and k % 2 == 1, "conv_fwd_style: odd square only"
    r = k // 2
    w_flip = jnp.flip(w, (0, 1)).swapaxes(2, 3)  # [k, k, Cout, Cin]
    dx = lax.conv_general_dilated(
        g, w_flip, (1, 1), "SAME", dimension_numbers=_DN
    )
    dw = lax.conv_general_dilated(
        x, g, (1, 1), [(r, r), (r, r)],
        dimension_numbers=("CHWN", "IHWO", "HWNC"),
    ).astype(w.dtype)
    return dx, dw


conv_fwd_style.defvjp(_fs_fwd, _fs_bwd)
