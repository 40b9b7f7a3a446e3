"""Block attention as Pallas TPU kernels — a block pair's tiles never
leave VMEM.

The kernels behind :func:`tpfl.parallel.ring_attention.blockwise_attention`
on a TPU (its XLA block loop is the path everywhere else, and what the
kernels are tested against), behind :func:`flash_attention` and behind
the flash ring's per-step inner. One family, static shapes differ:
grouped query heads (``rows = groups * block`` query rows meet a key
block: a key head's ``groups`` query heads), a value width of its own,
equal heads as the case ``groups = 1``.

Layout. The kernels index ``[B, S', H, D]`` as ``[B, S', H * D]``, and
a program instance takes the fewest heads whose lanes are whole
128-lane tiles (two 64-wide heads; one 128-wide head): no operand is
transposed, and D = 64 is not padded to 128. That reshape is free only
in the LOGICAL layout: on a TPU a ``[.., 12, 64]`` array is tiled over
its last two dimensions, so where XLA cannot fold the reshape away it
is a copy. A caller that reshapes ``[.., H * D]`` to heads itself pays
nothing (the two reshapes fold: the zoo block splits its joint
projection first and names the heads after, PERF.md §6 PR 30); one that
hands over slices of a ``[.., 3 * H, D]`` array paid 4.3 ms a round on
GPT-2. Inside, a head whose lanes are not whole tiles is taken by
ZEROING the other heads' lanes of the resident operand: the contraction then adds exact
zeros, and a 64-wide head half-fills the 128 x 128 MXU either way.

**Grouped query heads are LANES** (PR 37): q, out, dO and dq are ``[B,
S', H * groups * D]`` too, query head ``h`` on lanes ``[h * D, (h + 1) *
D)`` as the projection and rotary left it, so a key head's ``groups``
query heads are one run of ``groups * D`` lanes of a ``[block, heads *
groups * D]`` block. A pair's tile still holds all of them, ``[block,
rows]``: the ``groups`` lane slices of a q or dO block are stacked on
sublanes IN VMEM (``_query_rows``; a query head narrower than a lane
tile is rolled onto its key head's lanes first), and ``acc^T`` and dq
are written back slice by slice. Only a query's scalars keep the tile's
row order, a key head's query heads one after the other within a block:
lse and delta, ``[B, H, S' * groups]`` float32 (``delta_rows``). Before,
a transpose in HBM laid the heads of a group side by side as rows: 87 ms
a round of copies on Mellum 2's cell (PERF.md §6, PR 37).

``jax.vmap`` (the engine's silos) becomes one more grid dimension, so
no tile size depends on how many silos share the chip.

Both kernels hold a pair's tile as ``[block, rows]``, keys on sublanes
and queries on lanes: a query's scalars (max, denominator, lse, delta)
are then ROWS, stored and read as ``[.., H, S']`` has them, and no
tile-sized operand is ever transposed but ``dS`` for ``dQ``.

- forward, grid ``(batch, head block, query block, key sweep)``: the
  scores, P and the running ``acc^T`` / max / denominator of one query
  block live in VMEM across the sweep (the last grid dimension runs in
  order on one core); ``(P V)^T = V^T P^T`` transposes the small
  operand, and the output block is transposed once, when it is written.
- backward, grid ``(batch, head block, key block, query sweep)``: ONE
  sweep (FlashAttention-2's): S, P = exp(S - lse), dP and dS of a pair
  are computed once and feed dQ, dK and dV — 5 tile matmuls. dK / dV
  accumulate in VMEM over the sweep; dQ accumulates in a float32 VMEM
  buffer of the WHOLE sequence (one head block's: 8 MB at 8k tokens
  with two query heads a key head), written once when the head block
  is done — no float32 dq in HBM. (Measured against a dQ kernel over a
  key sweep plus a dK / dV kernel over a query sweep, 7 matmuls: one
  sweep is 16% faster a call at both cells' shapes, PERF.md §6 PR 30.)

Causal pairs above the diagonal are neither computed nor fetched (the
index maps clamp to the diagonal, so the block index does not change
and nothing is copied; ``pl.when`` skips the body); the mask is built
on pairs that touch the diagonal only. With a band (``window``: ``0 <=
q - k < window``, PR 33) a sweep is ``reach + 1`` steps long and offset
from the diagonal — the forward's ends on it, the backward's starts on
it — so pairs wholly behind the band are not even grid steps (at 32
blocks and a reach of 4 an ``n x n`` grid would spend 85% of its steps
on nothing); the mask is built on the diagonal and on the pairs the
band's far edge cuts, the pairs between run unmasked. The backward
still keeps the head block's dq for the WHOLE sequence in VMEM.
Numerics are the block loop's:
P and dS are rounded to the inputs' dtype for their matmuls alone;
accumulators, max, denominator, lse and delta are float32; float32
inputs compute in float32 throughout (on a TPU at the MXU's default
precision, like XLA's own float32 matmuls: 0.7% of the largest element
against a dense float32 softmax on the v5e, 5e-7 on the CPU). The
softmax scale is folded into the resident operand where that is exact
(a power of two), and multiplies the float32 scores where it is not.

Interprets on the CPU (tests), compiles on a TPU.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpfl.parallel import compat

F32 = jnp.float32
LANES = 128
_NEG = -1e30  # large-negative instead of -inf: exp() stays exact, no NaNs
_VMEM_LIMIT = 100 * 1024 * 1024
#: What the backward's resident dq of a head block, the WHOLE sequence's,
#: may take of it: its float32 scratch and its output block, which the
#: pipeline double-buffers in the gradients' dtype. With bf16 gradients
#: that is 64k grouped query rows of a 128-lane head block (a 32 MiB
#: scratch), with float32 ones two thirds of that; the rest of the limit
#: is the pair's operands and tiles.
_DQ_BYTES = 64 * 1024 * 1024
#: The largest float32 ``[block, rows]`` tile admitted: ``[1024, 1024]``,
#: the largest compiled for the described v5e (``flash_8k``; the cells
#: run ``[512, 512]`` and ``[512, 1024]``). A pair holds four such tiles.
_TILE_BYTES = 4 * 1024 * 1024
#: The kernels' names in a device trace (``attention_kernel_share_pct``).
FORWARD, BACKWARD = "block_attention_forward", "block_attention_backward"
#: ... and the small kernel beside them (``delta_rows``): not one of the
#: block loop's two, so that share keeps reading what it read.
DELTA = "attention_delta_rows"

_NT = ((1,), (1,))  # a @ b^T
_NN = ((1,), (0,))  # a @ b
_TN = ((0,), (0,))  # a^T @ b


def _dot(a, b, dims):
    """MXU matmul on the operands' own dtype with a float32 accumulator:
    bf16 operands run the MXU at full rate, float32 operands (the
    exactness tests) compute in float32."""
    return lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=F32
    )


class _Plan(NamedTuple):
    """The static shapes of one call."""

    heads: int  # heads a program instance
    d: int
    d_v: int
    block: int
    groups: int
    n_blocks: int
    causal: bool
    s_len: int
    scale: float
    fold: bool  # the scale is a power of two: folded into an operand
    window: Optional[int] = None  # the causal band: 0 <= q - k < window

    @property
    def rows(self) -> int:
        return self.groups * self.block

    @property
    def reach(self) -> int:
        """How many key blocks behind its own a query block's band
        reaches (``ring_attention._band_reach``), within the sequence."""
        return min(self.n_blocks - 1, -(-(self.window - 1) // self.block))

    @property
    def edge(self) -> int:
        """From how many blocks apart the band's far edge cuts a pair:
        the farthest rows of blocks i and j are ``(i - j + 1) * block -
        1`` apart."""
        return -(-(self.window + 1) // self.block) - 1

    @property
    def sweep(self) -> int:
        """Steps of a sweep: every block, or those the band reaches."""
        return self.n_blocks if self.window is None else self.reach + 1

    @property
    def masked_last(self) -> bool:
        """Not causal, and the last key block holds padding."""
        return not self.causal and self.n_blocks * self.block > self.s_len

    @property
    def pre(self) -> float:
        """What the resident key-side operand (q forward, k backward) is
        multiplied by: the scale where folding it is exact."""
        return self.scale if self.fold else 1.0

    def own_tiles(self, width: int) -> bool:
        """A head ``width`` lanes wide is whole lane tiles of its own
        within the instance's block (else it shares one with others)."""
        return self.heads == 1 or width % LANES == 0

    def operand_width(self, width: int) -> int:
        """Lanes of one head's matmul operand: its own, or the block's."""
        return width if self.own_tiles(width) else self.heads * width


def _heads_per_instance(h: int, d: int, d_v: int) -> int:
    """The fewest heads, a divisor of ``h``, whose key and value lanes
    are whole 128-lane tiles; all of them where none is (a block as wide
    as the array is always legal)."""
    for n in range(1, h + 1):
        if h % n == 0 and n * d % LANES == 0 and n * d_v % LANES == 0:
            return n
    return h


def _plan(k, v, causal, block, s_len, groups, window=None) -> _Plan:
    _, sp, h, d = k.shape
    scale = 1.0 / math.sqrt(d)
    return _Plan(
        heads=_heads_per_instance(h, d, v.shape[-1]), d=d, d_v=v.shape[-1],
        block=block, groups=groups, n_blocks=sp // block, causal=causal,
        s_len=s_len, scale=scale, fold=math.log2(scale).is_integer(),
        window=window,
    )


def tiles(
    k_shape: tuple, d_v: int, block: int, groups: int = 1,
    grad_bytes: int = 2,
) -> bool:
    """Whether the kernels take padded keys of ``k_shape`` on a TPU —
    only what was compiled for one (``tests/test_chip_compile.py``): a
    block that is whole 128-lane tiles (lse and delta are stored with
    the sequence on lanes), a float32 score tile within ``_TILE_BYTES``,
    and a head block's dq for the whole sequence — float32 scratch plus
    the double-buffered output block, ``grad_bytes`` an element — within
    ``_DQ_BYTES`` of VMEM. Everything else is the XLA loop's, as it was
    before the kernels: a short or unaligned sequence that is one block
    of its own length, blocks of thousands of rows, a million tokens."""
    _, sp, h, d = k_shape
    rows = groups * block
    dq_elements = groups * sp * _heads_per_instance(h, d, d_v) * d
    return (
        block % LANES == 0
        and 4 * block * rows <= _TILE_BYTES
        and (4 + 2 * grad_bytes) * dq_elements <= _DQ_BYTES
    )


# --- a head's lanes within a block of several heads ------------------------


def _lanes_of(h: int, width: int, shape: tuple):
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= h * width) & (lane < (h + 1) * width)


def _only(plan: _Plan, x, h: int, width: int, pre: float = 1.0):
    """Head ``h``'s operand ``x`` times ``pre``, fit to stay for a whole
    sweep: where heads share lane tiles the other heads' lanes are
    zeroed (module docstring)."""
    if plan.own_tiles(width):
        return x if pre == 1.0 else (x.astype(F32) * pre).astype(x.dtype)
    wide = x.astype(F32) * pre
    return jnp.where(_lanes_of(h, width, x.shape), wide, 0.0).astype(x.dtype)


def _resident(plan: _Plan, x, h: int, width: int, pre: float = 1.0):
    """Head ``h``'s operand from a key-side block that stays for a whole
    sweep, times ``pre``: its own lanes where they are whole tiles, else
    the block with the other heads' lanes zeroed."""
    return _only(plan, _streamed(plan, x, h, width), h, width, pre)


def _streamed(plan: _Plan, x, h: int, width: int):
    """Head ``h``'s operand from a key-side block that changes every
    step: its own lanes where they are whole tiles, else the block as it
    is (the resident operand it meets has the other heads zeroed)."""
    if plan.own_tiles(width):
        return x[:, h * width:(h + 1) * width]
    return x


def _group_lanes(plan: _Plan, h: int, g: int, width: int):
    """Where query head ``g`` of key head ``h`` lies in a query-side
    block ``[block, heads * groups * width]``: ``(the lanes of its
    operand, how far to roll them)``. A head of whole lane tiles is its
    own operand; a narrower one comes with the heads that share its
    ``heads * width`` lanes, rolled until it stands on key head ``h``'s
    lanes — where the key-side operand it meets is not zero."""
    head = h * plan.groups + g
    if plan.own_tiles(width):
        return slice(head * width, (head + 1) * width), 0
    wide = plan.heads * width
    at = head * width // wide * wide
    return slice(at, at + wide), (h - head) % plan.heads * width


def _query_rows(plan: _Plan, x, h: int, width: int):
    """Key head ``h``'s query rows ``[rows, operand width]`` from a
    query-side block (q, dO): its ``groups`` query heads are runs of
    LANES there, as the projection left them, and are stacked on
    sublanes here, in VMEM. With equal heads this is ``_streamed``."""
    if plan.groups == 1:
        return _streamed(plan, x, h, width)
    runs = []
    for g in range(plan.groups):
        lanes, shift = _group_lanes(plan, h, g, width)
        run = x[:, lanes]
        runs.append(_rolled(run, shift) if shift else run)
    return jnp.concatenate(runs, axis=0)


def _rolled(x, shift: int):
    """``x`` with its lanes rolled ``shift`` to the right (the chip
    rotates 32-bit lanes alone)."""
    return pltpu.roll(x.astype(F32), shift, 1).astype(x.dtype)


def _add(plan: _Plan, ref, h: int, width: int, value) -> None:
    """``ref[:, lanes of h] += value``. ``value`` is ``width`` wide where
    a head has its own tiles, else as wide as the block with junk in the
    other heads' lanes, which a select keeps out."""
    if plan.own_tiles(width):
        ref[:, h * width:(h + 1) * width] += value
        return
    old = ref[...]
    ref[...] = jnp.where(_lanes_of(h, width, old.shape), old + value, old)


def _visible(plan: _Plan, i, j):
    """Which (key, query) of a masked pair's ``[block, rows]`` tile is
    seen: on the diagonal of a causal call ``q >= k`` (a group's heads
    are ``groups`` runs of the block's positions along the lanes), with
    a band ``0 <= q - k < window`` (the diagonal and the far edge), in
    the last key block of a padded non-causal call ``k < s_len``."""
    one = (plan.block, plan.block)
    k_pos = lax.broadcasted_iota(jnp.int32, one, 0)
    if plan.window is not None:
        apart = (i - j) * plan.block + (
            lax.broadcasted_iota(jnp.int32, one, 1) - k_pos
        )
        seen = (apart >= 0) & (apart < plan.window)
    elif plan.causal:
        seen = lax.broadcasted_iota(jnp.int32, one, 1) >= k_pos
    else:
        seen = j * plan.block + k_pos < plan.s_len
    # (tiled as int32: the compiler concatenates no masks)
    return jnp.concatenate([seen.astype(jnp.int32)] * plan.groups, axis=1) > 0


def _on_pairs(plan: _Plan, i, j, last, pair) -> None:
    """Run ``pair(masked)`` on the visible pairs of query block ``i`` and
    key block ``j``, with the mask where a pair needs one. A band's sweep
    is offset from the diagonal, so a step may lie outside the sequence;
    of the pairs inside, the diagonal and those the far edge cuts are
    masked, the ones between are not."""
    if plan.window is not None:
        apart = i - j
        inside = (j >= 0) & (i < plan.n_blocks)
        masked = (apart == 0) | (apart >= plan.edge)
        if plan.edge > 1:
            pl.when(inside & ~masked)(functools.partial(pair, False))
        pl.when(inside & masked)(functools.partial(pair, True))
    elif plan.causal:
        pl.when(j < i)(functools.partial(pair, False))
        pl.when(j == i)(functools.partial(pair, True))
    elif plan.masked_last:
        pl.when(j < last)(functools.partial(pair, False))
        pl.when(j == last)(functools.partial(pair, True))
    else:
        pair(False)


# --- kernels -----------------------------------------------------------------


def _forward_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, qh_scr, acc_scr, m_scr, l_scr,
    *, plan: _Plan,
):
    i, t = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1
    # The key block of step t: with a band the sweep ends on the diagonal.
    j = t if plan.window is None else i - plan.reach + t
    lowp = q_ref.dtype
    d_v = plan.d_v

    @pl.when(t == 0)
    def _():
        q = q_ref[...]
        for h in range(plan.heads):
            qh_scr[h] = _only(
                plan, _query_rows(plan, q, h, plan.d), h, plan.d, plan.pre
            )
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)

    def pair(masked: bool):
        k, v = k_ref[...], v_ref[...]
        seen = _visible(plan, i, j) if masked else None
        # [block, rows]: keys x queries, so a query's max and sum run down
        # the sublanes and its scalars lie along the lanes. Every head's
        # scores first: the next head's matmul then runs beside this
        # head's vector work (measured, PERF.md PR 30: -7% a call).
        scores = [
            _dot(_streamed(plan, k, h, plan.d), qh_scr[h], _NT)
            for h in range(plan.heads)
        ]
        for h, s in enumerate(scores):
            if not plan.fold:
                s = s * plan.scale
            if masked:
                s = jnp.where(seen, s, _NEG)
            m_prev, l_prev = m_scr[h:h + 1, :], l_scr[h:h + 1, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h:h + 1, :] = l_prev * corr + jnp.sum(p, axis=0, keepdims=True)
            m_scr[h:h + 1, :] = m_new
            # (P V)^T = V^T P^T: the small operand is the transposed one,
            # and a head's values are ROWS of the result.
            pv = _dot(_streamed(plan, v, h, d_v), p.astype(lowp), _TN)
            if not plan.own_tiles(d_v):
                pv = pv[h * d_v:(h + 1) * d_v]
            at = slice(h * d_v, (h + 1) * d_v)
            acc_scr[at, :] = acc_scr[at, :] * corr + pv

    _on_pairs(plan, i, j, last, pair)

    @pl.when(t == last)
    def _():
        for h in range(plan.heads):
            m, l = m_scr[h:h + 1, :], l_scr[h:h + 1, :]
            at = slice(h * d_v, (h + 1) * d_v)
            acc_scr[at, :] = acc_scr[at, :] / jnp.maximum(l, 1e-30)
            # Rows with no visible key get +LARGE: the backward's
            # exp(s - lse) is exactly 0 for them.
            lse_ref[h:h + 1, :] = jnp.where(
                l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 1e30
            )
        if plan.groups == 1:
            o_ref[...] = jnp.transpose(acc_scr[...]).astype(o_ref.dtype)
        else:  # a query head's values go back to its own lanes of the block
            for head in range(plan.heads * plan.groups):
                h, g = divmod(head, plan.groups)
                o_ref[:, head * d_v:(head + 1) * d_v] = jnp.transpose(acc_scr[
                    h * d_v:(h + 1) * d_v, g * plan.block:(g + 1) * plan.block
                ]).astype(o_ref.dtype)


def _backward_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    kh_scr, vh_scr, dk_scr, dv_scr, dq_scr, *, plan: _Plan,
):
    j, t = pl.program_id(2), pl.program_id(3)
    last_j, last_t = pl.num_programs(2) - 1, pl.num_programs(3) - 1
    # The query block of step t: with a band the sweep starts on the diagonal.
    i = t if plan.window is None else j + t
    lowp = q_ref.dtype

    @pl.when((t == 0) & (j == 0))
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(t == 0)
    def _():
        k, v = k_ref[...], v_ref[...]
        for h in range(plan.heads):
            kh_scr[h] = _resident(plan, k, h, plan.d, plan.pre)
            vh_scr[h] = _resident(plan, v, h, plan.d_v)
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def pair(masked: bool):
        q, do = q_ref[...], do_ref[...]
        seen = _visible(plan, i, j) if masked else None
        at = pl.ds(pl.multiple_of(i * plan.block, plan.block), plan.block)
        for h in range(plan.heads):
            q_h = _query_rows(plan, q, h, plan.d)
            do_h = _query_rows(plan, do, h, plan.d_v)
            k_h = kh_scr[h]
            s = _dot(k_h, q_h, _NT)  # [block, rows]: keys x queries
            if not plan.fold:
                s = s * plan.scale
            p = jnp.exp(s - lse_ref[h:h + 1, :])
            if masked:
                p = jnp.where(seen, p, 0.0)
            dp = _dot(vh_scr[h], do_h, _NT)
            ds = (p * (dp - delta_ref[h:h + 1, :])).astype(lowp)
            _add(plan, dv_scr, h, plan.d_v, _dot(p.astype(lowp), do_h, _NN))
            _add(plan, dk_scr, h, plan.d, _dot(ds, q_h, _NN))
            # dS^T K: the other heads' lanes of k_h are zero, so are dq's.
            # A query head's rows go back to the lanes q has it on.
            dq = _dot(ds, k_h, _TN)
            for g in range(plan.groups):
                lanes, shift = _group_lanes(plan, h, g, plan.d)
                run = dq[g * plan.block:(g + 1) * plan.block]
                if shift:
                    run = _rolled(run, run.shape[1] - shift)
                dq_scr[at, lanes] += run

    _on_pairs(plan, i, j, last_j, pair)

    @pl.when(t == last_t)
    def _():
        dk_ref[...] = (dk_scr[...] * plan.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((t == last_t) & (j == last_j))
    def _():
        post = 1.0 if plan.fold else plan.scale  # k_h carried it
        dq_ref[...] = (dq_scr[...] * post).astype(dq_ref.dtype)


# --- calls -------------------------------------------------------------------


def _lanes(x):
    """``[B, S, H, D] -> [B, S, H * D]``. Free in the logical layout;
    on a TPU an array whose last two dimensions are ``[12, 64]`` is
    tiled over them, so this is a COPY unless it folds with the
    caller's own reshape to heads (module docstring)."""
    return x.reshape(*x.shape[:2], -1)


def _params(*semantics: str):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT
    )


def attention_forward(
    q, k, v, *, causal: bool, block: int, s_len: int, groups: int = 1,
    window: Optional[int] = None, interpret: bool = False, out_dtype=None,
):
    """Padded k ``[B, nb * block, H, D]``, v ``[.., Dv]`` and q ``[B, nb *
    block, H * groups, D]`` (query head ``h`` reads key head ``h //
    groups``) -> ``(out [B, nb * block, H * groups, Dv], lse [B, H, nb *
    rows] float32)``, lse in the kernels' own row order: a key head's
    ``rows = groups * block`` query rows a block, its query heads one
    after the other (``delta_rows``). ``window`` (causal only): the band
    ``0 <= q - k < window``."""
    plan = _plan(k, v, causal, block, s_len, groups, window)
    b, _, h, _ = k.shape
    n, rows, heads = plan.n_blocks, plan.rows, plan.heads
    w, w_v = heads * plan.d, heads * plan.d_v
    query = lambda bi, hi, i, j: (bi, i, hi)  # noqa: E731

    def key_block(bi, hi, i, t):
        if window is not None:  # steps before the sequence refetch block 0
            return bi, jnp.maximum(i - plan.reach + t, 0), hi
        return bi, jnp.minimum(i, t) if causal else t, hi

    out, lse = pl.pallas_call(
        functools.partial(_forward_kernel, plan=plan),
        out_shape=[
            jax.ShapeDtypeStruct(
                (b, n * block, groups * h * plan.d_v), out_dtype or q.dtype
            ),
            jax.ShapeDtypeStruct((b, h // heads, heads, n * rows), F32),
        ],
        grid=(b, h // heads, n, plan.sweep),
        in_specs=[
            pl.BlockSpec((None, block, groups * w), query),
            pl.BlockSpec((None, block, w), key_block),
            pl.BlockSpec((None, block, w_v), key_block),
        ],
        out_specs=[
            pl.BlockSpec((None, block, groups * w_v), query),
            pl.BlockSpec(
                (None, None, heads, rows), lambda bi, hi, i, j: (bi, hi, 0, i)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, rows, plan.operand_width(plan.d)), q.dtype),
            pltpu.VMEM((w_v, rows), F32),  # acc^T
            pltpu.VMEM((heads, rows), F32),  # running max
            pltpu.VMEM((heads, rows), F32),  # denominator
        ],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name=FORWARD,
    )(_lanes(q), _lanes(k), _lanes(v))
    out = out.reshape(b, n * block, groups * h, plan.d_v)
    return out, lse.reshape(b, h, n * rows)


def attention_backward(
    q, k, v, do, lse, delta, *, causal: bool, block: int, s_len: int,
    groups: int = 1, window: Optional[int] = None, interpret: bool = False,
    grad_dtype=None,
):
    """dq, dk, dv of :func:`attention_forward`'s operands from its
    ``lse``, the cotangent ``do`` (laid out as ``out``) and ``delta =
    rowsum(do * out)`` in lse's row order (``delta_rows``), ``[B, H, nb *
    rows]`` float32 (both may cover MORE keys than this call sees: the
    ring's are the whole row's)."""
    plan = _plan(k, v, causal, block, s_len, groups, window)
    b, _, h, _ = k.shape
    n, rows, heads = plan.n_blocks, plan.rows, plan.heads
    w, w_v = heads * plan.d, heads * plan.d_v

    def fetched(j, t):
        """The query block of step (j, t): clamped to the diagonal, and
        with a band to the sequence's last block."""
        if window is not None:
            return jnp.minimum(j + t, n - 1)
        return jnp.maximum(t, j) if causal else t

    def query_block(bi, hi, j, i):
        return bi, fetched(j, i), hi

    def query_rows(bi, hi, j, i):
        return bi, hi, 0, fetched(j, i)

    def key_block(bi, hi, j, i):
        return bi, j, hi

    per_row = pl.BlockSpec((None, None, heads, rows), query_rows)
    stats = lambda x: x.astype(F32).reshape(b, h // heads, heads, n * rows)  # noqa: E731
    dq, dk, dv = pl.pallas_call(
        functools.partial(_backward_kernel, plan=plan),
        out_shape=[
            jax.ShapeDtypeStruct(
                (b, n * block, groups * h * plan.d), grad_dtype or q.dtype
            ),
            jax.ShapeDtypeStruct((b, n * block, h * plan.d), grad_dtype or k.dtype),
            jax.ShapeDtypeStruct((b, n * block, h * plan.d_v), grad_dtype or v.dtype),
        ],
        grid=(b, h // heads, n, plan.sweep),
        in_specs=[
            pl.BlockSpec((None, block, groups * w), query_block),
            pl.BlockSpec((None, block, w), key_block),
            pl.BlockSpec((None, block, w_v), key_block),
            pl.BlockSpec((None, block, groups * w_v), query_block),
            per_row, per_row,
        ],
        out_specs=[
            # One head block's dq for the whole sequence stays in VMEM.
            pl.BlockSpec(
                (None, n * block, groups * w), lambda bi, hi, j, i: (bi, 0, hi)
            ),
            pl.BlockSpec((None, block, w), key_block),
            pl.BlockSpec((None, block, w_v), key_block),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block, plan.operand_width(plan.d)), k.dtype),
            pltpu.VMEM((heads, block, plan.operand_width(plan.d_v)), v.dtype),
            pltpu.VMEM((block, w), F32),
            pltpu.VMEM((block, w_v), F32),
            pltpu.VMEM((n * block, groups * w), F32),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name=BACKWARD,
    )(_lanes(q), _lanes(k), _lanes(v), _lanes(do).astype(v.dtype),
      stats(lse), stats(delta))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# --- entry points ------------------------------------------------------------


def _delta_kernel(do_ref, o_ref, delta_ref, *, groups: int, d_v: int):
    """One key head's ``[1, rows]`` of delta from its query heads' lanes
    of a dO and an O block: a head's row sums, turned to lie along the
    lanes, one head after the other."""
    prod = do_ref[...].astype(F32) * o_ref[...].astype(F32)
    delta_ref[...] = jnp.concatenate([
        jnp.sum(
            jnp.transpose(prod[:, g * d_v:(g + 1) * d_v]), axis=0, keepdims=True
        )
        for g in range(groups)
    ], axis=1)


def delta_rows(
    do, out, block: int = 0, groups: int = 1, interpret: bool = False
):
    """``rowsum(dO * O)`` of ``[B, S, H * groups, Dv]`` as ``[B, H, S *
    groups]`` float32, the softmax derivative's correction term, in the
    row order the kernels keep their scalars in: within each ``block``
    of positions a key head's query heads one after the other — the only
    array that is ever rearranged for the groups. Grouped heads of whole
    lane tiles are summed by one more small kernel, which reads dO and O
    where they lie, ``[B, S, Hq * Dv]``: XLA's sum would first copy
    their float32 product, 0.5 GB in Mellum 2's cell, into the ``[..,
    Hq, Dv]`` tiling (2.7 ms a layer against 0.72, PERF.md §6, PR 37).
    Equal heads keep XLA's sum, and their program what it was."""
    b, s, hq, d_v = do.shape
    h = hq // groups
    if groups > 1 and d_v % LANES == 0:
        rows = groups * block
        query = pl.BlockSpec(
            (None, block, groups * d_v), lambda bi, hi, i: (bi, i, hi)
        )
        return pl.pallas_call(
            functools.partial(_delta_kernel, groups=groups, d_v=d_v),
            out_shape=jax.ShapeDtypeStruct((b, h, 1, s * groups), F32),
            grid=(b, h, s // block),
            in_specs=[query, query],
            out_specs=pl.BlockSpec(
                (None, None, 1, rows), lambda bi, hi, i: (bi, hi, 0, i)
            ),
            compiler_params=_params("parallel", "parallel", "parallel"),
            interpret=interpret,
            name=DELTA,
        )(_lanes(do), _lanes(out)).reshape(b, h, -1)
    delta = jnp.sum(do.astype(F32) * out.astype(F32), axis=-1)
    if groups == 1:
        return jnp.moveaxis(delta, 1, 2)
    delta = delta.reshape(b, s // block, block, h, groups)
    return delta.transpose(0, 3, 1, 4, 2).reshape(b, h, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal: bool, block: int, s_len: int, interpret: bool):
    return _flash_fwd(q, k, v, causal, block, s_len, interpret)[0]


def _flash_fwd(q, k, v, causal, block, s_len, interpret):
    out, lse = attention_forward(
        q, k, v, causal=causal, block=block, s_len=s_len, interpret=interpret
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block, s_len, interpret, res, do):
    q, k, v, out, lse = res
    return attention_backward(
        q, k, v, do, lse, delta_rows(do, out), causal=causal, block=block,
        s_len=s_len, interpret=interpret,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    block: int = 1024,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The kernels by name, differentiable: q/k/v ``[B, S, H, D]`` ->
    ``[B, S, H, D]``, on any backend (``interpret=None``: compiled on a
    TPU, the emulator elsewhere). What
    :func:`~tpfl.parallel.ring_attention.blockwise_attention` runs on a
    TPU by itself, with the block chosen here.

    ``block``: the key block and, with equal heads, the query block; a
    shorter sequence is one block, a longer one is padded to a multiple
    (pad keys are masked, pad rows dropped). 1024 suits H = 8, D = 128
    at 8k and more (pre-PR-1 rates, not re-measured:
    ``docs/perf_attention.md``); the benchmark's cells run 512 through
    ``blockwise_attention``."""
    interpret = compat.pallas_interpret(interpret)
    s = q.shape[1]
    blk = min(block, s)
    pad = -s % blk
    fits = tiles(
        (0, s + pad, *k.shape[2:]), v.shape[-1], blk,
        grad_bytes=q.dtype.itemsize,
    )
    if not (interpret or fits):
        from tpfl.parallel.ring_attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal, block_size=blk)
    if pad:
        q, k, v = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v)
        )
    return _flash(q, k, v, causal, blk, s, interpret)[:, :s]


def ring_block_size(s: int, block: int) -> int:
    """Largest kernel block <= ``block`` that tiles ``s`` exactly — ring
    steps need no padding (an off-diagonal ring step is FULL attention
    over the step's keys). Multiples of 128 keep the kernels' tiling; if
    none divides, one s-sized block is always legal."""
    if s <= block:
        return s
    blk = (block // LANES) * LANES
    while blk >= LANES and s % blk:
        blk -= LANES
    return blk if blk >= LANES and s % blk == 0 else s


def flash_block_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool,
    block: int = 1024,
    interpret: bool | None = None,
):
    """One forward over a (q-block, kv-block) pair of the ring,
    returning ``(out float32, lse [B, H, S] float32)`` — the ring merges
    steps by logsumexp at float32, so it needs the softmax residual and
    an output no step has rounded. Not differentiable on its own: the
    ring defines its own VJP."""
    s = q.shape[1]
    return attention_forward(
        q, k, v, causal=causal, block=ring_block_size(s, block), s_len=s,
        interpret=compat.pallas_interpret(interpret), out_dtype=F32,
    )


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
    """The backward for one (q-block, kv-block) pair of the ring with
    EXTERNAL softmax residuals: ``lse`` / ``delta`` are ``[B, H, S]``
    float32 over the FULL attention row (all ring steps), so the
    per-step contributions recomputed here sum exactly to the global
    gradient. Returns float32 (dq, dk, dv): they sum in the ring's
    float32 accumulators, and rounding each step first would compound."""
    s = q.shape[1]
    return attention_backward(
        q, k, v, do, lse, delta, causal=causal,
        block=ring_block_size(s, block), s_len=s,
        interpret=compat.pallas_interpret(interpret), grad_dtype=F32,
    )
