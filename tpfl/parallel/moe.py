"""Sparse experts: the layer on the engine's path, and the two
``ep``-axis originals.

**On the engine's path** — :func:`held_experts_moe` (with
:func:`route_top_k`, or :func:`route_by_probability` where the gate is
the probability itself): a DROPLESS top-k SwiGLU layer that is told which
experts it holds. The router keeps its published width (all ``E``
experts) and its ``k`` experts a token; this chip computes the part of
the result that its ``n_held`` experts give — the (token, choice) pairs
whose expert is held are sorted by expert, their tokens gathered into a
row buffer whose static size is the worst case (every choice of every
token held: ``k`` slots a token), and the SwiGLU products (gate and up
side by side as one, then down) run as grouped matmuls over the rows
actually routed here, whatever their split over the experts: nothing is
dropped and nothing is computed for an expert a token did not choose.
What the absent experts would add is left out (the model-configs guide's
cut: the chip's share of an expert-parallel layer, run without its
exchange). Under the engine's ``jax.vmap`` over silos the batch FOLDS
into the groups (one row buffer, ``silos x n_held`` groups: no batched
kernel and no loop over silos); it runs inside ``nn.remat``; its
backward recomputes its row buffer, so nothing of the buffer's size is
banked. On a TPU the way back from rows to tokens, forward and
backward, is the Pallas kernel of :mod:`tpfl.parallel.moe_kernel` where
a token's choices are many enough to pay for it (``_with_runs``).
``tpfl.models.MellumLM`` (top-8 of 64, a quarter held) and
``tpfl.models.ZayaLM`` (top-1 of 16, half held) are its users; the
benchmark cells ``mellum2_silo_8k`` and ``zaya1_silo_8k`` run it.

**The ``ep``-axis originals, which no cell and no zoo model runs** —
``make_moe_layer`` (top-1 serving dispatch) and ``make_moe_train_layer``
(a trainable top-k layer with a Switch-style load-balance loss): ONE
expert per device of an ``ep`` mesh axis, tokens packed into a static
``[n, capacity, D]`` buffer and swapped by ``all_to_all``;
over-capacity tokens pass through on the residual path (they DROP).
They stay as the exchange an expert rule on a mesh axis would start
from (ROADMAP R1).

Static shapes throughout — routing is data-dependent but expressed as
sorts, gathers and group sizes, never shape-changing, so every layer
jits.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpfl.parallel import compat, moe_kernel
from tpfl.parallel.compat import shard_map

# --- the held-experts layer (the engine's path) -------------------------------


def _load(expert: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """The share of the token-choices ``expert [T, k]`` each of the
    ``n_experts`` received."""
    chosen = expert[..., None] == jnp.arange(n_experts)
    return jnp.sum(chosen, axis=(0, 1), dtype=jnp.float32) / expert.size


def route_top_k(logits: jnp.ndarray, k: int) -> tuple:
    """``logits [T, E]`` -> ``(gate [T, k], expert [T, k], load [E])``:
    softmax over ALL ``E`` experts in float32, the ``k`` largest, their
    probabilities normalised over the ``k`` (``norm_topk_prob``), and the
    share of the ``T k`` token-choices each expert received."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    gate = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return gate, top_e, _load(top_e, logits.shape[-1])


def route_by_probability(
    logits: jnp.ndarray, k: int, bias: "jnp.ndarray | float" = 0.0
) -> tuple:
    """:func:`route_top_k` with the gate left as it is: ``gate [T, k]``
    is the chosen experts' PROBABILITY under the softmax over all ``E``,
    not normalised over the ``k`` — at ``k = 1`` normalising makes every
    gate 1 and cuts the router off from the loss. ``bias [E]`` (a
    load-balancing bias kept as state, not trained by the loss:
    ``ZayaLM``'s ``balance_bias``) moves the CHOICE, ``top_k(probs +
    bias)``, and not the gate."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, top_e = lax.top_k(probs + lax.stop_gradient(bias), k)
    gate = jnp.take_along_axis(probs, top_e, axis=-1)
    return gate, top_e, _load(top_e, logits.shape[-1])


# The three grouped products. ``sizes [G]`` splits the leading rows of a
# row buffer into G consecutive groups; rows past their sum belong to no
# group: they are not computed, and what a result holds there is
# UNSPECIFIED (callers mask with ``where``, never by multiplying).
#
# Two forms, chosen by what can be observed (``compat.on_tpu``), as the
# attention block loop's are: on a TPU the Pallas grouped-matmul kernels
# of ``jax.experimental.pallas.ops.tpu.megablox`` (their grid is the
# row tiles the groups cover, not the buffer's; and they carry the
# caller's named scope into a device trace, which XLA's own lowering of
# ``ragged_dot`` does not: PERF.md §6, PR 32), elsewhere
# ``lax.ragged_dot``. Neither takes a batch dimension on a TPU: the
# layer folds a ``vmap`` into the groups instead (``_fold_silos``).

#: Rows a tile of the Pallas kernels, at most (a buffer that is no
#: multiple takes the largest power of two that divides it, down to a
#: sublane tile); the other two tile sizes are ``_tile`` of the widths
#: (2304 -> 768, 896 -> 896, 1792 -> 896, 2048 and 4096 -> 1024: whole
#: divisors, within the 16 MiB of VMEM a kernel gets by default; 1024
#: rows do not fit it).
_TILE_ROWS = 512
#: What the weight-gradient kernel's tiles may take of those 16 MiB (its
#: float32 output tile is held three times over: ``_weight_grad_tiles``).
_VMEM_BYTES = 14 * 2**20


def _tile(width: int, most: int = 1024) -> int:
    """The largest multiple of 128 up to ``most`` that divides
    ``width``; ``width`` itself where none does (a block as wide as the
    array is always legal)."""
    fits = [t for t in range(128, most + 1, 128) if width % t == 0]
    return max(fits) if fits else width


def _weight_grad_tiles(rows: int, k: int, n: int) -> tuple:
    """Tiles of ``_gmm_tn``'s kernel, ``(rows, k, n)``: ``_tile`` of
    each width, the wider halved (to its next divisor) while the float32
    output tile — an accumulator and a double-buffered block, 12 bytes
    an element — and the double-buffered bf16 operand tiles pass
    ``_VMEM_BYTES``: 768 x 896 stands, 1024 x 1024 becomes 1024 x 512."""
    tm, tk, tn = _row_tile(rows), _tile(k), _tile(n)
    while 12 * tk * tn + 4 * tm * (tk + tn) > _VMEM_BYTES and max(tk, tn) > 128:
        if tn >= tk:
            tn = _tile(n, tn - 128)
        else:
            tk = _tile(k, tk - 128)
    return tm, tk, tn


def _row_tile(rows: int) -> int:
    """Rows a tile for a buffer of ``rows``: ``_TILE_ROWS`` halved until
    it divides (0 where not even 8 rows do)."""
    tile = _TILE_ROWS
    while tile >= 8 and rows % tile:
        tile //= 2
    return tile if tile >= 8 else 0


def _pallas(rows: int) -> bool:
    """Whether the grouped products run as the Pallas kernels: on a TPU,
    on a row buffer of whole row tiles."""
    return compat.on_tpu() and _row_tile(rows) > 0


def _megablox():
    """The kernels' module (its package exports a function under the
    same name)."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm"
    )


def _gmm(rows, w, sizes):
    """``rows [M, K]`` x ``w [G, K, N]`` -> ``[M, N]``: every row by its
    group's matrix, in the rows' dtype (float32 accumulator)."""
    if _pallas(rows.shape[0]):
        return _megablox().gmm(
            rows, w, sizes, rows.dtype,
            (_row_tile(rows.shape[0]), _tile(w.shape[1]), _tile(w.shape[2])),
            interpret=compat.pallas_interpret(None),
        )
    return lax.ragged_dot(rows, w, sizes, preferred_element_type=rows.dtype)


def _gmm_nt(rows, w, sizes):
    """``rows [M, N]`` x ``w [G, K, N]`` transposed -> ``[M, K]``."""
    if _pallas(rows.shape[0]):
        return _megablox().gmm(
            rows, w, sizes, rows.dtype,
            (_row_tile(rows.shape[0]), _tile(w.shape[2]), _tile(w.shape[1])),
            transpose_rhs=True, interpret=compat.pallas_interpret(None),
        )
    return lax.ragged_dot_general(
        rows, w, sizes,
        lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((1,), (2,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[0],
        ),
        preferred_element_type=rows.dtype,
    )


def _gmm_tn(rows, grads, sizes):
    """``rows [M, K]`` transposed x ``grads [M, N]``, group by group ->
    ``[G, K, N]`` float32: each group's weight gradient."""
    if _pallas(rows.shape[0]):
        return _megablox().tgmm(
            rows.T, grads, sizes, jnp.float32,
            _weight_grad_tiles(rows.shape[0], rows.shape[1], grads.shape[1]),
            interpret=compat.pallas_interpret(None),
        )
    return lax.ragged_dot_general(
        rows, grads, sizes,
        lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
        ),
        preferred_element_type=jnp.float32,
    )


def _fold_silos(fn: Callable, share: tuple) -> Callable:
    """``fn(share, x [T, d], gate [T, k], key [T, k], w_in [G, ..],
    w_out [G, ..], *token_arrays) -> (token arrays.., group arrays..)``
    (``share`` static: the layer's held share, which no fold changes)
    made batchable by FOLDING the batch into the groups: under ``vmap`` over
    S silos it is one call on ``S T`` tokens and ``S G`` groups (silo
    ``s``'s group ``g`` is group ``s G + g``; key ``G``, "not held",
    becomes ``S G``), so the live rows of all silos lie side by side at
    the head of ONE row buffer and the grouped products see no batch
    dimension. A result whose leading size is the tokens' unfolds as a
    token array, one whose leading size is the groups' as a group
    array."""
    folded = jax.custom_batching.custom_vmap(partial(fn, share))

    @folded.def_vmap
    def rule(axis_size, in_batched, x, gate, key, w_in, w_out, *more):
        args = [
            a if batched else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, batched in zip((x, gate, key, w_in, w_out, *more), in_batched)
        ]
        x, gate, key, w_in, w_out, *more = args
        groups = w_in.shape[1]
        silo = jnp.arange(axis_size, dtype=key.dtype)[:, None, None]
        key = jnp.where(key < groups, key + silo * groups, axis_size * groups)
        flat = lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])  # noqa: E731
        outs = folded(*map(flat, (x, gate, key, w_in, w_out, *more)))
        unfolded = tuple(
            o.reshape(axis_size, o.shape[0] // axis_size, *o.shape[1:])
            for o in outs
        )
        return unfolded, tuple(True for _ in outs)

    return folded


#: Positions a block of the counting sort's prefix sums (a triangular
#: matmul a block, exact in float32).
_RANK_BLOCK = 512


def _plan(key, groups: int) -> tuple:
    """``key [T, k]`` (a pair's group; ``groups`` = not held) -> (row ->
    pair ``order``, pair -> row ``pos``, rows a group ``sizes``): the
    held pairs sorted by group at the head of the row buffer, in token
    order within a group, the others behind them. ONE sort (``order``);
    ``pos`` is a counting sort's arithmetic — a pair's row is its
    group's first row plus the number of earlier pairs of that group, a
    prefix sum taken block by block as a triangular matmul — where a
    second sort cost as much as the first."""
    flat = key.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    pairs = flat.shape[0]
    blocks = -(-pairs // _RANK_BLOCK)
    onehot = (flat[:, None] == jnp.arange(groups + 1)).astype(jnp.float32)
    onehot = jnp.pad(onehot, ((0, blocks * _RANK_BLOCK - pairs), (0, 0)))
    onehot = onehot.reshape(blocks, _RANK_BLOCK, groups + 1)
    lower = jnp.tril(jnp.ones((_RANK_BLOCK, _RANK_BLOCK), jnp.float32))
    within = jnp.einsum(
        "ij,bjg->big", lower, onehot, precision=lax.Precision.HIGHEST
    )  # inclusive count inside the block
    totals = within[:, -1, :]
    before = jnp.cumsum(totals, axis=0) - totals  # pairs in earlier blocks
    counts = jnp.sum(totals, axis=0)
    first_row = jnp.cumsum(counts) - counts
    row = jnp.sum(
        onehot * (within + before[:, None, :] + first_row - 1.0), axis=-1
    )
    pos = row.reshape(-1)[:pairs].astype(jnp.int32)
    return order, pos, counts[:groups].astype(jnp.int32)


def _head_rows(slots: int, share: tuple) -> int:
    """Slots of the row buffer's HEAD, the part the layer always works
    on. The held pairs lie sorted at the front of ``slots`` = ``k T``
    slots, the worst case; everything that is no grouped product
    (gathers, the gates, their gradients) costs by the SLOT. So the
    layer splits the buffer in two: a head of one and a half times the
    load of balanced routing — ``share`` = (experts held, experts the
    router chooses among): three eighths of the slots when a quarter of
    the experts is held, three quarters when half of them is — and the
    rest, which it enters only when a live row lies there
    (``lax.cond``): as a rule never, always when every choice of every
    token is held. A small buffer, or one whose experts are all held,
    is all head."""
    n_held, n_experts = share
    if slots <= 8 * _TILE_ROWS:
        return slots
    balanced_x3 = 3 * slots * n_held // (2 * n_experts)
    return min(slots, -(-balanced_x3 // _TILE_ROWS) * _TILE_ROWS)


def _run_edges(key, groups: int, tile: int):
    """``[T / tile + 1, groups]``: the row of the buffer at which the
    pairs that the tokens from tile ``i`` on have in group ``g`` begin —
    the buffer is sorted by group and by token inside a group, so the
    rows of tile ``i``'s tokens in group ``g`` are the ONE run
    ``[edges[i, g], edges[i + 1, g])``."""
    t, k = key.shape
    chosen = key.reshape(t // tile, tile * k, 1) == jnp.arange(groups)
    per_tile = jnp.sum(chosen, axis=1, dtype=jnp.int32)
    sizes = jnp.sum(per_tile, axis=0)
    return (jnp.cumsum(sizes) - sizes) + jnp.concatenate(
        [jnp.zeros((1, groups), jnp.int32), jnp.cumsum(per_tile, axis=0)]
    )


def _parts(order, pos, sizes, held, share) -> tuple:
    """(whether a live row lies past the head, the buffer's parts): a
    part is (its slice of ``order``, the rows of each group inside it,
    and for the way back each pair's row within it with whether the
    pair is held AND there)."""
    slots = order.shape[0]
    head = _head_rows(slots, share)
    ends = jnp.cumsum(sizes)

    def part(lo: int, hi: int) -> tuple:
        inside = lambda edge: jnp.clip(edge, lo, hi)  # noqa: E731
        at = pos - lo
        return (
            order[lo:hi], inside(ends) - inside(ends - sizes),
            jnp.clip(at, 0, hi - lo - 1), held & (at >= 0) & (at < hi - lo),
        )

    parts = [part(0, head)] + ([part(head, slots)] if head < slots else [])
    return ends[-1] > head, parts


#: Slots the gather back to tokens fetches for each run the kernel
#: copies, from which the kernel is taken. A run costs the kernel a
#: 128-row block or two (~1.9 us on the v5e) whatever it holds; the
#: gather pays 27-56 ns a slot. Measured both ways (PERF.md §6, PR 35):
#: 256 slots a run at Mellum 2's shapes — 14.7 ms a call by gather, 2.9
#: by kernel; 64 at ZAYA1's — 0.90 by gather, 1.27 by kernel.
_RUN_SLOTS = 128


def _with_runs(parts: list, x, key, share: tuple) -> list:
    """Each part with the form its way back to tokens takes
    (``_rows_to_tokens``), chosen as ``_pallas`` chooses the grouped
    products' — by ``compat.on_tpu`` and the shapes: where the Pallas
    kernel takes the part's rows (``moe_kernel.tiles``) and a token
    tile's ``k`` slots a token are at least ``_RUN_SLOTS`` for each of
    its ``n_held`` runs, each token tile's run of rows in each group,
    ``(first row, end)`` within the part and cut at its edges; ``None``
    where it is the gather."""
    groups, k = parts[0][1].shape[0], key.shape[1]
    out, lo = [], 0
    for part in parts:
        hi = lo + part[0].shape[0]
        tile = compat.on_tpu() and moe_kernel.tiles(
            (hi - lo, x.shape[1]), x.dtype, key.shape[0], groups
        )
        runs = None
        if tile and k * tile >= _RUN_SLOTS * share[0]:
            edges = jnp.clip(_run_edges(key, groups, tile), lo, hi) - lo
            runs = (edges[:-1], edges[1:])
        out.append((*part, runs))
        lo = hi
    return out


def _over_parts(overflows, parts: list, work: Callable):
    """``work(part)`` summed over the buffer's parts, the part past the
    head entered only when it holds a live row."""
    total = work(parts[0])
    if len(parts) == 1:
        return total
    rest = lax.cond(
        overflows, lambda: work(parts[1]),
        lambda: jax.tree_util.tree_map(jnp.zeros_like, total),
    )
    return jax.tree_util.tree_map(jnp.add, total, rest)


def _rows_to_tokens(rows, part, k: int):
    """``out[t] = sum over a token's k choices of rows[at[t, c]]`` where
    ``here``, float32. Both directions of the layer move rows by GATHER
    (by a row's token one way, by a pair's row the other): a scatter-add
    of ``k T`` rows, which autodiff of a gather brings, a TPU runs row by
    row. Two forms of the same sum, chosen by what can be observed
    (``_with_runs``): on a TPU the Pallas kernel of ``moe_kernel``, which
    reads each token tile's runs of rows where they lie; elsewhere, and
    for a shape the kernel does not take, a gather by the pair's row —
    ``k`` slots a token, the pairs not held masked away."""
    part_order, _, at, here, runs = part
    if runs is not None:
        return moe_kernel.rows_to_tokens(
            rows, part_order // k, *runs, here.shape[0] // runs[0].shape[0],
            compat.pallas_interpret(None),
        )
    t, k = here.shape
    picked = jnp.take(rows, at.reshape(-1), axis=0).reshape(t, k, rows.shape[-1])
    return jnp.sum(
        jnp.where(here[..., None], picked.astype(jnp.float32), 0.0), axis=1
    )


def _expert_rows(x, gate, order, sizes, w_in, k: int):
    """A part's rows and the experts' hidden rows: (rows [n, d], the
    router's weight of each row, gate and up products [n, f] each)."""
    rows = jnp.take(x, order // k, axis=0)
    row_gate = jnp.take(gate.reshape(-1), order)
    return rows, row_gate, jnp.split(_gmm(rows, w_in, sizes), 2, axis=-1)


def _moe_forward(share, x, gate, key, w_in, w_out):
    """(y [T, d], the plan: order and pos [T k])."""
    groups, k = w_in.shape[0], key.shape[1]
    f32, dtype = jnp.float32, x.dtype
    held = key < groups
    with jax.named_scope("moe_dispatch"):
        order, pos, sizes = _plan(key, groups)
        overflows, parts = _parts(
            order, pos.reshape(held.shape), sizes, held, share
        )
        parts = _with_runs(parts, x, key, share)
    with jax.named_scope("moe_experts"):
        w_in, w_out = w_in.astype(dtype), w_out.astype(dtype)

    def work(part):
        part_order, part_sizes = part[:2]
        with jax.named_scope("moe_experts"):
            _, row_gate, (g, u) = _expert_rows(x, gate, part_order, part_sizes, w_in, k)
            # The router's weight on the rows of the NARROW product: the
            # same sum (the down projection is linear), f / d of the work.
            hidden = (
                jax.nn.silu(g.astype(f32)) * u.astype(f32) * row_gate[:, None]
            ).astype(dtype)
            out_rows = _gmm(hidden, w_out, part_sizes)
        with jax.named_scope("moe_combine"):
            return _rows_to_tokens(out_rows, part, k)

    y = _over_parts(overflows, parts, work)
    with jax.named_scope("moe_combine"):
        return y.astype(dtype), order, pos


def _moe_backward(share, x, gate, key, w_in, w_out, dy, order, pos):
    """(dx, dgate, dw_in, dw_out) from the layer's inputs, the forward's
    plan and ``dy``: a RECOMPUTE backward — a part's rows and its first
    product are made again, so the forward banks nothing of the buffer's
    size."""
    groups, k = w_in.shape[0], key.shape[1]
    f32, dtype = jnp.float32, x.dtype
    held = key < groups
    with jax.named_scope("moe_dispatch"):
        sizes = jnp.sum(
            key.reshape(-1, 1) == jnp.arange(groups), axis=0, dtype=jnp.int32
        )
        overflows, parts = _parts(
            order, pos.reshape(held.shape), sizes, held, share
        )
        parts = _with_runs(parts, x, key, share)
    with jax.named_scope("moe_experts"):
        w_in_c, w_out_c = w_in.astype(dtype), w_out.astype(dtype)

    def work(part):
        part_order, part_sizes, at, here = part[:4]
        with jax.named_scope("moe_combine"):
            # The transpose of the combine: a row's gradient is its
            # token's. (Rows past the groups carry their pairs' tokens'
            # too: finite, and never counted.)
            dy_rows = jnp.take(dy, part_order // k, axis=0)
        with jax.named_scope("moe_experts"):
            rows, row_gate, (g, u) = _expert_rows(
                x, gate, part_order, part_sizes, w_in_c, k
            )
            g, u = g.astype(f32), u.astype(f32)
            sig = jax.nn.sigmoid(g)
            act = g * sig * u  # silu(g) * u
            hidden = (act * row_gate[:, None]).astype(dtype)
            d_hidden = _gmm_nt(dy_rows, w_out_c, part_sizes).astype(f32)
            d_w_out = _gmm_tn(hidden, dy_rows, part_sizes)
            d_row_gate = jnp.sum(d_hidden * act, axis=-1)
            d_act = d_hidden * row_gate[:, None]
            d_gu = jnp.concatenate(
                [d_act * u * sig * (1.0 + g * (1.0 - sig)), d_act * g * sig],
                axis=-1,
            ).astype(dtype)
            d_rows = _gmm_nt(d_gu, w_in_c, part_sizes)
            d_w_in = _gmm_tn(rows, d_gu, part_sizes)
        with jax.named_scope("moe_dispatch"):
            # The transpose of the dispatch. A pair that is not held has
            # its row past the groups, where gradients are unspecified:
            # masked by ``where``, never by a product.
            dx = _rows_to_tokens(d_rows, part, k)
            d_gate = jnp.where(
                here, jnp.take(d_row_gate, at.reshape(-1)).reshape(here.shape), 0.0
            )
        return dx, d_gate, d_w_in, d_w_out

    dx, d_gate, d_w_in, d_w_out = _over_parts(overflows, parts, work)
    return (
        dx.astype(dtype), d_gate.astype(gate.dtype),
        d_w_in.astype(w_in.dtype), d_w_out.astype(w_out.dtype),
    )


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(share, x, gate, key, w_in, w_out):
    return _fold_silos(_moe_forward, share)(x, gate, key, w_in, w_out)[0]


def _held_experts_fwd(share, x, gate, key, w_in, w_out):
    y, order, pos = _fold_silos(_moe_forward, share)(x, gate, key, w_in, w_out)
    return y, (x, gate, key, w_in, w_out, order, pos)


def _held_experts_bwd(share, res, dy):
    x, gate, key, w_in, w_out, order, pos = res
    dx, d_gate, d_w_in, d_w_out = _fold_silos(_moe_backward, share)(
        x, gate, key, w_in, w_out, dy, order, pos
    )
    return dx, d_gate, None, d_w_in, d_w_out


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def held_experts_moe(
    x: jnp.ndarray, gate: jnp.ndarray, expert: jnp.ndarray,
    w_in: jnp.ndarray, w_out: jnp.ndarray, first: int, n_experts: int,
) -> jnp.ndarray:
    """The held experts' part of a top-k SwiGLU expert layer.

    ``x [T, d]`` tokens (the compute dtype); ``gate [T, k]`` float32 and
    ``expert [T, k]`` int32 from :func:`route_top_k` or
    :func:`route_by_probability` over ALL ``n_experts`` experts (the
    router's width, static: it sizes the row buffer's head,
    ``_head_rows``, and nothing else);
    ``w_in [n_held, d, 2 f]`` (gate and up projections side by side) and
    ``w_out [n_held, f, d]`` the weights of experts ``first .. first +
    n_held - 1``, the ones this chip holds (any float dtype: multiplied
    in ``x``'s dtype, accumulated in float32; their gradients leave the
    float32 accumulators unrounded, as ``head_cross_entropy``'s).
    Returns ``sum over a token's choices e that are held of gate_e *
    down_e(silu(gate_e x) * up_e x)`` as ``[T, d]`` in ``x``'s dtype —
    ``gate`` is used as it comes (normalised over all ``k`` choices or
    not), so the shares of the chips that hold the other experts add up
    to the whole layer.

    Dropless: the (token, choice) pairs whose expert is held are sorted
    by expert, the others behind them, and their tokens gathered into a
    row buffer of ``k T`` rows — the worst case, every choice held; the
    grouped products visit the rows routed here only, however they split
    over the experts. Differentiable in ``x``, ``gate`` and the weights
    by a recompute backward (nothing of the buffer's size is banked).
    Under ``jax.vmap`` the batch folds into the groups (one row buffer,
    ``S n_held`` groups): no batched kernel, no loop over silos.

    Named scopes ``moe_dispatch`` (sort, plan, and in the backward the
    way back of the tokens' gradients), ``moe_experts`` (the row gather,
    the grouped products and the gates) and ``moe_combine`` (the way
    back to tokens: on a TPU the kernel ``moe_rows_to_tokens``),
    forward and backward."""
    n_held = w_in.shape[0]
    local = expert - first
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    return _held_experts(
        (n_held, n_experts), x, gate, key.astype(jnp.int32), w_in, w_out
    )


# --- the ep-axis originals -----------------------------------------------------


def _dispatch(
    x: jnp.ndarray,
    expert_of: jnp.ndarray,
    expert_fn: Callable[[jnp.ndarray], jnp.ndarray],
    capacity: int,
    axis_name: str = "ep",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One all_to_all dispatch/return pass. ``x``: local tokens [T, D];
    ``expert_of``: [T] int32 — ids in [0, n) dispatch, anything else
    (e.g. -1) means "drop". Returns ``(out [T, D], keep [T] bool)``:
    expert outputs where kept; out rows for dropped/over-capacity
    tokens are zero. Indices are integer (no gradient); gradients flow
    through the token values and the expert computation."""
    n = jax.lax.psum(1, axis_name)
    t, d = x.shape

    valid = (expert_of >= 0) & (expert_of < n)
    expert_of = jnp.where(valid, expert_of, 0)
    # Position of each token within its expert's queue (stable order);
    # invalid tokens occupy no slot.
    onehot = jax.nn.one_hot(expert_of, n, dtype=jnp.int32) * valid[:, None]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot  # 1-based
    pos = jnp.sum(pos_in_expert, axis=1) - 1  # [T], 0-based; invalid -> -1
    keep = valid & (pos < capacity)

    # Pack tokens into the [n, C, D] dispatch buffer.
    buf = jnp.zeros((n, capacity, d), x.dtype)
    slot_e = jnp.where(keep, expert_of, 0)
    slot_c = jnp.where(keep, pos, 0)
    contrib = jnp.where(keep[:, None], x, 0.0)
    buf = buf.at[slot_e, slot_c].add(contrib)

    # Swap: device i's buf[e] goes to device e; device e receives its
    # expert's tokens from everyone -> [n_src, C, D].
    received = jax.lax.all_to_all(
        buf, axis_name, split_axis=0, concat_axis=0, tiled=False
    )
    out = expert_fn(received.reshape(n * capacity, d)).reshape(
        n, capacity, d
    )
    # Swap back: results return to the token owners.
    returned = jax.lax.all_to_all(
        out, axis_name, split_axis=0, concat_axis=0, tiled=False
    )
    gathered = returned[slot_e, slot_c]  # [T, D]
    return jnp.where(keep[:, None], gathered, 0.0), keep


def moe_dispatch(
    x: jnp.ndarray,
    expert_of: jnp.ndarray,
    expert_fn: Callable[[jnp.ndarray], jnp.ndarray],
    capacity: int,
    axis_name: str = "ep",
) -> jnp.ndarray:
    """Top-1 dispatch with residual passthrough: expert outputs for
    dispatched tokens, the token itself for dropped/over-capacity ones
    (standard Switch-style dropping)."""
    out, keep = _dispatch(x, expert_of, expert_fn, capacity, axis_name)
    return jnp.where(keep[:, None], out, x)


def moe_forward_topk(
    router_w: jnp.ndarray,
    expert_params: Any,
    x: jnp.ndarray,
    expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    capacity: int,
    k: int = 2,
    axis_name: str = "ep",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run inside shard_map: differentiable top-k MoE for TRAINING.

    ``router_w`` [D, n] (replicated), ``expert_params`` stacked with
    this device's expert at index 0 after sharding, ``x`` local tokens
    [T, D]. Returns ``(y [T, D], aux_loss scalar)``.

    - Routing: softmax over router logits; ``lax.top_k`` picks k
      experts per token; combine weights are the renormalized top-k
      probabilities, so the router receives gradients through the
      weighted combine (the standard top-k MoE estimator — dispatch
      indices themselves are integers and carry none).
    - Unprocessed probability mass (dropped/over-capacity choices)
      falls back to the residual path: y includes (1 - kept mass) * x,
      keeping the layer smooth as capacity bites.
    - ``aux_loss``: Switch-Transformer load-balance loss, n * sum_e
      (token fraction routed to e) * (mean router prob of e), pmean'd
      over the axis — minimized (= 1) at a uniform expert load.
    """
    n = jax.lax.psum(1, axis_name)
    my_params = jax.tree_util.tree_map(lambda p: p[0], expert_params)
    logits = x @ router_w  # [T, n]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # [T, k]
    gate = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    y = jnp.zeros_like(x)
    kept_mass = jnp.zeros((x.shape[0],), x.dtype)
    # k dispatch passes, each with its own capacity-C buffer (capacity
    # is counted per choice rank, not jointly — document at call site).
    for j in range(k):
        out_j, keep_j = _dispatch(
            x,
            top_e[:, j],
            lambda toks: expert_fn(my_params, toks),
            capacity,
            axis_name,
        )
        w_j = gate[:, j].astype(x.dtype) * keep_j.astype(x.dtype)
        y = y + w_j[:, None] * out_j
        kept_mass = kept_mass + w_j
    y = y + (1.0 - kept_mass)[:, None] * x

    # Load-balance: fraction of tokens whose TOP choice is e, times the
    # mean router probability of e (Shazeer/Fedus et al.).
    f = jnp.mean(jax.nn.one_hot(top_e[:, 0], n, dtype=jnp.float32), axis=0)
    p_mean = jnp.mean(probs, axis=0)
    f = jax.lax.pmean(f, axis_name)
    p_mean = jax.lax.pmean(p_mean, axis_name)
    aux_loss = n * jnp.sum(f * p_mean)
    return y, aux_loss


def make_moe_train_layer(
    mesh: Mesh,
    expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    capacity: int,
    k: int = 2,
    axis_name: str = "ep",
):
    """Trainable expert-parallel layer over ``mesh[axis_name]``.

    Returns ``apply(params, tokens) -> (y, aux_loss)`` (jitted), where
    ``params = {"router": [D, n_experts], "experts": stacked expert
    params [n_experts, ...]}``. Differentiable end-to-end: router
    gradients flow through the top-k combine weights, expert gradients
    through the dispatched tokens, and ``aux_loss`` (add it to the task
    loss scaled by ~1e-2) pushes the router toward balanced expert
    load. Capacity is per choice rank (k buffers of ``capacity``), not
    a joint budget."""
    n = mesh.shape[axis_name]
    param_spec = PartitionSpec(axis_name)
    tok_spec = PartitionSpec(axis_name)

    fn = shard_map(
        partial(
            _train_local,
            expert_fn=expert_fn,
            capacity=capacity,
            k=k,
            axis_name=axis_name,
        ),
        mesh=mesh,
        in_specs=(PartitionSpec(), param_spec, tok_spec),
        out_specs=(tok_spec, PartitionSpec()),
        check_vma=False,
    )

    def apply(params: Any, tokens: jnp.ndarray):
        experts = params["experts"]
        for leaf in jax.tree_util.tree_leaves(experts):
            if leaf.shape[0] != n:
                raise ValueError(
                    f"Expert param leading dim {leaf.shape[0]} != mesh "
                    f"axis {axis_name}={n} (one expert per device)"
                )
        router = params["router"]
        if router.shape[-1] != n:
            raise ValueError(
                f"Router output dim {router.shape[-1]} != n_experts {n}"
            )
        experts = jax.tree_util.tree_map(
            lambda p: jax.device_put(p, NamedSharding(mesh, param_spec)),
            experts,
        )
        return fn(
            router,
            experts,
            jax.device_put(tokens, NamedSharding(mesh, tok_spec)),
        )

    return jax.jit(apply)


def _train_local(router_w, expert_params, x, *, expert_fn, capacity, k, axis_name):
    return moe_forward_topk(
        router_w, expert_params, x, expert_fn, capacity, k, axis_name
    )


def make_moe_layer(
    mesh: Mesh,
    expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    router_fn: Callable[[jnp.ndarray], jnp.ndarray],
    capacity: int,
    axis_name: str = "ep",
):
    """Jitted expert-parallel layer over ``mesh[axis_name]``.

    ``expert_fn(expert_params, tokens)``: one expert's computation;
    expert params arrive stacked [n_experts, ...] and are sharded one
    per device. ``router_fn(tokens) -> [T] int32`` picks the expert.
    Tokens [T_global, D] are sharded over the axis."""
    n = mesh.shape[axis_name]
    param_spec = PartitionSpec(axis_name)
    tok_spec = PartitionSpec(axis_name)

    def local(params, x):
        # Router ids outside [0, n) take the residual passthrough (the
        # moe_dispatch drop convention) — never silently clamped onto a
        # wrong expert.
        expert_of = router_fn(x).astype(jnp.int32)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        return moe_dispatch(
            x,
            expert_of,
            lambda toks: expert_fn(my_params, toks),
            capacity,
            axis_name,
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(param_spec, tok_spec),
        out_specs=tok_spec,
        check_vma=False,
    )

    def apply(stacked_expert_params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
        for leaf in jax.tree_util.tree_leaves(stacked_expert_params):
            if leaf.shape[0] != n:
                raise ValueError(
                    f"Expert param leading dim {leaf.shape[0]} != mesh "
                    f"axis {axis_name}={n} (one expert per device; "
                    f"p[0] would silently drop the rest)"
                )
        stacked_expert_params = jax.tree_util.tree_map(
            lambda p: jax.device_put(p, NamedSharding(mesh, param_spec)),
            stacked_expert_params,
        )
        return fn(
            stacked_expert_params,
            jax.device_put(tokens, NamedSharding(mesh, tok_spec)),
        )

    return jax.jit(apply)
