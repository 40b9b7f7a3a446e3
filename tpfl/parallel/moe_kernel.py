"""The expert layer's way back to tokens as a Pallas TPU kernel — a
token tile's rows are read where they lie.

:func:`tpfl.parallel.moe.held_experts_moe` sorts its (token, choice)
pairs by group and, inside a group, by token. So the rows that the
tokens of one tile ``[i tt, (i + 1) tt)`` have in one group are ONE
contiguous run of the row buffer. The gather that brought rows back to
tokens fetched ``k`` slots a token (most of them not held, clipped to
some row and masked away) at 50-65 ns a row whatever the row held
(PERF.md §5.6, PR 33); here a tile's runs are copied whole.

Grid: the token tiles, in order. The output block ``[tt, d]`` float32
stays in VMEM for its tile. For each group with rows in the tile, the
128-row blocks of the buffer that its run touches (one, two where the
run straddles a block's edge, more where a router crowds one expert: a
DYNAMIC count, so nothing is dropped and no imbalance needs another
path) are copied from HBM to a VMEM stage — always one block ahead of
the block being spread, across runs and across tiles. Spreading is one
MXU product with a 0 / 1 matrix ``[tt, 128]`` — token ``t`` of the tile
against the token of each staged row (``row_token``, resident in VMEM;
-1 for a staged row outside the run, so a neighbouring group's rows of
the same tile are not counted twice) — accumulated in float32: with
bf16 rows a product by 0 or 1 is exact and the sum over a token's at
most ``k`` rows is the float32 sum the gather made, in another order;
float32 rows multiply at the highest precision. Past the groups a
buffer holds whatever its kernels left there: a block that reaches
there has those rows ZEROED first (``where``, not a product). A group
with no row in the tile costs nothing: the sequence of runs skips it
(``following``).

What a call costs is set by the runs it copies, not by the rows they
hold, so the layer takes the kernel where the gather fetches many slots
a run and keeps the gather elsewhere (``moe._RUN_SLOTS``, with both
cells' readings on the v5e; PERF.md §6, PR 35).

Interprets on the CPU (tests), compiles on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Rows a staged block: the MXU's contraction width, and a whole number
#: of sublane tiles of every row dtype.
BLOCK = 128
#: Tokens a tile, at most (``tiles``).
TOKEN_TILE = 512
#: What the kernel may take of the chip's vector memory (128 MiB on a
#: v5e): a tile's float32 output block twice (the pipeline's), the stage
#: twice, the resident ``row_token`` twice and the product's float32
#: result — 17 MiB at Mellum 2's shapes.
_VMEM_LIMIT = 64 * 2**20


def tiles(rows: tuple, dtype, tokens: int, groups: int) -> int:
    """The token tile with which the kernel takes a buffer of ``rows``
    ``[n, d]`` back to ``tokens`` tokens over ``groups`` groups —
    ``TOKEN_TILE`` halved until it divides the tokens; 0 where it does
    not take it: a buffer that is no whole number of blocks or of lane
    tiles, a row dtype the MXU does not multiply by 0 and 1 exactly,
    fewer than 8 tokens a tile, or tables too large to stay resident."""
    n, d = rows
    tile = TOKEN_TILE
    while tile >= 8 and tokens % tile:
        tile //= 2
    if (
        tile < 8 or n % BLOCK or d % 128
        or dtype not in (jnp.bfloat16, jnp.float32)
    ):
        return 0
    resident = 2 * (tile * d * 4 + BLOCK * d * 4 + n * 4) + tile * d * 4
    if resident > _VMEM_LIMIT or (tokens // tile) * groups > 2**16:
        return 0
    return tile


def _kernel(
    lo_ref, hi_ref, next_ref, live_ref, token_ref, rows_ref, out_ref,
    stage, sem, done,
    *, groups: int, tile: int, precision,
):
    """One token tile. The runs of all tiles are ONE sequence (``lo``,
    ``hi`` flat, tile by tile); ``next_ref[r]`` is the first run from
    ``r`` on that has rows. Blocks are copied one ahead of the block
    being spread, across runs and across tiles: when a tile begins, the
    first block it needs is already on its way (``done`` counts the
    blocks spread so far; a block's stage slot is its parity)."""
    tile_id, n_runs = pl.program_id(0), pl.num_programs(0) * groups
    end = (tile_id + 1) * groups
    out_ref[...] = jnp.zeros_like(out_ref)
    # Token t of the tile on sublane t, against a staged row on each lane.
    tile_token = tile_id * tile + lax.broadcasted_iota(
        jnp.int32, (tile, BLOCK), 0
    )
    lane = lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)
    live = live_ref[0]

    def first_block(run):
        return lo_ref[jnp.minimum(run, n_runs - 1)] // BLOCK

    def copy(block, slot):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(pl.multiple_of(block * BLOCK, BLOCK), BLOCK)],
            stage.at[slot], sem.at[slot],
        )

    @pl.when(tile_id == 0)
    def _():
        done[0] = 0

        @pl.when(next_ref[0] < n_runs)
        def _():
            copy(first_block(next_ref[0]), 0).start()

    def spread(carry):
        """``out[t] += stage[j]`` for the staged rows of ``block`` that
        lie in ``run`` and whose token is ``t``; the block after it is
        set going first."""
        run, block, count = carry
        slot = count % 2
        lo, hi = lo_ref[run], hi_ref[run]
        within = block < (hi - 1) // BLOCK
        after = jnp.where(within, run, next_ref[run + 1])
        after_block = jnp.where(within, block + 1, first_block(after))

        @pl.when(after < n_runs)
        def _():
            copy(after_block, 1 - slot).start()

        copy(block, slot).wait()
        at = block * BLOCK

        # Past the groups a buffer holds whatever its kernels left there:
        # zeroed (never multiplied by 0) before the product reads it.
        @pl.when(at + BLOCK > live)
        def _():
            rows = at + lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
            stage[slot] = jnp.where(rows < live, stage[slot], 0)

        token = jnp.where(
            (at + lane >= lo) & (at + lane < hi),
            token_ref[pl.ds(block, 1), :], -1,
        )
        out_ref[...] += jnp.dot(
            (tile_token == token).astype(stage.dtype), stage[slot],
            preferred_element_type=jnp.float32, precision=precision,
        )
        return after, after_block, count + 1

    run = next_ref[tile_id * groups]
    _, _, done[0] = lax.while_loop(
        lambda carry: carry[0] < end, spread,
        (run, first_block(run), done[0]),
    )


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def rows_to_tokens(rows, row_token, lo, hi, tile: int, interpret: bool):
    """``out[t] = sum of rows[r] over the rows r of token t`` as
    ``[T, d]`` float32, for a buffer ``rows [n, d]`` sorted by group and
    by token inside a group: ``row_token [n]`` int32 a row's token;
    ``lo, hi [T / tile, groups]`` int32 the run ``[lo, hi)`` of each
    token tile's rows in each group (empty where it has none). The rows
    from the largest ``hi`` on belong to no token and are not read as
    values; every row before it is finite or poisons its block's tile.
    ``tile`` from :func:`tiles`. Jitted, so that the kernel is traced
    once a shape and not once a trace of the layer (56 times in a window
    of Mellum 2's cell: 4 s of set-up)."""
    n, d = rows.shape
    n_tiles, groups = lo.shape
    lo, hi = lo.reshape(-1), hi.reshape(-1)
    runs = jnp.arange(lo.shape[0] + 1, dtype=jnp.int32)
    has_rows = jnp.concatenate([hi > lo, jnp.ones((1,), bool)])
    following = lax.cummin(
        jnp.where(has_rows, runs, runs[-1]), axis=0, reverse=True
    )
    precision = (
        lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, groups=groups, tile=tile, precision=precision
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((n // BLOCK, BLOCK), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, BLOCK, d), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_rows_to_tokens",
    )(
        lo, hi, following, jnp.max(hi, keepdims=True),
        row_token.reshape(n // BLOCK, BLOCK), rows,
    )
