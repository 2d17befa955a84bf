"""The grouped product of the routed experts as a Pallas TPU kernel: rows
sorted by expert against the stacked expert matrices ``[E, K, N]``, each
touched expert's matrix streamed from HBM once a call.

Why this exists: at the serving shapes an expert gets 2-30 rows against a
matrix of 4-6 MB, so the product is a weight stream and the arithmetic is
free; XLA's ``ragged_dot`` reads the experts at a third of the chip's
bandwidth, runs every row of the call whether an expert of this chip owns it
or not, and is called three times an expert layer.

How it works.  ``counts [E]`` says how many of the sorted rows each expert
owns; rows past ``counts.sum()`` belong to nobody (pairs of experts this chip
does not hold, masked tokens) and come out as zeros.  The rows are cut into
tiles of :data:`ROW_TILE`; :func:`plan` lists the WORK ITEMS of a call, one
for every (expert with a row, row tile it reaches into), in expert order, and
after them one item for every row tile no expert reaches.  The grid is
``(column blocks of N, items)``; the lists go in by scalar prefetch.  The
experts' matrices stay in HBM and the kernel copies a block ``[K, columns]``
of an item's expert into one of two VMEM buffers itself: an expert's FIRST
item waits for its block and at once starts the copy of the next expert
that has a row (in the last expert's case, of the first one's next column
block), so a copy is in flight while every item of the expert before it
computes, an expert is fetched once a column block however many row tiles
it reaches into, and an expert without a row is never fetched.  (Left to
Pallas's own pipeline, which looks one grid step ahead, the second item of
an expert that straddles a tile edge leaves the copy engine idle: 5-10% of
a call at the cells' shapes.)  An item computes its whole row tile against
the block and keeps the rows its expert owns (a mask): an output tile is
visited by consecutive items only, the first of which also zeroes the rows
nobody owns.  The number of items is bounded by ``row tiles + E - 1``
whatever the counts, which is the (static) grid; the items past the last
real one do nothing.

Block sizes follow from the shapes a call sees (:func:`_column_block`): the
whole contraction dim and as many columns as keep one fetch near
:data:`BLOCK_BYTES`.  On the CPU backend the kernel runs interpreted
(``flash_attention._interpret``).  Nothing differentiates through it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dstack_tpu.ops import flash_attention as _fa

#: rows of a row tile: a multiple of the sublane tile of every dtype served,
#: and one pass of the 128-wide MXU
ROW_TILE = 128
#: bytes of expert weights one copy brings, at most: a DMA of ten
#: microseconds against a third of a microsecond of grid-step overhead (on
#: the chip 4, 8 and 16 MB read within 1.5% of each other, 2 MB 3-5% lower)
BLOCK_BYTES = 8 << 20
#: the kernel's name in a compiled program and in a profiler trace
KERNEL_NAME = "grouped_matmul"


def _running_sum(v):
    """Inclusive running sum of a short int vector as one masked sum: the
    plan is made in every program of every layer, and ``jnp.cumsum`` (an
    associative scan) costs ten times this to lower and to run at 64-512
    entries."""
    i = jnp.arange(v.shape[0])
    return jnp.sum(jnp.where(i[None, :] <= i[:, None], v[None, :], 0), axis=1)


def _reach(counts) -> tuple:
    """``(starts, ends, reach)`` of every expert ``[E]``: where its rows
    start and end among the sorted rows, and how many row tiles they reach
    into (0 without a row)."""
    ends = _running_sum(counts)
    starts = ends - counts
    reach = (ends - 1) // ROW_TILE - starts // ROW_TILE + 1
    return starts, ends, jnp.where(counts > 0, reach, 0)


def row_tiles_visited(counts):
    """Row tiles the product computes for ``counts [E]``: for every expert
    with a row, the tiles its rows reach into (int32 scalar)."""
    return _reach(counts)[2].sum().astype(jnp.int32)


def plan(counts, tiles: int) -> tuple:
    """The work items of a call over ``tiles`` row tiles, as the scalar
    prefetch operands of the kernel: ``(expert, out_tile, x_tile, lead,
    next_expert)`` of every item ``[tiles + E - 1]`` (``lead``: 1 on an
    expert's first item, 2 on the last expert's; ``next_expert``: the expert
    with a row after this item's, the first one after the last), ``starts``
    and ``ends`` of every expert's rows ``[E]``, and ``[computing items,
    computing + zeroing items]``.  Short vectors and small comparison
    tables only: nothing here scans or sorts."""
    counts = counts.astype(jnp.int32)
    e = counts.shape[0]
    experts = jnp.arange(e, dtype=jnp.int32)
    starts, ends, reach = _reach(counts)
    first = starts // ROW_TILE
    item_end = _running_sum(reach)
    item_start = item_end - reach
    live_items = item_end[-1]
    w = jnp.arange(tiles + e - 1, dtype=jnp.int32)
    # items past the computing ones keep the last computing item's expert
    # and row tile, so that they fetch nothing
    at = jnp.minimum(w, jnp.maximum(live_items - 1, 0))
    # an item's expert: how many experts' items end at or before it
    expert = jnp.minimum(jnp.sum(item_end[None, :] <= at[:, None], axis=1,
                                 dtype=jnp.int32), e - 1)
    x_tile = jnp.where(live_items > 0, first[expert] + at - item_start[expert],
                       0)
    # the row tiles no expert reaches, then nothing: the last tile again
    untouched = (ends[-1] + ROW_TILE - 1) // ROW_TILE
    out_tile = jnp.where(w < live_items, x_tile,
                         jnp.minimum(untouched + w - live_items, tiles - 1))
    zeroing = jnp.maximum(tiles - untouched, 0)
    # the experts with a row, as a ring: who follows whom, who is last
    with_row = jnp.where(counts > 0, experts, e)
    after = jnp.min(jnp.where(experts[None, :] > experts[:, None],
                              with_row[None, :], e), axis=1)
    following = jnp.minimum(jnp.where(after < e, after, with_row.min()),
                            e - 1)
    last = jnp.max(jnp.where(counts > 0, experts, -1))
    lead = jnp.where((w < live_items) & (w == item_start[expert]),
                     jnp.where(expert == last, 2, 1), 0)
    return (expert, out_tile.astype(jnp.int32), x_tile.astype(jnp.int32),
            lead.astype(jnp.int32), following[expert], starts, ends,
            jnp.stack([live_items, live_items + zeroing]))


def _kernel(expert_ref, out_tile_ref, x_tile_ref, lead_ref, next_ref,
            starts_ref, ends_ref, items_ref, x_ref, *refs, matrices: int,
            columns: int, transposed: bool):
    w_hbm, o_ref = refs[:matrices], refs[matrices]
    buffers, sem, turn = refs[matrices + 1:-2], refs[-2], refs[-1]
    j, w = pl.program_id(0), pl.program_id(1)
    tile = out_tile_ref[w]
    opens = (w == 0) | (tile != out_tile_ref[jnp.maximum(w - 1, 0)])
    computing = w < items_ref[0]
    lead = lead_ref[w]

    def copies(expert, column_block, slot):
        """The copies of one expert's column block into buffer ``slot``."""
        block = pl.ds(pl.multiple_of(column_block * columns, columns),
                      columns)
        return [pltpu.make_async_copy(
            hbm.at[expert, block, :] if transposed
            else hbm.at[expert, :, block], buf.at[slot], sem.at[slot, i])
            for i, (hbm, buf) in enumerate(zip(w_hbm, buffers))]

    @pl.when(computing & (lead > 0))
    def _fetch():   # an expert's first item
        @pl.when((j == 0) & (w == 0))
        def _():    # the call's first: nobody started its copy
            turn[0] = 0
            for copy in copies(expert_ref[0], 0, 0):
                copy.start()

        slot = turn[0]

        # the other buffer is free: every item of the expert before is done
        @pl.when((lead == 1) | (j < pl.num_programs(0) - 1))
        def _():    # the next expert's block, or the first one's next column
            for copy in copies(next_ref[w], j + (lead == 2).astype(jnp.int32),
                               1 - slot):
                copy.start()

        for copy in copies(expert_ref[w], j, slot):
            copy.wait()
        turn[1] = slot
        turn[0] = 1 - slot

    @pl.when(computing)
    def _compute():
        e = expert_ref[w]
        slot = turn[1]
        row = tile * ROW_TILE + jax.lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, 1), 0)
        mine = (row >= starts_ref[e]) & (row < ends_ref[e])
        x = x_ref[...]

        def product(block):
            if transposed:      # the block is [columns, K]: x @ block.T
                return jax.lax.dot_general(
                    x, block, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return jnp.dot(x, block, preferred_element_type=jnp.float32)

        y = product(buffers[0][slot])
        if matrices == 2:     # gate and up: silu(gate) * up, in float32
            y = jax.nn.silu(y) * product(buffers[1][slot])
        y = y.astype(o_ref.dtype)

        @pl.when(opens)
        def _():    # the tile's first item: rows nobody owns are zeros
            o_ref[...] = jnp.where(mine, y, jnp.zeros_like(y))

        @pl.when(jnp.logical_not(opens))
        def _():
            o_ref[...] = jnp.where(mine, y, o_ref[...])

    @pl.when((w >= items_ref[0]) & (w < items_ref[1]))
    def _zero():    # a row tile no expert reaches
        o_ref[...] = jnp.zeros_like(o_ref)


def _column_block(k: int, n: int, itemsize: int, matrices: int,
                  block_bytes: int) -> int:
    """Columns of a weight block ``[K, columns]``: all of ``n`` if one
    expert's copy (``matrices`` blocks) stays within ``block_bytes``, else
    the widest divisor of ``n`` in whole 128-lane tiles that does (128 at
    least)."""
    if k * n * itemsize * matrices <= block_bytes or n % 128:
        return n
    lanes = n // 128
    fits = [d for d in range(1, lanes + 1) if lanes % d == 0
            and k * d * 128 * itemsize * matrices <= block_bytes]
    return 128 * max(fits, default=1)


def grouped_matmul(x, weights, counts=None, *, work=None,
                   block_bytes: int = BLOCK_BYTES, transposed: bool = False):
    """``x [M, K]`` (rows sorted by expert) against the experts' matrices:
    ``out [M, N]`` in ``x``'s dtype, row ``r`` of expert ``e`` (the
    ``counts[e]`` rows after those of the experts before it) being ``x[r] @
    w[e]`` accumulated in float32 and rounded once; the rows past
    ``counts.sum()`` are zeros.  ``weights`` is one matrix stack ``[E, K,
    N]`` or a ``(gate, up)`` pair of them, which gives ``silu(x @ gate[e])
    * (x @ up[e])`` formed in float32.  ``transposed``: the stack is ``[E,
    N, K]`` (an expert's matrix as ``nn.Linear`` keeps it) and row ``r`` is
    ``x[r] @ w[e].T``; an ``N`` that is no whole number of 128-lane tiles
    (1856) then lies on the stack's second-minor dim, where the chip stores
    it without padding and the kernel copies it in one block.  ``work`` is
    :func:`plan`'s result for these ``counts`` where two calls share it."""
    weights = tuple(weights) if isinstance(weights, (tuple, list)) else (
        weights,)
    m, k = x.shape
    e, n = weights[0].shape[0], weights[0].shape[1 if transposed else 2]
    tiles = pl.cdiv(m, ROW_TILE)
    if work is None:
        work = plan(counts, tiles)
    if tiles * ROW_TILE != m:
        x = jnp.pad(x, ((0, tiles * ROW_TILE - m), (0, 0)))
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    tn = _column_block(k, n, itemsize, len(weights), block_bytes)

    def x_block(j, w, expert, out_tile, x_tile, *_):
        return (x_tile[w], 0)

    def o_block(j, w, expert, out_tile, *_):
        return (out_tile[w], j)

    # the blocks twice, the float32 products, and room to breathe
    vmem = (2 * (ROW_TILE * k * x.dtype.itemsize
                 + len(weights) * k * tn * itemsize
                 + ROW_TILE * tn * x.dtype.itemsize)
            + (len(weights) + 2) * ROW_TILE * tn * 4 + (8 << 20))
    out = pl.pallas_call(
        functools.partial(_kernel, matrices=len(weights), columns=tn,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(work),
            grid=(n // tn, tiles + e - 1),
            in_specs=[pl.BlockSpec((ROW_TILE, k), x_block,
                                   memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(weights),
            out_specs=pl.BlockSpec((ROW_TILE, tn), o_block,
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, tn, k) if transposed
                                       else (2, k, tn), weights[0].dtype)
                            for _ in weights] + [
                pltpu.SemaphoreType.DMA((2, len(weights))),
                pltpu.SMEM((2,), jnp.int32),    # next buffer, this expert's
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * ROW_TILE, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        name=KERNEL_NAME,
        interpret=_fa._interpret(),
    )(*work, x, *weights)
    return out[:m] if tiles * ROW_TILE != m else out


def grouped_swiglu(rows, w_gate, w_up, w_down, counts):
    """The experts' gated MLP over rows sorted by expert: ``(silu(rows @
    gate[e]) * (rows @ up[e])) @ down[e]`` in two calls of the kernel that
    share one plan; rows past ``counts.sum()`` come out as zeros."""
    work = plan(counts, pl.cdiv(rows.shape[0], ROW_TILE))
    gated = grouped_matmul(rows, (w_gate, w_up), work=work)
    return grouped_matmul(gated, w_down, work=work)


def grouped_relu2(rows, w_up, w_down, counts):
    """The experts' two-matrix MLP over rows sorted by expert: ``relu(rows @
    up[e].T)^2 @ down[e]``, both stacks ``[E, width, hidden]`` (up through
    the kernel's ``transposed`` mode), in two calls that share one plan;
    rows past ``counts.sum()`` come out as zeros."""
    work = plan(counts, pl.cdiv(rows.shape[0], ROW_TILE))
    up = grouped_matmul(rows, w_up, work=work, transposed=True)
    return grouped_matmul(jnp.square(jax.nn.relu(up)), w_down, work=work)
