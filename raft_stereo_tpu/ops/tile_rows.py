"""Copies of the expert layer's LIVE rows: dispatch and combine as two Pallas
kernels that walk the tile table `grouped_matmul` walks.

`ops/grouped_matmul.group_layout` sizes a chunk's row buffer for the worst
case (every assignment held here) and the product kernels skip the dead
tiles. These two make the copies around them skip the dead tiles too: a grid
step takes up to `_STEP_TILES` tiles of `tile_m` rows, a step past
`num_tiles` repeats the last live block's index (nothing is copied for it)
and does nothing, and within a live tile one row moves at a time. What a row
holds is read from SMEM (scalar prefetch): `source[r]` is its assignment,
negative for a padding row; the assignment's position is `source[r] //
per_position` (an assignment is one of a position's `per_position` choices,
position-major) and its weight `weights[source[r]]`.

`gather_rows(table, source, num_tiles, tile_m, per_position)`: `out[r] =
table[position of r]`, a zero row for a padding row (inside a live tile: the
weight gradient sums over whole tiles). The table stays whole in VMEM as
32-bit words, so that a row is a dynamic sublane index (a bfloat16 table is
packed on arrival, column j beside column j + D / 2, and a gathered tile
unpacked at once). With `weights` (A,) each row is multiplied by its
assignment's weight in float32 before the one rounding, and with `dot_with`
(R, D) `dots[r] = <table[position of r], dot_with[r]>` in float32 (the
router weights' gradient, row-major).

`scatter_add_rows(rows, source, num_tiles, tile_m, positions,
per_position)`: `out[position of r] += weights[source[r]] * rows[r]` over the
live tiles' rows that hold an assignment, summed in float32 in a block that
stays in VMEM over the tile axis and rounded once. Rows are taken in order:
two of them may land on one position.

Rows of dead tiles are never written by the one and never read by the
other. `fits` is the static rule for when a call's blocks fit VMEM and its
scalars SMEM; `*_dense` are the same results in `jax.numpy` over every row,
which the kernels are tested against.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.grouped_matmul import _block
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 88 * 1024 * 1024  # what a call's blocks and scratch may take of it
_SMEM_BUDGET = 512 * 1024  # a call's scalars: the rows' assignments and their weights
_STEP_TILES = 8  # tiles a grid step takes: a dead step costs a third of a microsecond
_UNROLL = 8  # rows moved between two loop tests
_COLUMNS = 2048  # columns the scatter's accumulator holds at a time
_HIGH_HALF = np.uint32(0xFFFF0000)
_PAD = 8  # rows after the table's: the zero row a padding row reads, the row it adds into


def fits(positions: int, width: int, dtype, rows: int, tile_m: int, per_position: int) -> bool:
    """Whether both kernels take a table of `positions` x `width` and a
    buffer of `rows`: from shapes alone (called under a trace with static
    shapes and dtypes only, which the lint's taint of parameters cannot see)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):  # graftlint: disable=GL002
        return False
    if tile_m % _UNROLL or rows % tile_m or width % 2:  # graftlint: disable=GL002
        return False
    size = dtype.itemsize
    block = _step_tiles(rows // tile_m) * tile_m
    # every blocked operand is double-buffered (the table, the result and
    # `dot_with`; the rows and the result); the table's words (as many bytes
    # as the table), the float32 accumulator and a tile's staging are scratch
    gather = (2 * positions + positions + _PAD + 4 * block + tile_m) * width * size
    tn = _block(width, _COLUMNS)
    scatter = (positions + _PAD) * tn * 4 + 2 * positions * tn * size + 2 * block * tn * size + tile_m * tn * 4
    return max(gather, scatter) <= _VMEM_BUDGET and (rows + positions * per_position) * 4 <= _SMEM_BUDGET


def _step_tiles(tiles: int) -> int:
    return max(n for n in range(1, _STEP_TILES + 1) if tiles % n == 0)


def _live_step(g, num_tiles_ref, per):
    return jnp.minimum(g, (num_tiles_ref[0] - 1) // per)


def _row(column: Array) -> Array:
    """(n, 1) -> (1, n) without a transpose: the column laid over n lanes,
    its diagonal summed down the sublanes."""
    n = column.shape[0]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(column, (n, n)), 0.0), axis=0, keepdims=True)


def _bits(x: Array) -> Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _floats(x: Array) -> Array:
    return jax.lax.bitcast_convert_type(x, jnp.float32)


def _each_live_row(source_ref, num_tiles_ref, g, per, tile_m, per_position, pad_row, tile_fn, row_fn, done_fn):
    """For every live tile `i` of grid step `g`: `tile_fn(i)`, then
    `row_fn(r, position, assignment)` for its rows in order (`pad_row` and
    assignment 0 for a padding row), then `done_fn(i)`."""
    live = jnp.clip(num_tiles_ref[0] - g * per, 0, per)
    # a floor division of a signed scalar is a handful of operations a row
    shift = per_position.bit_length() - 1
    position_of = (lambda a: a >> shift) if per_position == 1 << shift else (lambda a: jax.lax.div(a, per_position))

    def one_tile(i, _):
        base = (g * per + i) * tile_m
        tile_fn(i)

        def some_rows(q, _):
            for u in range(_UNROLL):
                r = q * _UNROLL + u
                source = source_ref[base + r]
                assignment = jnp.maximum(source, 0)
                row_fn(r, jnp.where(source < 0, pad_row, position_of(assignment)), assignment)

        jax.lax.fori_loop(0, tile_m // _UNROLL, some_rows, None)
        done_fn(i)

    jax.lax.fori_loop(0, live, one_tile, None)


def _gather_kernel(source_ref, num_tiles_ref, *refs, tile_m, per, per_position, weighted, dotted):
    refs = list(refs)
    weights_ref = refs.pop(0) if weighted else None  # the third scalar-prefetch operand
    table_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    words_ref, tile_ref = refs[:2]
    scale_ref = refs[2] if weighted else None
    positions, width = table_ref.shape
    packed = table_ref.dtype == jnp.bfloat16
    half = width // 2
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        zeros = jnp.zeros((_PAD, words_ref.shape[1]), words_ref.dtype)
        words_ref[pl.ds(positions, _PAD), :] = zeros.astype(words_ref.dtype)
        if not packed:
            words_ref[pl.ds(0, positions), :] = table_ref[...]
            return
        block = next(n for n in (256, 128, 64, 32, 16, positions) if positions % n == 0)

        def pack(b, _):
            start = pl.multiple_of(b * block, block)
            x = table_ref[pl.ds(start, block), :].astype(jnp.float32)  # a bfloat16 is its float32's high half
            words = (_bits(x[:, half:]) & _HIGH_HALF) | (_bits(x[:, :half]) >> 16)
            words_ref[pl.ds(start, block), :] = words.astype(words_ref.dtype)

        jax.lax.fori_loop(0, positions // block, pack, None)

    def gather(r, position, assignment):
        tile_ref[pl.ds(r, 1), :] = words_ref[pl.ds(position, 1), :]
        if weighted:
            weight = jnp.full((1, scale_ref.shape[1]), weights_ref[assignment], jnp.float32)
            scale_ref[pl.ds(r, 1), :] = weight.astype(scale_ref.dtype)

    def unpack(i):
        at = pl.ds(pl.multiple_of(i * tile_m, tile_m), tile_m)
        x = tile_ref[...]
        parts = (_floats(x << 16), _floats(x & _HIGH_HALF)) if packed else (x,)
        columns = [slice(0, half), slice(half, width)] if packed else [slice(0, width)]
        if dotted:
            with_rows = dot_ref[at, :].astype(jnp.float32)
            dots = sum(jnp.sum(p * with_rows[:, c], axis=1, keepdims=True) for p, c in zip(parts, columns))
            dots_ref[0, pl.ds(i, 1), :] = _row(dots).astype(dots_ref.dtype)
        if weighted:
            parts = [p * scale_ref[:, :1] for p in parts]
        for p, c in zip(parts, columns):
            out_ref[at, c] = p.astype(out_ref.dtype)

    _each_live_row(source_ref, num_tiles_ref, g, per, tile_m, per_position, positions, lambda i: None, gather, unpack)


def _scatter_kernel(source_ref, num_tiles_ref, *refs, tile_m, per, per_position, weighted):
    refs = list(refs)
    weights_ref = refs.pop(0) if weighted else None
    rows_ref, out_ref, acc_ref, tile_ref = refs
    positions = out_ref.shape[0]
    g = pl.program_id(1)

    @pl.when(g == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32).astype(acc_ref.dtype)

    def stage(i):
        tile_ref[...] = rows_ref[pl.ds(pl.multiple_of(i * tile_m, tile_m), tile_m), :].astype(jnp.float32)

    def add(r, position, assignment):
        row = tile_ref[pl.ds(r, 1), :]
        total = acc_ref[pl.ds(position, 1), :] + (row * weights_ref[assignment] if weighted else row)
        acc_ref[pl.ds(position, 1), :] = total.astype(acc_ref.dtype)

    _each_live_row(source_ref, num_tiles_ref, g, per, tile_m, per_position, positions, stage, add, lambda i: None)

    @pl.when(g == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[pl.ds(0, positions), :].astype(out_ref.dtype)


@scoped("gather_rows")
def gather_rows(
    table: Array, source: Array, num_tiles: Array, tile_m: int, per_position: int = 1,
    weights: Optional[Array] = None, dot_with: Optional[Array] = None,
):
    """table: (C, D); source: (R,) int32, the assignment a row holds
    (position `source // per_position`), negative for a padding row;
    `num_tiles` (1,) from `group_layout`; weights: (A,) float32 by
    assignment; dot_with: (R, D). -> (R, D) in the table's dtype, and with
    `dot_with` also (R,) float32. Rows of dead tiles are undefined in both.
    Not differentiable: the expert layer's VJPs call it."""
    positions, width = table.shape
    rows = source.shape[0]
    per = _step_tiles(rows // tile_m)
    block = per * tile_m
    packed = table.dtype == jnp.bfloat16
    weighted, dotted = weights is not None, dot_with is not None
    by_rows = lambda g, src, nt, *_: (_live_step(g, nt, per), 0)
    by_tile = lambda g, src, nt, *_: (_live_step(g, nt, per), 0, 0)
    operands = [table] + ([dot_with] if dotted else [])
    in_specs = [pl.BlockSpec((positions, width), lambda g, *_: (0, 0), memory_space=pltpu.VMEM)]
    in_specs += [pl.BlockSpec((block, width), by_rows, memory_space=pltpu.VMEM)] if dotted else []
    out_shape = [jax.ShapeDtypeStruct((rows, width), table.dtype)]
    out_specs = [pl.BlockSpec((block, width), by_rows, memory_space=pltpu.VMEM)]
    if dotted:
        out_shape.append(jax.ShapeDtypeStruct((rows // block, per, tile_m), jnp.float32))
        out_specs.append(pl.BlockSpec((1, per, tile_m), by_tile, memory_space=pltpu.VMEM))
    words = (jnp.uint32, width // 2) if packed else (table.dtype, width)
    scratch = [pltpu.VMEM((positions + _PAD, words[1]), words[0]), pltpu.VMEM((tile_m, words[1]), words[0])]
    scalars = [source, num_tiles] + ([weights.astype(jnp.float32)] if weighted else [])
    out = pl.pallas_call(
        functools.partial(
            _gather_kernel, tile_m=tile_m, per=per, per_position=per_position, weighted=weighted, dotted=dotted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(rows // block,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch + ([pltpu.VMEM((tile_m, 128), jnp.float32)] if weighted else []),
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name="gather_rows",
    )(*scalars, *operands)
    return (out[0], out[1].reshape(rows)) if dotted else out[0]


@scoped("scatter_add_rows")
def scatter_add_rows(
    rows: Array, source: Array, num_tiles: Array, tile_m: int, positions: int, per_position: int = 1,
    weights: Optional[Array] = None,
) -> Array:
    """rows: (R, D); source: (R,) int32 as for `gather_rows`, a negative
    row adds nothing; weights: (A,) float32 by assignment. -> (positions, D)
    in rows' dtype. Not differentiable: the expert layer's VJPs call it."""
    count, width = rows.shape
    per = _step_tiles(count // tile_m)
    block = per * tile_m
    tn = _block(width, _COLUMNS)
    weighted = weights is not None
    scalars = [source, num_tiles] + ([weights.astype(jnp.float32)] if weighted else [])
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, tile_m=tile_m, per=per, per_position=per_position, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(width // tn, count // block),
            in_specs=[pl.BlockSpec(
                (block, tn), lambda j, g, src, nt, *_: (_live_step(g, nt, per), j), memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((positions, tn), lambda j, g, *_: (0, j), memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((positions + _PAD, tn), jnp.float32), pltpu.VMEM((tile_m, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((positions, width), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name="scatter_add_rows",
    )(*scalars, rows)
    # XLA fuses a kernel into the update that stacks its result (a scan's
    # output), and the fused call keeps the default 16 MiB of scoped VMEM,
    # whatever the kernel asked for: keep the call on its own.
    return jax.lax.optimization_barrier(out)


def _held(source: Array, num_tiles: Array, tile_m: int) -> Array:
    return ((jnp.arange(source.shape[0]) < num_tiles[0] * tile_m) & (source >= 0))[:, None]


def gather_rows_dense(
    table: Array, source: Array, num_tiles: Array, tile_m: int, per_position: int = 1,
    weights: Optional[Array] = None, dot_with: Optional[Array] = None,
):
    """`gather_rows` over every row (zeros outside the live tiles): what the
    kernel is tested against."""
    held, safe = _held(source, num_tiles, tile_m), jnp.maximum(source, 0)
    taken = jnp.where(held, table[safe // per_position], 0).astype(jnp.float32)
    out = (taken if weights is None else taken * weights[safe][:, None]).astype(table.dtype)
    if dot_with is None:
        return out
    return out, jnp.sum(jnp.where(held, taken * dot_with.astype(jnp.float32), 0.0), axis=1)


def scatter_add_rows_dense(
    rows: Array, source: Array, num_tiles: Array, tile_m: int, positions: int, per_position: int = 1,
    weights: Optional[Array] = None,
) -> Array:
    held, safe = _held(source, num_tiles, tile_m), jnp.maximum(source, 0)
    scaled = rows.astype(jnp.float32) if weights is None else rows.astype(jnp.float32) * weights[safe][:, None]
    total = jnp.zeros((positions, rows.shape[1]), jnp.float32)
    total = total.at[safe // per_position].add(jnp.where(held, scaled, 0.0))
    return total.astype(rows.dtype)
