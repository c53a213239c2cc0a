"""The product over the experts a chip holds: rows sorted by expert, group
sizes known only at run time, as three Pallas kernels (forward, and the
backward's two products), and the activation between two such products as
two more (`swiglu_rows` and its backward).

**Layout** (`group_layout`). An assignment is one (position, chosen expert)
pair; its expert is a held one, `0..E-1`, or `E` for an expert that lives on
another chip. Rows are laid out by expert, each expert's group starting on a
tile boundary and holding at least one tile, so that a tile of `tile_m` rows
belongs to ONE expert: `tile_expert[t]` (scalar-prefetched) picks the weight
block, and tiles from `num_tiles` on hold nothing and are skipped (their
block indices repeat the last live tile's, so nothing is copied for them).
**No assignment is ever dropped**: the buffer has room for the worst case,
every assignment held here, `A + E * tile_m` rows for A assignments. The
caller bounds A (the expert layer walks the positions in chunks), not this
module. What is dynamic is the work: only live tiles are computed.

`grouped_matmul(lhs, rhs, tile_expert, num_tiles)`: `out[r] = lhs[r] @
rhs[expert of r's tile]`. Its VJP is `d_lhs = grouped_matmul(d_out, rhs^T)`
(the same kernel, contracting the weight's other axis) and `d_rhs[e] = sum
over e's tiles of lhs[t]^T @ d_out[t]` (`grouped_matmul_drhs`: an expert's
tiles are consecutive, so its block stays in VMEM while they accumulate).
Rows of dead tiles are never written: read them only under a mask.

`swiglu_rows(gate_up, num_tiles)`: `out[r] = silu(gate_up[r, :F]) *
gate_up[r, F:]` for the rows of the live tiles, widened to float32 in VMEM
and rounded once; its VJP is one more call over the same tiles. The
activation has no weights, so it reads `num_tiles` alone of the table. Rows
of dead tiles are neither read nor written by either call.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


def rows_bound(assignments: int, num_experts: int, tile_m: int) -> int:
    """Rows that hold any routing of `assignments` over `num_experts` held
    experts: each group is padded to whole tiles and has at least one."""
    return -(-assignments // tile_m) * tile_m + num_experts * tile_m


def group_layout(expert: Array, num_experts: int, tile_m: int) -> Dict[str, Array]:
    """`expert`: (A,) int32 in [0, E], E meaning "not held here".

    -> `row_source` (R,): the assignment a row holds (0 for padding);
    `row_live` (R,) bool; `slot_row` (A,): the row of an assignment (0 if not
    held); `held` (A,) bool; `tile_expert` (R / tile_m,); `num_tiles` (1,);
    `counts` (E,): rows of each held expert."""
    e = num_experts
    a = expert.shape[0]
    rows = rows_bound(a, e, tile_m)
    held = expert < e
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)  # held first, by expert
    counts = jnp.sum(jax.nn.one_hot(expert, e + 1, dtype=jnp.int32), axis=0)[:e]
    starts = jnp.cumsum(counts) - counts
    tiles = jnp.maximum(-(-counts // tile_m), 1)
    tile_ends = jnp.cumsum(tiles)
    padded_starts = (tile_ends - tiles) * tile_m

    tile = jnp.arange(rows // tile_m, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.searchsorted(tile_ends, tile, side="right"), e - 1).astype(jnp.int32)
    row = jnp.arange(rows, dtype=jnp.int32)
    row_expert = tile_expert[row // tile_m]
    rank = row - padded_starts[row_expert]
    row_live = (rank < counts[row_expert]) & (row < tile_ends[-1] * tile_m)
    row_source = jnp.where(row_live, order[jnp.clip(starts[row_expert] + rank, 0, a - 1)], 0)

    # the inverse: an assignment's rank among its expert's is its place in
    # the sorted order less its group's start
    place = jnp.zeros((a,), jnp.int32).at[order].set(jnp.arange(a, dtype=jnp.int32))
    safe = jnp.minimum(expert, e - 1)
    slot_row = jnp.where(held, padded_starts[safe] + place - starts[safe], 0)
    return {
        "row_source": row_source, "row_live": row_live, "slot_row": slot_row, "held": held,
        "tile_expert": tile_expert, "num_tiles": tile_ends[-1:].astype(jnp.int32), "counts": counts,
    }


# -- kernels ----------------------------------------------------------------------


def _live(t, num_tiles_ref):
    return jnp.minimum(t, num_tiles_ref[0] - 1)


def _gmm_kernel(tile_expert_ref, num_tiles_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    @pl.when(pl.program_id(1) < num_tiles_ref[0])
    def _():
        if transpose_rhs:
            out = jax.lax.dot_general(lhs_ref[...], rhs_ref[0], _NT, preferred_element_type=jnp.float32)
        else:
            out = jnp.dot(lhs_ref[...], rhs_ref[0], preferred_element_type=jnp.float32)
        out_ref[...] = out.astype(out_ref.dtype)


def _drhs_kernel(tile_expert_ref, num_tiles_ref, lhs_ref, dout_ref, out_ref):
    t = pl.program_id(2)
    first = (t == 0) | (tile_expert_ref[t] != tile_expert_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first & (t < num_tiles_ref[0]))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32).astype(out_ref.dtype)

    @pl.when(t < num_tiles_ref[0])
    def _():
        product = jax.lax.dot_general(lhs_ref[...], dout_ref[...], _TN, preferred_element_type=jnp.float32)
        out_ref[0] = (out_ref[0] + product).astype(out_ref.dtype)


def _block(dim: int, want: int) -> int:
    """The largest divisor of `dim` that is at most `want` and a multiple of
    128, or `dim` itself."""
    sizes = range(min(want, dim) // 128 * 128, 0, -128)
    return dim if dim <= want else next((size for size in sizes if dim % size == 0), dim)


def _gmm(lhs, rhs, tile_expert, num_tiles, tile_m, transpose_rhs, block_n):
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _block(n, block_n)
    # The weight block is the slow-moving one: columns outermost, so an
    # expert's block is copied once for all its consecutive tiles.
    rhs_spec = (
        pl.BlockSpec((1, tn, k), lambda j, t, te, nt: (te[_live(t, nt)], j, 0), memory_space=pltpu.VMEM)
        if transpose_rhs else
        pl.BlockSpec((1, k, tn), lambda j, t, te, nt: (te[_live(t, nt)], 0, j), memory_space=pltpu.VMEM)
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, k), lambda j, t, te, nt: (_live(t, nt), 0), memory_space=pltpu.VMEM),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tile_m, tn), lambda j, t, te, nt: (_live(t, nt), j), memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name="grouped_matmul",
    )(tile_expert, num_tiles, lhs, rhs)


def _drhs(lhs, dout, tile_expert, num_tiles, num_experts, tile_m, block_k, block_n):
    rows, k = lhs.shape
    n = dout.shape[1]
    tk, tn = _block(k, block_k), _block(n, block_n)
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, rows // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, tk), lambda i, j, t, te, nt: (_live(t, nt), i), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_m, tn), lambda i, j, t, te, nt: (_live(t, nt), j), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda i, j, t, te, nt: (te[_live(t, nt)], i, j), memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((num_experts, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(),
        name="grouped_matmul_drhs",
    )(tile_expert, num_tiles, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped_matmul(lhs, rhs, tile_expert, num_tiles, tile_m, block_n):
    return _gmm(lhs, rhs, tile_expert, num_tiles, tile_m, False, block_n)


def _grouped_matmul_fwd(lhs, rhs, tile_expert, num_tiles, tile_m, block_n):
    return _grouped_matmul(lhs, rhs, tile_expert, num_tiles, tile_m, block_n), (lhs, rhs, tile_expert, num_tiles)


def _grouped_matmul_bwd(tile_m, block_n, residuals, dout):
    lhs, rhs, tile_expert, num_tiles = residuals
    dlhs = _gmm(dout, rhs, tile_expert, num_tiles, tile_m, True, block_n)
    drhs = _drhs(lhs, dout, tile_expert, num_tiles, rhs.shape[0], tile_m, block_n, block_n)
    return dlhs, drhs.astype(rhs.dtype), None, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@scoped("grouped_matmul")
def grouped_matmul(
    lhs: Array, rhs: Array, tile_expert: Array, num_tiles: Array, tile_m: int, block_n: int = 1024
) -> Array:
    """lhs: (R, K), R a multiple of `tile_m`; rhs: (E, K, N); `tile_expert`
    (R / tile_m,) and `num_tiles` (1,) from `group_layout`. -> (R, N) in
    lhs's dtype, rows of dead tiles undefined."""
    if lhs.shape[0] % tile_m or tile_expert.shape[0] != lhs.shape[0] // tile_m or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, {tile_expert.shape[0]} tiles of {tile_m}")
    return _grouped_matmul(lhs, rhs, tile_expert, num_tiles, tile_m, block_n)


def grouped_matmul_dense(lhs: Array, rhs: Array, tile_expert: Array, num_tiles: Array, tile_m: int) -> Array:
    """The same product one expert at a time over every row (dead tiles give
    zeros): what the kernels are tested against."""
    row_expert = jnp.repeat(tile_expert, tile_m)
    live = jnp.repeat(jnp.arange(tile_expert.shape[0]) < num_tiles[0], tile_m)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for e in range(rhs.shape[0]):
        mine = (live & (row_expert == e))[:, None]
        out = out + jnp.where(mine, jnp.dot(lhs, rhs[e], preferred_element_type=jnp.float32), 0.0)
    return out.astype(lhs.dtype)


# -- the activation between two products ---------------------------------------


def _swiglu_kernel(num_tiles_ref, gate_up_ref, out_ref):
    @pl.when(pl.program_id(0) < num_tiles_ref[0])
    def _():
        f = out_ref.shape[1]
        gate = gate_up_ref[:, :f].astype(jnp.float32)
        up = gate_up_ref[:, f:].astype(jnp.float32)
        out_ref[...] = (jax.nn.silu(gate) * up).astype(out_ref.dtype)


def _swiglu_bwd_kernel(num_tiles_ref, d_out_ref, gate_up_ref, d_gate_up_ref):
    @pl.when(pl.program_id(0) < num_tiles_ref[0])
    def _():
        f = d_out_ref.shape[1]
        gate = gate_up_ref[:, :f].astype(jnp.float32)
        up = gate_up_ref[:, f:].astype(jnp.float32)
        d_out = d_out_ref[...].astype(jnp.float32)
        sigmoid = jax.nn.sigmoid(gate)
        d_gate = d_out * up * sigmoid * (1.0 + gate * (1.0 - sigmoid))
        d_gate_up_ref[:, :f] = d_gate.astype(d_gate_up_ref.dtype)
        d_gate_up_ref[:, f:] = (d_out * gate * sigmoid).astype(d_gate_up_ref.dtype)


def _by_tile(kernel, name, operands, num_tiles, tile_m, width):
    """`kernel` over the row tiles of `operands` (each (R, its own width)),
    one tile a grid step, dead tiles skipped as `_gmm` skips them (eight
    tiles a step saved 0.011 ms of a call's 0.086 at the token cell's chunk:
    PERF.md section 5). -> (R, width)."""
    rows, dtype = operands[0].shape[0], operands[0].dtype
    spec = lambda n: pl.BlockSpec((tile_m, n), lambda t, nt: (_live(t, nt), 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // tile_m,),
            in_specs=[spec(x.shape[1]) for x in operands],
            out_specs=spec(width),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=name,
    )(num_tiles, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _swiglu_rows(gate_up, num_tiles, tile_m):
    return _by_tile(_swiglu_kernel, "swiglu_rows", (gate_up,), num_tiles, tile_m, gate_up.shape[1] // 2)


def _swiglu_rows_fwd(gate_up, num_tiles, tile_m):
    return _swiglu_rows(gate_up, num_tiles, tile_m), (gate_up, num_tiles)


def _swiglu_rows_bwd(tile_m, residuals, d_out):
    gate_up, num_tiles = residuals
    d_gate_up = _by_tile(_swiglu_bwd_kernel, "swiglu_rows_bwd", (d_out, gate_up), num_tiles, tile_m, gate_up.shape[1])
    return d_gate_up, None


_swiglu_rows.defvjp(_swiglu_rows_fwd, _swiglu_rows_bwd)


@scoped("swiglu_rows")
def swiglu_rows(gate_up: Array, num_tiles: Array, tile_m: int) -> Array:
    """gate_up: (R, 2F), R a multiple of `tile_m`, the gate's columns then the
    up projection's; `num_tiles` (1,) from `group_layout`. -> `silu(gate) *
    up`, (R, F) in gate_up's dtype, computed in float32; rows of dead tiles
    undefined."""
    if gate_up.shape[0] % tile_m or gate_up.shape[1] % 2:
        raise ValueError(f"swiglu_rows: gate_up {gate_up.shape}, tiles of {tile_m}")
    return _swiglu_rows(gate_up, num_tiles, tile_m)
