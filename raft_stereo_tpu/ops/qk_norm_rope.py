"""The head prologue of the routed-expert families' attention as one Pallas
pass over a projection's output, and one pass back.

For x = a projection's output, (B, S, H x d) in the compute dtype, per
position and head

    y = x * rsqrt(mean(x^2) + eps) * w          (RMSNorm over the head, w: (d,))
    z = y * cos + rotate_half(y) * sin          (rotary, by position id)

over the first r dimensions of the head, r the tables' width, and `z = y`
over the other d - r (a partial rotary: `rotate_half` turns the first r
dimensions among themselves). The tables are whatever the caller built: a
scaled rotary (YaRN's attention factor) rides in cos and sin.

written as (B, H, S, d) in x's dtype: the operand `ops.block_attention`
takes. Everything between the load and the store is float32 in VMEM, so the
only roundings are the input's and the output's, and no float32 array and no
half-head array goes to HBM. `rotate_half(y) = concat(-y[d/2:], y[:d/2])` is a
lane rotation by d/2 (`pltpu.roll`) against the sine table with the sign
folded in; a rotation by half the lanes is its own inverse, which is all the
backward pass needs of it. A partial rotary is the same pass over tables
padded to the head (cos 1, sin 0 beyond r) with the two halves of the first r
lanes swapped by two rotations and a select, zero beyond r, so that the swap
stays its own inverse.

A grid step takes `tile` positions of up to `_HEADS` heads: lane-aligned column
blocks of x (d = 128 is one lane tile) in, whole (tile, d) planes of z out, so
the transposition costs nothing. The backward kernel reads dz and x, rebuilds
the inverse root mean square, writes dx in x's layout and the weight's
gradient as one float32 partial sum a grid step, added outside. The tables
carry no gradient. On a multi-device mesh each device runs the kernels on its
own rows of the batch (ops/data_axis.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.data_axis import over_data_axis
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array
_LANES = 128
_HEADS = 8  # heads a grid step, where the head count allows


def _rotate_half(x: Array, rotary: int) -> Array:
    """rotate-half within the first `rotary` dimensions, zero beyond."""
    half = rotary // 2
    return jnp.concatenate([-x[..., half:rotary], x[..., :half], jnp.zeros_like(x[..., rotary:])], axis=-1)


def qk_norm_rope_dense(x: Array, weight: Array, cos: Array, sin: Array, heads: int, eps: float) -> Array:
    """The same prologue in `jax.numpy`, float32 between the input and the
    output: what the kernels are tested against."""
    rotary = cos.shape[1]
    b, s, _ = x.shape
    x32 = x.astype(jnp.float32).reshape(b, s, heads, -1)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * weight
    cos, sin = _padded(cos, sin, y.shape[-1])
    z = y * cos[None, :, None, :] + _rotate_half(y, rotary) * sin[None, :, None, :]
    return z.transpose(0, 2, 1, 3).astype(x.dtype)


def _padded(cos, sin, d):
    """Tables of the first r dimensions as tables of the head: beyond r,
    cos 1 and sin 0 pass a dimension through."""
    if cos.shape[1] == d:  # graftlint: disable=GL002  (two static sizes: a whole-head rotary lowers as it did)
        return cos, sin
    beyond = [(0, 0), (0, d - cos.shape[1])]
    return jnp.pad(cos, beyond, constant_values=1.0), jnp.pad(sin, beyond)


def _signed(sin, rotary):
    """sin with rotate-half's sign folded in: -sin over the first half of
    the `rotary` lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1)
    return jnp.where(lane < rotary // 2, -sin, sin)


def _swap_halves(x, rotary):
    """The two halves of the first `rotary` lanes swapped; zero beyond."""
    d = x.shape[1]
    if rotary == d:  # graftlint: disable=GL002  (two static sizes: the tables' width and the head's)
        return pltpu.roll(x, d // 2, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    swapped = jnp.where(lane < rotary // 2, pltpu.roll(x, d - rotary // 2, 1), pltpu.roll(x, rotary // 2, 1))
    return jnp.where(lane < rotary, swapped, 0.0)


def _fwd_kernel(x_ref, w_ref, cos_ref, sin_ref, z_ref, *, eps, rotary):
    d = w_ref.shape[1]
    w, cos, sin = w_ref[...], cos_ref[...], _signed(sin_ref[...], rotary)
    for j in range(z_ref.shape[1]):
        x = x_ref[0, :, j * d:(j + 1) * d].astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
        z_ref[0, j] = (y * cos + _swap_halves(y, rotary) * sin).astype(z_ref.dtype)


def _bwd_kernel(dz_ref, x_ref, w_ref, cos_ref, sin_ref, dx_ref, dw_ref, *, eps, rotary):
    d = w_ref.shape[1]
    w, cos, sin = w_ref[...], cos_ref[...], _signed(sin_ref[...], rotary)
    dw = jnp.zeros((1, d), jnp.float32)
    for j in range(dz_ref.shape[1]):
        dz = dz_ref[0, j].astype(jnp.float32)
        x = x_ref[0, :, j * d:(j + 1) * d].astype(jnp.float32)
        dy = dz * cos + _swap_halves(dz * sin, rotary)
        inv_rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        normed = x * inv_rms
        dw = dw + jnp.sum(dy * normed, axis=0, keepdims=True)
        g = dy * w
        dx = (g - normed * jnp.mean(g * normed, axis=-1, keepdims=True)) * inv_rms
        dx_ref[0, :, j * d:(j + 1) * d] = dx.astype(dx_ref.dtype)
    dw_ref[0, 0, 0] = dw.astype(dw_ref.dtype)


def _plan(x, heads, tile):
    """(grid, block specs) of both calls: the grid is (position tile, batch
    row, group of heads), the tables' tile outermost, so that a tile's tables
    are copied once; the specs are x's / dx's column block of a group's heads,
    z's / dz's planes of the same heads, the weight, a table's tile, and a
    grid step's own partial sum of the weight's gradient."""
    b, s, width = x.shape
    d = width // heads
    per = max(n for n in range(1, _HEADS + 1) if heads % n == 0)
    t = min(tile, s)
    vmem = lambda shape, index_map: pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)
    by_position = vmem((1, t, per * d), lambda i, b_, h: (b_, i, h))
    by_head = vmem((1, per, t, d), lambda i, b_, h: (b_, h, i, 0))
    whole = vmem((1, d), lambda i, b_, h: (0, 0))
    table = vmem((t, d), lambda i, b_, h: (i, 0))
    partial_sum = vmem((1, 1, 1, 1, d), lambda i, b_, h: (i, b_, h, 0, 0))
    return (s // t, b, heads // per), by_position, by_head, whole, table, partial_sum


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel"))


def _forward(x, weight, cos, sin, heads, eps, tile):
    grid, by_position, by_head, whole, table, _ = _plan(x, heads, tile)
    b, s, width = x.shape
    rotary = cos.shape[1]
    cos, sin = _padded(cos, sin, width // heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, rotary=rotary),
        grid=grid,
        in_specs=[by_position, whole, table, table],
        out_specs=by_head,
        out_shape=jax.ShapeDtypeStruct((b, heads, s, width // heads), x.dtype),
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="qk_norm_rope",
    )(x, weight[None, :], cos, sin)


def _backward(dz, x, weight, cos, sin, heads, eps, tile):
    grid, by_position, by_head, whole, table, partial_sum = _plan(x, heads, tile)
    rotary = cos.shape[1]
    cos, sin = _padded(cos, sin, weight.shape[0])
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, rotary=rotary),
        grid=grid,
        in_specs=[by_head, by_position, whole, table, table],
        out_specs=[by_position, partial_sum],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((*grid, 1, weight.shape[0]), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="qk_norm_rope_bwd",
    )(dz, x, weight[None, :], cos, sin)
    return dx, jnp.sum(dw, axis=(0, 1, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _prologue(x, weight, cos, sin, heads, eps, tile):
    return _forward(x, weight, cos, sin, heads, eps, tile)


def _prologue_fwd(x, weight, cos, sin, heads, eps, tile):
    return _forward(x, weight, cos, sin, heads, eps, tile), (x, weight, cos, sin)


def _prologue_bwd(heads, eps, tile, residuals, dz):
    x, weight, cos, sin = residuals
    dx, dw = _backward(dz, x, weight, cos, sin, heads, eps, tile)
    return dx, dw, None, None


_prologue.defvjp(_prologue_fwd, _prologue_bwd)


@scoped("qk_norm_rope")
def qk_norm_rope(x: Array, weight: Array, cos: Array, sin: Array, heads: int, eps: float, tile: int = 512) -> Array:
    """x: (B, S, heads x d); weight: (d,) float32; cos, sin: (S, r) float32,
    r <= d the rotary dimension (`rotary_tables` gives r = d).
    -> (B, heads, S, d) in x's dtype. Compiled for the
    chip, d must be whole lane tiles (a multiple of 128): a head is a column
    block of x and a rotation of whole vector registers; the interpreter,
    which has no lane tiles, takes the CPU tests' narrow heads as well."""
    _, s, width = x.shape
    d, rotary = weight.shape[0], cos.shape[-1]
    if (width != heads * d or cos.shape != (s, rotary) or sin.shape != (s, rotary) or rotary % 2
            or not 0 < rotary <= d or s % min(tile, s)):
        raise ValueError(
            f"qk_norm_rope: x {x.shape}, weight {weight.shape}, tables {cos.shape} / {sin.shape}, {heads} heads, "
            f"tiles of {tile}")
    if d % _LANES and not pallas_interpret():
        raise ValueError(f"qk_norm_rope: a head dimension of {d} is not a multiple of {_LANES} lanes")
    call = lambda x, weight, cos, sin: _prologue(x, weight, cos, sin, heads, float(eps), tile)
    return over_data_axis(call, (x,), (weight, cos, sin))
