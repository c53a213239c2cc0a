"""Mamba-2's state-space scan in its chunked form (SSD, Dao & Gu 2024,
arXiv:2405.21060), the chunk-local part as two Pallas kernels.

A head h carries a state S (N x P: `mamba_d_state` x `mamba_d_head`) along
the sequence:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t B_t x_t^T,    y_t = S_t^T C_t + D_h x_t

with `A_h < 0` a scalar a head, `dt_t > 0` a scalar a head and position, and
`B_t`, `C_t` (N) shared by every head (`mamba_n_groups` 1). Over chunks of Q
positions (`mamba_chunk_size`), with `cum_i` the running sum of `dt A` inside
a chunk (float32, never positive):

- within a chunk  `Y = ((C B^T) * E) (dt * X)`, `E_ij = exp(cum_i - cum_j)`
  for `i >= j`, else 0;
- a chunk's own state  `S_c = B^T (exp(cum_Q - cum) * dt * X)`;
- the states carried by a short recurrence over the chunks,
  `S_in[c + 1] = exp(cum_Q[c]) S_in[c] + S_c`, from zero;
- what the past adds  `Y += exp(cum) * (C S_in)`.

Every decay is the exponential of a non-positive float32 sum. The first two
items are the chunk-local part: the kernel `ssd_chunk` (one chunk and
`heads` heads a grid step; the Q x Q scores of a head live in VMEM only) and
its backward `ssd_chunk_bwd`, which rebuilds a chunk's scores from `cum`
rather than keeping them. `C B^T` is shared by the heads, small (Q numbers a
position) and computed once by XLA, as are the running sums, the recurrence
over the chunks and the past's term, which `jax` differentiates itself.
`ssd_scan_dense` is the same mathematics with the chunk-local part as
`jax.numpy` einsums (scores of every head and chunk in memory at once: tests
and small sizes only).

Products take `x`'s dtype (bf16 in the train step) and accumulate in
float32; decays, running sums, the carried state and the outputs of the
kernels are float32. A sequence that is not a multiple of the chunk is padded
with steps of `dt = 0`, which neither decay the state nor add to it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.block_attention import _column, _row, _vmem
from raft_stereo_tpu.ops.data_axis import over_data_axis
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array
_NEG = -1e30  # exp gives 0
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_VMEM_LIMIT = 64 * 1024 * 1024


# -- the chunk-local part: jax.numpy ------------------------------------------------
#
# Both forms take xdt = dt * x with the heads side by side in the last axis
# (B, L, H P); cum (B, H, L) float32; g = C B^T by chunk (B, L, Q) float32;
# b (B, L, N). They give the chunk-local output (B, L, H P) float32 and every
# chunk's own state (B, L / Q, N, H P) float32.


def _chunk_local_dense(xdt: Array, cum: Array, g: Array, b: Array) -> Tuple[Array, Array]:
    bsz, h, seq = cum.shape
    q, n = g.shape[-1], b.shape[-1]
    nc = seq // q
    cum = cum.reshape(bsz, h, nc, q)
    xdt = xdt.reshape(bsz, nc, q, h, -1)
    lower = jnp.tril(jnp.ones((q, q), bool))
    e = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], _NEG))
    m = g.reshape(bsz, 1, nc, q, q) * e
    y = jnp.einsum("bhcij,bcjhp->bcihp", m.astype(xdt.dtype), xdt, preferred_element_type=jnp.float32)
    z = xdt.astype(jnp.float32) * jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), 1, -1)[..., None]
    states = jnp.einsum(
        "bcjn,bcjhp->bcnhp", b.reshape(bsz, nc, q, n).astype(xdt.dtype), z.astype(xdt.dtype),
        preferred_element_type=jnp.float32)
    return y.reshape(bsz, seq, -1), states.reshape(bsz, nc, n, -1)


# -- the chunk-local part: kernels ----------------------------------------------------
#
# A grid step takes one chunk of `heads` heads: a (Q, heads x P) block whose
# lanes are whole (P is 64, half a lane tile). A head's running sums travel
# as a (1, Q) row (a (Q, 1) column of float32 takes a whole lane tile a
# number in HBM); the kernels turn it (`block_attention._column`, `_row`).


def _decays(cumc, cumr, q):
    """E (queries down: E_ij = exp(cum_i - cum_j), i >= j) and its transpose,
    from a head's running sums as a (Q, 1) column and a (1, Q) row."""
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    e = jnp.exp(jnp.where(row >= col, cumc - cumr, _NEG))
    e_t = jnp.exp(jnp.where(row <= col, cumr - cumc, _NEG))
    return e, e_t


def _to_chunk_end(columns, q, p):
    """exp(cum_Q - cum) of each head, (Q, 1), side by side over the heads'
    lanes: (Q, heads x P)."""
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(columns) * p), 1) // p
    out = jnp.zeros((q, len(columns) * p), jnp.float32)
    for k, cumc in enumerate(columns):
        out = jnp.where(head_of_lane == k, jnp.exp(cumc[q - 1:q] - cumc), out)
    return out


def _fwd_kernel(g_ref, bt_ref, xdt_ref, cum_ref, y_ref, s_ref, *, heads, p):
    g, bt, xdt = g_ref[0], bt_ref[0], xdt_ref[0]
    q = g.shape[0]
    columns = []
    for k in range(heads):
        lanes = slice(k * p, (k + 1) * p)
        cumr = cum_ref[0, k]
        columns.append(_column(cumr))
        e, _ = _decays(columns[k], cumr, q)
        y = jnp.dot((g * e).astype(xdt.dtype), xdt[:, lanes], preferred_element_type=jnp.float32)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
    z = xdt.astype(jnp.float32) * _to_chunk_end(columns, q, p)
    s_ref[0, 0] = jnp.dot(bt, z.astype(xdt.dtype), preferred_element_type=jnp.float32).astype(s_ref.dtype)


def _bwd_kernel(g_ref, gt_ref, b_ref, xdt_ref, cum_ref, dy_ref, ds_ref, dxdt_ref, dcum_ref, dg_ref, db_ref, *, heads, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dg_ref[...] = jnp.zeros(dg_ref.shape, jnp.float32).astype(dg_ref.dtype)
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32).astype(db_ref.dtype)

    g, g_t, b, xdt = g_ref[0], gt_ref[0], b_ref[0], xdt_ref[0]
    dy, ds = dy_ref[0].astype(xdt.dtype), ds_ref[0, 0].astype(xdt.dtype)
    q = g.shape[0]
    columns = [_column(cum_ref[0, k]) for k in range(heads)]
    # S = b^T (w * xdt), w = exp(cum_Q - cum): every head of the step at once
    w = _to_chunk_end(columns, q, p)
    x32 = xdt.astype(jnp.float32)
    dz = jnp.dot(b, ds, preferred_element_type=jnp.float32)
    db = jax.lax.dot_general((x32 * w).astype(xdt.dtype), ds, _NT, preferred_element_type=jnp.float32)
    db_ref[0] = (db_ref[0] + db).astype(db_ref.dtype)
    from_state = w * dz
    dlog_w = from_state * x32
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    for k in range(heads):
        lanes = slice(k * p, (k + 1) * p)
        cumr = cum_ref[0, k]
        e, e_t = _decays(columns[k], cumr, q)
        # Y = (g * e) xdt
        dm = jax.lax.dot_general(dy[:, lanes], xdt[:, lanes], _NT, preferred_element_type=jnp.float32)
        dg_ref[0] = (dg_ref[0] + dm * e).astype(dg_ref.dtype)
        t = dm * (g * e)  # d(cum_i - cum_j), i >= j
        dxdt = jnp.dot((g_t * e_t).astype(xdt.dtype), dy[:, lanes], preferred_element_type=jnp.float32)
        dxdt_ref[0, :, lanes] = (dxdt + from_state[:, lanes]).astype(dxdt_ref.dtype)
        to_end = jnp.sum(dlog_w[:, lanes], axis=1, keepdims=True)
        total = jnp.sum(to_end, axis=0, keepdims=True)
        down = jnp.sum(t, axis=1, keepdims=True) - to_end + jnp.where(last, total, 0.0)
        dcum_ref[0, k] = (_row(down) - jnp.sum(t, axis=0, keepdims=True)).astype(dcum_ref.dtype)


def _specs(q, n, p, heads):
    """Block specs of the grid (batch, chunk, head group), the head groups
    innermost so that a chunk's shared blocks are fetched once."""
    shared = lambda shape: _vmem((1,) + shape, lambda b_, c, hg: (b_, c, 0))
    return {
        "g": shared((q, q)), "b": shared((q, n)),
        "bt": _vmem((1, n, q), lambda b_, c, hg: (b_, 0, c)),
        "x": _vmem((1, q, heads * p), lambda b_, c, hg: (b_, c, hg)),
        "row": _vmem((1, heads, 1, q), lambda b_, c, hg: (b_, hg, 0, c)),
        "state": _vmem((1, 1, n, heads * p), lambda b_, c, hg: (b_, c, 0, hg)),
    }


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def _forward(xdt, cum, g, b, heads):
    bsz, h, seq = cum.shape
    q, n, p = g.shape[-1], b.shape[-1], xdt.shape[-1] // h
    nc = seq // q
    s = _specs(q, n, p, heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, p=p),
        grid=(bsz, nc, h // heads),
        in_specs=[s["g"], s["bt"], s["x"], s["row"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(xdt.shape, jnp.float32), jax.ShapeDtypeStruct((bsz, nc, n, h * p), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=pallas_interpret(),
        name="ssd_chunk",
    )(g, b.astype(xdt.dtype).transpose(0, 2, 1), xdt, cum[:, :, None, :])


def _backward(xdt, cum, g, b, dy, ds, heads):
    bsz, h, seq = cum.shape
    q, n, p = g.shape[-1], b.shape[-1], xdt.shape[-1] // h
    nc = seq // q
    s = _specs(q, n, p, heads)
    g_t = g.reshape(bsz, nc, q, q).transpose(0, 1, 3, 2).reshape(bsz, seq, q)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dxdt, dcum, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, p=p),
        grid=(bsz, nc, h // heads),
        in_specs=[s["g"], s["g"], s["b"], s["x"], s["row"], s["x"], s["state"]],
        out_specs=[s["x"], s["row"], s["g"], s["b"]],
        out_shape=[f32(xdt.shape), f32((bsz, h, 1, seq)), f32(g.shape), f32(b.shape)],
        # the head groups of a chunk add into its dg and db
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=pallas_interpret(),
        name="ssd_chunk_bwd",
    )(g, g_t, b.astype(xdt.dtype), xdt, cum[:, :, None, :], dy, ds)
    return dxdt.astype(xdt.dtype), dcum[:, :, 0], dg, db.astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunk_local(xdt, cum, g, b, heads):
    return tuple(_forward(xdt, cum, g, b, heads))


def _chunk_local_fwd(xdt, cum, g, b, heads):
    return _chunk_local(xdt, cum, g, b, heads), (xdt, cum, g, b)


def _chunk_local_bwd(heads, residuals, cotangents):
    return _backward(*residuals, *cotangents, heads)


_chunk_local.defvjp(_chunk_local_fwd, _chunk_local_bwd)


# -- the scan ---------------------------------------------------------------------------


def _scan(x, dt, a, b, c, d, chunk, chunk_local):
    bsz, seq, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, seq)
    pad = -seq % chunk
    if pad:
        along = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = along(x), along(dt), along(b), along(c)
    padded = seq + pad
    nc = padded // chunk
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, h), axis=2)
    by_chunk = lambda v: v.reshape(bsz, nc, chunk, n).astype(x.dtype)
    g = jnp.einsum("bcin,bcjn->bcij", by_chunk(c), by_chunk(b), preferred_element_type=jnp.float32)
    x32 = x.astype(jnp.float32)
    xdt = (x32 * dt[..., None]).astype(x.dtype).reshape(bsz, padded, h * p)
    y, states = chunk_local(xdt, jnp.moveaxis(cum.reshape(bsz, padded, h), 2, 1), g.reshape(bsz, padded, chunk), b)
    over_lanes = lambda per_head: jnp.repeat(per_head, p, axis=-1)  # (..., H) -> (..., H P)

    def carry_on(carried, chunk_of):
        own, decay = chunk_of
        return carried * decay[:, None] + own, carried

    final, entering = jax.lax.scan(
        carry_on, jnp.zeros((bsz, n, h * p), jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(over_lanes(jnp.exp(cum[:, :, -1])), 1, 0)))
    past = jnp.einsum("bcin,cbnf->bcif", by_chunk(c), entering.astype(x.dtype), preferred_element_type=jnp.float32)
    past = (past * over_lanes(jnp.exp(cum))).reshape(bsz, padded, h, p)
    y = y.reshape(bsz, padded, h, p) + past + d[:, None] * x32
    return y[:, :seq].astype(x.dtype), jnp.moveaxis(final.reshape(bsz, n, h, p), 2, 1)


def _heads_a_step(h: int, p: int, heads: int) -> int:
    """How many heads a grid step takes: the most up to `heads` that divide
    `h` and fill whole lane tiles; every head where none does (small sizes)."""
    fitting = [k for k in range(1, min(heads, h) + 1) if h % k == 0 and (k * p) % 128 == 0]
    return max(fitting) if fitting else h


@scoped("ssm_scan")
def ssd_scan(x: Array, dt: Array, a: Array, b: Array, c: Array, d: Array, chunk: int = 256,
             heads: int = 4) -> Tuple[Array, Array]:
    """x: (B, L, H, P); dt: (B, L, H) float32, positive; a: (H,) float32,
    negative; b, c: (B, L, N); d: (H,) float32. Returns (y (B, L, H, P) in
    x's dtype, the state after the last position (B, H, N, P) float32).
    `heads`: heads a grid step of the kernels takes."""
    per_step = _heads_a_step(x.shape[2], x.shape[3], heads)
    kernels = lambda *operands: over_data_axis(lambda *o: _chunk_local(*o, per_step), operands)
    return _scan(x, dt, a, b, c, d, chunk, kernels)


def ssd_scan_dense(x: Array, dt: Array, a: Array, b: Array, c: Array, d: Array, chunk: int = 256) -> Tuple[Array, Array]:
    """`ssd_scan` without kernels: what they are tested against."""
    return _scan(x, dt, a, b, c, d, chunk, _chunk_local_dense)
