"""Mamba-2's state-space scan in its chunked form (SSD, Dao & Gu 2024,
arXiv:2405.21060), as two Pallas kernels that carry the state over the
chunks.

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
- the states carried over the chunks, `S_in[c + 1] = exp(cum_Q[c]) S_in[c]
  + S_c`, from zero;
- what the past adds  `Y += exp(cum) * (C S_in)`, and `D x`.

Every decay is the exponential of a non-positive float32 sum. The kernel
`ssd_chunk` walks a row's chunks in order, one chunk and `heads` heads a
grid step, and does all four: it forms `dt x`, the chunk's scores (a head's
Q x Q scores live in VMEM only), the past's term and `D x`, writes `y` once
in `x`'s dtype, and carries every head's state in a float32 VMEM scratch; it
writes the state each chunk enters with (the residual of the backward) and
the last one. Its backward `ssd_chunk_bwd` walks the chunks in reverse with
the cotangent of the carried state in a float32 scratch, and rebuilds a
chunk's scores from `cum` rather than keeping them. XLA forms the running
sums and `C B^T` (shared by the heads, Q numbers a position) and
differentiates them. `ssd_scan_dense` is the same mathematics with the
chunk-local part as `jax.numpy` einsums (scores of every head and chunk in
memory at once) and the recurrence as `lax.scan`: tests and small sizes
only.

Products take `x`'s dtype (bf16 in the train step: `C` and the entering
state are rounded to it as the past's operands) and accumulate in float32;
decays, running sums, the carried state and every sum are float32, and `y`
is rounded to `x`'s dtype once. A sequence that is not a multiple of the
chunk is padded with steps of `dt = 0`, which neither decay the state nor
add to it.
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


def _prologue(x, dt, a, b, c, chunk):
    """Pads the sequence to whole chunks and forms the running sums (B, L, H)
    and `C B^T` by chunk (B, L, Q), both float32."""
    bsz, seq, h, _ = x.shape
    n = b.shape[-1]
    chunk = min(chunk, seq)
    pad = -seq % chunk
    if pad:
        along = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = along(x), along(dt), along(b), along(c)
    padded = seq + pad
    nc = padded // chunk
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, h), axis=2).reshape(bsz, padded, h)
    by_chunk = lambda v: v.reshape(bsz, nc, chunk, n).astype(x.dtype)
    g = jnp.einsum("bcin,bcjn->bcij", by_chunk(c), by_chunk(b), preferred_element_type=jnp.float32)
    return x, dt, b, c, cum, g.reshape(bsz, padded, chunk)


# -- the dense form ---------------------------------------------------------------------
#
# The chunk-local part takes xdt = dt * x with the heads side by side in the
# last axis (B, L, H P); cum (B, H, L) float32; g = C B^T by chunk (B, L, Q)
# float32; b (B, L, N). It gives the chunk-local output (B, L, H P) float32
# and every chunk's own state (B, L / Q, N, H P) float32.


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


def _scan(x, dt, a, b, c, d, chunk):
    bsz, seq, h, p = x.shape
    n = b.shape[-1]
    x, dt, b, c, cum, g = _prologue(x, dt, a, b, c, chunk)
    padded, chunk = x.shape[1], g.shape[-1]
    nc = padded // chunk
    x32 = x.astype(jnp.float32)
    xdt = (x32 * dt[..., None]).astype(x.dtype).reshape(bsz, padded, h * p)
    y, states = _chunk_local_dense(xdt, jnp.moveaxis(cum, 2, 1), g, b)
    cum = cum.reshape(bsz, nc, chunk, h)
    by_chunk = lambda v: v.reshape(bsz, nc, chunk, n).astype(x.dtype)
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


# -- the kernels --------------------------------------------------------------------------
#
# A grid step takes one chunk of `heads` heads: a (Q, heads x P) block whose
# lanes are whole (P is 64, half a lane tile). The grid is (batch, chunk,
# head group), the chunks in order (in reverse for the backward) and the head
# groups innermost, so that a chunk's shared blocks are fetched once and its
# dg, db and dc gather over the groups; every head's state (or its
# cotangent) rides from chunk to chunk in a (groups, N, heads x P) float32
# scratch. A head's per-position scalars (dt, running sums) travel as a
# (1, Q) row (a (Q, 1) column of float32 takes a whole lane tile a number in
# HBM); the kernels turn them (`block_attention._column`, `_row`).


def _decays(cumc, cumr, q):
    """E (queries down: E_ij = exp(cum_i - cum_j), i >= j) and its transpose,
    from a head's running sums as a (Q, 1) column and a (1, Q) row."""
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    e = jnp.exp(jnp.where(row >= col, cumc - cumr, _NEG))
    e_t = jnp.exp(jnp.where(row <= col, cumr - cumc, _NEG))
    return e, e_t


def _over_lanes(columns, p):
    """Each head's (R, 1) column across its P lanes: (R, heads x P)."""
    rows, width = columns[0].shape[0], len(columns) * p
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.broadcast_to(columns[0], (rows, width))
    for k in range(1, len(columns)):
        out = jnp.where(lane >= k * p, columns[k], out)
    return out


def _chunk_scalars(dt_ref, cum_ref, heads, p, q):
    """The running sums as columns, and over the heads' lanes: dt, exp(cum),
    exp(cum_Q - cum) (Q, heads x P) and exp(cum_Q) (1, heads x P)."""
    columns = [_column(cum_ref[0, k]) for k in range(heads)]
    dt = _over_lanes([_column(dt_ref[0, k]) for k in range(heads)], p)
    to_end = _over_lanes([jnp.exp(cc[q - 1:q] - cc) for cc in columns], p)
    decay = _over_lanes([jnp.exp(cc[q - 1:q]) for cc in columns], p)
    return columns, dt, jnp.exp(_over_lanes(columns, p)), to_end, decay


def _fwd_kernel(g_ref, bt_ref, c_ref, x_ref, dt_ref, cum_ref, d_ref, y_ref, s_in_ref, final_ref, s_scr, *, heads, p):
    hg = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[hg] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    g, x = g_ref[0], x_ref[0]
    q = g.shape[0]
    columns, dt, from_start, to_end, decay = _chunk_scalars(dt_ref, cum_ref, heads, p, q)
    x32 = x.astype(jnp.float32)
    xdt = (x32 * dt).astype(x.dtype)
    s_in = s_scr[hg]
    s_in_ref[0, 0] = s_in.astype(s_in_ref.dtype)
    past = jnp.dot(c_ref[0], s_in.astype(x.dtype), preferred_element_type=jnp.float32) * from_start
    skip = d_ref[hg] * x32
    for k in range(heads):
        lanes = slice(k * p, (k + 1) * p)
        e, _ = _decays(columns[k], cum_ref[0, k], q)
        y = jnp.dot((g * e).astype(x.dtype), xdt[:, lanes], preferred_element_type=jnp.float32)
        y_ref[0, :, lanes] = (y + past[:, lanes] + skip[:, lanes]).astype(y_ref.dtype)
    z = xdt.astype(jnp.float32) * to_end
    s_out = decay * s_in + jnp.dot(bt_ref[0], z.astype(x.dtype), preferred_element_type=jnp.float32)
    s_scr[hg] = s_out

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        final_ref[0, hg] = s_out.astype(final_ref.dtype)


def _bwd_kernel(g_ref, gt_ref, b_ref, c_ref, ct_ref, x_ref, dt_ref, cum_ref, d_ref, s_in_ref, dy_ref, dfinal_ref,
                dx_ref, ddt_ref, dcum_ref, dg_ref, db_ref, dc_ref, dd_ref, ds_scr, *, heads, p):
    hg = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)  # the last chunk: the final state's cotangent enters
    def _():
        ds_scr[hg] = dfinal_ref[0, hg]
        dd_ref[0, hg] = jnp.zeros(dd_ref.shape[2:], jnp.float32).astype(dd_ref.dtype)

    @pl.when(hg == 0)  # the head groups of a chunk add into its dg, db and dc
    def _():
        for ref in (dg_ref, db_ref, dc_ref):
            ref[...] = jnp.zeros(ref.shape, jnp.float32).astype(ref.dtype)

    g, g_t, x, dy = g_ref[0], gt_ref[0], x_ref[0], dy_ref[0]
    q = g.shape[0]
    columns, dt, from_start, to_end, decay = _chunk_scalars(dt_ref, cum_ref, heads, p, q)
    x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
    xdt = (x32 * dt).astype(x.dtype)
    xdt32 = xdt.astype(jnp.float32)
    s_in, ds = s_in_ref[0, 0], ds_scr[hg]  # ds: the cotangent of the state the chunk hands on
    # the chunk's own state, S_c = b^T (to_end * xdt): every head of the step at once
    ds_low = ds.astype(x.dtype)
    dz = jnp.dot(b_ref[0], ds_low, preferred_element_type=jnp.float32)
    db = jax.lax.dot_general((xdt32 * to_end).astype(x.dtype), ds_low, _NT, preferred_element_type=jnp.float32)
    from_state = to_end * dz
    dlog_to_end = from_state * xdt32
    # the past's term, from_start * (c s_in); its cotangent stays a float32
    # operand of the two products that take it back, as in the dense form's
    s_low = s_in.astype(x.dtype)
    dpast = dy32 * from_start
    dlog_from_start = dpast * jnp.dot(c_ref[0], s_low, preferred_element_type=jnp.float32)
    dc = jax.lax.dot_general(dpast, s_low.astype(jnp.float32), _NT, preferred_element_type=jnp.float32)
    # the carry, s_out = decay * s_in + S_c
    dlog_decay = jnp.sum(ds * s_in, axis=0, keepdims=True) * decay
    ds_scr[hg] = decay * ds + jnp.dot(ct_ref[0].astype(jnp.float32), dpast, preferred_element_type=jnp.float32)
    dd_ref[0, hg] = (dd_ref[0, hg] + jnp.sum(dy32 * x32, axis=0, keepdims=True)).astype(dd_ref.dtype)
    skip = d_ref[hg] * dy32
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dg = jnp.zeros(g.shape, jnp.float32)
    for k in range(heads):
        lanes = slice(k * p, (k + 1) * p)
        e, e_t = _decays(columns[k], cum_ref[0, k], q)
        # Y = (g * e) xdt
        dm = jax.lax.dot_general(dy[:, lanes], xdt[:, lanes], _NT, preferred_element_type=jnp.float32)
        dg = dg + dm * e
        t = dm * (g * e)  # d(cum_i - cum_j), i >= j
        dxdt = jnp.dot((g_t * e_t).astype(x.dtype), dy[:, lanes], preferred_element_type=jnp.float32)
        dxdt = dxdt + from_state[:, lanes]
        dx_ref[0, :, lanes] = (dxdt * dt[:, lanes] + skip[:, lanes]).astype(dx_ref.dtype)
        ddt_ref[0, k] = _row(jnp.sum(dxdt * x32[:, lanes], axis=1, keepdims=True)).astype(ddt_ref.dtype)
        to_end_k = jnp.sum(dlog_to_end[:, lanes], axis=1, keepdims=True)
        at_end = jnp.sum(to_end_k, axis=0, keepdims=True) + jnp.sum(dlog_decay[:, lanes], axis=1, keepdims=True)
        from_start_k = jnp.sum(dlog_from_start[:, lanes], axis=1, keepdims=True)
        down = jnp.sum(t, axis=1, keepdims=True) - to_end_k + from_start_k + jnp.where(last, at_end, 0.0)
        dcum_ref[0, k] = (_row(down) - jnp.sum(t, axis=0, keepdims=True)).astype(dcum_ref.dtype)
    dg_ref[0] = (dg_ref[0] + dg).astype(dg_ref.dtype)
    db_ref[0] = (db_ref[0] + db).astype(db_ref.dtype)
    dc_ref[0] = (dc_ref[0] + dc).astype(dc_ref.dtype)


def _specs(q, n, heads, p, groups, chunk_of):
    """Block specs of the grid (batch, step, head group); `chunk_of(step)` is
    the chunk a step takes."""
    width = heads * p
    at = lambda index: (lambda b_, s, hg: index(b_, chunk_of(s), hg))
    return {
        "g": _vmem((1, q, q), at(lambda b_, c, hg: (b_, c, 0))),
        "b": _vmem((1, q, n), at(lambda b_, c, hg: (b_, c, 0))),
        "bt": _vmem((1, n, q), at(lambda b_, c, hg: (b_, 0, c))),
        "x": _vmem((1, q, width), at(lambda b_, c, hg: (b_, c, hg))),
        "row": _vmem((1, heads, 1, q), at(lambda b_, c, hg: (b_, hg, 0, c))),
        "state": _vmem((1, 1, n, width), at(lambda b_, c, hg: (b_, c, 0, hg))),
        "d": _vmem((groups, 1, width), lambda b_, s, hg: (0, 0, 0)),
        # a row's blocks of every group, held over its whole walk
        "final": _vmem((1, groups, n, width), lambda b_, s, hg: (b_, 0, 0, 0)),
        "dd": _vmem((1, groups, 1, width), lambda b_, s, hg: (b_, 0, 0, 0)),
    }


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _sizes(x, cum, g, b, heads):
    """Batch, chunks, head groups, Q, N, P."""
    bsz, h, _, seq = cum.shape
    q = g.shape[-1]
    return bsz, seq // q, h // heads, q, b.shape[-1], x.shape[-1] // h


def _forward(x, dt, cum, g, b, c, d, heads):
    bsz, nc, groups, q, n, p = _sizes(x, cum, g, b, heads)
    s = _specs(q, n, heads, p, groups, lambda step: step)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, p=p),
        grid=(bsz, nc, groups),
        in_specs=[s["g"], s["bt"], s["b"], s["x"], s["row"], s["row"], s["d"]],
        out_specs=[s["x"], s["state"], s["final"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), f32((bsz, nc, n, x.shape[-1])),
                   f32((bsz, groups, n, heads * p))],
        scratch_shapes=[pltpu.VMEM((groups, n, heads * p), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_interpret(),
        name="ssd_chunk",
    )(g, b.transpose(0, 2, 1), c, x, dt, cum, d)


def _backward(x, dt, cum, g, b, c, d, entering, dy, dfinal, heads):
    bsz, nc, groups, q, n, p = _sizes(x, cum, g, b, heads)
    s = _specs(q, n, heads, p, groups, lambda step: nc - 1 - step)
    g_t = g.reshape(bsz, nc, q, q).transpose(0, 1, 3, 2).reshape(g.shape)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dx, ddt, dcum, dg, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, p=p),
        grid=(bsz, nc, groups),
        in_specs=[s["g"], s["g"], s["b"], s["b"], s["bt"], s["x"], s["row"], s["row"], s["d"], s["state"], s["x"],
                  s["final"]],
        out_specs=[s["x"], s["row"], s["row"], s["g"], s["b"], s["b"], s["dd"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), f32(dt.shape), f32(cum.shape), f32(g.shape),
                   f32(b.shape), f32(c.shape), f32((bsz, groups, 1, heads * p))],
        scratch_shapes=[pltpu.VMEM((groups, n, heads * p), jnp.float32)],
        compiler_params=_params(),
        interpret=pallas_interpret(),
        name="ssd_chunk_bwd",
    )(g, g_t, b, c, c.transpose(0, 2, 1), x, dt, cum, d, entering, dy, dfinal)
    return dx, ddt, dcum, dg, db.astype(b.dtype), dc.astype(c.dtype), jnp.sum(dd, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _chunked(x, dt, cum, g, b, c, d, heads):
    y, _, final = _forward(x, dt, cum, g, b, c, d, heads)
    return y, final


def _chunked_fwd(x, dt, cum, g, b, c, d, heads):
    y, entering, final = _forward(x, dt, cum, g, b, c, d, heads)
    return (y, final), (x, dt, cum, g, b, c, d, entering)


def _chunked_bwd(heads, residuals, cotangents):
    return _backward(*residuals, *cotangents, heads)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


# -- the scan ---------------------------------------------------------------------------


def _heads_a_step(h: int, p: int, heads: int) -> int:
    """How many heads a grid step takes: the most up to `heads` that divide
    `h` and fill whole lane tiles; every head where none does (small sizes)."""
    fitting = [k for k in range(1, min(heads, h) + 1) if h % k == 0 and (k * p) % 128 == 0]
    return max(fitting) if fitting else h


@scoped("ssm_scan")
def ssd_scan(x: Array, dt: Array, a: Array, b: Array, c: Array, d: Array, chunk: int = 256,
             heads: int = 8) -> Tuple[Array, Array]:
    """x: (B, L, H, P); dt: (B, L, H) float32, positive; a: (H,) float32,
    negative; b, c: (B, L, N); d: (H,) float32. Returns (y (B, L, H, P) in
    x's dtype, the state after the last position (B, H, N, P) float32).
    `heads`: heads a grid step of the kernels takes at most. On a TPU v5e, at
    a row of 8192 positions, 64 heads of 64 and a state of 128, 4, 8 and 16
    heads a step take a layer's forward, rebuild and backward 6.23, 5.90 and
    5.76 ms; the kernels' bodies are unrolled over the heads, and a train
    step of nine such layers lowers in 7.5, 8.5 and 12 s, which every
    process pays before its first step."""
    bsz, seq, h, p = x.shape
    n = b.shape[-1]
    per_step = _heads_a_step(h, p, heads)
    x, dt, b, c, cum, g = _prologue(x, dt, a, b, c, chunk)
    rows = lambda v: jnp.moveaxis(v, 2, 1)[:, :, None, :]  # (B, L, H) -> (B, H, 1, L)
    d = jnp.repeat(d.astype(jnp.float32), p).reshape(h // per_step, 1, per_step * p)
    operands = (x.reshape(bsz, -1, h * p), rows(dt), rows(cum), g, b.astype(x.dtype), c.astype(x.dtype))
    y, final = over_data_axis(lambda *o: _chunked(*o, per_step), operands, (d,))
    final = final.reshape(bsz, h // per_step, n, per_step, p).transpose(0, 1, 3, 2, 4)
    return y.reshape(bsz, -1, h, p)[:, :seq], final.reshape(bsz, h, n, p)


def ssd_scan_dense(x: Array, dt: Array, a: Array, b: Array, c: Array, d: Array, chunk: int = 256) -> Tuple[Array, Array]:
    """`ssd_scan` without kernels: what they are tested against."""
    return _scan(x, dt, a, b, c, d, chunk)
