"""Attention under the block-diffusion training mask (BD3-LM's vectorised
form), as three Pallas kernels: forward, dq, dk/dv.

A row holds 2L positions: L noised tokens `x_t`, then the L clean tokens
`x_0`; position p has block `b(p) = (p mod L) // block_length`. Query i sees
key j iff

- both noised and `b(i) == b(j)` (a block denoises itself), or
- i noised, j clean and `b(j) < b(i)` (the clean past), or
- both clean and `b(j) <= b(i)` (block-causal); a clean query never sees a
  noised key.

The mask is never an array of 2L x 2L. Three int32 vectors of 2L (`mask_codes`)
carry it: a key's code is its block (clean) or `-(block + 2)` (noised); a
query sees a key iff `0 <= code <= q_lim` or `code == q_eq`. A kernel compares
a tile's codes in registers. Tiles no query of the tile can see are never
visited: for a query tile the grid's last axis walks only the clean key tiles
up to its own and, for a noised tile, its own noised tile (`_fwd_key_tile`);
for a key tile only the query tiles at or after it (`_bwd_query_tile`). A
step past a tile's last visible partner keeps the previous block index, so
nothing is copied for it, and computes nothing.

Grouped-query: `q` has G = Hq / Hkv heads a key-value head; the forward and
dq index k/v by `h // G`, dk/dv sum their group inside the kernel. Scores and
softmax are float32; the matrix products take the inputs' dtype (bf16 in the
train step) and accumulate in float32. On a multi-device mesh each device
runs the kernels on its own rows of the batch (ops/data_axis.py).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.data_axis import over_data_axis
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def mask_codes(seq_len: int, block_length: int) -> Tuple[Array, Array, Array]:
    """(q_lim, q_eq, k_code), int32 of 2 x seq_len: see the module docstring."""
    pos = jnp.arange(2 * seq_len, dtype=jnp.int32)
    block = (pos % seq_len) // block_length
    clean = pos >= seq_len
    q_lim = jnp.where(clean, block, block - 1)
    q_eq = jnp.where(clean, -1, -(block + 2))
    k_code = jnp.where(clean, block, -(block + 2))
    return q_lim, q_eq, k_code


def _visible(q_lim, q_eq, k_code):
    return ((k_code >= 0) & (k_code <= q_lim)) | (k_code == q_eq)


def block_mask(seq_len: int, block_length: int) -> Array:
    """The dense (2L, 2L) mask, for tests and small sizes only."""
    q_lim, q_eq, k_code = mask_codes(seq_len, block_length)
    return _visible(q_lim[:, None], q_eq[:, None], k_code[None, :])


def block_attention_dense(q: Array, k: Array, v: Array, seq_len: int, block_length: int) -> Array:
    """The same attention with a materialised mask: what the kernels are
    tested against."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    scores = jnp.where(block_mask(seq_len, block_length), scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v).astype(q.dtype)


# -- which tiles a tile sees ----------------------------------------------------


def _fwd_steps(qt, nh):
    """Visible key tiles of query tile `qt`: the clean tiles 0..qh, then for
    a noised tile its own."""
    noised = qt < nh
    qh = jnp.where(noised, qt, qt - nh)
    return qh, qh + 1 + noised.astype(jnp.int32)


def _fwd_key_tile(qt, s, nh):
    qh, steps = _fwd_steps(qt, nh)
    s = jnp.minimum(s, steps - 1)
    return jnp.where(s <= qh, nh + s, qt)


def _bwd_steps(kt, nh):
    """Visible query tiles of key tile `kt`: a noised tile's own; a clean
    tile's noised and clean tiles from its own on."""
    noised = kt < nh
    kh = jnp.where(noised, kt, kt - nh)
    return kh, jnp.where(noised, 1, 2 * (nh - kh))


def _bwd_query_tile(kt, u, nh):
    kh, steps = _bwd_steps(kt, nh)
    u = jnp.minimum(u, steps - 1)
    clean_key = jnp.where(u < nh - kh, kh + u, 2 * kh + u)
    return jnp.where(kt < nh, kt, clean_key)


def _scores(a, b, q_lim, q_eq, k_code, scale):
    """Masked scores a @ b.T (queries down and keys across, or the other way
    round: the codes broadcast to whichever it is) and the mask."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32) * scale
    mask = _visible(q_lim, q_eq, k_code)
    return jnp.where(mask, s, _NEG), mask


def _row(column):
    """(T, 1) -> (1, T), by the one transpose Mosaic has for it: of a
    (T, 128) array."""
    return jnp.broadcast_to(column, (column.shape[0], 128)).T[0:1]


def _column(row):
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, 0:1]


# -- kernels ----------------------------------------------------------------------


def _fwd_kernel(ql_ref, qe_ref, kc_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, nh, scale):
    qt, s = pl.program_id(2), pl.program_id(3)

    @pl.when(s == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(s < _fwd_steps(qt, nh)[1])
    def _():
        v = v_ref[0, 0]
        sc, mask = _scores(q_ref[0, 0], k_ref[0, 0], ql_ref[...], qe_ref[...], kc_ref[...], scale)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(s == pl.num_programs(3) - 1)
    def _():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _row(m_scr[...] + jnp.log(l)).astype(lse_ref.dtype)


def _dq_kernel(
    ql_ref, qe_ref, kc_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scr, lse_scr, delta_scr,
    *, nh, scale,
):
    qt, s = pl.program_id(2), pl.program_id(3)

    @pl.when(s == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        lse_scr[...] = _column(lse_ref[0, 0])
        delta_scr[...] = _column(delta_ref[0, 0])

    @pl.when(s < _fwd_steps(qt, nh)[1])
    def _():
        k, v, do = k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        sc, mask = _scores(q_ref[0, 0], k, ql_ref[...], qe_ref[...], kc_ref[...], scale)
        p = jnp.where(mask, jnp.exp(sc - lse_scr[...]), 0.0)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[...]) * scale
        acc_scr[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(s == pl.num_programs(3) - 1)
    def _():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    ql_ref, qe_ref, kc_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, nh, scale,
):
    kt, step = pl.program_id(2), pl.program_id(3)

    @pl.when(step == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(step % (2 * nh) < _bwd_steps(kt, nh)[1])
    def _():
        # Keys down, queries across: a query's statistics are rows, and both
        # accumulations are plain products.
        q, v, do = q_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        sc, mask = _scores(k_ref[0, 0], q, ql_ref[...], qe_ref[...], kc_ref[...], scale)
        p = jnp.where(mask, jnp.exp(sc - lse_ref[0, 0]), 0.0)
        dv_scr[...] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_scr[...] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


# -- calls ------------------------------------------------------------------------


def _tile(seq_len: int, block_length: int, tile: int) -> int:
    tile = min(tile, seq_len)
    if seq_len % tile or tile % block_length:
        raise ValueError(
            f"block_attention: a tile of {tile} must divide the sequence ({seq_len}) "
            f"and hold whole blocks of {block_length}"
        )
    return tile


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _code_specs(tile, q_tile_of, k_tile_of, keys_down=False):
    """Block specs of (q_lim, q_eq, k_code) by the tile functions of the
    grid: the queries' codes as (2L, 1) columns and the keys' as a (1, 2L)
    row, or with `keys_down` the other way round."""
    column = lambda tile_of: _vmem((tile, 1), lambda *g: (tile_of(*g), 0))
    row = lambda tile_of: _vmem((1, tile), lambda *g: (0, tile_of(*g)))
    q_spec, k_spec = (row, column) if keys_down else (column, row)
    return [q_spec(q_tile_of), q_spec(q_tile_of), k_spec(k_tile_of)]


def _codes(seq_len, block_length, keys_down=False):
    q_lim, q_eq, k_code = mask_codes(seq_len, block_length)
    down, across = (lambda x: x[:, None]), (lambda x: x[None, :])
    queries, keys = (across, down) if keys_down else (down, across)
    return queries(q_lim), queries(q_eq), keys(k_code)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _query_major_specs(t, hd, nh, group):
    """Block specs of the grid (batch, query head, query tile, step) the
    forward and dq share: the three codes, a query-tile block, a key-tile
    block of the head's key-value head, a query tile's statistics row."""
    q_tile = lambda b_, h, qt, s: qt
    k_tile = lambda b_, h, qt, s: _fwd_key_tile(qt, s, nh)
    head = lambda tile_of, per: _vmem((1, 1, t, hd), lambda b_, h, qt, s: (b_, h // per, tile_of(b_, h, qt, s), 0))
    stat = _vmem((1, 1, 1, t), lambda b_, h, qt, s: (b_, h, 0, qt))
    return _code_specs(t, q_tile, k_tile), head(q_tile, 1), head(k_tile, group), stat


def _forward(q, k, v, seq_len, block_length, tile):
    b, hq, s2, hd = q.shape
    t = _tile(seq_len, block_length, tile)
    nh = seq_len // t
    codes, by_q, by_k, stat = _query_major_specs(t, hd, nh, hq // k.shape[1])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nh=nh, scale=1.0 / math.sqrt(hd)),
        grid=(b, hq, 2 * nh, nh + 1),
        in_specs=codes + [by_q, by_k, by_k],
        out_specs=[by_q, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct((b, hq, 1, s2), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, hd), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="block_attention",
    )(*_codes(seq_len, block_length), q, k, v)


def _backward(q, k, v, o, lse, do, seq_len, block_length, tile):
    b, hq, s2, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    t = _tile(seq_len, block_length, tile)
    nh = seq_len // t
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]

    codes, by_q, by_k, stat = _query_major_specs(t, hd, nh, group)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nh=nh, scale=scale),
        grid=(b, hq, 2 * nh, nh + 1),
        in_specs=codes + [by_q, by_k, by_k, by_q, stat, stat],
        out_specs=by_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((t, hd), jnp.float32), pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, 1), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="block_attention_dq",
    )(*_codes(seq_len, block_length), q, k, v, do, lse, delta)

    # dk/dv: one key tile of one key-value head at a time; the last axis
    # walks the group's query heads, and under each the visible query tiles.
    q_of = lambda b_, hk, kt, step: _bwd_query_tile(kt, step % (2 * nh), nh)
    k_of = lambda b_, hk, kt, step: kt
    q_head = lambda b_, hk, kt, step: hk * group + step // (2 * nh)
    by_q = _vmem((1, 1, t, hd), lambda b_, hk, kt, step: (b_, q_head(b_, hk, kt, step), q_of(b_, hk, kt, step), 0))
    q_stat = _vmem((1, 1, 1, t), lambda b_, hk, kt, step: (b_, q_head(b_, hk, kt, step), 0, q_of(b_, hk, kt, step)))
    by_k = _vmem((1, 1, t, hd), lambda b_, hk, kt, step: (b_, hk, kt, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nh=nh, scale=scale),
        grid=(b, hkv, 2 * nh, group * 2 * nh),
        in_specs=_code_specs(t, q_of, k_of, keys_down=True) + [by_q, by_k, by_k, by_q, q_stat, q_stat],
        out_specs=[by_k, by_k],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((t, hd), jnp.float32), pltpu.VMEM((t, hd), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name="block_attention_dkv",
    )(*_codes(seq_len, block_length, keys_down=True), q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, seq_len, block_length, tile):
    return _forward(q, k, v, seq_len, block_length, tile)[0]


def _attention_fwd(q, k, v, seq_len, block_length, tile):
    o, lse = _forward(q, k, v, seq_len, block_length, tile)
    return o, (q, k, v, o, lse)


def _attention_bwd(seq_len, block_length, tile, residuals, do):
    return _backward(*residuals, do, seq_len, block_length, tile)


_attention.defvjp(_attention_fwd, _attention_bwd)


@scoped("block_attention")
def block_attention(q: Array, k: Array, v: Array, seq_len: int, block_length: int, tile: int = 512) -> Array:
    """q: (B, Hq, 2L, d); k, v: (B, Hkv, 2L, d), Hq a multiple of Hkv;
    scores are scaled by 1/sqrt(d). Returns (B, Hq, 2L, d) in q's dtype."""
    if q.shape[2] != 2 * seq_len or k.shape[2] != 2 * seq_len or q.shape[1] % k.shape[1]:
        raise ValueError(f"block_attention: q {q.shape} / k {k.shape} do not fit 2 x {seq_len} positions")
    return over_data_axis(lambda q, k, v: _attention(q, k, v, seq_len, block_length, tile), (q, k, v))
