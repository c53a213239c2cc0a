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
nothing is copied for it, and computes nothing. Of the visited pairs, those
the walk knows from the two tile indices to be wholly visible (`interior`;
tiles hold whole blocks) run a body with no mask arithmetic in the dq and
dk/dv kernels: a clean key tile before the query tile's own half-index under
the block-diffusion mask, a key tile before the query tile under the causal
one, and under a window an earlier key tile the query tile's last query
still reaches (none where the window is under two tiles: those kernels hold
the masked body only). The rest, the diagonal and the window's edge, test
their codes, and so does the forward on every pair: its body is slower
without the test than with it (PERF.md section 6, PR 37).

Grouped-query: `q` has G = Hq / Hkv heads a key-value head; the forward and
dq index k/v by `h // G`, dk/dv sum their group inside the kernel. Scores and
softmax are float32; the matrix products take the inputs' dtype (bf16 in the
train step) and accumulate in float32. On a multi-device mesh each device
runs the kernels on its own rows of the batch (ops/data_axis.py).

`causal_attention` runs the same three kernels under the plain causal mask
of a row of L positions: the codes are `q_lim = pos`, `q_eq = -1`,
`k_code = pos`, a query tile walks the key tiles up to its own and a key tile
the query tiles from its own on, and the caller gives the scale of the scores
(a model may fix a multiplier other than 1/sqrt(d)).

`window_attention` is the causal row under a sliding window: query i sees
the `window` keys `i - window + 1 .. i`. The codes' middle vector carries the
lower limit in place of the code a query equals (`q_low = pos - window + 1`;
a query sees a key iff `q_low <= code <= q_lim`: `_in_window`), a query tile
walks only the key tiles its window reaches, `ceil((window - 1) / tile)`
before its own, and a key tile the matching query tiles after it. Its Pallas
calls are named `window_attention*`, so that a trace tells them from the
full mask's `block_attention*` calls of the same program.

Which tiles a tile sees, and a tile's mask test, are all the entries do not
share: `_BlockWalk` / `_CausalWalk` / `_WindowWalk`.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_stereo_tpu.obs.scopes import scoped
from raft_stereo_tpu.ops.data_axis import over_data_axis
from raft_stereo_tpu.ops.pallas_mode import pallas_interpret

Array = jax.Array
_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
# The Pallas calls' names (forward, dq, dk/dv): the full masks', the window's.
KERNEL_NAMES = {
    False: ("block_attention", "block_attention_dq", "block_attention_dkv"),
    True: ("window_attention", "window_attention_dq", "window_attention_dkv"),
}
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


def _in_window(q_lim, q_low, k_code):
    return (k_code <= q_lim) & (k_code >= q_low)


def block_mask(seq_len: int, block_length: int) -> Array:
    """The dense (2L, 2L) mask, for tests and small sizes only."""
    q_lim, q_eq, k_code = mask_codes(seq_len, block_length)
    return _visible(q_lim[:, None], q_eq[:, None], k_code[None, :])


def _dense(q: Array, k: Array, v: Array, mask: Array, scale: float) -> Array:
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v).astype(q.dtype)


def block_attention_dense(q: Array, k: Array, v: Array, seq_len: int, block_length: int) -> Array:
    """The same attention with a materialised mask: what the kernels are
    tested against."""
    return _dense(q, k, v, block_mask(seq_len, block_length), 1.0 / math.sqrt(q.shape[-1]))


def causal_attention_dense(q: Array, k: Array, v: Array, scale: float) -> Array:
    """`causal_attention` with a materialised mask: what the causal entry is
    tested against."""
    return _dense(q, k, v, jnp.tril(jnp.ones((q.shape[2], q.shape[2]), bool)), scale)


def window_attention_dense(q: Array, k: Array, v: Array, window: int, scale: float) -> Array:
    """`window_attention` with a materialised mask: what the window entry is
    tested against."""
    pos = jnp.arange(q.shape[2])
    return _dense(q, k, v, _in_window(pos[:, None], pos[:, None] - window + 1, pos[None, :]), scale)


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


class _BlockWalk(NamedTuple):
    """The block-diffusion layout in tiles: `nh` noised tiles, then `nh`
    clean ones. `tiles`: tiles of a row; `fwd_max` / `bwd_max`: the longest
    walk of a query tile / of a key tile (the grid's last axis)."""

    nh: int
    visible = staticmethod(_visible)

    @property
    def tiles(self):
        return 2 * self.nh

    @property
    def fwd_max(self):
        return self.nh + 1

    @property
    def bwd_max(self):
        return 2 * self.nh

    def fwd_steps(self, qt):
        return _fwd_steps(qt, self.nh)[1]

    def fwd_key_tile(self, qt, s):
        return _fwd_key_tile(qt, s, self.nh)

    def bwd_steps(self, kt):
        return _bwd_steps(kt, self.nh)[1]

    def bwd_query_tile(self, kt, u):
        return _bwd_query_tile(kt, u, self.nh)

    def interior(self, qt, kt):
        """Every query of tile `qt` sees every key of tile `kt`: a clean key
        tile before the query tile's own half-index (`q_lim` is the block or
        the block before, and an earlier tile's blocks lie under both)."""
        return (kt >= self.nh) & (kt - self.nh < jnp.where(qt < self.nh, qt, qt - self.nh))


class _CausalWalk(NamedTuple):
    """A causal row of `nt` tiles: query tile qt sees key tiles 0..qt, key
    tile kt is seen by query tiles kt..nt-1."""

    nt: int
    visible = staticmethod(_visible)

    @property
    def tiles(self):
        return self.nt

    fwd_max = bwd_max = tiles

    def fwd_steps(self, qt):
        return qt + 1

    def fwd_key_tile(self, qt, s):
        return jnp.minimum(s, qt)

    def bwd_steps(self, kt):
        return self.nt - kt

    def bwd_query_tile(self, kt, u):
        return kt + jnp.minimum(u, self.nt - kt - 1)

    def interior(self, qt, kt):
        return kt < qt


class _WindowWalk(NamedTuple):
    """A causal row of `nt` tiles of `tile` positions under a window of
    `window` keys, which reaches `reach` tiles back: query tile qt sees key
    tiles max(0, qt - reach)..qt, key tile kt is seen by query tiles
    kt..min(nt - 1, kt + reach)."""

    nt: int
    tile: int
    window: int
    visible = staticmethod(_in_window)

    @property
    def tiles(self):
        return self.nt

    @property
    def reach(self):
        return -(-(self.window - 1) // self.tile)

    @property
    def fwd_max(self):
        return min(self.reach + 1, self.nt)

    bwd_max = fwd_max

    def fwd_steps(self, qt):
        return jnp.minimum(qt, self.reach) + 1

    def fwd_key_tile(self, qt, s):
        return jnp.maximum(qt - self.reach, 0) + jnp.minimum(s, jnp.minimum(qt, self.reach))

    def bwd_steps(self, kt):
        return jnp.minimum(self.reach, self.nt - 1 - kt) + 1

    def bwd_query_tile(self, kt, u):
        return kt + jnp.minimum(u, jnp.minimum(self.reach, self.nt - 1 - kt))

    def interior(self, qt, kt):
        """An earlier tile whose first key the query tile's last query still
        sees. A window under two tiles holds none: the Python `False` tells
        the kernels so while they are traced."""
        if self.window < 2 * self.tile:
            return False
        return (kt < qt) & ((qt - kt + 1) * self.tile <= self.window)


class _Mask(NamedTuple):
    """What the calls are traced for: `block_length` 0 is the causal mask of
    `seq_len` positions (under a sliding window of `window` keys, where that
    is not 0), otherwise the block-diffusion mask of 2 x `seq_len`."""

    seq_len: int
    block_length: int
    window: int = 0

    @property
    def positions(self):
        return 2 * self.seq_len if self.block_length else self.seq_len

    @property
    def kernels(self):
        """The Pallas calls' names: forward, dq, dk/dv."""
        return KERNEL_NAMES[bool(self.window)]

    def codes(self):
        if self.block_length:
            return mask_codes(self.seq_len, self.block_length)
        pos = jnp.arange(self.seq_len, dtype=jnp.int32)
        return pos, (pos - self.window + 1 if self.window else jnp.full_like(pos, -1)), pos

    def walk(self, tile: int):
        t = _tile(self.seq_len, self.block_length or 1, tile)
        if self.window:
            return t, _WindowWalk(self.seq_len // t, t, self.window)
        return t, (_BlockWalk if self.block_length else _CausalWalk)(self.seq_len // t)


@functools.lru_cache(maxsize=None)
def interior_pair_share(seq_len: int, block_length: int = 0, window: int = 0, tile: int = 512) -> float:
    """Wholly visible tile pairs over visited tile pairs of one head's walk
    (the three kernels visit the same pairs): how often dq and dk/dv run the
    body without the mask. The arguments are `_Mask`'s and the entries'
    `tile`; a constant from shapes, also where it is called under a trace."""
    _, walk = _Mask(seq_len, block_length, window).walk(tile)
    with jax.ensure_compile_time_eval():
        qt, s = jnp.arange(walk.tiles)[:, None], jnp.arange(walk.fwd_max)[None, :]
        live = s < walk.fwd_steps(qt)
        interior = live & walk.interior(qt, walk.fwd_key_tile(qt, s))
        return int(interior.sum()) / int(live.sum())


def _scores(a, b, scale):
    """Scores a @ b.T: queries down and keys across, or the other way round."""
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32) * scale


def _by_pair(walk, live, qt, kt, code_refs, body):
    """Run `body(mask)` on a live step: with the walk's test of the tiles'
    codes (they broadcast to whichever way round the scores are), or with
    None where the walk knows from the two tile indices alone that every
    entry passes it. A dead step runs neither."""
    masked = lambda: body(walk.visible(*(ref[...] for ref in code_refs)))
    interior = walk.interior(qt, kt)
    if interior is False:
        pl.when(live)(masked)
        return
    pl.when(live & interior)(lambda: body(None))
    pl.when(live & jnp.logical_not(interior))(masked)


def _probs(sc, lse, mask):
    """The backward kernels' rebuilt softmax of the scores `sc`."""
    if mask is None:
        return jnp.exp(sc - lse)
    return jnp.where(mask, jnp.exp(jnp.where(mask, sc, _NEG) - lse), 0.0)


def _row(column):
    """(T, 1) -> (1, T), by the one transpose Mosaic has for it: of a
    (T, 128) array."""
    return jnp.broadcast_to(column, (column.shape[0], 128)).T[0:1]


def _column(row):
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, 0:1]


# -- kernels ----------------------------------------------------------------------


def _fwd_kernel(ql_ref, qe_ref, kc_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, walk, scale):
    qt, s = pl.program_id(2), pl.program_id(3)

    @pl.when(s == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(s < walk.fwd_steps(qt))
    def _():
        # One select, on every pair: a hidden score is _NEG, and exp(_NEG - m)
        # is 0 under any real maximum m. A row that has seen nothing yet has
        # m = _NEG and gathers exp(0) = 1 for its hidden keys, finite sums
        # that alpha = exp(_NEG - m) = 0 wipes at the row's first real score
        # (every query sees itself). Zeroing p by a second select costs an
        # eighth of the call and a body without the mask more still
        # (PERF.md section 6, PR 37), so the forward takes no `_by_pair`.
        v = v_ref[0, 0]
        sc = _scores(q_ref[0, 0], k_ref[0, 0], scale)
        sc = jnp.where(walk.visible(ql_ref[...], qe_ref[...], kc_ref[...]), sc, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
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
    *, walk, scale,
):
    qt, s = pl.program_id(2), pl.program_id(3)

    @pl.when(s == 0)
    def _():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        lse_scr[...] = _column(lse_ref[0, 0])
        delta_scr[...] = _column(delta_ref[0, 0])

    def pair(mask):
        k, v, do = k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        p = _probs(_scores(q_ref[0, 0], k, scale), lse_scr[...], mask)
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[...]) * scale
        acc_scr[...] += jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    _by_pair(walk, s < walk.fwd_steps(qt), qt, walk.fwd_key_tile(qt, s), (ql_ref, qe_ref, kc_ref), pair)

    @pl.when(s == pl.num_programs(3) - 1)
    def _():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    ql_ref, qe_ref, kc_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    *, walk, scale,
):
    kt, step = pl.program_id(2), pl.program_id(3)

    @pl.when(step == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def pair(mask):
        # Keys down, queries across: a query's statistics are rows, and both
        # accumulations are plain products.
        q, v, do = q_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        p = _probs(_scores(k_ref[0, 0], q, scale), lse_ref[0, 0], mask)
        dv_scr[...] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dk_scr[...] += jnp.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32)

    u = step % walk.bwd_max
    _by_pair(walk, u < walk.bwd_steps(kt), walk.bwd_query_tile(kt, u), kt, (ql_ref, qe_ref, kc_ref), pair)

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
    grid: the queries' codes as (S, 1) columns and the keys' as a (1, S)
    row, or with `keys_down` the other way round."""
    column = lambda tile_of: _vmem((tile, 1), lambda *g: (tile_of(*g), 0))
    row = lambda tile_of: _vmem((1, tile), lambda *g: (0, tile_of(*g)))
    q_spec, k_spec = (row, column) if keys_down else (column, row)
    return [q_spec(q_tile_of), q_spec(q_tile_of), k_spec(k_tile_of)]


def _codes(mask, keys_down=False):
    q_lim, q_eq, k_code = mask.codes()
    down, across = (lambda x: x[:, None]), (lambda x: x[None, :])
    queries, keys = (across, down) if keys_down else (down, across)
    return queries(q_lim), queries(q_eq), keys(k_code)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _query_major_specs(t, hd, walk, group):
    """Block specs of the grid (batch, query head, query tile, step) the
    forward and dq share: the three codes, a query-tile block, a key-tile
    block of the head's key-value head, a query tile's statistics row."""
    q_tile = lambda b_, h, qt, s: qt
    k_tile = lambda b_, h, qt, s: walk.fwd_key_tile(qt, s)
    head = lambda tile_of, per: _vmem((1, 1, t, hd), lambda b_, h, qt, s: (b_, h // per, tile_of(b_, h, qt, s), 0))
    stat = _vmem((1, 1, 1, t), lambda b_, h, qt, s: (b_, h, 0, qt))
    return _code_specs(t, q_tile, k_tile), head(q_tile, 1), head(k_tile, group), stat


def _forward(q, k, v, mask, scale, tile):
    b, hq, positions, hd = q.shape
    t, walk = mask.walk(tile)
    codes, by_q, by_k, stat = _query_major_specs(t, hd, walk, hq // k.shape[1])
    name = mask.kernels[0]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, walk=walk, scale=scale),
        grid=(b, hq, walk.tiles, walk.fwd_max),
        in_specs=codes + [by_q, by_k, by_k],
        out_specs=[by_q, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct((b, hq, 1, positions), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, hd), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name=name,
    )(*_codes(mask), q, k, v)


def _backward(q, k, v, o, lse, do, mask, scale, tile):
    b, hq, _, hd = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    t, walk = mask.walk(tile)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, :, None, :]

    codes, by_q, by_k, stat = _query_major_specs(t, hd, walk, group)
    name = mask.kernels[1]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, walk=walk, scale=scale),
        grid=(b, hq, walk.tiles, walk.fwd_max),
        in_specs=codes + [by_q, by_k, by_k, by_q, stat, stat],
        out_specs=by_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((t, hd), jnp.float32), pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, 1), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name=name,
    )(*_codes(mask), q, k, v, do, lse, delta)

    # dk/dv: one key tile of one key-value head at a time; the last axis
    # walks the group's query heads, and under each the visible query tiles.
    q_of = lambda b_, hk, kt, step: walk.bwd_query_tile(kt, step % walk.bwd_max)
    k_of = lambda b_, hk, kt, step: kt
    q_head = lambda b_, hk, kt, step: hk * group + step // walk.bwd_max
    by_q = _vmem((1, 1, t, hd), lambda b_, hk, kt, step: (b_, q_head(b_, hk, kt, step), q_of(b_, hk, kt, step), 0))
    q_stat = _vmem((1, 1, 1, t), lambda b_, hk, kt, step: (b_, q_head(b_, hk, kt, step), 0, q_of(b_, hk, kt, step)))
    by_k = _vmem((1, 1, t, hd), lambda b_, hk, kt, step: (b_, hk, kt, 0))
    name = mask.kernels[2]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, walk=walk, scale=scale),
        grid=(b, hkv, walk.tiles, group * walk.bwd_max),
        in_specs=_code_specs(t, q_of, k_of, keys_down=True) + [by_q, by_k, by_k, by_q, q_stat, q_stat],
        out_specs=[by_k, by_k],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((t, hd), jnp.float32), pltpu.VMEM((t, hd), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=pallas_interpret(),
        name=name,
    )(*_codes(mask, keys_down=True), q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, mask, scale, tile):
    return _forward(q, k, v, mask, scale, tile)[0]


def _attention_fwd(q, k, v, mask, scale, tile):
    o, lse = _forward(q, k, v, mask, scale, tile)
    return o, (q, k, v, o, lse)


def _attention_bwd(mask, scale, tile, residuals, do):
    return _backward(*residuals, do, mask, scale, tile)


_attention.defvjp(_attention_fwd, _attention_bwd)


def _over_rows(q, k, v, mask, scale, tile):
    if q.shape[2] != mask.positions or k.shape[2] != mask.positions or q.shape[1] % k.shape[1]:
        raise ValueError(f"attention: q {q.shape} / k {k.shape} do not fit {mask.positions} positions")
    return over_data_axis(lambda q, k, v: _attention(q, k, v, mask, scale, tile), (q, k, v))


@scoped("block_attention")
def block_attention(q: Array, k: Array, v: Array, seq_len: int, block_length: int, tile: int = 512) -> Array:
    """q: (B, Hq, 2L, d); k, v: (B, Hkv, 2L, d), Hq a multiple of Hkv;
    scores are scaled by 1/sqrt(d). Returns (B, Hq, 2L, d) in q's dtype."""
    return _over_rows(q, k, v, _Mask(seq_len, block_length), 1.0 / math.sqrt(q.shape[-1]), tile)


@scoped("block_attention")
def causal_attention(q: Array, k: Array, v: Array, scale: float, tile: int = 512) -> Array:
    """q: (B, Hq, L, d); k, v: (B, Hkv, L, d), Hq a multiple of Hkv; scores
    are scaled by `scale`; position i sees positions 0..i. Returns
    (B, Hq, L, d) in q's dtype."""
    return _over_rows(q, k, v, _Mask(q.shape[2], 0), float(scale), tile)


@scoped("window_attention")
def window_attention(q: Array, k: Array, v: Array, window: int, scale: float, tile: int = 512) -> Array:
    """q: (B, Hq, L, d); k, v: (B, Hkv, L, d), Hq a multiple of Hkv; scores
    are scaled by `scale`; position i sees the `window` positions
    i - window + 1 .. i. Returns (B, Hq, L, d) in q's dtype."""
    if window < 1:
        raise ValueError(f"window_attention: a window of {window} keys")
    return _over_rows(q, k, v, _Mask(q.shape[2], 0, int(window)), float(scale), tile)
