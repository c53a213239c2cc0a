"""The `sdar-moe` family: a routed-expert decoder trained by block diffusion.

Per layer, for the hidden state `h` (no bias anywhere):

- `attention`: `a = RMSNorm(h)`; q (Hq heads), k, v (Hkv heads) of
  `head_dim`; RMSNorm over the head dimension of q and k (a learned weight
  per head dimension), then the rotary embedding over the whole head
  dimension (rotate-half) by position id, both in `ops.qk_norm_rope`'s one
  pass from a projection's output to the heads-first operand;
  `ops.block_attention` under the block-diffusion mask;
  `h1 = h + concat(heads) Wo`.
- `router`: `m = RMSNorm(h1)`; `p = softmax(m Wr)` in float32 over ALL
  `num_experts * expert_parallel` experts; the `num_experts_per_tok`
  largest, weights renormalised over them (`norm_topk_prob`).
- `experts`: `h2 = h1 + sum over chosen experts HELD HERE of w_e *
  (silu(m Wgate_e) * (m Wup_e)) Wdown_e`, by `ops.grouped_matmul` over rows
  sorted by expert. What the other chips' experts would add is left out.

Then a last RMSNorm and the untied head (`lm_head`) over the vocabulary rows
held here. The input is `[x_t ; x_0]`: a row's L tokens noised (`masked`
positions replaced by `mask_token_id`), then the same L tokens clean,
position ids `[0..L-1, 0..L-1]`; logits are read at the noised half.

Layers are one `nn.scan` over stacked parameters with per-layer remat, so
compile time does not grow with depth. Matrix products take the compute dtype
(bf16 under `mixed_precision`) and accumulate in float32; the norms, the
router's softmax, the attention softmax and the loss are float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_stereo_tpu.config import SDARMoEConfig
from raft_stereo_tpu.ops.block_attention import block_attention, interior_pair_share
from raft_stereo_tpu.ops.data_axis import over_data_axis
from raft_stereo_tpu.ops.grouped_matmul import group_layout, grouped_matmul, swiglu_rows
from raft_stereo_tpu.ops.qk_norm_rope import qk_norm_rope
from raft_stereo_tpu.ops.tile_rows import fits, gather_rows, scatter_add_rows

Array = jax.Array


# N(0, 1 / fan_in), the fan-in being the axis a product contracts.
_DENSE_INIT = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1)


def _matmul(x: Array, w: Array) -> Array:
    """x @ w in x's dtype, float32 accumulation inside the product."""
    return jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x: Array) -> Array:
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * weight).astype(x.dtype)


def rotary_tables(seq_len: int, head_dim: int, theta: float) -> Tuple[Array, Array]:
    """(cos, sin), each (2L, head_dim) float32, for position ids
    `[0..L-1, 0..L-1]`."""
    inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    pos = jnp.tile(jnp.arange(seq_len, dtype=jnp.float32), 2)
    angles = pos[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


class HeadNormWeight(nn.Module):
    """The learned weight of a q or k norm, (head_dim,), where an `RMSNorm`
    of that name keeps it; `ops.qk_norm_rope` applies it."""

    @nn.compact
    def __call__(self, head_dim: int) -> Array:
        return self.param("weight", nn.initializers.ones, (head_dim,), jnp.float32)


class Attention(nn.Module):
    config: SDARMoEConfig

    @nn.compact
    def __call__(self, a: Array, cos: Array, sin: Array) -> Array:
        cfg = self.config
        b, s, d = a.shape
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        w_q = self.param("w_q", _DENSE_INIT, (d, hq * hd), jnp.float32)
        w_k = self.param("w_k", _DENSE_INIT, (d, hkv * hd), jnp.float32)
        w_v = self.param("w_v", _DENSE_INIT, (d, hkv * hd), jnp.float32)
        w_o = self.param("w_o", _DENSE_INIT, (hq * hd, d), jnp.float32)
        q = qk_norm_rope(_matmul(a, w_q), HeadNormWeight(name="q_norm")(hd), cos, sin, hq, cfg.rms_norm_eps)
        k = qk_norm_rope(_matmul(a, w_k), HeadNormWeight(name="k_norm")(hd), cos, sin, hkv, cfg.rms_norm_eps)
        heads_first = lambda x: x.transpose(0, 2, 1, 3)
        v = heads_first(_matmul(a, w_v).reshape(b, s, hkv, hd))
        o = block_attention(q, k, v, s // 2, cfg.block_length, cfg.attention_tile)
        return _matmul(heads_first(o).reshape(b, s, hq * hd), w_o)


class Router(nn.Module):
    config: SDARMoEConfig

    @nn.compact
    def __call__(self, m: Array) -> Tuple[Array, Array]:
        """m: (N, D) -> (chosen expert ids (N, k) int32 over ALL experts,
        their weights (N, k) float32)."""
        cfg = self.config
        w_router = self.param("w_router", _DENSE_INIT, (m.shape[-1], cfg.router_width), jnp.float32)
        logits = jnp.dot(m, w_router.astype(m.dtype), preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), weights


# -- dispatch and combine -------------------------------------------------------------
#
# A row's source and an assignment's row are each other's inverse
# (`group_layout`). Two formulations of the same copies:
#
# - `_dispatch_live` / `_combine_live`: the kernels of `ops/tile_rows.py`,
#   which walk the tile table `grouped_matmul` walks and move the LIVE rows
#   only (with 16 of 128 experts held, an eighth of a chunk's buffer). The
#   combine is a scatter-add by rows, its backward a gather by rows, and the
#   router weights' gradient a row's dot product, gathered as a scalar.
# - `_dispatch` / `_combine`: `jax.numpy` gathers in both directions over every
#   row of the worst-case buffer (a scatter-add in either direction written
#   as the gather the other map gives). The path for a table that does not
#   fit the kernels' VMEM budget (`tile_rows.fits`), and what the tests hold
#   the kernels to.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dispatch_live(m, source, num_tiles, tile, positions, k):
    """rows[r] = m[source[r] // k], a zero row where `source[r]` (the
    assignment row r holds) is negative; rows of dead tiles are not written.
    m: (positions, D)."""
    return gather_rows(m, source, num_tiles, tile, k)


def _dispatch_live_fwd(m, source, num_tiles, tile, positions, k):
    return _dispatch_live(m, source, num_tiles, tile, positions, k), (source, num_tiles)


def _dispatch_live_bwd(tile, positions, k, residuals, d_rows):
    source, num_tiles = residuals
    return scatter_add_rows(d_rows, source, num_tiles, tile, positions, k), None, None


_dispatch_live.defvjp(_dispatch_live_fwd, _dispatch_live_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _combine_live(rows, weights, source, slot_row, held, num_tiles, tile):
    """y[c] = sum over the live rows r of position c of weights[assignment of
    r] * rows[r], in float32. rows: (R, D); weights: (C, k) float32."""
    c, k = weights.shape
    return scatter_add_rows(rows, source, num_tiles, tile, c, k, weights.reshape(-1))


def _combine_live_fwd(rows, weights, source, slot_row, held, num_tiles, tile):
    out = _combine_live(rows, weights, source, slot_row, held, num_tiles, tile)
    return out, (rows, weights, source, slot_row, held, num_tiles)


def _combine_live_bwd(tile, residuals, d_y):
    rows, weights, source, slot_row, held, num_tiles = residuals
    d_rows, d_row_weight = gather_rows(
        d_y, source, num_tiles, tile, weights.shape[1], weights=weights.reshape(-1), dot_with=rows)
    return d_rows, jnp.where(held, d_row_weight[slot_row], 0.0), None, None, None, None


_combine_live.defvjp(_combine_live_fwd, _combine_live_bwd)


@jax.custom_vjp
def _dispatch(m, row_token, row_live, slot_row, held):
    """rows[r] = m[token of r] for a live row, else 0. m: (C, D)."""
    return jnp.where(row_live[:, None], m[row_token], 0).astype(m.dtype)


def _dispatch_fwd(m, row_token, row_live, slot_row, held):
    return _dispatch(m, row_token, row_live, slot_row, held), (slot_row, held)


def _dispatch_bwd(residuals, d_rows):
    slot_row, held = residuals  # (C, k)
    taken = jnp.where(held[..., None], d_rows[slot_row], 0)
    return jnp.sum(taken.astype(jnp.float32), axis=1).astype(d_rows.dtype), None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, row_token, row_slot, row_live, slot_row, held):
    """y[c] = sum over the position's held choices s of weights[c, s] *
    rows[row of (c, s)]. rows: (R, D); weights: (C, k) float32."""
    taken = jnp.where(held[..., None], rows[slot_row], 0).astype(jnp.float32)
    return jnp.sum(taken * weights[..., None], axis=1).astype(rows.dtype)


def _combine_fwd(rows, weights, row_token, row_slot, row_live, slot_row, held):
    out = _combine(rows, weights, row_token, row_slot, row_live, slot_row, held)
    return out, (rows, weights, row_token, row_slot, row_live, slot_row, held)


def _combine_bwd(residuals, d_y):
    rows, weights, row_token, row_slot, row_live, slot_row, held = residuals
    row_weight = weights[row_token, row_slot]
    d_rows = jnp.where(row_live[:, None], d_y[row_token].astype(jnp.float32) * row_weight[:, None], 0)
    taken = jnp.where(held[..., None], rows[slot_row], 0).astype(jnp.float32)
    d_weights = jnp.sum(taken * d_y[:, None, :].astype(jnp.float32), axis=-1)
    return d_rows.astype(rows.dtype), d_weights, None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class Experts(nn.Module):
    config: SDARMoEConfig

    @nn.compact
    def __call__(self, m: Array, chosen: Array, weights: Array) -> Tuple[Array, Array, Array]:
        """m: (N, D); chosen / weights: (N, k). -> (this chip's experts' part
        of the layer's output (N, D), rows each held expert took (E,), the
        live tiles' share of the row buffers they lie in)."""
        cfg = self.config
        d = m.shape[1]
        e, f, k = cfg.num_experts, cfg.moe_intermediate_size, cfg.num_experts_per_tok
        w_gate = self.param("w_gate", _DENSE_INIT, (e, d, f), jnp.float32)
        w_up = self.param("w_up", _DENSE_INIT, (e, d, f), jnp.float32)
        w_down = self.param("w_down", _DENSE_INIT, (e, f, d), jnp.float32)
        w_gate_up = jnp.concatenate([w_gate, w_up], axis=-1).astype(m.dtype)
        w_down = w_down.astype(m.dtype)
        tile = cfg.moe_tile_rows
        local = chosen - cfg.expert_shard * e
        expert = jnp.where((local >= 0) & (local < e), local, e)

        def one_chunk(m_c, expert_c, weights_c, w_gate_up, w_down):
            c = m_c.shape[0]
            layout = group_layout(expert_c.reshape(-1), e, tile)
            row_source, row_live = layout["row_source"], layout["row_live"]
            slot_row, held = layout["slot_row"].reshape(-1, k), layout["held"].reshape(-1, k)
            groups = (layout["tile_expert"], layout["num_tiles"], tile)
            live_copies = fits(c, d, m_c.dtype, row_live.shape[0], tile, k)
            if live_copies:
                source = jnp.where(row_live, row_source, -1)
                rows = _dispatch_live(m_c, source, layout["num_tiles"], tile, c, k)
            else:
                row_token, row_slot = row_source // k, row_source % k
                rows = _dispatch(m_c, row_token, row_live, slot_row, held)
            hidden = swiglu_rows(grouped_matmul(rows, w_gate_up, *groups), layout["num_tiles"], tile)
            out_rows = grouped_matmul(hidden, w_down, *groups)
            if live_copies:
                y = _combine_live(out_rows, weights_c, source, slot_row, held, layout["num_tiles"], tile)
            else:
                y = _combine(out_rows, weights_c, row_token, row_slot, row_live, slot_row, held)
            return y, layout["counts"], layout["num_tiles"] * (tile / row_live.shape[0])

        def positions_here(m, expert, weights, w_gate_up, w_down):
            """A device's own positions (all of them on one device)."""
            n = m.shape[0]
            chunk = cfg.moe_chunk if n % cfg.moe_chunk == 0 else n
            if chunk == n:
                y, counts, live = one_chunk(m, expert, weights, w_gate_up, w_down)
                return y, counts[None], live
            shape = lambda x: x.reshape(n // chunk, chunk, *x.shape[1:])
            # The worst-case row buffers live for one chunk: a chunk's products
            # are rebuilt in the backward from its inputs alone.
            body = jax.checkpoint(lambda _, xs: (None, one_chunk(*xs, w_gate_up, w_down)), prevent_cse=False)
            _, (y, counts, live) = jax.lax.scan(body, None, (shape(m), shape(expert), shape(weights)))
            return y.reshape(n, d), jnp.sum(counts, axis=0)[None], jnp.mean(live, axis=0)

        y, counts, live = over_data_axis(positions_here, (m, expert, weights), (w_gate_up, w_down))
        return y, jnp.sum(counts, axis=0), jnp.mean(live)


class DecoderLayer(nn.Module):
    config: SDARMoEConfig

    @nn.compact
    def __call__(self, h: Array, tables: Tuple[Array, Array]):
        cfg = self.config
        b, s, d = h.shape
        a = RMSNorm(cfg.rms_norm_eps, name="input_norm")(h)
        h = h + Attention(cfg, name="attention")(a, *tables)
        m = RMSNorm(cfg.rms_norm_eps, name="post_attention_norm")(h).reshape(b * s, d)
        chosen, weights = Router(cfg, name="router")(m)
        y, counts, live = Experts(cfg, name="experts")(m, chosen, weights)
        return h + y.reshape(b, s, d), (counts, live)


class LMHead(nn.Module):
    """The untied head over the held vocabulary rows. `__call__` gives
    logits; `loss_sum` the weighted negative log-likelihood without ever
    holding the logits of more than `loss_chunk` positions."""

    config: SDARMoEConfig

    def setup(self):
        cfg = self.config
        self.w_head = self.param("w_head", _DENSE_INIT, (cfg.hidden_size, cfg.vocab_size), jnp.float32)

    def __call__(self, h: Array) -> Array:
        return jnp.dot(h, self.w_head.astype(h.dtype), preferred_element_type=jnp.float32)

    def loss_sum(self, h: Array, targets: Array, weights: Array) -> Array:
        """h: (N, D); targets (N,) int32; weights (N,) float32 -> sum of
        weights * (-log softmax(logits)[target])."""
        return chunked_loss_sum(h, self.w_head, targets, weights, self.config.loss_chunk, "block_diffusion_loss")


def chunked_loss_sum(h: Array, w_head: Array, targets: Array, weights: Array, loss_chunk: int, scope: str,
                     logit_scale: float = 1.0) -> Array:
    """sum of weights * (-log softmax(logit_scale * h w_head)[target]) over N
    positions, holding the logits of `loss_chunk` of them at a time (each
    chunk's are rebuilt in the backward pass). h: (N, D); w_head: (D, V)
    float32; targets (N,) int32; weights (N,) float32. The softmax's part is
    traced under the named scope `scope`."""
    n = h.shape[0]
    chunk = loss_chunk if n % loss_chunk == 0 else n
    w_head = w_head.astype(h.dtype)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk_loss(total, xs):
        h_c, target_c, weight_c = xs
        logits = jnp.dot(h_c, w_head, preferred_element_type=jnp.float32)
        if logit_scale != 1.0:
            logits = logits * logit_scale
        with jax.named_scope(scope):
            log_z = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, target_c[:, None], axis=-1)[:, 0]
            return total + jnp.sum(weight_c * (log_z - picked)), None

    shape = lambda x: x.reshape(n // chunk, chunk, *x.shape[1:])
    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32), (shape(h), shape(targets), shape(weights)))
    return total


class SDARDecoder(nn.Module):
    config: SDARMoEConfig

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=jnp.float32)
        self.norm = RMSNorm(cfg.rms_norm_eps)
        self.lm_head = LMHead(cfg)
        layer = nn.remat(DecoderLayer, prevent_cse=False) if cfg.remat_layers else DecoderLayer
        self.layers = nn.scan(
            layer, variable_axes={"params": 0}, split_rngs={"params": True}, in_axes=nn.broadcast,
            length=cfg.num_hidden_layers,
        )(cfg)

    def hidden(self, tokens: Array, masked: Array) -> Tuple[Array, Array, Array]:
        """tokens (B, L) int32, masked (B, L) bool -> (the last norm's output
        at the noised half (B, L, D), rows each held expert took in each layer
        (layers, E), each layer's live share of its row buffers (layers,))."""
        cfg = self.config
        seq_len = tokens.shape[1]
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        with jax.named_scope("embed"):
            noised = jnp.where(masked, cfg.mask_token_id, tokens)
            ids = jnp.concatenate([noised, tokens], axis=1)
        h = self.embed(ids).astype(dtype)
        with jax.named_scope("attention"):
            tables = rotary_tables(seq_len, cfg.head_dim, cfg.rope_theta)
        h, (counts, live) = self.layers(h, tables)
        return self.norm(h[:, :seq_len]), counts, live

    def __call__(self, tokens: Array, masked: Array) -> Tuple[Array, Array]:
        """Logits at the noised half, (B, L, V) float32, and the rows count."""
        h, counts, _ = self.hidden(tokens, masked)
        return self.lm_head(h), counts

    def loss(self, tokens: Array, masked: Array, noise_t: Array) -> Tuple[Array, Dict[str, Array]]:
        """The block-diffusion loss: `1 / (B L) * sum over masked positions
        of (1 / t_block) * nll`; `noise_t` (B, L / block_length) is each
        block's noise level."""
        cfg = self.config
        b, seq_len = tokens.shape
        h, counts, live = self.hidden(tokens, masked)
        with jax.named_scope("block_diffusion_loss"):
            per_position = jnp.repeat(1.0 / noise_t, cfg.block_length, axis=1)
            weights = jnp.where(masked, per_position, 0.0).astype(jnp.float32) / (b * seq_len)
        total = self.lm_head.loss_sum(h.reshape(b * seq_len, -1), tokens.reshape(-1), weights.reshape(-1))
        counts = counts.astype(jnp.float32)
        load = jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts, axis=1), 1.0)
        return total, {
            "moe_held_rows": jnp.sum(counts),
            "moe_max_over_mean_load": jnp.mean(load),
            # rows of the live tiles over the rows of the buffers they lie in:
            # what the copies of `ops/tile_rows.py` touch of the worst case
            "moe_live_row_share": jnp.mean(live),
            # tile pairs the attention backward kernels run without their mask test, of those they visit
            "attn_interior_pair_share": jnp.float32(interior_pair_share(seq_len, cfg.block_length, tile=cfg.attention_tile)),
            "masked_tokens": jnp.sum(masked.astype(jnp.float32)),
        }


@functools.lru_cache(maxsize=8)
def _cached_init_fn(config: SDARMoEConfig, seq_len: int):
    model = SDARDecoder(config)
    tokens = jnp.zeros((1, seq_len), jnp.int32)
    return jax.jit(lambda rng: model.init(rng, tokens, jnp.zeros((1, seq_len), bool)))


def init_sdar_variables(config: SDARMoEConfig, rng, seq_len: int):
    """Fresh variables through a per-config cached jitted init (as
    models/init_cache.py does for the stereo family). The sequence length
    shapes no parameter; the step's own is taken so that the kernels are
    traced at the tiles they run with."""
    return _cached_init_fn(config, seq_len)(rng)
