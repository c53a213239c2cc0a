"""The `laguna-moe` family: a routed-expert decoder whose layers differ in
kind (poolside Laguna, `model_type: laguna`), trained on the plain next-token
loss.

`h = E[ids]`. For layer l (no bias anywhere), with `H_l =
num_attention_heads_per_layer[l]` query heads and `num_key_value_heads`
key-value heads of `head_dim`:

- attention: `a = RMSNorm(h)`; q, k, v projections; q and k RMS-normed over
  the head (a learned weight) and turned by the rotary embedding of the
  layer's kind (`rotary_tables`: over `partial_rotary_factor` of the head,
  YaRN's inverse frequencies and attention factor where the kind has them),
  both in `ops.qk_norm_rope`'s one pass; scores `q k^T / sqrt(head_dim)` over
  the keys `j <= i` (`full_attention`: `ops.block_attention.causal_attention`)
  or `i - sliding_window < j <= i` (`sliding_attention`:
  `ops.block_attention.window_attention`); `g = sigmoid(a Wg)`, one gate a
  query head, float32; `h1 = h + concat(g_n o_n) Wo`.
- `dense` second half: `h2 = h1 + Wout (silu(x) * y)`, `[x, y] = Win
  RMSNorm(h1)`, of `intermediate_size`.
- `sparse` second half: `m = RMSNorm(h1)`; `s = sigmoid(m Wr)` in float32
  over ALL `num_experts * expert_parallel` experts; the `num_experts_per_tok`
  largest, weights `s_e / their sum`; `h2 = h1 + shared(m) +
  moe_routed_scaling_factor * sum over the chosen experts HELD HERE of w_e
  expert_e(m)`, every expert and the shared one a gated MLP (`Experts` of
  models/sdar_moe.py, told which experts it holds). What the other chips'
  experts would add is left out.

Then a last RMSNorm and the untied head over the vocabulary rows held; the
loss is the mean cross-entropy of position t's logits against id t + 1.

The layers are a Python loop over the three per-layer lists, each rebuilt in
the backward pass from its input (`remat_layers`). Matrix products take the
compute dtype (bf16 under `mixed_precision`) and accumulate in float32;
parameters, the norms, both sigmoids, the attention softmax and the loss are
float32. A layer's attention is traced under the module name
`attention_full` or `attention_window`, so that a trace tells the kinds
apart (obs/scopes.py).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.config import LagunaConfig
from raft_stereo_tpu.models.sdar_moe import (
    _DENSE_INIT, Experts, HeadNormWeight, LMHead, RMSNorm, _matmul, chunked_loss_sum)
from raft_stereo_tpu.ops.block_attention import causal_attention, interior_pair_share, window_attention
from raft_stereo_tpu.ops.qk_norm_rope import qk_norm_rope

Array = jax.Array


def yarn_inv_freq(dim: int, base: float, factor: float, original: int, beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies over a rotary dimension of `dim`, as
    `transformers.modeling_rope_utils._compute_yarn_parameters` computes
    them: a frequency that turns more than `beta_fast` times over the
    `original` positions is kept, one that turns fewer than `beta_slow` times
    is divided by `factor`, a linear ramp between the two correction
    dimensions (truncated to whole ones) in between."""
    correction = lambda turns: dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(correction(beta_fast)), 0), min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    keep = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0.0, 1.0)
    return ((1.0 / (factor * pos_freqs)) * (1.0 - keep) + (1.0 / pos_freqs) * keep).astype(np.float32)


def rotary_tables(seq_len: int, head_dim: int, rope: Dict[str, Any]) -> Tuple[Array, Array]:
    """(cos, sin), each (L, r) float32, r = `partial_rotary_factor` x
    `head_dim`, for position ids 0..L-1 under one kind's `rope_parameters`;
    YaRN's attention factor scales both."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    if rope.get("rope_type", "default") == "yarn":
        inv_freq = yarn_inv_freq(dim, rope["rope_theta"], rope["factor"], rope["original_max_position_embeddings"],
                                 rope.get("beta_fast") or 32, rope.get("beta_slow") or 1)
        scale = rope["attention_factor"]
    else:
        inv_freq = (rope["rope_theta"] ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)).astype(np.float32)
        scale = 1.0
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return scale * jnp.cos(angles), scale * jnp.sin(angles)


class GatedAttention(nn.Module):
    """One layer's attention, residual included; the module's name is its
    kind's scope."""

    config: LagunaConfig
    kind: str  # "full_attention" | "sliding_attention"
    heads: int

    @nn.compact
    def __call__(self, h: Array, cos: Array, sin: Array) -> Tuple[Array, Array]:
        """-> (h + the gated attention's output, the gate's mean)."""
        cfg = self.config
        b, s, d = h.shape
        hq, hkv, hd = self.heads, cfg.num_key_value_heads, cfg.head_dim
        a = RMSNorm(cfg.rms_norm_eps, name="attention_norm")(h)
        w_q = self.param("w_q", _DENSE_INIT, (d, hq * hd), jnp.float32)
        w_k = self.param("w_k", _DENSE_INIT, (d, hkv * hd), jnp.float32)
        w_v = self.param("w_v", _DENSE_INIT, (d, hkv * hd), jnp.float32)
        w_gate = self.param("w_gate", _DENSE_INIT, (d, hq), jnp.float32)
        w_o = self.param("w_o", _DENSE_INIT, (hq * hd, d), jnp.float32)
        q = qk_norm_rope(_matmul(a, w_q), HeadNormWeight(name="q_norm")(hd), cos, sin, hq, cfg.rms_norm_eps)
        k = qk_norm_rope(_matmul(a, w_k), HeadNormWeight(name="k_norm")(hd), cos, sin, hkv, cfg.rms_norm_eps)
        v = _matmul(a, w_v).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        scale = 1.0 / math.sqrt(hd)
        if self.kind == "sliding_attention":
            o = window_attention(q, k, v, cfg.sliding_window, scale, cfg.attention_tile)
        else:
            o = causal_attention(q, k, v, scale, cfg.attention_tile)
        gate = jax.nn.sigmoid(jnp.dot(a, w_gate.astype(a.dtype), preferred_element_type=jnp.float32))
        gated = (o.transpose(0, 2, 1, 3).astype(jnp.float32) * gate[..., None]).astype(a.dtype)
        return h + _matmul(gated.reshape(b, s, hq * hd), w_o), jnp.mean(jax.lax.stop_gradient(gate))


class GatedMLP(nn.Module):
    """`Wout (silu(x) * y)`, `[x, y] = Win u`: the dense second half and the
    shared expert."""

    width: int

    @nn.compact
    def __call__(self, u: Array) -> Array:
        w_in = self.param("w_in", _DENSE_INIT, (u.shape[-1], 2 * self.width), jnp.float32)
        w_out = self.param("w_out", _DENSE_INIT, (self.width, u.shape[-1]), jnp.float32)
        x, y = jnp.split(_matmul(u, w_in), 2, axis=-1)
        hidden = (jax.nn.silu(x.astype(jnp.float32)) * y.astype(jnp.float32)).astype(u.dtype)
        return _matmul(hidden, w_out)


class SigmoidRouter(nn.Module):
    config: LagunaConfig

    @nn.compact
    def __call__(self, m: Array) -> Tuple[Array, Array]:
        """m: (N, D) -> (chosen expert ids (N, k) int32 over ALL experts,
        their weights (N, k) float32, the routed scaling factor included)."""
        cfg = self.config
        w_router = self.param("w_router", _DENSE_INIT, (m.shape[-1], cfg.router_width), jnp.float32)
        scores = jax.nn.sigmoid(jnp.dot(m, w_router.astype(m.dtype), preferred_element_type=jnp.float32))
        weights, chosen = jax.lax.top_k(scores, cfg.num_experts_per_tok)
        weights = cfg.moe_routed_scaling_factor * weights / jnp.sum(weights, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), weights


class LagunaLayer(nn.Module):
    config: LagunaConfig
    index: int

    @nn.compact
    def __call__(self, h: Array, tables: Dict[str, Tuple[Array, Array]]):
        """-> (h, (rows each held expert took (E,), the live share of the
        row buffers, the attention gate's mean)); zeros for the first two in
        a dense layer."""
        cfg, i = self.config, self.index
        kind = cfg.layer_types[i]
        scope = "attention_window" if kind == "sliding_attention" else "attention_full"
        h, gate_mean = GatedAttention(cfg, kind, cfg.num_attention_heads_per_layer[i], name=scope)(h, *tables[kind])
        b, s, d = h.shape
        if cfg.mlp_layer_types[i] == "dense":
            out = GatedMLP(cfg.intermediate_size, name="mlp")(RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(h))
            with jax.named_scope("mlp"):
                h = h + out
            return h, (jnp.zeros((cfg.num_experts,), jnp.int32), jnp.zeros((), jnp.float32), gate_mean)
        m = RMSNorm(cfg.rms_norm_eps, name="post_attention_norm")(h).reshape(b * s, d)
        chosen, weights = SigmoidRouter(cfg, name="router")(m)
        routed, counts, live = Experts(cfg, name="experts")(m, chosen, weights)
        shared = GatedMLP(cfg.shared_expert_intermediate_size, name="shared_expert")(m)
        with jax.named_scope("shared_expert"):
            h = h + (shared + routed).reshape(b, s, d)
        return h, (counts, live, gate_mean)


class Laguna(nn.Module):
    config: LagunaConfig

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, param_dtype=jnp.float32)
        self.norm = RMSNorm(cfg.rms_norm_eps)
        self.lm_head = LMHead(cfg)
        layer = nn.remat(LagunaLayer) if cfg.remat_layers else LagunaLayer
        self.layers = [layer(cfg, i, name=f"layers_{i}") for i in range(cfg.num_hidden_layers)]

    def hidden(self, tokens: Array) -> Tuple[Array, Array, Array, Array]:
        """tokens (B, L) int32 -> (the last norm's output (B, L, D), rows each
        held expert took in each sparse layer (layers, E), each sparse layer's
        live share of its row buffers (layers,), the last layer's mean gate)."""
        cfg = self.config
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        h = self.embed(tokens).astype(dtype)
        with jax.named_scope("embed"):
            tables = {kind: rotary_tables(tokens.shape[1], cfg.head_dim, cfg.rope(kind)) for kind in set(cfg.layer_types)}
        counts, live = [], []
        for mlp_kind, layer in zip(cfg.mlp_layer_types, self.layers):
            h, (rows, share, gate_mean) = layer(h, tables)
            if mlp_kind == "sparse":
                counts.append(rows)
                live.append(share)
        if not counts:
            counts, live = [jnp.zeros((cfg.num_experts,), jnp.int32)], [jnp.zeros((), jnp.float32)]
        return self.norm(h), jnp.stack(counts), jnp.stack(live), gate_mean

    def __call__(self, tokens: Array) -> Tuple[Array, Array]:
        """Logits (B, L, V) float32 over the rows held, and the rows count."""
        h, counts, _, _ = self.hidden(tokens)
        return self.lm_head(h), counts

    def loss(self, tokens: Array) -> Tuple[Array, Dict[str, Array]]:
        """The mean over the B (L - 1) predicting positions of -log
        softmax(logits_t)[id_{t+1}]; a row's last position predicts nothing."""
        cfg = self.config
        b, seq_len = tokens.shape
        h, counts, live, gate_mean = self.hidden(tokens)
        with jax.named_scope("next_token_loss"):
            targets = jnp.roll(tokens, -1, axis=1)
            predicting = jnp.arange(seq_len) < seq_len - 1
            weights = jnp.broadcast_to(predicting / (b * (seq_len - 1)), (b, seq_len)).astype(jnp.float32)
        with jax.named_scope("lm_head"):
            total = chunked_loss_sum(
                h.reshape(b * seq_len, -1), self.lm_head.w_head, targets.reshape(-1), weights.reshape(-1),
                cfg.loss_chunk, "next_token_loss")
        counts = counts.astype(jnp.float32)
        load = jnp.max(counts, axis=1) / jnp.maximum(jnp.mean(counts, axis=1), 1.0)
        return total, {
            "moe_held_rows": jnp.sum(counts),
            "moe_max_over_mean_load": jnp.mean(load),
            "moe_live_row_share": jnp.mean(live),
            # tile pairs the attention backward kernels run without their mask test, of those they visit, by layer kind
            "attn_interior_pair_share": jnp.float32(interior_pair_share(seq_len, tile=cfg.attention_tile)),
            "attn_window_interior_pair_share": jnp.float32(
                interior_pair_share(seq_len, window=cfg.sliding_window, tile=cfg.attention_tile)),
            # a gate that saturates at 0 or 1 is the first thing to look for
            "attn_gate_mean": gate_mean,
        }


@functools.lru_cache(maxsize=8)
def _cached_init_fn(config: LagunaConfig, seq_len: int):
    model = Laguna(config)
    return jax.jit(lambda rng: model.init(rng, jnp.zeros((1, seq_len), jnp.int32)))


def init_laguna_variables(config: LagunaConfig, rng, seq_len: int):
    """Fresh variables through a per-config cached jitted init (as
    `models/sdar_moe.init_sdar_variables`). The sequence length shapes no
    parameter: init traces one attention tile of it at most."""
    return _cached_init_fn(config, min(seq_len, config.attention_tile))(rng)
