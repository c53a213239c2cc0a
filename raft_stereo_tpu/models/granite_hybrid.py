"""The `granite-hybrid` family: Mamba-2 layers and grouped-query attention
layers in one stack (IBM Granite 4.0-H, `model_type: granitemoehybrid` without
routed experts), trained on the plain next-token loss.

`h0 = embedding_multiplier * E[ids]`. Per layer, in the order `layer_types`
gives (no bias but the convolution's):

    r = h; u = RMSNorm(h); h = r + residual_multiplier * mixer(u)
    r = h; u = RMSNorm(h); h = r + residual_multiplier * W_out (silu(a) * b),  [a, b] = W_in u

- `mamba` mixer: `[z, xBC, dt] = W_in u` (`d_inner`, `d_inner + 2 d_state`,
  `n_heads`); `xBC = silu(causal depthwise conv_4(xBC) + bias)`; `[x, B, C]`
  split, x as `n_heads` heads of `d_head`; `dt = softplus(dt + dt_bias)`;
  `A = -exp(A_log)`; the state-space scan (`ops.ssd_scan`), B and C shared by
  every head; `y = RMSNorm(y * silu(z)) * w` over all of `d_inner`; `W_out y`.
- `attention` mixer: q (Hq heads), k, v (Hkv heads) of `hidden / Hq`, no
  rotary and no other positional term, causal, scores scaled by
  `attention_multiplier` (`ops.block_attention.causal_attention`).

Then a last RMSNorm and the TIED head: logits `= RMSNorm(h) E^T /
logits_scaling` over the vocabulary rows held here; the loss is the mean
cross-entropy of position t's logits against id t + 1.

Each layer is rebuilt in the backward pass from its input (`remat_layers`);
the layers are a Python loop, since their kinds differ. Matrix products take
the compute dtype (bf16 under `mixed_precision`) and accumulate in float32;
parameters, the norms, `dt`'s product, softplus, every decay and the carried
state, the attention softmax and the loss are float32.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_stereo_tpu.config import GraniteHybridConfig
from raft_stereo_tpu.models.sdar_moe import _DENSE_INIT, RMSNorm, _matmul, chunked_loss_sum
from raft_stereo_tpu.ops.block_attention import causal_attention, interior_pair_share
from raft_stereo_tpu.ops.ssd_scan import ssd_scan

Array = jax.Array


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniformly from [0.001, 0.1]."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def _conv_init(key, shape, dtype):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def causal_conv(x: Array, taps: Array, bias: Array) -> Array:
    """Depthwise over the last axis, causal over axis 1: `y_t = bias + sum_k
    taps[k] x_{t - K + 1 + k}` (the last tap weighs the position itself),
    float32. x: (B, L, C); taps: (K, C)."""
    width, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), [(0, 0), (width - 1, 0), (0, 0)])
    return bias + sum(taps[k] * padded[:, k:k + seq] for k in range(width))


class MambaMixer(nn.Module):
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u: Array) -> Tuple[Array, Array]:
        """u: (B, L, D) -> (the mixer's output (B, L, D), the root mean
        square of the scan's state after the last position)."""
        cfg = self.config
        bsz, seq, d = u.shape
        inner, n, heads = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_heads
        channels = inner + 2 * n
        w_in = self.param("w_in", _DENSE_INIT, (d, inner + channels + heads), jnp.float32)
        conv_w = self.param("conv_w", _conv_init, (cfg.mamba_d_conv, channels), jnp.float32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (channels,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,), jnp.float32)
        a_log = self.param("a_log", _a_log_init, (heads,), jnp.float32)
        skip = self.param("d", nn.initializers.ones, (heads,), jnp.float32)
        w_out = self.param("w_out", _DENSE_INIT, (inner, d), jnp.float32)
        with jax.named_scope("ssm_proj"):
            w_z, w_xbc, w_dt = jnp.split(w_in.astype(u.dtype), [inner, inner + channels], axis=1)
            z, xbc = _matmul(u, w_z), _matmul(u, w_xbc)
            dt = jnp.dot(u, w_dt, preferred_element_type=jnp.float32)
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(causal_conv(xbc, conv_w, conv_b)).astype(u.dtype)
            x, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
            dt, a = jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log)
        y, final = ssd_scan(
            x.reshape(bsz, seq, heads, cfg.mamba_d_head), dt, a, b, c, skip, cfg.mamba_chunk_size)
        with jax.named_scope("ssm_gate_norm"):
            gated = y.reshape(bsz, seq, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
            y = RMSNorm(cfg.rms_norm_eps, name="gate_norm")(gated).astype(u.dtype)
        with jax.named_scope("ssm_proj"):
            out = _matmul(y, w_out)
        with jax.named_scope("ssm_scan"):
            rms = jnp.sqrt(jnp.mean(jnp.square(jax.lax.stop_gradient(final))))
        return out, rms


class Attention(nn.Module):
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u: Array) -> Array:
        cfg = self.config
        b, s, d = u.shape
        hq, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        w_q = self.param("w_q", _DENSE_INIT, (d, hq * hd), jnp.float32)
        w_k = self.param("w_k", _DENSE_INIT, (d, hkv * hd), jnp.float32)
        w_v = self.param("w_v", _DENSE_INIT, (d, hkv * hd), jnp.float32)
        w_o = self.param("w_o", _DENSE_INIT, (hq * hd, d), jnp.float32)
        heads_first = lambda x, h: x.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
        o = causal_attention(
            heads_first(_matmul(u, w_q), hq), heads_first(_matmul(u, w_k), hkv), heads_first(_matmul(u, w_v), hkv),
            cfg.attention_multiplier, cfg.attention_tile)
        return _matmul(o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd), w_o)


class MLP(nn.Module):
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u: Array) -> Array:
        cfg = self.config
        w_in = self.param("w_in", _DENSE_INIT, (u.shape[-1], 2 * cfg.intermediate_size), jnp.float32)
        w_out = self.param("w_out", _DENSE_INIT, (cfg.intermediate_size, u.shape[-1]), jnp.float32)
        a, b = jnp.split(_matmul(u, w_in), 2, axis=-1)
        hidden = (jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)).astype(u.dtype)
        return _matmul(hidden, w_out)


def _add(h: Array, out: Array, multiplier: float) -> Array:
    """h + multiplier * out, summed in float32."""
    return (h.astype(jnp.float32) + multiplier * out.astype(jnp.float32)).astype(h.dtype)


class HybridLayer(nn.Module):
    config: GraniteHybridConfig
    kind: str  # "mamba" | "attention"

    @nn.compact
    def __call__(self, h: Array) -> Tuple[Array, Array]:
        cfg = self.config
        if self.kind == "mamba":
            mixed, state_rms = MambaMixer(cfg, name="mixer")(RMSNorm(cfg.rms_norm_eps, name="ssm_norm")(h))
            with jax.named_scope("ssm_proj"):
                h = _add(h, mixed, cfg.residual_multiplier)
        else:
            mixed, state_rms = Attention(cfg, name="attention")(RMSNorm(cfg.rms_norm_eps, name="input_norm")(h)), None
            with jax.named_scope("attention"):
                h = _add(h, mixed, cfg.residual_multiplier)
        out = MLP(cfg, name="mlp")(RMSNorm(cfg.rms_norm_eps, name="mlp_norm")(h))
        with jax.named_scope("mlp"):
            h = _add(h, out, cfg.residual_multiplier)
        return h, jnp.zeros((), jnp.float32) if state_rms is None else state_rms


class GraniteHybrid(nn.Module):
    config: GraniteHybridConfig

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, param_dtype=jnp.float32, embedding_init=nn.initializers.normal(0.1))
        self.norm = RMSNorm(cfg.rms_norm_eps)
        layer = nn.remat(HybridLayer) if cfg.remat_layers else HybridLayer
        self.layers = [layer(cfg, kind, name=f"layers_{i}") for i, kind in enumerate(cfg.layer_types)]

    def hidden(self, tokens: Array) -> Tuple[Array, Array]:
        """tokens (B, L) int32 -> (the last norm's output (B, L, D), the last
        state-space layer's final-state root mean square)."""
        cfg = self.config
        dtype = jnp.bfloat16 if cfg.mixed_precision else jnp.float32
        h = self.embed(tokens)
        with jax.named_scope("embed"):
            h = (cfg.embedding_multiplier * h).astype(dtype)
        state_rms = jnp.zeros((), jnp.float32)
        for kind, layer in zip(cfg.layer_types, self.layers):
            h, rms = layer(h)
            if kind == "mamba":
                state_rms = rms
        return self.norm(h), state_rms

    def __call__(self, tokens: Array) -> Array:
        """Logits (B, L, V) float32 over the rows held."""
        h, _ = self.hidden(tokens)
        logits = jnp.dot(h, self.embed.embedding.T.astype(h.dtype), preferred_element_type=jnp.float32)
        return logits / self.config.logits_scaling

    def loss(self, tokens: Array) -> Tuple[Array, Dict[str, Array]]:
        """The mean over the B (L - 1) predicting positions of -log
        softmax(logits_t)[id_{t+1}]; a row's last position predicts nothing."""
        cfg = self.config
        b, seq_len = tokens.shape
        h, state_rms = self.hidden(tokens)
        with jax.named_scope("next_token_loss"):
            targets = jnp.roll(tokens, -1, axis=1)
            predicting = jnp.arange(seq_len) < seq_len - 1
            weights = jnp.broadcast_to(predicting / (b * (seq_len - 1)), (b, seq_len)).astype(jnp.float32)
        with jax.named_scope("lm_head"):
            total = chunked_loss_sum(
                h.reshape(b * seq_len, -1), self.embed.embedding.T, targets.reshape(-1), weights.reshape(-1),
                cfg.loss_chunk, "next_token_loss", 1.0 / cfg.logits_scaling)
        return total, {
            "ssm_final_state_rms": state_rms,
            # tile pairs the attention backward kernels run without their mask test, of those they visit
            "attn_interior_pair_share": jnp.float32(interior_pair_share(seq_len, tile=cfg.attention_tile)),
        }


@functools.lru_cache(maxsize=8)
def _cached_init_fn(config: GraniteHybridConfig, seq_len: int):
    model = GraniteHybrid(config)
    return jax.jit(lambda rng: model.init(rng, jnp.zeros((1, seq_len), jnp.int32)))


def init_granite_variables(config: GraniteHybridConfig, rng, seq_len: int):
    """Fresh variables through a per-config cached jitted init (as
    `models/sdar_moe.init_sdar_variables`). The sequence length shapes no
    parameter: init traces one chunk of it at most."""
    return _cached_init_fn(config, min(seq_len, config.mamba_chunk_size))(rng)
