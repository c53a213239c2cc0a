"""Plain reference of the `granite-hybrid` family (IBM Granite 4.0-H,
`model_type: granitemoehybrid` with no routed experts): straight `jax.numpy`,
float32 at the highest matmul precision, the state-space recurrence one
position at a time, a dense causal mask. It imports nothing of
`raft_stereo_tpu` and is handed only a configuration's file, a weight tree
and a batch.

The layer equations (keys as published; no bias but the convolution's):

    h0 = embedding_multiplier * E[ids]
    for each layer, of the kind `layer_types` gives:
        r = h; u = RMSNorm(h); h = r + residual_multiplier * mixer(u)
        r = h; u = RMSNorm(h); h = r + residual_multiplier * W_out (silu(a) * b),
                                   [a, b] = W_in u  (`shared_intermediate_size` each)
    logits = RMSNorm(h) E^T / logits_scaling      (tied head, over the rows held)
    loss   = mean over t < L - 1 of -log softmax(logits_t)[id_{t+1}]

- `mamba` mixer (Mamba-2; `mamba_n_heads` H heads of `mamba_d_head` P, state
  `mamba_d_state` N, `mamba_n_groups` 1, `mamba_d_conv` K, no projection bias,
  a convolution bias): `[z, xBC, dt] = W_in u` (H P, H P + 2 N, H);
  `xBC = silu(causal depthwise conv_K(xBC) + b)`; `[x, B, C]` = split
  (H P, N, N), x as H heads of P; `dt = softplus(dt + dt_bias)`, clamped to
  `time_step` limits (0, inf), which clamp nothing; `A = -exp(A_log)` a head;
  `S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T` (N x P a head), `y_t = S_t^T
  C_t + D x_t`; `y = RMSNorm_{H P}(y * silu(z)) * w`; out `= W_out y`. B and C
  are shared by all heads.
- `attention` mixer: `num_attention_heads` query and `num_key_value_heads`
  key-value heads of `hidden_size / num_attention_heads`, no bias, no rotary
  or other positional term (`position_embedding_type: nope`), causal, scores
  scaled by `attention_multiplier` (not 1 / sqrt(head)), softmax in float32.

The recurrence is a `lax.scan` over positions, wrapped by `jax.checkpoint`
over segments of positions (and each layer by another): that changes what is
kept for the backward pass and no arithmetic.

`precision`: "float32" is the reference; "bfloat16" / "fp8" round every
matrix product's inputs (straight-through: the projections, the outer
product and the readout of the recurrence, attention's two products, the
head), the control one step below what the configuration states. `fault`
plants a wrong program for the limits' readings: "chunk_reset" (the state is
not carried from one chunk of `mamba_chunk_size` positions into the next),
"bidirectional_attention" (no causal mask).

Departures from the published model, each an `assumed` of the configuration:
the loss is taken at this stage's output over the vocabulary rows held here.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import learning_rate, rounder

HIGHEST = lax.Precision.HIGHEST
_NEG = -1e30


def _dims(cfg: Dict) -> Dict[str, int]:
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    if cfg["mamba_n_groups"] != 1 or heads * p != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("granite_reference: one group of B and C, and n_heads * d_head = expand * hidden_size")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("granite_reference: layer_types does not name num_hidden_layers layers")
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "f": cfg["shared_intermediate_size"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
        "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
        "h": heads, "p": p, "n": n, "inner": heads * p, "channels": heads * p + 2 * n, "k": cfg["mamba_d_conv"],
    }


def param_shapes(cfg: Dict) -> Dict[str, dict]:
    """The weight tree's layout: `layers_<i>` a layer, its mixer's leaves by
    its kind."""
    s = _dims(cfg)
    d = s["d"]
    mixers = {
        "mamba": {
            "ssm_norm": {"weight": (d,)},
            "mixer": {
                "w_in": (d, s["inner"] + s["channels"] + s["h"]), "conv_w": (s["k"], s["channels"]),
                "conv_b": (s["channels"],), "dt_bias": (s["h"],), "a_log": (s["h"],), "d": (s["h"],),
                "gate_norm": {"weight": (s["inner"],)}, "w_out": (s["inner"], d),
            },
        },
        "attention": {
            "input_norm": {"weight": (d,)},
            "attention": {
                "w_q": (d, s["hq"] * s["hd"]), "w_k": (d, s["hkv"] * s["hd"]), "w_v": (d, s["hkv"] * s["hd"]),
                "w_o": (s["hq"] * s["hd"], d),
            },
        },
    }
    mlp = {"mlp_norm": {"weight": (d,)}, "mlp": {"w_in": (d, 2 * s["f"]), "w_out": (s["f"], d)}}
    tree = {"embed": {"embedding": (s["v"], d)}, "norm": {"weight": (d,)}}
    for i, kind in enumerate(cfg["layer_types"]):
        tree[f"layers_{i}"] = {**mixers[kind], **mlp}
    return tree


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _dot(q_, a, b):
    return jnp.dot(q_(a), q_(b), precision=HIGHEST)


def _segment(seq_len: int) -> int:
    return next(s for s in (128, 64, 32, 16, 8, 4, 2, 1) if seq_len % s == 0)


def _recurrence(q_, x, dt, a, b, c, skip, reset_every):
    """x: (L, H, P); dt: (L, H); a, skip: (H,); b, c: (L, N) -> (y (L, H, P),
    the state after the last position (H, N, P)). `reset_every` > 0 plants
    the fault: the state is dropped at every multiple of it."""
    seq_len, h, p = x.shape
    n = b.shape[-1]
    seg = _segment(seq_len)
    position = jnp.arange(seq_len)

    def one(state, at):
        x_t, dt_t, b_t, c_t, t = at
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        decay = jnp.exp(dt_t * a)
        state = decay[:, None, None] * state + dt_t[:, None, None] * (q_(b_t)[None, :, None] * q_(x_t)[:, None, :])
        y_t = jnp.einsum("hnp,n->hp", q_(state), q_(c_t), precision=HIGHEST) + skip[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def one_segment(state, segment):
        return lax.scan(one, state, segment)

    by_segment = lambda v: v.reshape(seq_len // seg, seg, *v.shape[1:])
    final, y = lax.scan(
        one_segment, jnp.zeros((h, n, p), jnp.float32), tuple(map(by_segment, (x, dt, b, c, position))))
    return y.reshape(seq_len, h, p), final


def _mamba(q_, cfg, s, p, u, fault):
    """u: (L, D) of one row -> (the mixer's output (L, D), the final state)."""
    seq_len = u.shape[0]
    z, xbc, dt = jnp.split(_dot(q_, u, p["w_in"]), [s["inner"], s["inner"] + s["channels"]], axis=-1)
    padded = jnp.pad(xbc, [(s["k"] - 1, 0), (0, 0)])
    conv = p["conv_b"] + sum(p["conv_w"][k] * padded[k:k + seq_len] for k in range(s["k"]))
    x, b, c = jnp.split(jax.nn.silu(conv), [s["inner"], s["inner"] + s["n"]], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    reset_every = cfg["mamba_chunk_size"] if fault == "chunk_reset" else 0
    y, final = _recurrence(
        q_, x.reshape(seq_len, s["h"], s["p"]), dt, -jnp.exp(p["a_log"]), b, c, p["d"], reset_every)
    y = _rms_norm(y.reshape(seq_len, s["inner"]) * jax.nn.silu(z), p["gate_norm"]["weight"], cfg["rms_norm_eps"])
    return _dot(q_, y, p["w_out"]), final


def _attention(q_, cfg, s, p, u, fault):
    """u: (L, D) of one row."""
    seq_len = u.shape[0]
    q = _dot(q_, u, p["w_q"]).reshape(seq_len, s["hq"], s["hd"])
    k = _dot(q_, u, p["w_k"]).reshape(seq_len, s["hkv"], s["hd"])
    v = _dot(q_, u, p["w_v"]).reshape(seq_len, s["hkv"], s["hd"])
    group = s["hq"] // s["hkv"]
    mask = jnp.tril(jnp.ones((seq_len, seq_len), bool)) | (fault == "bidirectional_attention")

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        scores = jnp.dot(q_(qh), q_(kh).T, precision=HIGHEST) * cfg["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
        return jnp.dot(q_(probs), q_(vh), precision=HIGHEST)

    heads = lax.map(head, (q.transpose(1, 0, 2), jnp.repeat(k, group, axis=1).transpose(1, 0, 2),
                           jnp.repeat(v, group, axis=1).transpose(1, 0, 2)))
    return _dot(q_, heads.transpose(1, 0, 2).reshape(seq_len, -1), p["w_o"])


def _row_hidden(cfg, params, tokens, precision, fault):
    """One row: tokens (L,) -> (the last norm's output (L, D), the last
    state-space layer's final state (H, N, P))."""
    s = _dims(cfg)
    q_ = rounder(precision)
    eps, residual = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = cfg["embedding_multiplier"] * params["embed"]["embedding"][tokens]
    final = jnp.zeros((s["h"], s["n"], s["p"]), jnp.float32)
    for i, kind in enumerate(cfg["layer_types"]):

        @jax.checkpoint
        def layer(h, p, kind=kind):
            if kind == "mamba":
                mixed, state = _mamba(q_, cfg, s, p["mixer"], _rms_norm(h, p["ssm_norm"]["weight"], eps), fault)
            else:
                mixed = _attention(q_, cfg, s, p["attention"], _rms_norm(h, p["input_norm"]["weight"], eps), fault)
                state = None
            h = h + residual * mixed
            a, b = jnp.split(_dot(q_, _rms_norm(h, p["mlp_norm"]["weight"], eps), p["mlp"]["w_in"]), 2, axis=-1)
            return h + residual * _dot(q_, jax.nn.silu(a) * b, p["mlp"]["w_out"]), state

        h, state = layer(h, params[f"layers_{i}"])
        if state is not None:
            final = state
    return _rms_norm(h, params["norm"]["weight"], eps), final


def _row_logits(cfg, params, tokens, precision, fault):
    q_ = rounder(precision)
    h, final = _row_hidden(cfg, params, tokens, precision, fault)
    return _dot(q_, h, params["embed"]["embedding"].T) / cfg["logits_scaling"], final


def _rms(states):
    return jnp.sqrt(jnp.mean(jnp.square(lax.stop_gradient(jnp.stack(states)))))


def forward(cfg: Dict, params, tokens, precision: str = "float32", fault: str = None):
    """tokens: (B, L) -> (logits (B, L, V) over the rows held, the root mean
    square of the last state-space layer's final state over all rows)."""
    with jax.default_matmul_precision("highest"):
        rows = [_row_logits(cfg, params, tokens[i], precision, fault) for i in range(tokens.shape[0])]
        return jnp.stack([logits for logits, _ in rows]), _rms([final for _, final in rows])


def loss(cfg: Dict, params, batch, precision: str = "float32", fault: str = None):
    """A batch's loss (and the final state's root mean square), one row at a
    time."""
    tokens = batch["tokens"]
    b, seq_len = tokens.shape
    with jax.default_matmul_precision("highest"):
        total, finals = 0.0, []
        for i in range(b):
            logits, final = _row_logits(cfg, params, tokens[i], precision, fault)
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, jnp.roll(tokens[i], -1)[:, None], axis=-1)[:, 0]
            total = total + jnp.sum(nll[:-1]) / (b * (seq_len - 1))
            finals.append(final)
        return total, _rms(finals)


def train_steps(cfg: Dict, train: Dict, params, batches: List[Dict], precision: str = "float32",
                fault: str = None) -> Tuple[list, dict, dict, list]:
    """`len(batches)` AdamW steps from `params` under the recipe `train` (lr,
    num_steps, wdecay, grad_clip_norm). Returns (losses, the first clipped
    gradient as host arrays, the parameters after the last step, each step's
    final-state root mean square).

    The float32 activations of one 8k row have to fit beside a
    0.77G-parameter model on one chip, so a step is the loss and its
    gradient as one program (parameters and gradient on the device: 8 bytes
    a parameter, and the program's own reservation, which the runtime keeps),
    then the update LEAF BY LEAF: AdamW's two moments are held on the host,
    and a leaf's pair visits the device for its update only. The caller's
    `params` are consumed."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def gradient(params, batch):
        (total, state_rms), grads = jax.value_and_grad(
            lambda p: loss(cfg, p, batch, precision, fault), has_aux=True)(params)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, train["grad_clip_norm"] / jnp.maximum(norm, 1e-30))
        return total, state_rms, jax.tree.map(lambda g: g * scale, grads)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update_leaf(p, g, m, v, count):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        t = (count + 1).astype(jnp.float32)
        lr = learning_rate(count, train["lr"], train["num_steps"])
        step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + train["wdecay"] * p
        return p - lr * step, m, v

    leaves, tree = jax.tree.flatten(params)
    mu, nu = [None] * len(leaves), [None] * len(leaves)  # host arrays
    losses, state_rms, first_grad = [], [], None
    for count, batch in enumerate(batches):
        total, rms, grads = gradient(jax.tree.unflatten(tree, leaves), batch)
        losses.append(total)
        state_rms.append(rms)
        grads = jax.tree.leaves(grads)
        for i, p in enumerate(leaves):
            m, v = (jnp.zeros_like(p), jnp.zeros_like(p)) if mu[i] is None else (jnp.asarray(mu[i]), jnp.asarray(nu[i]))
            leaves[i], m, v = update_leaf(p, grads[i], m, v, jnp.asarray(count, jnp.int32))
            mu[i], nu[i] = jax.device_get((m, v))
            m.delete(), v.delete(), grads[i].delete()
        del grads
        if first_grad is None:
            # the first moment after one step is (1 - b1) x the clipped gradient
            first_grad = jax.tree.unflatten(tree, [m / (1 - b1) for m in mu])
    params = jax.tree.unflatten(tree, leaves)
    return losses, first_grad, params, state_rms
