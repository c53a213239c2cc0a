"""Plain reference of the `sdar-moe` family: straight `jax.numpy`, float32 at
the highest matmul precision, a dense mask, a Python loop over experts. It
imports nothing of `raft_stereo_tpu` and is handed only a configuration's
file, a weight tree and a batch.

Per layer, for the hidden state h (no bias anywhere; keys as published):

- a = RMSNorm(h); q = a Wq as `num_attention_heads` heads of `head_dim`,
  k = a Wk, v = a Wv as `num_key_value_heads`; q, k <- RMSNorm over the head
  dimension (a learned weight per head dimension); rotary embedding over the
  whole head dimension, rotate-half, theta `rope_theta`, by position id; each
  key-value head serves Hq / Hkv query heads; scores q k^T / sqrt(head_dim),
  the mask, softmax; h1 = h + concat(heads) Wo.
- m = RMSNorm(h1); p = softmax(m Wr) over ALL `num_experts *
  program.expert_parallel` experts; the `num_experts_per_tok` largest, weights
  p_e / their sum; h2 = h1 + sum over e among them AND held here of
  w_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e. The held experts are
  `program.expert_shard * num_experts ...`; what the others would add is left
  out (the chip's share, model-configs guide section 4).
- after the last layer: RMSNorm, the head over the `vocab_size` rows held.

Block diffusion (BD3-LM's vectorised training form). Input `[x_t ; x_0]`, 2L
positions, x_t = where(masked, mask_token_id, x_0), position ids
`[0..L-1, 0..L-1]`, b(i) = (i mod L) // block_length. Query i sees key j iff
both noised and b(i) == b(j); or i noised, j clean and b(j) < b(i); or both
clean and b(j) <= b(i). Loss = 1 / (B L) * sum over masked i of (1 / t_b(i))
* -log softmax(logits_i)[x0_i], logits at the noised half, no shift.

`precision`: "float32" is the reference; "bfloat16" / "fp8" round every
matrix product's inputs (straight-through), the control one step below what
the configuration states. `fault` plants a wrong program for the limits'
readings: "causal_mask" (a plain causal mask among the noised positions in
place of the same-block rule), "capacity" (each held expert takes at most
its even share of rows, positions x k / all experts, and drops the rest).

Departures from the published model, each an `assumed` of the configuration:
the q/k norms' form, block_length, the noise schedule and its weight, no
shift of the targets.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import learning_rate, rounder

HIGHEST = lax.Precision.HIGHEST
_NEG = -1e30


def _dims(cfg: Dict) -> Dict[str, int]:
    program = cfg["program"]
    return {
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"], "hq": cfg["num_attention_heads"],
        "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"], "e": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "f": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
        "e_all": cfg["num_experts"] * program["expert_parallel"], "first": cfg["num_experts"] * program["expert_shard"],
        "block": program["block_length"], "mask_id": program["mask_token_id"],
    }


def param_shapes(cfg: Dict) -> Dict[str, dict]:
    """The weight tree's layout: every layer's leaf stacked on a leading
    axis of `num_hidden_layers`."""
    s = _dims(cfg)
    n, d, hd = s["layers"], s["d"], s["hd"]
    return {
        "embed": {"embedding": (s["v"], d)},
        "layers": {
            "input_norm": {"weight": (n, d)},
            "attention": {
                "w_q": (n, d, s["hq"] * hd), "w_k": (n, d, s["hkv"] * hd), "w_v": (n, d, s["hkv"] * hd),
                "w_o": (n, s["hq"] * hd, d), "q_norm": {"weight": (n, hd)}, "k_norm": {"weight": (n, hd)},
            },
            "post_attention_norm": {"weight": (n, d)},
            "router": {"w_router": (n, d, s["e_all"])},
            "experts": {"w_gate": (n, s["e"], d, s["f"]), "w_up": (n, s["e"], d, s["f"]), "w_down": (n, s["e"], s["f"], d)},
        },
        "norm": {"weight": (d,)},
        "lm_head": {"w_head": (d, s["v"])},
    }


def block_mask(seq_len: int, block: int, fault: str = None):
    """(2L, 2L) bool: may query i see key j."""
    pos = jnp.arange(2 * seq_len)
    noised = pos < seq_len
    b = (pos % seq_len) // block
    qn, kn, qb, kb = noised[:, None], noised[None, :], b[:, None], b[None, :]
    among_noised = (qb == kb) if fault != "causal_mask" else (pos[None, :] <= pos[:, None])
    return (qn & kn & among_noised) | (qn & ~kn & (kb < qb)) | (~qn & ~kn & (kb <= qb))


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, seq_len, theta):
    """x: (S, H, hd), position ids [0..L-1, 0..L-1], rotate-half."""
    hd = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    pos = jnp.tile(jnp.arange(seq_len, dtype=jnp.float32), 2)
    angles = pos[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _attention(q_, cfg, s, p, a, mask):
    """a: (S, D) of one row."""
    seq2 = a.shape[0]
    q = jnp.dot(q_(a), q_(p["w_q"]), precision=HIGHEST).reshape(seq2, s["hq"], s["hd"])
    k = jnp.dot(q_(a), q_(p["w_k"]), precision=HIGHEST).reshape(seq2, s["hkv"], s["hd"])
    v = jnp.dot(q_(a), q_(p["w_v"]), precision=HIGHEST).reshape(seq2, s["hkv"], s["hd"])
    q = _rope(_rms_norm(q, p["q_norm"]["weight"], cfg["rms_norm_eps"]), seq2 // 2, cfg["rope_theta"])
    k = _rope(_rms_norm(k, p["k_norm"]["weight"], cfg["rms_norm_eps"]), seq2 // 2, cfg["rope_theta"])
    group = s["hq"] // s["hkv"]

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        scores = jnp.dot(q_(qh), q_(kh).T, precision=HIGHEST) / math.sqrt(s["hd"])
        probs = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
        return jnp.dot(q_(probs), q_(vh), precision=HIGHEST)

    heads = lax.map(head, (q.transpose(1, 0, 2), jnp.repeat(k, group, axis=1).transpose(1, 0, 2),
                           jnp.repeat(v, group, axis=1).transpose(1, 0, 2)))
    return jnp.dot(q_(heads.transpose(1, 0, 2).reshape(seq2, -1)), q_(p["w_o"]), precision=HIGHEST)


def _experts(q_, cfg, s, p, router, m, fault):
    """m: (N, D) -> (the held experts' part of the output, held rows)."""
    logits = jnp.dot(q_(m), q_(router["w_router"]), precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = lax.top_k(probs, s["k"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(m)
    held_rows = jnp.zeros((), jnp.int32)
    capacity = -(-m.shape[0] * s["k"] // s["e_all"])
    for e in range(s["e"]):
        mine = chosen == s["first"] + e  # (N, k): at most one true a row
        took = jnp.any(mine, axis=-1)
        if fault == "capacity":
            took = took & (jnp.cumsum(took) <= capacity)
        weight = jnp.where(took, jnp.sum(jnp.where(mine, top, 0.0), axis=-1), 0.0)
        gate = jnp.dot(q_(m), q_(p["w_gate"][e]), precision=HIGHEST)
        up = jnp.dot(q_(m), q_(p["w_up"][e]), precision=HIGHEST)
        down = jnp.dot(q_(jax.nn.silu(gate) * up), q_(p["w_down"][e]), precision=HIGHEST)
        out = out + weight[:, None] * down
        held_rows = held_rows + jnp.sum(took)
    return out, held_rows


def _row_hidden(cfg, params, tokens, masked, precision, fault):
    """One row: tokens (L,), masked (L,) -> (last norm's output at the
    noised half (L, D), held rows summed over the layers)."""
    s = _dims(cfg)
    q_ = rounder(precision)
    seq_len = tokens.shape[0]
    ids = jnp.concatenate([jnp.where(masked, s["mask_id"], tokens), tokens])
    h = params["embed"]["embedding"][ids]
    mask = block_mask(seq_len, s["block"], fault)
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def layer(h, p):
        a = _rms_norm(h, p["input_norm"]["weight"], eps)
        h = h + _attention(q_, cfg, s, p["attention"], a, mask)
        m = _rms_norm(h, p["post_attention_norm"]["weight"], eps)
        y, held = _experts(q_, cfg, s, p["experts"], p["router"], m, fault)
        return h + y, held

    h, held = lax.scan(layer, h, params["layers"])
    return _rms_norm(h[:seq_len], params["norm"]["weight"], eps), jnp.sum(held)


def forward(cfg: Dict, params, tokens, masked, precision: str = "float32", fault: str = None):
    """tokens, masked: (B, L) -> (logits at the noised half (B, L, V), held
    rows over all rows and layers)."""
    q_ = rounder(precision)
    with jax.default_matmul_precision("highest"):
        logits, held = [], 0
        for i in range(tokens.shape[0]):
            h, rows = _row_hidden(cfg, params, tokens[i], masked[i], precision, fault)
            logits.append(jnp.dot(q_(h), q_(params["lm_head"]["w_head"]), precision=HIGHEST))
            held = held + rows
        return jnp.stack(logits), held


def row_loss(cfg: Dict, params, tokens, masked, noise_t, count, precision: str = "float32", fault: str = None):
    """One row's share of the batch's loss (`count` = B x L) and its held
    rows."""
    q_ = rounder(precision)
    with jax.default_matmul_precision("highest"):
        h, held = _row_hidden(cfg, params, tokens, masked, precision, fault)
        logits = jnp.dot(q_(h), q_(params["lm_head"]["w_head"]), precision=HIGHEST)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        weight = jnp.where(masked, jnp.repeat(1.0 / noise_t, cfg["program"]["block_length"]), 0.0)
        return jnp.sum(weight * nll) / count, held


def loss(cfg: Dict, params, batch, precision: str = "float32", fault: str = None):
    """A batch's loss (and held rows), one row at a time."""
    b, seq_len = batch["tokens"].shape
    total, held = 0.0, 0
    for i in range(b):
        value, rows = row_loss(cfg, params, batch["tokens"][i], batch["masked"][i], batch["noise_t"][i],
                               float(b * seq_len), precision, fault)
        total, held = total + value, held + rows
    return total, held


def train_steps(cfg: Dict, train: Dict, params, batches: List[Dict], precision: str = "float32",
                fault: str = None) -> Tuple[list, dict, dict, list]:
    """`len(batches)` AdamW steps from `params` under the recipe `train` (lr,
    num_steps, wdecay, grad_clip_norm). Returns (losses, the first clipped
    gradient as host arrays, the parameters after the last step, held rows
    of each step).

    A step is one program: loss and gradient summed over the batch one row
    at a time (a scan whose backward pass adds each row's gradient into one
    buffer), then the update; `params` and the moments are donated, so that
    four float32 trees of a 0.5G-parameter model and one row's 2L = 8192
    positions fit one chip. The caller's `params` are consumed."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    def batch_loss(params, batch):
        b, seq_len = batch["tokens"].shape

        @jax.checkpoint
        def row(carry, xs):
            value, held = row_loss(cfg, params, *xs, float(b * seq_len), precision, fault)
            return (carry[0] + value, carry[1] + held), None

        start = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
        (total, held), _ = lax.scan(row, start, (batch["tokens"], batch["masked"], batch["noise_t"]))
        return total, held

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, batch):
        (total, held), grads = jax.value_and_grad(batch_loss, has_aux=True)(params, batch)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, train["grad_clip_norm"] / jnp.maximum(norm, 1e-30))
        grads = jax.tree.map(lambda g: g * scale, grads)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        t = count + 1
        lr = learning_rate(count, train["lr"], train["num_steps"])
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + train["wdecay"] * p), params, mu, nu)
        return params, mu, nu, t, total, held

    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses, held_rows, first_grad = [], [], None
    for batch in batches:
        params, mu, nu, count, total, held = step(params, mu, nu, count, batch)
        losses.append(total)
        held_rows.append(held)
        if first_grad is None:
            # the first moment after one step is (1 - b1) x the clipped gradient;
            # it is fetched before the next step consumes it (a fifth tree of
            # 0.5G parameters would not fit beside the step)
            first_grad = jax.tree.map(lambda m: m / (1 - b1), jax.device_get(mu))
    return losses, first_grad, params, held_rows
