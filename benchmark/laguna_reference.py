"""Plain reference of the `laguna-moe` family (poolside Laguna, `model_type:
laguna`): straight `jax.numpy`, float32 at the highest matmul precision,
dense masks, a Python loop over experts. It imports nothing of
`raft_stereo_tpu` and is handed only a configuration's file, a weight tree
and a batch.

The layer equations (keys as published; no bias anywhere). `h = E[ids]`. For
layer l, `H_l = num_attention_heads_per_layer[l]` query heads and
`num_key_value_heads` key-value heads of `head_dim`, `H_l / num_key_value_heads`
query heads a key-value head:

- `a = RMSNorm(h)`; `q = a Wq`, `k = a Wk`, `v = a Wv`. ASSUMED (b): q and k
  are RMS-normed over the head with a learned weight, before the rotary
  embedding (the `qwen3_moe` lineage the expert keys come from has these
  norms and no key for them).
- The rotary embedding of the layer's kind, `rope_parameters[layer_types[l]]`,
  over the first `r = partial_rotary_factor x head_dim` dimensions of a head:
  rotate-half WITHIN those r, the other `head_dim - r` passed through (the HF
  convention for a partial rotary). `full_attention`: `rope_type: yarn`,
  inverse frequencies exactly as transformers'
  `modeling_rope_utils._compute_yarn_parameters` computes them (dim r, base
  `rope_theta`, `factor` over `original_max_position_embeddings`, the linear
  ramp between the correction dimensions of `beta_fast` and `beta_slow`,
  truncated), cos and sin multiplied by `attention_factor`.
  `sliding_attention`: `rope_type: default`, base `rope_theta`, no scaling.
- Scores `q k^T / sqrt(head_dim)`, softmax in float32 over the keys `j <= i`
  (`full_attention`) or `i - sliding_window < j <= i` (`sliding_attention`:
  `sliding_window` keys with the query's own, the HF window's edge).
- ASSUMED (a): `gating: true` is a per-head gate: `g = sigmoid(a Wg)`, `Wg`
  of `hidden_size x H_l`; head n of the attention's output is multiplied by
  `g_n` before `Wo` (the sibling Laguna-S-2.1 spells it `gating: per-head`,
  and only a per-head gate keeps the parameter count at the published 33.4B).
  `h1 = h + concat(g_n o_n) Wo`.
- `m = RMSNorm(h1)`. `mlp_layer_types[l] == "dense"`: `h2 = h1 + Wout
  (silu(x) * y)`, `[x, y] = Win m`, of `intermediate_size`. `"sparse"`:
  ASSUMED (c): `s = sigmoid(m Wr)` over ALL `num_experts x
  program.expert_parallel` experts, the `num_experts_per_tok` largest,
  `w = s_chosen / sum(s_chosen)` (every public model that pairs a routed
  scaling factor with renormalised choices scores by sigmoid; no key for
  groups or a selection bias, so neither); `h2 = h1 + shared(m) +
  moe_routed_scaling_factor x sum over the chosen experts HELD HERE of w_e
  expert_e(m)`; ASSUMED (d): the shared expert is added ungated; every expert
  a gated MLP of `moe_intermediate_size`, the shared one of
  `shared_expert_intermediate_size`. The held experts are
  `program.expert_shard x num_experts ...`; what the others would add is left
  out (the chip's share, model-configs guide section 4). No auxiliary loss.
- logits `= RMSNorm(h) W_head` over the `vocab_size` rows held (untied);
  loss `= mean over t < L - 1 of -log softmax(logits_t)[id_{t+1}]`.

A row is taken whole; a head's scores are built `_QUERY_ROWS` query rows at a
time against every key under a dense mask of that block, so that 16,384
positions fit; `jax.checkpoint` around a block, a head and a layer changes
what is kept for the backward pass and no arithmetic.

`precision`: "float32" is the reference; "bfloat16" / "fp8" round every
matrix product's inputs (straight-through), the control one step below what
the configuration states. `fault` plants a wrong program for the limits'
readings: "window_off" (sliding layers see the whole past), "gate_off"
(g = 1), "rotary_whole_head" (full layers rotate all of the head).

Departures from the published model, each an `assumed` of the configuration:
(a)-(d) above; the loss is taken at this stage's output over the vocabulary
rows held here.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import learning_rate, rounder

HIGHEST = lax.Precision.HIGHEST
_NEG = -1e30
_QUERY_ROWS = 2048
FAULTS = ("window_off", "gate_off", "rotary_whole_head")


def _dims(cfg: Dict) -> Dict[str, int]:
    program = cfg["program"]
    layers = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        if len(cfg[key]) != layers:
            raise ValueError(f"laguna_reference: {key} does not name num_hidden_layers layers")
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "f": cfg["intermediate_size"],
        "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"], "e": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "fe": cfg["moe_intermediate_size"],
        "fs": cfg["shared_expert_intermediate_size"],
        "e_all": cfg["num_experts"] * program["expert_parallel"], "first": cfg["num_experts"] * program["expert_shard"],
    }


def _attention_name(kind: str) -> str:
    return "attention_window" if kind == "sliding_attention" else "attention_full"


def param_shapes(cfg: Dict) -> Dict[str, dict]:
    """The weight tree's layout: `layers_<i>` a layer, its attention's leaves
    under its kind's name, its second half's by its kind."""
    s = _dims(cfg)
    d, hd = s["d"], s["hd"]
    tree = {"embed": {"embedding": (s["v"], d)}, "norm": {"weight": (d,)}, "lm_head": {"w_head": (d, s["v"])}}
    for i, (kind, mlp, hq) in enumerate(zip(cfg["layer_types"], cfg["mlp_layer_types"], cfg["num_attention_heads_per_layer"])):
        layer = {_attention_name(kind): {
            "attention_norm": {"weight": (d,)},
            "w_q": (d, hq * hd), "w_k": (d, s["hkv"] * hd), "w_v": (d, s["hkv"] * hd), "w_gate": (d, hq),
            "w_o": (hq * hd, d), "q_norm": {"weight": (hd,)}, "k_norm": {"weight": (hd,)},
        }}
        if mlp == "dense":
            layer.update({"mlp_norm": {"weight": (d,)}, "mlp": {"w_in": (d, 2 * s["f"]), "w_out": (s["f"], d)}})
        else:
            layer.update({
                "post_attention_norm": {"weight": (d,)},
                "router": {"w_router": (d, s["e_all"])},
                "experts": {"w_gate": (s["e"], d, s["fe"]), "w_up": (s["e"], d, s["fe"]), "w_down": (s["e"], s["fe"], d)},
                "shared_expert": {"w_in": (d, 2 * s["fs"]), "w_out": (s["fs"], d)},
            })
        tree[f"layers_{i}"] = layer
    return tree


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _dot(q_, a, b):
    return jnp.dot(q_(a), q_(b), precision=HIGHEST)


def yarn_inv_freq(dim: int, rope: Dict) -> np.ndarray:
    """transformers' `_compute_yarn_parameters`, in numpy."""
    base, factor, original = rope["rope_theta"], rope["factor"], rope["original_max_position_embeddings"]
    beta_fast, beta_slow = rope.get("beta_fast") or 32, rope.get("beta_slow") or 1

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low, high = max(math.floor(correction_dim(beta_fast)), 0), min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # no singularity
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    pos_freqs = base ** (np.arange(0, dim, 2).astype(np.float32) / dim)
    interpolation, extrapolation = 1.0 / (factor * pos_freqs), 1.0 / pos_freqs
    return (interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor).astype(np.float32)


def _rope(x, rope: Dict, whole_head: bool):
    """x: (L, H, hd), position ids 0..L-1: the first r dimensions turned
    (rotate-half within them), the rest passed through."""
    seq_len, _, hd = x.shape
    r = hd if whole_head else int(hd * rope.get("partial_rotary_factor", 1))
    if rope.get("rope_type", "default") == "yarn":
        inv_freq, scale = yarn_inv_freq(r, rope), rope["attention_factor"]
    else:
        inv_freq, scale = 1.0 / (rope["rope_theta"] ** (np.arange(0, r, 2).astype(np.float32) / r)), 1.0
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    turned, passed = x[..., :r], x[..., r:]
    rotated = jnp.concatenate([-turned[..., r // 2:], turned[..., : r // 2]], axis=-1)
    return jnp.concatenate([turned * (scale * jnp.cos(angles)) + rotated * (scale * jnp.sin(angles)), passed], axis=-1)


def _query_rows(seq_len: int) -> int:
    return next(r for r in (_QUERY_ROWS, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1) if seq_len % r == 0)


def _heads(q_, q, k, v, window, scale):
    """q: (L, H, hd); k, v: (L, Hkv, hd) -> (L, H, hd). `window` None: the
    whole past."""
    seq_len, hq, hd = q.shape
    group = hq // k.shape[1]
    rows = _query_rows(seq_len)
    k_by_head, v_by_head = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    q_blocks = q.transpose(1, 0, 2).reshape(hq, seq_len // rows, rows, hd)
    starts = jnp.arange(0, seq_len, rows)
    key = jnp.arange(seq_len)[None, :]

    @jax.checkpoint
    def block(q_block, head, start):
        query = (start + jnp.arange(rows))[:, None]
        mask = key <= query
        if window is not None:
            mask = mask & (key > query - window)
        scores = jnp.dot(q_(q_block), q_(k_by_head[head // group]).T, precision=HIGHEST) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, _NEG), axis=-1)
        return jnp.dot(q_(probs), q_(v_by_head[head // group]), precision=HIGHEST)

    @jax.checkpoint
    def head(args):
        blocks, index = args
        return lax.map(lambda xs: block(xs[0], index, xs[1]), (blocks, starts))

    out = lax.map(head, (q_blocks, jnp.arange(hq)))
    return out.reshape(hq, seq_len, hd).transpose(1, 0, 2)


def _attention(q_, cfg, s, p, h, kind, hq, fault):
    """h: (L, D) of one row -> (h1, the gate's mean)."""
    seq_len = h.shape[0]
    eps = cfg["rms_norm_eps"]
    a = _rms_norm(h, p["attention_norm"]["weight"], eps)
    q = _dot(q_, a, p["w_q"]).reshape(seq_len, hq, s["hd"])
    k = _dot(q_, a, p["w_k"]).reshape(seq_len, s["hkv"], s["hd"])
    v = _dot(q_, a, p["w_v"]).reshape(seq_len, s["hkv"], s["hd"])
    rope = cfg["rope_parameters"][kind]
    whole_head = fault == "rotary_whole_head" and kind == "full_attention"
    q = _rope(_rms_norm(q, p["q_norm"]["weight"], eps), rope, whole_head)
    k = _rope(_rms_norm(k, p["k_norm"]["weight"], eps), rope, whole_head)
    window = cfg["sliding_window"] if kind == "sliding_attention" and fault != "window_off" else None
    o = _heads(q_, q, k, v, window, 1.0 / math.sqrt(s["hd"]))
    gate = jax.nn.sigmoid(_dot(q_, a, p["w_gate"]))
    if fault == "gate_off":
        gate = jnp.ones_like(gate)
    out = _dot(q_, (o * gate[..., None]).reshape(seq_len, hq * s["hd"]), p["w_o"])
    return h + out, jnp.mean(lax.stop_gradient(gate))


def _gated_mlp(q_, p, m):
    x, y = jnp.split(_dot(q_, m, p["w_in"]), 2, axis=-1)
    return _dot(q_, jax.nn.silu(x) * y, p["w_out"])


def routing(q_, cfg, s, router, m):
    """m: (N, D) -> (chosen expert ids over ALL experts (N, k), their weights
    without the scaling factor (N, k))."""
    scores = jax.nn.sigmoid(_dot(q_, m, router["w_router"]))
    top, chosen = lax.top_k(scores, s["k"])
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def routed_experts(q_, cfg, s, p, m, chosen, weights):
    """The held experts' part of a sparse layer's routed sum, the scaling
    factor included, and the rows they took. Every expert a plain product
    over all positions times its weight-or-zero."""
    out = jnp.zeros_like(m)
    held_rows = jnp.zeros((), jnp.int32)
    for e in range(s["e"]):
        mine = chosen == s["first"] + e  # (N, k): at most one true a row
        weight = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)
        gate, up = _dot(q_, m, p["w_gate"][e]), _dot(q_, m, p["w_up"][e])
        out = out + weight[:, None] * _dot(q_, jax.nn.silu(gate) * up, p["w_down"][e])
        held_rows = held_rows + jnp.sum(jnp.any(mine, axis=-1))
    return cfg["moe_routed_scaling_factor"] * out, held_rows


def sparse_half(q_, cfg, s, p, h):
    """h: (L, D) -> (h2, held rows)."""
    m = _rms_norm(h, p["post_attention_norm"]["weight"], cfg["rms_norm_eps"])
    chosen, weights = routing(q_, cfg, s, p["router"], m)
    routed, held = routed_experts(q_, cfg, s, p["experts"], m, chosen, weights)
    return h + _gated_mlp(q_, p["shared_expert"], m) + routed, held


def _row_hidden(cfg, params, tokens, precision, fault):
    """One row: tokens (L,) -> (the last norm's output (L, D), held rows
    summed over the sparse layers, the last layer's mean gate)."""
    s = _dims(cfg)
    q_ = rounder(precision)
    h = params["embed"]["embedding"][tokens]
    held = jnp.zeros((), jnp.int32)
    for i, (kind, mlp, hq) in enumerate(zip(cfg["layer_types"], cfg["mlp_layer_types"], cfg["num_attention_heads_per_layer"])):

        @jax.checkpoint
        def layer(h, p, kind=kind, mlp=mlp, hq=hq):
            h, gate_mean = _attention(q_, cfg, s, p[_attention_name(kind)], h, kind, hq, fault)
            if mlp == "dense":
                m = _rms_norm(h, p["mlp_norm"]["weight"], cfg["rms_norm_eps"])
                return h + _gated_mlp(q_, p["mlp"], m), jnp.zeros((), jnp.int32), gate_mean
            h, rows = sparse_half(q_, cfg, s, p, h)
            return h, rows, gate_mean

        h, rows, gate_mean = layer(h, params[f"layers_{i}"])
        held = held + rows
    return _rms_norm(h, params["norm"]["weight"], cfg["rms_norm_eps"]), held, gate_mean


def _row_logits(cfg, params, tokens, precision, fault):
    h, held, gate_mean = _row_hidden(cfg, params, tokens, precision, fault)
    return _dot(rounder(precision), h, params["lm_head"]["w_head"]), held, gate_mean


def forward(cfg: Dict, params, tokens, precision: str = "float32", fault: str = None):
    """tokens: (B, L) -> (logits (B, L, V) over the rows held, held rows over
    all rows and layers)."""
    with jax.default_matmul_precision("highest"):
        rows = [_row_logits(cfg, params, tokens[i], precision, fault) for i in range(tokens.shape[0])]
        return jnp.stack([logits for logits, _, _ in rows]), sum(held for _, held, _ in rows)


def loss(cfg: Dict, params, batch, precision: str = "float32", fault: str = None):
    """A batch's loss, one row at a time -> (loss, (held rows, the last
    layer's mean gate over the rows))."""
    tokens = batch["tokens"]
    b, seq_len = tokens.shape
    with jax.default_matmul_precision("highest"):
        total, held, gates = 0.0, 0, []
        for i in range(b):
            logits, rows, gate_mean = _row_logits(cfg, params, tokens[i], precision, fault)
            picked = jnp.take_along_axis(logits, jnp.roll(tokens[i], -1)[:, None], axis=-1)[:, 0]
            nll = jax.nn.logsumexp(logits, axis=-1) - picked
            total = total + jnp.sum(nll[:-1]) / (b * (seq_len - 1))
            held = held + rows
            gates.append(gate_mean)
        return total, (held, jnp.mean(jnp.stack(gates)))


def train_steps(cfg: Dict, train: Dict, params, batches: List[Dict], precision: str = "float32",
                fault: str = None) -> Tuple[list, dict, dict, list]:
    """`len(batches)` AdamW steps from `params` under the recipe `train` (lr,
    num_steps, wdecay, grad_clip_norm). Returns (losses, the first clipped
    gradient as host arrays, the parameters after the last step, held rows
    of each step).

    As `granite_reference.train_steps`: the float32 activations of one 16k
    row have to fit beside a 0.69G-parameter model on one chip, so a step is
    the loss and its gradient as one program (8 bytes a parameter on the
    device), then the update LEAF BY LEAF: AdamW's two moments are held on
    the host, and a leaf's pair visits the device for its update only. The
    caller's `params` are consumed."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def gradient(params, batch):
        (total, (held, _)), grads = jax.value_and_grad(
            lambda p: loss(cfg, p, batch, precision, fault), has_aux=True)(params)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, train["grad_clip_norm"] / jnp.maximum(norm, 1e-30))
        return total, held, jax.tree.map(lambda g: g * scale, grads)

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
    losses, held_rows, first_grad = [], [], None
    for count, batch in enumerate(batches):
        total, held, grads = gradient(jax.tree.unflatten(tree, leaves), batch)
        losses.append(total)
        held_rows.append(held)
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
    return losses, first_grad, jax.tree.unflatten(tree, leaves), held_rows
