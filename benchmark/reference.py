"""The plain reference: RAFT-Stereo in straightforward `jax.numpy`.

Follows the published architecture (Lipson et al., 3DV 2021; princeton-vl/
RAFT-Stereo `core/`): two residual encoders, an all-pairs 1D correlation
pyramid sampled around the running estimate, coupled multi-scale conv GRUs, a
flow head, convex upsampling; for training the exponentially weighted L1
sequence loss and AdamW under a linear one-cycle schedule with global-norm
clipping. No kernels, no batching tricks, no cache, float32 at `highest`
matmul precision. It imports nothing of `raft_stereo_tpu` and is handed only
the weight tree that `benchmark/weights.py` drew from the seed (whose layout
`param_shapes` below declares) and the inputs.

Departures from the paper's code, each matching what the system under test
documents: the flow is one (x) channel, since the y channel is zeroed every
iteration there; BatchNorm always uses its stored statistics (the recipe
freezes it); the upsampling mask is computed once after the loop at inference
(it feeds no recurrence).

`precision` selects the arithmetic: "float32" is the reference; "bfloat16"
and "fp8" are the same mathematics with every convolution's and the
correlation's inputs rounded to that type first — the controls, one step
below what a configuration states.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
_CONV_DIMS = ("NHWC", "HWIO", "NHWC")
_BN_EPS = 1e-5


# --------------------------------------------------------------------------
# precision: identity for the reference, a round trip for the controls
# --------------------------------------------------------------------------


def rounder(precision: str):
    """x -> x rounded to `precision`, as float32. The rounding is straight
    through for gradients: a backward pass sees the rounded values and is
    itself float32 (gradients of order 1e-6 would all vanish in fp8)."""
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        # e4m3 has no infinity: clip to its largest finite value first.
        rounded = lambda x: jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32
        )
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return lambda x: x + lax.stop_gradient(rounded(x) - x)


# --------------------------------------------------------------------------
# the weight tree's layout
# --------------------------------------------------------------------------


def _stride(downsample: int, threshold: int) -> int:
    return 1 + int(downsample > threshold)


def _conv_shape(kh, kw, cin, cout):
    return {"Conv_0": {"kernel": (kh, kw, cin, cout), "bias": (cout,)}}


def _res_block_shapes(cin, cout, stride, norm) -> Tuple[dict, dict]:
    params = {"conv1": _conv_shape(3, 3, cin, cout), "conv2": _conv_shape(3, 3, cout, cout)}
    stats = {}
    skip = not (stride == 1 and cin == cout)
    if skip:
        params["downsample"] = _conv_shape(1, 1, cin, cout)
    if norm == "batch":
        for i in range(3 if skip else 2):
            params[f"FrozenBatchNorm_{i}"] = {"scale": (cout,), "bias": (cout,)}
            stats[f"FrozenBatchNorm_{i}"] = {"mean": (cout,), "var": (cout,)}
    return params, stats


_TRUNK = (("layer1_0", 64, 64, None), ("layer1_1", 64, 64, None), ("layer2_0", 64, 96, 1),
          ("layer2_1", 96, 96, None), ("layer3_0", 96, 128, 0), ("layer3_1", 128, 128, None))


def _trunk_shapes(cfg, norm) -> Tuple[dict, dict]:
    params = {"conv1": _conv_shape(7, 7, 3, 64)}
    stats = {}
    if norm == "batch":
        params["FrozenBatchNorm_0"] = {"scale": (64,), "bias": (64,)}
        stats["FrozenBatchNorm_0"] = {"mean": (64,), "var": (64,)}
    for name, cin, cout, threshold in _TRUNK:
        stride = 1 if threshold is None else _stride(cfg["n_downsample"], threshold)
        p, s = _res_block_shapes(cin, cout, stride, norm)
        params[name] = p
        if s:
            stats[name] = s
    return params, stats


def param_shapes(cfg: Dict) -> Dict[str, dict]:
    """{"params": ..., "batch_stats": ...}: every leaf's shape, under the
    names the system's checkpoints use. `cfg` is a configuration file's
    `model` group."""
    hidden = tuple(cfg["hidden_dims"])
    n = cfg["n_gru_layers"]
    factor = 2 ** cfg["n_downsample"]
    corr_channels = cfg["corr_levels"] * (2 * cfg["corr_radius"] + 1)
    params, stats = {}, {}

    cnet_p, cnet_s = {}, {}
    cnet_p["trunk"], cnet_s["trunk"] = _trunk_shapes(cfg, "batch")

    def block(name, stride=1):
        cnet_p[name], cnet_s[name] = _res_block_shapes(128, 128, stride, "batch")

    for j in range(2):
        block(f"res08_{j}")
        cnet_p[f"out08_{j}"] = _conv_shape(3, 3, 128, hidden[2])
    if n >= 2:
        block("layer4_0", 2)
        block("layer4_1")
        for j in range(2):
            block(f"res16_{j}")
            cnet_p[f"out16_{j}"] = _conv_shape(3, 3, 128, hidden[1])
    if n >= 3:
        block("layer5_0", 2)
        block("layer5_1")
        for j in range(2):
            cnet_p[f"out32_{j}"] = _conv_shape(3, 3, 128, hidden[0])
    params["cnet"], stats["cnet"] = cnet_p, cnet_s

    if cfg["shared_backbone"]:
        params["conv2_res"], _ = _res_block_shapes(128, 128, 1, "instance")
        params["conv2_out"] = _conv_shape(3, 3, 128, 256)
    else:
        trunk, _ = _trunk_shapes(cfg, "instance")
        params["fnet"] = {"trunk": trunk, "conv2": _conv_shape(1, 1, 128, 256)}
    for i in range(n):
        params[f"context_zqr_conv{i}"] = _conv_shape(3, 3, 128, 3 * hidden[2 - i])

    def gru(width, cin):
        return {g: _conv_shape(3, 3, cin, width) for g in ("convz", "convr", "convq")}

    block_p = {
        "encoder": {
            "convc1": _conv_shape(1, 1, corr_channels, 64),
            "convc2": _conv_shape(3, 3, 64, 64),
            "convf1": _conv_shape(7, 7, 1, 64),
            "convf2": _conv_shape(3, 3, 64, 64),
            "conv": _conv_shape(3, 3, 128, 126),
        },
        "flow_head": {"conv1": _conv_shape(3, 3, hidden[2], 256), "conv2": _conv_shape(3, 3, 256, 1)},
        "gru08": gru(hidden[2], hidden[2] + 128 + (hidden[1] if n > 1 else 0)),
    }
    if n >= 2:
        block_p["gru16"] = gru(hidden[1], hidden[1] + hidden[2] + (hidden[0] if n > 2 else 0))
    if n >= 3:
        block_p["gru32"] = gru(hidden[0], hidden[0] + hidden[1])
    params["iteration"] = {"update_block": block_p}
    params["mask_head"] = {
        "mask_conv1": _conv_shape(3, 3, hidden[2], 256),
        "mask_conv2": _conv_shape(1, 1, 256, 9 * factor * factor),
    }
    return {"params": params, "batch_stats": stats}


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


def _conv(q, p, x, stride=1, pad=None):
    kernel = p["Conv_0"]["kernel"]
    if pad is None:
        pad = kernel.shape[0] // 2
    y = lax.conv_general_dilated(
        q(x), q(kernel), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=_CONV_DIMS, precision=HIGHEST,
    )
    return y + p["Conv_0"]["bias"]


def _instance_norm(x):
    mean = x.mean(axis=(1, 2), keepdims=True)
    var = jnp.square(x - mean).mean(axis=(1, 2), keepdims=True)
    return (x - mean) * lax.rsqrt(var + _BN_EPS)


def _norm(norm, p, s, index, x):
    if norm == "instance":
        return _instance_norm(x)
    name = f"FrozenBatchNorm_{index}"
    inv = lax.rsqrt(s[name]["var"] + _BN_EPS) * p[name]["scale"]
    return (x - s[name]["mean"]) * inv + p[name]["bias"]


def _res_block(q, norm, p, s, x, stride):
    y = jax.nn.relu(_norm(norm, p, s, 0, _conv(q, p["conv1"], x, stride)))
    y = jax.nn.relu(_norm(norm, p, s, 1, _conv(q, p["conv2"], y)))
    if "downsample" in p:
        x = _norm(norm, p, s, 2, _conv(q, p["downsample"], x, stride, pad=0))
    return jax.nn.relu(x + y)


def _trunk(q, cfg, norm, p, s, x):
    ds = cfg["n_downsample"]
    x = _conv(q, p["conv1"], x, _stride(ds, 2), pad=3)
    x = jax.nn.relu(_norm(norm, p, s, 0, x))
    for name, _, _, threshold in _TRUNK:
        stride = 1 if threshold is None else _stride(ds, threshold)
        x = _res_block(q, norm, p[name], s.get(name, {}), x, stride)
    return x


def _context_encoder(q, cfg, p, s, x, shared):
    x = _trunk(q, cfg, "batch", p["trunk"], s["trunk"], x)
    trunk_out = x
    if shared:
        x = x[: x.shape[0] // 2]

    def block(name, y, stride=1):
        return _res_block(q, "batch", p[name], s[name], y, stride)

    n = cfg["n_gru_layers"]
    scales = [tuple(_conv(q, p[f"out08_{j}"], block(f"res08_{j}", x)) for j in range(2))]
    if n >= 2:
        y = block("layer4_1", block("layer4_0", x, 2))
        scales.append(tuple(_conv(q, p[f"out16_{j}"], block(f"res16_{j}", y)) for j in range(2)))
    if n >= 3:
        z = block("layer5_1", block("layer5_0", y, 2))
        scales.append(tuple(_conv(q, p[f"out32_{j}"], z) for j in range(2)))
    return scales, trunk_out


def _pool2x(x):
    """3x3 average pool, stride 2, zero padding 1, divisor always 9."""
    b, h, w, c = x.shape
    oh, ow = (h + 1) // 2, (w + 1) // 2
    padded = jnp.pad(x, ((0, 0), (1, 2 * oh - h + 1), (1, 2 * ow - w + 1), (0, 0)))
    total = 0.0
    for dy in range(3):
        for dx in range(3):
            total = total + padded[:, dy : dy + 2 * oh : 2, dx : dx + 2 * ow : 2, :]
    return total / 9.0


def _interp_matrix(n_in, n_out):
    if n_in == 1 or n_out == 1:
        return jnp.zeros((n_out, n_in), jnp.float32).at[:, 0].set(1.0)
    pos = jnp.linspace(0.0, n_in - 1.0, n_out)
    i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n_in - 2)
    frac = pos - i0
    rows = jnp.arange(n_out)
    m = jnp.zeros((n_out, n_in), jnp.float32)
    return m.at[rows, i0].add(1.0 - frac).at[rows, i0 + 1].add(frac)


def _resize(x, out_h, out_w):
    """Bilinear, corners aligned."""
    x = jnp.einsum("oh,bhwc->bowc", _interp_matrix(x.shape[1], out_h), x, precision=HIGHEST)
    return jnp.einsum("ow,bhwc->bhoc", _interp_matrix(x.shape[2], out_w), x, precision=HIGHEST)


def _gru(q, p, h, context, *inputs):
    cz, cr, cq = context
    hx = jnp.concatenate((h, *inputs), axis=-1)
    z = jax.nn.sigmoid(_conv(q, p["convz"], hx) + cz)
    r = jax.nn.sigmoid(_conv(q, p["convr"], hx) + cr)
    rhx = jnp.concatenate((r * h, *inputs), axis=-1)
    cand = jnp.tanh(_conv(q, p["convq"], rhx) + cq)
    return (1.0 - z) * h + z * cand


def _motion_encoder(q, p, flow, corr):
    cor = jax.nn.relu(_conv(q, p["convc1"], corr, pad=0))
    cor = jax.nn.relu(_conv(q, p["convc2"], cor))
    flo = jax.nn.relu(_conv(q, p["convf1"], flow, pad=3))
    flo = jax.nn.relu(_conv(q, p["convf2"], flo))
    out = jax.nn.relu(_conv(q, p["conv"], jnp.concatenate((cor, flo), axis=-1)))
    return jnp.concatenate((out, flow, jnp.zeros_like(flow)), axis=-1)


def _update_block(q, cfg, p, net, context, corr=None, flow=None,
                  iter08=True, iter16=True, iter32=True, update=True):
    net = list(net)
    n = cfg["n_gru_layers"]
    if iter32 and n == 3:
        net[2] = _gru(q, p["gru32"], net[2], context[2], _pool2x(net[1]))
    if iter16 and n >= 2:
        extra = (_resize(net[2], *net[1].shape[1:3]),) if n > 2 else ()
        net[1] = _gru(q, p["gru16"], net[1], context[1], _pool2x(net[0]), *extra)
    if iter08:
        motion = _motion_encoder(q, p["encoder"], flow, corr)
        extra = (_resize(net[1], *net[0].shape[1:3]),) if n > 1 else ()
        net[0] = _gru(q, p["gru08"], net[0], context[0], motion, *extra)
    if not update:
        return tuple(net), None
    head = p["flow_head"]
    delta = _conv(q, head["conv2"], jax.nn.relu(_conv(q, head["conv1"], net[0])))
    return tuple(net), delta


# --------------------------------------------------------------------------
# correlation
# --------------------------------------------------------------------------


def _corr_pyramid(q, fmap1, fmap2, levels):
    dim = fmap1.shape[-1]
    volume = jnp.einsum("bhwd,bhvd->bhwv", q(fmap1), q(fmap2), precision=HIGHEST)
    pyramid = [q(volume / math.sqrt(dim))]
    for _ in range(levels - 1):
        last = pyramid[-1]
        half = last.shape[-1] // 2
        pairs = last[..., : 2 * half].reshape(*last.shape[:-1], half, 2)
        pyramid.append(pairs.mean(axis=-1))
    return pyramid


def _sample_row(values, x):
    """Linear interpolation of `values` (..., W) at `x` (..., K); taps that
    fall outside [0, W-1] contribute nothing."""
    w = values.shape[-1]
    x0 = jnp.floor(x)
    frac = x - x0
    x0 = x0.astype(jnp.int32)

    def tap(index, weight):
        inside = (index >= 0) & (index <= w - 1)
        got = jnp.take_along_axis(values, jnp.clip(index, 0, w - 1), axis=-1)
        return got * weight * inside

    return tap(x0, 1.0 - frac) + tap(x0 + 1, frac)


def _sample_row_dense(values, x):
    """`_sample_row` as a sum over the whole row with the hat function
    max(0, 1 - |x - v|) as weight: the same two products and one add per tap,
    no gather, so its backward pass is no scatter (which a TPU runs one
    update at a time). Costs a (..., K, W) weight tensor: for training crops,
    not for a full-resolution pair."""
    positions = jnp.arange(values.shape[-1], dtype=jnp.float32)
    weights = jnp.maximum(0.0, 1.0 - jnp.abs(x[..., None] - positions))
    return jnp.einsum("...kv,...v->...k", weights, values, precision=HIGHEST)


def _corr_lookup(pyramid, coords, radius, dense=False):
    offsets = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    sample = _sample_row_dense if dense else _sample_row
    taps = [
        sample(level, coords[..., None] / (2**i) + offsets)
        for i, level in enumerate(pyramid)
    ]
    return jnp.concatenate(taps, axis=-1)


# --------------------------------------------------------------------------
# upsampling
# --------------------------------------------------------------------------


def _convex_upsample(flow, mask, factor):
    """flow (B,h,w), mask logits (B,h,w,9*f*f) -> (B,h*f,w*f)."""
    b, h, w = flow.shape
    weights = jax.nn.softmax(mask.reshape(b, h, w, 9, factor, factor), axis=3)
    padded = jnp.pad(flow * factor, ((0, 0), (1, 1), (1, 1)))
    patches = jnp.stack(
        [padded[:, ky : ky + h, kx : kx + w] for ky in range(3) for kx in range(3)], axis=3
    )
    up = jnp.einsum("bhwkij,bhwk->bhiwj", weights, patches, precision=HIGHEST)
    return up.reshape(b, h * factor, w * factor)


def _mask(q, p, net0):
    hidden = jax.nn.relu(_conv(q, p["mask_conv1"], net0))
    return 0.25 * _conv(q, p["mask_conv2"], hidden, pad=0)


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------


def _normalize(image):
    return 2.0 * (image / 255.0) - 1.0


def _features(q, cfg, p, image):
    """One image through the feature encoder (instance norm)."""
    x = _trunk(q, cfg, "instance", p["fnet"]["trunk"], {}, _normalize(image))
    return _conv(q, p["fnet"]["conv2"], x, pad=0)


def _hidden_and_context(q, cfg, p, scales):
    n = cfg["n_gru_layers"]
    net = tuple(jnp.tanh(scale[0]) for scale in scales[:n])
    context = tuple(
        tuple(jnp.split(_conv(q, p[f"context_zqr_conv{i}"], jax.nn.relu(scale[1])), 3, axis=-1))
        for i, scale in enumerate(scales[:n])
    )
    return net, context


def _context(q, cfg, variables, image1):
    """The left image through the context encoder -> (net, context)."""
    p, s = variables["params"], variables["batch_stats"]
    scales, _ = _context_encoder(q, cfg, p["cnet"], s["cnet"], _normalize(image1), shared=False)
    return _hidden_and_context(q, cfg, p, scales)


def _shared(q, cfg, variables, image1, image2):
    """Shared backbone: both images through the context trunk, the feature
    maps from a head on it -> (net, context, fmap1, fmap2)."""
    p, s = variables["params"], variables["batch_stats"]
    both = _normalize(jnp.concatenate((image1, image2), axis=0))
    scales, trunk = _context_encoder(q, cfg, p["cnet"], s["cnet"], both, shared=True)
    fmaps = _res_block(q, "instance", p["conv2_res"], {}, trunk, 1)
    fmap1, fmap2 = jnp.split(_conv(q, p["conv2_out"], fmaps), 2, axis=0)
    return (*_hidden_and_context(q, cfg, p, scales), fmap1, fmap2)


def _encode(q, cfg, variables, image1, image2):
    if cfg["shared_backbone"]:
        return _shared(q, cfg, variables, image1, image2)
    p = variables["params"]
    net, context = _context(q, cfg, variables, image1)
    return net, context, _features(q, cfg, p, image1), _features(q, cfg, p, image2)


def _prelude(q, cfg, variables, image1, image2):
    net, context, fmap1, fmap2 = _encode(q, cfg, variables, image1, image2)
    pyramid = _corr_pyramid(q, fmap1, fmap2, cfg["corr_levels"])
    b, h, w, _ = net[0].shape
    coords0 = jnp.broadcast_to(jnp.arange(w, dtype=jnp.float32), (b, h, w))
    return net, context, pyramid, coords0


def _iteration(q, cfg, p, net, coords1, context, pyramid, coords0, dense_lookup=False):
    coords1 = lax.stop_gradient(coords1)
    corr = _corr_lookup(pyramid, coords1, cfg["corr_radius"], dense_lookup)
    flow = (coords1 - coords0)[..., None]
    n = cfg["n_gru_layers"]
    if cfg["slow_fast_gru"] and n == 3:
        net, _ = _update_block(q, cfg, p, net, context, iter16=False, iter08=False, update=False)
    if cfg["slow_fast_gru"] and n >= 2:
        net, _ = _update_block(q, cfg, p, net, context, iter32=n == 3, iter08=False, update=False)
    net, delta = _update_block(q, cfg, p, net, context, corr, flow, iter32=n == 3, iter16=n >= 2)
    return net, coords1 + delta[..., 0]


def _refine(q, cfg, p, net, context, fmap1, fmap2, iters):
    pyramid = _corr_pyramid(q, fmap1, fmap2, cfg["corr_levels"])
    b, h, w, _ = net[0].shape
    coords0 = jnp.broadcast_to(jnp.arange(w, dtype=jnp.float32), (b, h, w))

    def body(carry, _):
        net, coords1 = carry
        return _iteration(q, cfg, p["iteration"]["update_block"], net, coords1,
                          context, pyramid, coords0), None

    (net, coords1), _ = lax.scan(body, (net, coords0), None, length=iters)
    mask = _mask(q, p["mask_head"], net[0])
    return _convex_upsample(coords1 - coords0, mask, 2 ** cfg["n_downsample"])


def forward(cfg: Dict, variables, image1, image2, iters: int, precision: str = "float32"):
    """Inference: images (B,H,W,3) in [0, 255] -> disparity flow (B,H,W)."""
    q = rounder(precision)
    net, context, fmap1, fmap2 = _encode(q, cfg, variables, image1, image2)
    return _refine(q, cfg, variables["params"], net, context, fmap1, fmap2, iters)


def forward_staged(cfg: Dict, variables, image1, image2, iters: int, precision: str = "float32"):
    """`forward`, one encoder pass to a program, so that a full-resolution
    pair fits a chip in float32: the same functions in the same order, with
    only the feature maps and the context kept between programs."""
    q = rounder(precision)
    p = variables["params"]
    if cfg["shared_backbone"]:
        net, context, fmap1, fmap2 = jax.jit(lambda v, a, b: _shared(q, cfg, v, a, b))(
            variables, image1, image2)
    else:
        features = jax.jit(lambda p, image: _features(q, cfg, p, image))
        fmap1 = jax.block_until_ready(features(p, image1))
        fmap2 = jax.block_until_ready(features(p, image2))
        net, context = jax.jit(lambda v, a: _context(q, cfg, v, a))(variables, image1)
    jax.block_until_ready(context)
    refine = jax.jit(lambda p, *args: _refine(q, cfg, p, *args, iters))
    return refine(p, net, context, fmap1, fmap2)


def predictions(cfg: Dict, variables, image1, image2, iters: int, precision: str = "float32"):
    """Training: the upsampled prediction after every iteration,
    (iters,B,H,W)."""
    q = rounder(precision)
    p = variables["params"]
    net, context, pyramid, coords0 = _prelude(q, cfg, variables, image1, image2)
    factor = 2 ** cfg["n_downsample"]

    @jax.checkpoint
    def body(carry, _):
        net, coords1 = carry
        net, coords1 = _iteration(q, cfg, p["iteration"]["update_block"], net, coords1,
                                  context, pyramid, coords0, dense_lookup=True)
        up = _convex_upsample(coords1 - coords0, _mask(q, p["mask_head"], net[0]), factor)
        return (net, coords1), up

    _, ups = lax.scan(body, (net, coords0), None, length=iters)
    return ups


# --------------------------------------------------------------------------
# training: loss, schedule, AdamW
# --------------------------------------------------------------------------


def _valid_mask(flow_gt, valid, max_flow):
    return ((valid >= 0.5) & (jnp.abs(flow_gt[..., 0]) < max_flow)).astype(jnp.float32)


def sequence_loss(preds, flow_gt, valid, gamma=0.9, max_flow=700.0, count=None):
    """preds (iters,B,H,W); flow_gt (B,H,W,1); valid (B,H,W). `count`, where
    given, is the number of valid pixels to average over: a block of a
    batch's rows passes the whole batch's, so that the blocks' losses add up
    to the batch's."""
    n = preds.shape[0]
    mask = _valid_mask(flow_gt, valid, max_flow)
    if count is None:
        count = jnp.maximum(mask.sum(), 1.0)
    adjusted = gamma ** (15.0 / (n - 1)) if n > 1 else gamma
    weights = adjusted ** jnp.arange(n - 1, -1, -1, dtype=jnp.float32)
    per_iter = (jnp.abs(preds - flow_gt[None, ..., 0]) * mask[None]).sum(axis=(1, 2, 3)) / count
    return (weights * per_iter).sum()


def learning_rate(step, peak, num_steps, pct_start=0.01, div=25.0, final_div=1e4):
    """Linear one-cycle over num_steps + 100, as the recipe's scheduler."""
    total = num_steps + 100
    warm_end = max(int(round(pct_start * total)) - 1, 1)
    initial = peak / div
    final = initial / final_div
    step = jnp.asarray(step, jnp.float32)
    up = initial + (peak - initial) * jnp.minimum(step / warm_end, 1.0)
    down_steps = total - 1 - warm_end
    down = peak + (final - peak) * jnp.clip((step - warm_end) / down_steps, 0.0, 1.0)
    return jnp.where(step < warm_end, up, down)


def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def train_steps(cfg: Dict, train: Dict, variables, batches, precision: str = "float32",
                batch_rows=None):
    """Run `len(batches)` optimizer steps from `variables`. Returns
    (losses, first clipped gradient per leaf, params after the last step).

    `train` holds iters, lr, num_steps, wdecay, grad_clip_norm, loss_gamma,
    max_flow. A step's loss and gradient are summed over the batch one row at
    a time (each row's loss over the whole batch's count of valid pixels), so
    that a float32 backward pass through every iteration fits one chip.
    `batch_rows` (a slice) plants the half-batch fault for the limits'
    readings: the batch is then those rows only, the mean taken over them."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    # An argument, not a closed-over constant: the compiled program is then
    # the same for every seed, and a compilation cache finds it again.
    stats = variables["batch_stats"]

    def row_loss(params, stats, row, count):
        preds = predictions(cfg, {"params": params, "batch_stats": stats},
                            row["image1"], row["image2"], train["iters"], precision)
        return sequence_loss(preds, row["flow"], row["valid"],
                             train["loss_gamma"], train["max_flow"], count)

    row_grad = jax.jit(jax.value_and_grad(row_loss))

    @jax.jit
    def update(params, mu, nu, count, grads):
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
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + train["wdecay"] * p),
            params, mu, nu,
        )
        return params, mu, nu, t, grads

    params = variables["params"]
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.int32)
    losses, first_grad = [], None
    for batch in batches:
        if batch_rows is not None:
            batch = {k: v[batch_rows] for k, v in batch.items()}
        valid = jnp.maximum(_valid_mask(batch["flow"], batch["valid"], train["max_flow"]).sum(), 1.0)
        loss, grads = 0.0, None
        for i in range(batch["image1"].shape[0]):
            row = {k: v[i : i + 1] for k, v in batch.items()}
            row_value, row_grads = row_grad(params, stats, row, valid)
            loss = loss + row_value
            grads = row_grads if grads is None else jax.tree.map(jnp.add, grads, row_grads)
        params, mu, nu, count, clipped = update(params, mu, nu, count, grads)
        losses.append(loss)
        if first_grad is None:
            first_grad = clipped
    return losses, first_grad, params
