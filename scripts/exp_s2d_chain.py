"""Round-4 experiment, stage 2: the full fnet layer1 CHAIN in normal vs
W-s2d domain — isolation wins can die in context (round-3 lesson: the s2d
stem was fast alone, 40 ms slower in context), so this measures the whole
stretch the integration would replace:

    stem-IN-apply+relu -> RB64 -> RB64 -> layer2_0{conv1 s2 + 1x1 skip}

with one-pass InstanceNorm stats (sum+sumsq fused into producer convs) in
both forms. Parity first (small f32), then TPU timing at Middlebury-F fnet
shape. The s2d form consumes the stem output via pure reshape and exits
through phase-structured stride-2 kernels (no d2s anywhere).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from _timing import timed
from exp_s2d_layer1 import conv, dense_w_kernel, w_s2d


def _stats_dtype(x):
    # f32 accumulation for bf16/f32 inputs; f64 when the parity harness
    # runs in x64 (hardcoding f32 would round the stats and mask/unmask
    # grouping-order noise in the f64 exactness check).
    return jnp.float64 if x.dtype == jnp.float64 else jnp.float32


def in_norm(x, eps=1e-5):
    """One-pass instance norm (normal domain), fp32 stats."""
    b, h, w, c = x.shape
    n = h * w
    sd = _stats_dtype(x)
    s = jnp.sum(x, axis=(1, 2), dtype=sd)
    sq = jnp.sum(jnp.square(x.astype(sd)), axis=(1, 2), dtype=sd)
    mean = s / n
    var = jnp.maximum(sq / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    return (x - mean.astype(x.dtype)[:, None, None, :]) * inv.astype(x.dtype)[:, None, None, :]


def in_norm_s2d(y, phases=2, eps=1e-5):
    """Instance norm in the W-s2d domain: stats pool the phase channel
    blocks back to original channels, the affine tiles them back."""
    b, h, w2, pc = y.shape
    c = pc // phases
    n = h * w2 * phases
    sd = _stats_dtype(y)
    s = jnp.sum(y, axis=(1, 2), dtype=sd).reshape(b, phases, c).sum(axis=1)
    sq = (
        jnp.sum(jnp.square(y.astype(sd)), axis=(1, 2))
        .reshape(b, phases, c)
        .sum(axis=1)
    )
    mean = s / n
    var = jnp.maximum(sq / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    mean_t = jnp.tile(mean, (1, phases)).astype(y.dtype)[:, None, None, :]
    inv_t = jnp.tile(inv, (1, phases)).astype(y.dtype)[:, None, None, :]
    return (y - mean_t) * inv_t


def entry_w_kernel(k):
    """3x3xCxCo stride-(2,2) conv -> (3,2,2C,Co) stride-(2,1) kernel
    consuming the W-s2d domain (layer2_0 conv1). Col taps: dw=-1 -> block
    j-1 phase O; dw=0 -> block j phase E; dw=+1 -> block j phase O."""
    kh, kw, c, co = k.shape
    assert kw == 3
    K = jnp.zeros((kh, 2, 2 * c, co), k.dtype)
    K = K.at[:, 0, c:, :].set(k[:, 0])
    K = K.at[:, 1, :c, :].set(k[:, 1])
    K = K.at[:, 1, c:, :].set(k[:, 2])
    return K


def skip_w_kernel(k):
    """1x1xCxCo stride-(2,2) -> (1,1,2C,Co) stride-(2,1): even phase only."""
    kh, kw, c, co = k.shape
    assert kh == kw == 1
    K = jnp.zeros((1, 1, 2 * c, co), k.dtype)
    K = K.at[0, 0, :c, :].set(k[0, 0])
    return K


def make_params(rng, dtype):
    p = {}
    for name, shape in [
        ("l10_c1", (3, 3, 64, 64)), ("l10_c2", (3, 3, 64, 64)),
        ("l11_c1", (3, 3, 64, 64)), ("l11_c2", (3, 3, 64, 64)),
        ("l20_c1", (3, 3, 64, 96)), ("l20_skip", (1, 1, 64, 96)),
    ]:
        p[name] = jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 0.05).astype(dtype)
    return p


def chain_normal(x, p):
    """x: stem conv output (B,H,W,64), pre-norm. Through layer2_0 convs."""
    x = jax.nn.relu(in_norm(x))                      # stem IN+relu
    for blk in ("l10", "l11"):
        y = conv(x, p[f"{blk}_c1"])
        y = jax.nn.relu(in_norm(y))
        y = conv(y, p[f"{blk}_c2"])
        y = jax.nn.relu(in_norm(y))
        x = jax.nn.relu(x + y)
    main = conv(x, p["l20_c1"], strides=(2, 2), padding=((1, 1), (1, 1)))
    skip = conv(x, p["l20_skip"], strides=(2, 2), padding=((0, 0), (0, 0)))
    return main, skip


def chain_s2d(x, p):
    """Same math; layer1 in W-s2d domain, stride-2 exit kernels."""
    x = w_s2d(jax.nn.relu(in_norm(x)))               # reshape only
    for blk in ("l10", "l11"):
        y = conv(x, dense_w_kernel(p[f"{blk}_c1"]))
        y = jax.nn.relu(in_norm_s2d(y))
        y = conv(y, dense_w_kernel(p[f"{blk}_c2"]))
        y = jax.nn.relu(in_norm_s2d(y))
        x = jax.nn.relu(x + y)
    main = conv(x, entry_w_kernel(p["l20_c1"]), strides=(2, 1), padding=((1, 1), (1, 0)))
    skip = conv(x, skip_w_kernel(p["l20_skip"]), strides=(2, 1), padding=((0, 0), (0, 0)))
    return main, skip


def parity():
    # f64 proves the FORMULATION exact (contraction-order drift vanishes);
    # f32 then only has to meet the loose accumulation-noise band (the chain
    # stacks 6 convs and three rsqrt-amplifying instance norms).
    rng = np.random.default_rng(1)
    x64 = rng.standard_normal((1, 16, 24, 64))
    p64 = make_params(rng, jnp.float64)
    if jax.config.jax_enable_x64:
        a_main, a_skip = chain_normal(jnp.asarray(x64), p64)
        b_main, b_skip = chain_s2d(jnp.asarray(x64), p64)
        np.testing.assert_allclose(np.asarray(b_main), np.asarray(a_main), rtol=1e-8, atol=1e-8)
        np.testing.assert_allclose(np.asarray(b_skip), np.asarray(a_skip), rtol=1e-8, atol=1e-8)
        print("chain parity OK in f64 (formulation exact)")
        return
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p64)
    a_main, a_skip = chain_normal(jnp.asarray(x64, jnp.float32), p)
    b_main, b_skip = chain_s2d(jnp.asarray(x64, jnp.float32), p)
    np.testing.assert_allclose(np.asarray(b_main), np.asarray(a_main), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(b_skip), np.asarray(a_skip), rtol=1e-2, atol=1e-2)
    print("chain parity OK in f32 (accumulation-noise band)")


def timing():
    rng = np.random.default_rng(0)
    h, w = 1984, 2880
    dt = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((1, h, w, 64)).astype(np.float32)).astype(dt)
    p = make_params(rng, dt)
    tA = timed(lambda a: chain_normal(a, p), x, n=6, trials=3)
    print(f"chain normal: {tA*1e3:8.2f} ms")
    tB = timed(lambda a: chain_s2d(a, p), x, n=6, trials=3)
    print(f"chain s2d:    {tB*1e3:8.2f} ms")


if __name__ == "__main__":
    parity()
    if jax.default_backend() == "tpu":
        timing()
