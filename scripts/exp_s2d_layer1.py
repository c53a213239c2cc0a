"""Round-4 experiment: can the full-res C=64 layer1 resblock convs beat
XLA's 65 TF/s by moving to a space-to-depth (s2d) domain where the
contraction dimension fills the MXU's 128 lanes?

Context (ROADMAP round-3 trace): fnet layer1 runs 4 convs x 6.5 ms at
C=64 (65 TF/s); the same-arch gru08 convs with 128-channel inputs run at
~160 TF/s. Candidate transforms of conv3x3(C64->C64) at (1,1984,2880,64):

  A. direct conv (baseline)
  B. H-s2d "dense" variant: x -> (1,H/2,W,128); one 3x3x128x128 conv whose
     kernel embeds the original taps with 50% structural zeros (2x FLOPs,
     hopefully ~160 TF/s -> net ~1.23x).
  C. H-s2d "two-conv" variant: two 2x3x128x64 convs (E/O output phases,
     1.33x FLOPs, Cout=64 may half-starve the output lanes).
  D. W-s2d variant: (1,H,W/2,128) by pure reshape (W and C are adjacent in
     row-major, so no transpose); one 3x3x128x128 conv, 2x FLOPs like B.
  E. C=128 reference point: direct 3x3x128x128 conv at (1,992,2880,128)
     (same FLOPs as B/D) — the throughput ceiling the variants chase.

Parity is checked on small shapes on CPU-friendly sizes first; timing runs
on the TPU at the Middlebury-F fnet shape.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from _timing import timed


def conv(x, k, strides=(1, 1), padding=((1, 1), (1, 1))):
    return jax.lax.conv_general_dilated(
        x, k, strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=x.dtype,
    )


def h_s2d(x):
    """(B,H,W,C) -> (B,H/2,W,2C): channel block 0 = even rows, 1 = odd."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w, c).transpose(0, 1, 3, 2, 4).reshape(b, h // 2, w, 2 * c)


def h_d2s(y):
    b, h2, w, c2 = y.shape
    c = c2 // 2
    return y.reshape(b, h2, w, 2, c).transpose(0, 1, 3, 2, 4).reshape(b, 2 * h2, w, c)


def w_s2d(x):
    """(B,H,W,C) -> (B,H,W/2,2C): pure reshape (w,c adjacent in row-major)."""
    b, h, w, c = x.shape
    return x.reshape(b, h, w // 2, 2 * c)


def w_d2s(y):
    b, h, w2, c2 = y.shape
    return y.reshape(b, h, w2 * 2, c2 // 2)


def dense_h_kernel(k):
    """3x3xCxC -> 3x3x2Cx2C kernel for the H-s2d domain (variant B).

    Out channel block E (rows 2i): taps O(i-1)@k[0], E(i)@k[1], O(i)@k[1].
    Out channel block O (rows 2i+1): E(i)@k[1], O(i)@k[1], E(i+1)@k[2].
    Kernel row r of the s2d conv sees block row i+r-1 = [E(i+r-1), O(i+r-1)].
    """
    kh, kw, c, co = k.shape
    assert kh == 3 and co == c
    K = jnp.zeros((3, kw, 2 * c, 2 * c), k.dtype)
    # E outputs (cols 0:c): out_E(i) = k0*O(i-1) + k1*E(i) + k2*O(i)
    K = K.at[0, :, c:, :c].set(k[0])   # row i-1, O part, tap k[0]
    K = K.at[1, :, :c, :c].set(k[1])   # row i,   E part, tap k[1]
    K = K.at[1, :, c:, :c].set(k[2])   # row i,   O part, tap k[2]
    # O outputs (cols c:2c): out_O(i) = k0*E(i) + k1*O(i) + k2*E(i+1)
    K = K.at[1, :, :c, c:].set(k[0])   # row i,   E part, tap k[0]
    K = K.at[1, :, c:, c:].set(k[1])   # row i,   O part, tap k[1]
    K = K.at[2, :, :c, c:].set(k[2])   # row i+1, E part, tap k[2]
    return K


def dense_w_kernel(k):
    """3x3xCxC -> 3x3x2Cx2C kernel for the W-s2d domain (variant D).
    Same structure as dense_h_kernel but phases interleave along W: s2d
    channel block 0 = even cols, 1 = odd cols; kernel COLUMN r sees block
    col j+r-1."""
    kh, kw, c, co = k.shape
    assert kw == 3 and co == c
    K = jnp.zeros((kh, 3, 2 * c, 2 * c), k.dtype)
    K = K.at[:, 0, c:, :c].set(k[:, 0])
    K = K.at[:, 1, :c, :c].set(k[:, 1])
    K = K.at[:, 1, c:, :c].set(k[:, 2])
    K = K.at[:, 1, :c, c:].set(k[:, 0])
    K = K.at[:, 1, c:, c:].set(k[:, 1])
    K = K.at[:, 2, :c, c:].set(k[:, 2])
    return K


def two_conv_kernels(k):
    """3x3xCxC -> (2x3x2CxC, 2x3x2CxC) kernels for variant C."""
    kh, kw, c, co = k.shape
    kE = jnp.zeros((2, kw, 2 * c, c), k.dtype)
    kE = kE.at[0, :, c:, :].set(k[0])  # O(i-1)
    kE = kE.at[1, :, :c, :].set(k[1])  # E(i)
    kE = kE.at[1, :, c:, :].set(k[2])  # O(i)
    kO = jnp.zeros((2, kw, 2 * c, c), k.dtype)
    kO = kO.at[0, :, :c, :].set(k[0])  # E(i)
    kO = kO.at[0, :, c:, :].set(k[1])  # O(i)
    kO = kO.at[1, :, :c, :].set(k[2])  # E(i+1)
    return kE, kO


def parity():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 16, 12, 4)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((3, 3, 4, 4)).astype(np.float32))
    want = conv(x, k)

    # B: H-s2d dense
    yB = h_d2s(conv(h_s2d(x), dense_h_kernel(k), padding=((1, 1), (1, 1))))
    np.testing.assert_allclose(np.asarray(yB), np.asarray(want), rtol=1e-5, atol=1e-5)

    # C: two-conv
    kE, kO = two_conv_kernels(k)
    s = h_s2d(x)
    # E window {i-1,i}: pad (1,0); O window {i,i+1}: pad (0,1)
    yE = conv(s, kE, padding=((1, 0), (1, 1)))
    yO = conv(s, kO, padding=((0, 1), (1, 1)))
    yC = h_d2s(jnp.concatenate([yE, yO], axis=-1))
    np.testing.assert_allclose(np.asarray(yC), np.asarray(want), rtol=1e-5, atol=1e-5)

    # D: W-s2d dense
    yD = w_d2s(conv(w_s2d(x), dense_w_kernel(k), padding=((1, 1), (1, 1))))
    np.testing.assert_allclose(np.asarray(yD), np.asarray(want), rtol=1e-5, atol=1e-5)
    print("parity OK (B, C, D == direct conv)")


def timing():
    rng = np.random.default_rng(0)
    h, w, c = 1984, 2880, 64
    dt = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((1, h, w, c)).astype(np.float32)).astype(dt)
    k = jnp.asarray(rng.standard_normal((3, 3, c, c)).astype(np.float32)).astype(dt)
    gf = 2 * h * w * c * c * 9 / 1e9  # useful FLOPs (all variants)

    tA = timed(lambda a: conv(a, k), x, n=16)
    print(f"A direct C=64:        {tA*1e3:7.2f} ms  {gf/tA/1e3:6.1f} TF/s useful")

    KB = dense_h_kernel(k)
    xs = h_s2d(x)
    tB = timed(lambda a: conv(a, KB), xs, n=16)
    print(f"B H-s2d dense 128:    {tB*1e3:7.2f} ms  {gf/tB/1e3:6.1f} TF/s useful")

    kE, kO = two_conv_kernels(k)
    tC = timed(
        lambda a: (conv(a, kE, padding=((1, 0), (1, 1))), conv(a, kO, padding=((0, 1), (1, 1)))),
        xs, n=16,
    )
    print(f"C H-s2d two-conv:     {tC*1e3:7.2f} ms  {gf/tC/1e3:6.1f} TF/s useful")

    KD = dense_w_kernel(k)
    xw = w_s2d(x)
    tD = timed(lambda a: conv(a, KD), xw, n=16)
    print(f"D W-s2d dense 128:    {tD*1e3:7.2f} ms  {gf/tD/1e3:6.1f} TF/s useful")

    xe = jnp.asarray(rng.standard_normal((1, h // 2, w, 128)).astype(np.float32)).astype(dt)
    ke = jnp.asarray(rng.standard_normal((3, 3, 128, 128)).astype(np.float32)).astype(dt)
    tE = timed(lambda a: conv(a, ke), xe, n=16)
    gfE = 2 * (h // 2) * w * 128 * 128 * 9 / 1e9
    print(f"E direct C=128 ref:   {tE*1e3:7.2f} ms  {gfE/tE/1e3:6.1f} TF/s raw")

    # transform costs
    tT = timed(lambda a: h_s2d(a) * 1.0000001, x, n=16)
    print(f"h_s2d transform:      {tT*1e3:7.2f} ms")
    tR = timed(lambda a: w_s2d(a) * 1.0000001, x, n=16)
    print(f"w_s2d reshape(+mul):  {tR*1e3:7.2f} ms")


if __name__ == "__main__":
    parity()
    if jax.default_backend() == "tpu":
        timing()
