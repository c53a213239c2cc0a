"""End-to-end A/B of the fused Pallas encoder kernels (round 6).

Measures the config that matters — the full test-mode forward at
Middlebury-F resolution — with `fused_encoder` on vs off, NOT the kernels
in isolation (the gates_pallas lesson: a kernel that wins standalone can
lose end-to-end to layout-boundary copies). The per-iteration body is
identical in both paths, so the total-time delta IS the loop-invariant
overhead delta; a lo-iteration chain splits it explicitly, and component
chains attribute it between the encoders and the corr-state build.

Record the verdict in ops/encoder_pallas.py's module docstring (and flip
the bench default if negative). Re-run after every jax/libtpu upgrade —
the XLA-vs-Mosaic balance this measures is a toolchain artifact.

Usage (TPU):
  python scripts/exp_fused_encoder.py                 # full A/B
  python scripts/exp_fused_encoder.py --iters_hi 32 --iters_lo 8
On CPU this refuses the full-res timing (interpreter mode, hours) and runs
a small-shape parity check instead, exiting nonzero on mismatch.
"""

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from _timing import chain_model, time_compiled


def _make_model(fused: bool):
    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig(
        corr_implementation="pallas",
        mixed_precision=True,
        corr_dtype="bfloat16",
        sequential_encoder=True,
        fused_encoder=fused,
    )
    return RAFTStereo(cfg), cfg


def parity_check() -> int:
    """CPU path: small-shape fused-vs-XLA forward parity (interpret mode)."""
    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig(corr_implementation="pallas")
    model = RAFTStereo(cfg)
    fused = RAFTStereo(dataclasses.replace(cfg, fused_encoder=True))
    rng = np.random.default_rng(0)
    h, w = 48, 64
    img = jnp.zeros((1, h, w, 3))
    variables = jax.jit(lambda r: model.init(r, img, img, iters=1))(jax.random.PRNGKey(0))
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))

    def fwd(m):
        return jax.jit(lambda v, a, b: m.apply(v, a, b, iters=3, test_mode=True)[1])(
            variables, i1, i2
        )

    a, b = np.asarray(fwd(model)), np.asarray(fwd(fused))
    err = float(np.abs(a - b).max())
    ok = err < 2e-2  # recurrent amplification of fp32 conv reassociation
    print(f"parity (48x64, 3 iters): max |d(disparity)| = {err:.2e} -> "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters_hi", type=int, default=32)
    ap.add_argument("--iters_lo", type=int, default=8)
    ap.add_argument("--chain_n", type=int, default=4)
    ap.add_argument("--height", type=int, default=1984)
    ap.add_argument("--width", type=int, default=2880)
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        print("no TPU: running the small-shape parity check instead of the "
              "full-res timing (interpreter mode would take hours)", flush=True)
        return parity_check()

    rng = np.random.default_rng(0)
    h, w = args.height, args.width
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    small = jnp.zeros((1, 64, 96, 3))

    model_f, _ = _make_model(True)
    model_x, _ = _make_model(False)
    variables = jax.jit(lambda r: model_f.init(r, small, small, iters=1))(
        jax.random.PRNGKey(0)
    )

    results = {}
    for label, model in (("fused", model_f), ("xla", model_x)):
        hi = time_compiled(
            jax.jit(chain_model(model, args.iters_hi, args.chain_n)),
            (variables, i1, i2), args.chain_n,
        )
        lo = time_compiled(
            jax.jit(chain_model(model, args.iters_lo, args.chain_n)),
            (variables, i1, i2), args.chain_n,
        )
        slope = (hi - lo) / (args.iters_hi - args.iters_lo)
        overhead = hi - slope * args.iters_hi
        results[label] = (hi, overhead)
        print(
            f"{label}: total {hi*1e3:.1f} ms @ {args.iters_hi} iters, "
            f"per-iter {slope*1e3:.2f} ms, overhead {overhead*1e3:.1f} ms",
            flush=True,
        )

    d_total = (results["xla"][0] - results["fused"][0]) * 1e3
    d_over = (results["xla"][1] - results["fused"][1]) * 1e3
    verdict = "POSITIVE (fused wins)" if d_total > 0 else "NEGATIVE (retire per module docstring)"
    print(
        f"A/B: fused saves {d_total:+.1f} ms total, {d_over:+.1f} ms overhead "
        f"-> {verdict}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
