"""Per-component timing of the full-res (Middlebury-F) forward on the
current accelerator.

Timing methodology (scripts/_timing.py): every measurement chains N
executions inside ONE jitted scan and waits on its result with
`block_until_ready`. A dummy-scalar perturbation of the inputs defeats CSE
across chain steps, and the chain consumes every output element so XLA
cannot dead-code-eliminate part of the measured function.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from _timing import timed
from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models import RAFTStereo
from raft_stereo_tpu.models.extractor import BasicEncoder, MultiBasicEncoder


def main():
    h, w = 1984, 2880
    cfg = RAFTStereoConfig(
        corr_implementation="pallas" if jax.default_backend() == "tpu" else "reg",
        mixed_precision=True,
        corr_dtype="bfloat16",
        sequential_encoder=True,
    )
    model = RAFTStereo(cfg)
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    small = jnp.zeros((1, 64, 96, 3))
    variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(
        jax.random.PRNGKey(0)
    )
    params = variables["params"]

    compute_dtype = jnp.bfloat16
    x1 = (2.0 * (i1 / 255.0) - 1.0).astype(compute_dtype)

    # --- encoders ---
    fnet = BasicEncoder(output_dim=256, norm_fn="instance", downsample=cfg.n_downsample)
    t_fnet = timed(lambda x: fnet.apply({"params": params["fnet"]}, x), x1, n=8)
    print(f"fnet (one image):      {t_fnet*1e3:8.1f} ms")

    cnet = MultiBasicEncoder(
        output_dims=(tuple(cfg.hidden_dims), tuple(cfg.context_dims)),
        norm_fn="batch",
        downsample=cfg.n_downsample,
    )
    cnet_vars = {"params": params["cnet"]}
    if "batch_stats" in variables:
        cnet_vars["batch_stats"] = variables["batch_stats"]["cnet"]
    t_cnet = timed(lambda x: cnet.apply(cnet_vars, x, num_layers=3), x1, n=8)
    print(f"cnet:                  {t_cnet*1e3:8.1f} ms")

    # --- corr state ---
    from raft_stereo_tpu.ops.corr import corr_volume, corr_pyramid

    hq, wq = h // 4, w // 4
    f1 = jnp.asarray(rng.normal(size=(1, hq, wq, 256)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(1, hq, wq, 256)).astype(np.float32))
    t_vol = timed(
        lambda a, b: tuple(
            corr_pyramid(corr_volume(a, b, out_dtype=jnp.bfloat16), cfg.corr_levels)
        ),
        f1,
        f2,
        n=32,
    )
    print(f"corr volume+pyramid:   {t_vol*1e3:8.1f} ms")

    # --- lookup alone ---
    if jax.default_backend() == "tpu":
        from raft_stereo_tpu.ops.corr_pallas import (
            pallas_corr_state,
            pallas_corr_lookup_padded,
        )

        state = pallas_corr_state(f1, f2, cfg.corr_levels, corr_dtype=jnp.bfloat16)
        coords = jnp.tile(
            jnp.arange(wq, dtype=jnp.float32)[None, None, :], (1, hq, 1)
        )
        t_lkp = timed(
            lambda c: pallas_corr_lookup_padded(state, c, cfg.corr_radius), coords, n=64
        )
        print(f"pallas lookup (1 it):  {t_lkp*1e3:8.1f} ms")

    # --- full forward at two iteration counts -> per-iter slope ---
    # Same chained-jit methodology as every other measurement here (the
    # round-1 advisor flagged the earlier single-execution variant: the
    # (t32-t8)/24 slope amplifies run-to-run noise).
    def fwd(iters):
        return timed(
            lambda a, b: model.apply(variables, a, b, iters=iters, test_mode=True)[1],
            i1,
            i2,
            n=4,
            trials=3,
        )

    t8 = fwd(8)
    t32 = fwd(32)
    per_iter = (t32 - t8) / 24
    print(f"forward iters=8:       {t8*1e3:8.1f} ms")
    print(f"forward iters=32:      {t32*1e3:8.1f} ms")
    print(f"per-iteration slope:   {per_iter*1e3:8.1f} ms")
    print(f"loop-invariant part:   {(t8 - 8*per_iter)*1e3:8.1f} ms")


if __name__ == "__main__":
    main()
