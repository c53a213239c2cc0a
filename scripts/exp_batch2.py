"""Round-4 experiment: make B>=2 full-res inference beat B=1 in TOTAL
maps/s (round-3 verdict weak #2: B=2 ran 1.017 vs 1.075 at B=1).

Measures Middlebury-F test-mode forwards (32 iters) at:
  - B=1 anchor sequential encoder (the headline config)
  - B=2 scan-form sequential encoder (round-3 shipped form)
  - B=2 fully batched encoder (fits? round-2 said no at fp32; the round-4
    B=1 footprint is 5.4 GB static, so 2 full trunks may fit now)
  - B=4 variants if B=2 fits with room

Prints per-config: seconds/call, total maps/s, static HBM estimate.
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


def hbm_gb(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    try:
        ma = c.memory_analysis()
        peak = getattr(ma, "peak_memory_in_bytes", 0)
        return peak / 1e9 if peak else None
    except Exception:
        return None


def main():
    h, w, iters = 1984, 2880, 32
    rng = np.random.default_rng(0)
    small = jnp.zeros((1, 64, 96, 3))

    def build(seq):
        cfg = RAFTStereoConfig(
            corr_implementation="pallas",
            mixed_precision=True,
            corr_dtype="bfloat16",
            sequential_encoder=seq,
        )
        model = RAFTStereo(cfg)
        variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(jax.random.PRNGKey(0))
        return model, variables

    for label, seq, b in [
        ("B=1 seq-anchor", True, 1),
        ("B=2 seq-scan", True, 2),
        ("B=2 batched", False, 2),
    ]:
        i1 = jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)).astype(np.float32))
        i2 = jnp.asarray(rng.uniform(0, 255, (b, h, w, 3)).astype(np.float32))
        model, variables = build(seq)
        fn = lambda a, bb: model.apply(variables, a, bb, iters=iters, test_mode=True)[1]
        gb = hbm_gb(fn, i1, i2)
        if gb is not None and gb > 15.0:
            print(f"{label}: SKIP (static peak {gb:.1f} GB > 15)")
            continue
        t = timed(fn, i1, i2, n=3, trials=3)
        print(f"{label}: {t*1e3:8.1f} ms/call  {b/t:6.3f} maps/s  hbm {gb and round(gb,2)} GB")


if __name__ == "__main__":
    main()
