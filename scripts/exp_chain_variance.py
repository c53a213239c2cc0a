"""Round-5 diagnostic: is the bench's loop-invariant overhead stable within
one session across chain lengths, or does it drift session-to-session?

Context (round-4 verdict #3a): `fwd_overhead_ms` moved 219.2 (r03) → 237.8
(r04) with no error bars. Round 5 added the per-trial envelope, which is
TIGHT (±0.4 ms within one executable) — yet the same 32-iter forward
measured 904.6 ms in one session (scripts/exp_gate_fusion.py, chain n=2)
and 930.9 ms in another (bench.py, chain n=5). This script compiles BOTH
chain forms in ONE session and times them back to back, separating
"chain-length / executable artifact" from "session-to-session drift"
(device state, compile-schedule lottery).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models import RAFTStereo


def main():
    h, w = 1984, 2880
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    small = jnp.zeros((1, 64, 96, 3))
    cfg = RAFTStereoConfig(
        corr_implementation="pallas",
        mixed_precision=True,
        corr_dtype="bfloat16",
        sequential_encoder=True,
    )
    model = RAFTStereo(cfg)
    variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(jax.random.PRNGKey(0))

    def make(iters, n):
        @jax.jit
        def fwd(v, a, b):
            def body(c, _):
                _, up = model.apply(v, a + c * 1e-30, b, iters=iters, test_mode=True)
                return up.reshape(-1)[0], ()
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c
        return fwd

    fns = {}
    for n in (2, 5):
        for iters in (32, 8):
            f = make(iters, n)
            jax.block_until_ready(f(variables, i1, i2))  # compile
            fns[(iters, n)] = f

    # interleaved trials so drift over the run hits all forms equally
    times = {k: [] for k in fns}
    for _ in range(4):
        for (iters, n), f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f(variables, i1, i2))
            times[(iters, n)].append((time.perf_counter() - t0) / n)
    for (iters, n), ts in sorted(times.items()):
        print(
            f"iters={iters:2d} chain n={n}: per-fwd best {min(ts)*1e3:7.1f} ms  "
            f"trials {[round(t*1e3,1) for t in ts]}"
        )
    for n in (2, 5):
        hi, lo = min(times[(32, n)]), min(times[(8, n)])
        slope = (hi - lo) / 24 * 1e3
        print(f"chain n={n}: per-iter {slope:5.2f} ms  overhead {hi*1e3 - slope*32:6.1f} ms")


if __name__ == "__main__":
    main()
