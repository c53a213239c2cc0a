"""Round-4 experiment: GRU-scan unroll factor vs per-iteration time at
Middlebury-F (scan-carry copies were ~1.5 ms/iter in the round-3 trace;
unrolling lets XLA fuse across iteration boundaries).
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
    h, w, iters = 1984, 2880, 32
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    small = jnp.zeros((1, 64, 96, 3))

    for unroll in [int(x) for x in os.environ.get("UNROLLS", "1,4,8").split(",")]:
        cfg = RAFTStereoConfig(
            corr_implementation="pallas",
            mixed_precision=True,
            corr_dtype="bfloat16",
            sequential_encoder=True,
            scan_unroll=unroll,
        )
        model = RAFTStereo(cfg)
        variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(jax.random.PRNGKey(0))

        @jax.jit
        def fwd(v, a, b):
            def body(c, _):
                _, up = model.apply(v, a + c * 1e-30, b, iters=iters, test_mode=True)
                return up.reshape(-1)[0], ()
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=2)
            return c

        t0 = time.perf_counter()
        try:
            jax.block_until_ready(fwd(variables, i1, i2))  # compile+run
        except Exception as e:
            print(f"unroll={unroll}: FAILED {type(e).__name__}: {str(e)[:120]}")
            continue
        compile_s = time.perf_counter() - t0
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(fwd(variables, i1, i2))
            trial = (time.perf_counter() - t0) / 2
            best = trial if best is None else min(best, trial)
        print(f"unroll={unroll}: {best*1e3:7.1f} ms/forward  (compile+first {compile_s:.0f}s)")


if __name__ == "__main__":
    main()
