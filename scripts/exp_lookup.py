"""Micro-benchmark of the fused Pallas corr lookup at Middlebury-F scale
(round-4: select-accumulate vs round-3's masked-add; history in ROADMAP).
Chains 32 lookups (one per GRU iteration) with coord feedback so the
device executes them serially — the per-iteration cost the forward pays.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import time

import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.ops.corr_pallas import pallas_corr_state, pallas_corr_lookup_padded


def main():
    rng = np.random.default_rng(0)
    h, w, c = 496, 720, 256
    f1 = jnp.asarray(rng.normal(size=(1, h, w, c)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(1, h, w, c)).astype(np.float32))
    state = pallas_corr_state(f1, f2, 4, corr_dtype=jnp.bfloat16)
    coords0 = jnp.tile(jnp.arange(w, dtype=jnp.float32)[None, None, :], (1, h, 1))

    iters = 32

    @jax.jit
    def chained(state, coords0):
        def body(c, _):
            taps = pallas_corr_lookup_padded(state, c, 4, jnp.bfloat16)
            # feedback: next coords depend on this lookup's output
            return c + taps.astype(jnp.float32)[..., 0] * 1e-30, ()
        c, _ = jax.lax.scan(body, coords0, None, length=iters)
        return c.reshape(-1)[0]

    jax.block_until_ready(chained(state, coords0))  # compile
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(state, coords0))
        trial = (time.perf_counter() - t0) / iters
        best = trial if best is None else min(best, trial)
    print(f"lookup: {best*1e3:.3f} ms/iteration (32-iter chain, bf16 state)")


if __name__ == "__main__":
    main()
