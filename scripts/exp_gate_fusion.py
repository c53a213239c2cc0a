"""Round-5 experiment: Pallas-fused ConvGRU gating elementwise vs XLA's
epilogue fusions, at full Middlebury-F scale in full model context (the
round-4 verdict's one untried inference lever; ROADMAP round-5 #3).

A/B via RAFT_STEREO_TPU_PALLAS_GATES (read per trace): identical model,
identical params, only the gating lowering differs (ops/gates_pallas.py).
Also reports a correctness check (max |Δ| between the two forwards) and a
two-point iters decomposition so any delta localizes to per-iteration cost.
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

    def make_fwd(iters, n):
        @jax.jit
        def fwd(v, a, b):
            def body(c, _):
                _, up = model.apply(v, a + c * 1e-30, b, iters=iters, test_mode=True)
                return up.reshape(-1)[0], ()
            c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
            return c
        return fwd

    results = {}
    outs = {}
    for mode in ("xla", "pallas"):
        os.environ["RAFT_STEREO_TPU_PALLAS_GATES"] = "1" if mode == "pallas" else "0"
        hi, lo = make_fwd(32, 2), make_fwd(8, 2)
        single = jax.jit(
            lambda v, a, b: model.apply(v, a, b, iters=32, test_mode=True)[1]
        )
        outs[mode] = np.asarray(jax.device_get(single(variables, i1, i2)))
        t = {}
        for name, fn, n in (("hi", hi, 2), ("lo", lo, 2)):
            jax.block_until_ready(fn(variables, i1, i2))  # compile
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(variables, i1, i2))
                trial = (time.perf_counter() - t0) / n
                best = trial if best is None else min(best, trial)
            t[name] = best
        per_iter = (t["hi"] - t["lo"]) / 24 * 1e3
        overhead = t["hi"] * 1e3 - per_iter * 32
        results[mode] = (t["hi"] * 1e3, per_iter, overhead)
        print(
            f"{mode:6s}: fwd {t['hi']*1e3:7.1f} ms  per-iter {per_iter:6.2f} ms  "
            f"overhead {overhead:6.1f} ms"
        )
    d = float(np.nanmax(np.abs(outs["xla"] - outs["pallas"])))
    print(f"max |xla - pallas| on final flow: {d:.4f} px")
    dx = results["pallas"][0] - results["xla"][0]
    print(f"delta: {dx:+.1f} ms full fwd ({results['pallas'][1]-results['xla'][1]:+.3f} ms/iter)")


if __name__ == "__main__":
    main()
