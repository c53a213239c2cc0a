"""Shared timing helpers for the profiling scripts: host clock around work
that ends in `jax.block_until_ready` (jax returns before the device
finishes, so a timing without it measures the enqueue).

Work is chained N times inside one jit (each step perturbed by a scalar of
the previous one, so the device runs them serially): that keeps per-call
dispatch and the device-to-host copy of a full map out of the per-execution
number. bench.py keeps its own copy of `timed` (`_component_ms`: it runs
standalone).
"""

import time

import jax
import jax.numpy as jnp


def chain_model(model, iters: int, chain_n: int):
    """The model-forward serial chain shared by the A/B experiment scripts:
    `chain_n` test-mode forwards at `iters` refinement iterations inside one
    jit, each perturbing image1 with the previous step's carried scalar
    (defeats CSE across steps) and carrying one output element (defeats
    DCE). Returned UN-jitted so callers pick their compile path — plain
    `jax.jit`, or `.lower().compile(compiler_options=...)`."""

    def chained(variables, image1, image2):
        def body(carry, _):
            _, up = model.apply(
                variables, image1 + carry * 1e-30, image2,
                iters=iters, test_mode=True,
            )
            return up.reshape(-1)[0], ()

        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=chain_n)
        return c

    return chained


def time_compiled(fn, args, n: int, trials: int = 3) -> float:
    """Min-of-`trials` per-execution seconds for a compiled chain of `n`
    executions. Warms up (compiling if needed) immediately before the first
    trial so every caller enters timing from the same state — A/B drivers
    MUST go through this one helper or the comparison discipline drifts."""
    jax.block_until_ready(fn(*args))  # compile + warmup, immediately before the trials
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        trial = (time.perf_counter() - t0) / n
        best = trial if best is None else min(best, trial)
    return best


def timed(fn, *args, n=8, trials=2):
    """Per-execution seconds for fn chained n times inside one jit. The chain
    perturbs the first argument with a dummy scalar of the previous step
    (defeats CSE across steps) and reduces every output element into the
    carried scalar (defeats dead-code elimination of partially-consumed
    outputs)."""

    def chained(first, *rest):
        def body(c, _):
            out = fn(first + (c * 0).astype(first.dtype), *rest)
            tot = sum(
                jnp.sum(leaf.astype(jnp.float32)) for leaf in jax.tree.leaves(out)
            )
            return tot * 1e-30, ()

        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=n)
        return c

    return time_compiled(jax.jit(chained), args, n, trials=trials)
