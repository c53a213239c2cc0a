"""A/B XLA:TPU compiler options on the recipe train step and the full-res
inference forward (round 5).

Why this exists: every MODEL-level perf lever has a measured verdict
(ROADMAP), but the COMPILER-level knob space was untouched.
`jax.stages.Lowered.compile(compiler_options=...)` sets options per
executable — no process-wide `XLA_FLAGS`, and a bogus name fails that one
compile — so each value can be A/B'd inside one run.

Usage:
  python scripts/exp_compiler_options.py --mode train \
      --option xla_tpu_scoped_vmem_limit_kib --values 32768 65536 98304
  python scripts/exp_compiler_options.py --mode fwd --iters 8 \
      --option xla_tpu_scoped_vmem_limit_kib --values 65536
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _timing import chain_model, time_compiled


def bench_train(compiler_options, steps: int = 8, trials: int = 2) -> float:
    from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raft_stereo_tpu.parallel.mesh import shard_batch
    from raft_stereo_tpu.train.trainer import Trainer

    h, w, bs = 320, 720, 4
    cfg = TrainConfig(
        model=RAFTStereoConfig(
            mixed_precision=True, corr_dtype="bfloat16", corr_implementation="pallas"
        ),
        batch_size=bs,
        num_steps=10**9,
        train_iters=22,
        mesh_shape=(1, 1),
        checkpoint_every=10**9,
    )
    trainer = Trainer(cfg, sample_shape=(h, w, 3))
    rng = np.random.default_rng(0)
    batch = shard_batch(trainer.mesh, {
        "image1": rng.uniform(0, 255, (bs, h, w, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (bs, h, w, 3)).astype(np.float32),
        "flow": rng.uniform(-60, 0, (bs, h, w, 1)).astype(np.float32),
        "valid": np.ones((bs, h, w), np.float32),
    })
    step = trainer.train_step.lower(trainer.state, batch).compile(
        compiler_options=compiler_options or None
    )
    state = trainer.state
    state, metrics = step(state, batch)
    jax.block_until_ready(metrics["live_loss"])
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics["live_loss"])
        trial = (time.perf_counter() - t0) / steps
        best = trial if best is None else min(best, trial)
    return best


def bench_fwd(compiler_options, iters: int, chain_n: int = 3,
              trials: int = 2) -> float:
    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo

    cfg = RAFTStereoConfig(
        corr_implementation="pallas",
        mixed_precision=True,
        corr_dtype="bfloat16",
        sequential_encoder=True,
    )
    model = RAFTStereo(cfg)
    h, w = 1984, 2880
    rng = np.random.default_rng(0)
    i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
    small = jnp.zeros((1, 64, 96, 3))
    variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(jax.random.PRNGKey(0))

    fn = (
        jax.jit(chain_model(model, iters, chain_n))
        .lower(variables, i1, i2)
        .compile(compiler_options=compiler_options or None)
    )
    return time_compiled(fn, (variables, i1, i2), chain_n, trials=trials)


def parse_config_specs(specs, error):
    """Validate repeatable `--config name=value[,name=value...]` specs into
    (label, options-dict) runs, calling `error(message)` (argparse's
    ap.error in production: prints usage + exits 2) on the FIRST malformed
    pair — naming the offending spec AND pair, never the opaque
    'dictionary update sequence' ValueError the old dict(...) raised.
    Checks: missing '=', empty option name, empty value, empty spec."""
    runs = []
    for spec in specs:
        if not spec.strip():
            error("--config spec is empty (expected comma-separated name=value pairs)")
        opts = {}
        for pair in spec.split(","):
            if "=" not in pair:
                error(
                    f"--config spec {spec!r}: pair {pair!r} is missing '=' "
                    "(expected comma-separated name=value pairs, e.g. "
                    "--config xla_tpu_scoped_vmem_limit_kib=65536)"
                )
            name, value = pair.split("=", 1)
            name, value = name.strip(), value.strip()
            if not name:
                error(f"--config spec {spec!r}: pair {pair!r} has an empty option name")
            if not value:
                error(
                    f"--config spec {spec!r}: option {name!r} has an empty value "
                    "(the compiler rejects it with an opaque error)"
                )
            opts[name] = value
        runs.append((spec, opts))
    return runs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["train", "fwd"], default="train")
    ap.add_argument("--option", default="xla_tpu_scoped_vmem_limit_kib")
    ap.add_argument("--values", nargs="*", default=[])
    ap.add_argument(
        "--config",
        action="append",
        default=[],
        help="one config as comma-separated name=value pairs; repeatable "
        "(alternative to --option/--values)",
    )
    ap.add_argument("--iters", type=int, default=8, help="GRU iters (fwd mode)")
    ap.add_argument("--skip_baseline", action="store_true")
    args = ap.parse_args()

    # Validate every --config spec BEFORE the first compile — a malformed
    # spec should fail in milliseconds with a usage error (and never with
    # the opaque 'dictionary update sequence' ValueError the old dict(...)
    # comprehension raised).
    runs = [] if args.skip_baseline else [("baseline", {})]
    runs += [(f"{args.option}={v}", {args.option: v}) for v in args.values]
    runs += parse_config_specs(args.config, ap.error)

    for label, opts in runs:
        try:
            if args.mode == "train":
                dt = bench_train(opts)
                print(f"{label}: {dt*1e3:.1f} ms/step", flush=True)
            else:
                dt = bench_fwd(opts, args.iters)
                print(f"{label}: {dt*1e3:.1f} ms/forward ({args.iters} iters)", flush=True)
        except Exception as e:
            print(f"{label}: FAILED {type(e).__name__}: {str(e)[:160]}", flush=True)


if __name__ == "__main__":
    main()
