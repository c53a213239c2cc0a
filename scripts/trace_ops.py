"""Capture a device trace of the full-res forward and rank HLO ops by self
time — localizes the per-iteration small-op tail (round-1 trace: ~370 ops,
~13 ms of each ~31.5 ms iteration) without hand-reading the trace viewer.

Usage: python scripts/trace_ops.py [--iters 8] [--top 40] [--train]
"""

import argparse
import glob
import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def capture(fn, args, logdir):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    with jax.profiler.trace(logdir):
        out = fn(*args)
        jax.block_until_ready(out)


def rank_ops(logdir, top):
    """Rank device ops by total time from the trace-viewer JSON.

    Parses vm.trace.json.gz directly (the tensorboard_plugin_profile native
    converter is broken in this image: its _pywrap_profiler lacks
    xspace_to_tools_data). The device plane's "XLA Ops" line is a flat,
    non-overlapping sequence of op executions, so summing durations per op
    name IS self time."""
    import gzip
    import json
    import collections

    traces = sorted(
        glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True)
    )
    assert traces, f"no trace.json.gz under {logdir}"
    if len(traces) > 1:
        print(f"aggregating {len(traces)} trace files under {logdir}")
    ev = []
    for path in traces:
        with gzip.open(path) as f:
            ev.extend(json.load(f)["traceEvents"])
    device_pids = {
        e["pid"]
        for e in ev
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and "TPU" in e["args"].get("name", "")
    }
    op_tids = {
        (e["pid"], e["tid"])
        for e in ev
        if e.get("ph") == "M"
        and e.get("name") == "thread_name"
        and e["pid"] in device_pids
        and e["args"].get("name") == "XLA Ops"
    }
    per_op = collections.defaultdict(float)
    counts = collections.Counter()
    for e in ev:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in op_tids:
            per_op[e["name"]] += e.get("dur", 0)
            counts[e["name"]] += 1
    rows = sorted(per_op.items(), key=lambda kv: -kv[1])
    total = sum(per_op.values())
    print(f"total device op time: {total/1e3:.2f} ms over {len(rows)} distinct ops")

    def category(name):
        head = name.split(".")[0].rstrip("0123456789-")
        return head

    by_cat = collections.defaultdict(float)
    for name, t in rows:
        by_cat[category(name)] += t
    print("\n-- by category (leading HLO name token) --")
    for c, t in sorted(by_cat.items(), key=lambda kv: -kv[1])[:20]:
        print(f"{t/1e3:9.2f} ms  {c}")
    print(f"\n-- top {top} ops --")
    for name, t in rows[:top]:
        print(f"{t/1e3:9.3f} ms  x{counts[name]:<4d} {name[:100]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--train", action="store_true",
                    help="trace a training step at the reference recipe instead")
    ap.add_argument("--logdir", default="/tmp/trace_ops")
    ap.add_argument("--no_s2d", action="store_true",
                    help="disable the encoder_s2d fast path (A/B tracing)")
    args = ap.parse_args()

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.models import RAFTStereo

    rng = np.random.default_rng(0)
    if args.train:
        from raft_stereo_tpu.config import TrainConfig
        from raft_stereo_tpu.train.trainer import Trainer
        from raft_stereo_tpu.parallel.mesh import shard_batch

        cfg = TrainConfig(
            model=RAFTStereoConfig(
                corr_implementation="pallas" if jax.default_backend() == "tpu" else "reg",
                mixed_precision=True,
                corr_dtype="bfloat16",
            ),
            batch_size=4,
            train_iters=22,
            mesh_shape=(1, 1),
            num_steps=10,
        )
        trainer = Trainer(cfg, sample_shape=(320, 720, 3))
        batch = shard_batch(trainer.mesh, {
            "image1": rng.uniform(0, 255, (4, 320, 720, 3)).astype(np.float32),
            "image2": rng.uniform(0, 255, (4, 320, 720, 3)).astype(np.float32),
            "flow": rng.uniform(-40, 0, (4, 320, 720, 1)).astype(np.float32),
            "valid": np.ones((4, 320, 720), np.float32),
        })

        # train_step donates the state; thread it through a holder so the
        # warmup call's donated buffers are never reused.
        holder = {"state": trainer.state}

        def run(b):
            s, m = trainer.train_step(holder["state"], b)
            holder["state"] = s
            return m

        capture(run, (batch,), args.logdir)
    else:
        cfg = RAFTStereoConfig(
            corr_implementation="pallas" if jax.default_backend() == "tpu" else "reg",
            mixed_precision=True,
            corr_dtype="bfloat16",
            sequential_encoder=True,
            encoder_s2d=not args.no_s2d,
        )
        model = RAFTStereo(cfg)
        h, w = 1984, 2880
        small = jnp.zeros((1, 64, 96, 3))
        variables = jax.jit(lambda r: model.init(r, small, small, iters=1))(
            jax.random.PRNGKey(0)
        )
        i1 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
        i2 = jnp.asarray(rng.uniform(0, 255, (1, h, w, 3)).astype(np.float32))
        fwd = jax.jit(
            lambda v, a, b: model.apply(v, a, b, iters=args.iters, test_mode=True)[1]
        )
        capture(fwd, (variables, i1, i2), args.logdir)

    rank_ops(args.logdir, args.top)


if __name__ == "__main__":
    main()
