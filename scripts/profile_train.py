"""Training-step wall-clock on the current accelerator, reference recipe
(320x720 crops, 22 GRU iterations, bf16, batch 4 per chip —
/root/reference/README.md:109-113 trains batch 8 over 2 GPUs).

N steps are dispatched back-to-back (the donated state chains them) and
one fetch of the last loss waits for the whole chain.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def main():
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
    batch = {
        "image1": rng.uniform(0, 255, (bs, h, w, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (bs, h, w, 3)).astype(np.float32),
        "flow": rng.uniform(-60, 0, (bs, h, w, 1)).astype(np.float32),
        "valid": np.ones((bs, h, w), np.float32),
    }
    db = shard_batch(trainer.mesh, batch)
    state = trainer.state
    state, metrics = trainer.train_step(state, db)
    # Explicit fetch (GL005-clean): device_get blocks until the device
    # drains, so it is the same completion barrier the old float() sync was.
    float(jax.device_get(metrics["live_loss"]))  # compile + sync
    print("compiled", flush=True)

    n = 10
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = trainer.train_step(state, db)
    # one explicit fetch forces completion of the whole chain
    loss = float(jax.device_get(metrics["live_loss"]))
    dt = (time.perf_counter() - t0) / n
    print(
        f"train step: {dt*1e3:.0f} ms/step (batch {bs}, {h}x{w}, "
        f"{cfg.train_iters} iters) loss={loss:.3f}"
    )


if __name__ == "__main__":
    main()
