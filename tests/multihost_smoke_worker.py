"""Worker process for the 2-process multi-host CPU smoke
(tests/test_distributed.py). Each worker owns 4 virtual CPU devices; the two
workers connect through `init_multihost` (jax.distributed + gloo CPU
collectives) and jit ONE real sharded training step over the resulting
8-device global (4 data x 2 spatial) mesh — the first in-sandbox execution
of the `parallel/distributed.py` path (round-4 review item 4; previously
only single-process mesh tests and the driver dryrun existed).

Usage: multihost_smoke_worker.py <coordinator_host:port> <process_id>
Prints "RESULT <process_id> <loss>" on success; the driver asserts both
processes print the same finite loss (the metrics are replicated, so any
cross-process divergence is a sharding bug).
"""

import os
import sys

# Platform must be pinned before any jax device query (same as
# tests/conftest.py / __graft_entry__.dryrun_multichip).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    coordinator, process_id = sys.argv[1], int(sys.argv[2])

    from raft_stereo_tpu.parallel.distributed import host_shard_args, init_multihost

    info = init_multihost(
        coordinator_address=coordinator, num_processes=2, process_id=process_id
    )
    assert info["process_count"] == 2, info
    assert info["process_index"] == process_id, info
    assert info["local_devices"] == 4, info
    assert info["global_devices"] == 8, info
    # Per-host input sharding kwargs follow the process topology.
    assert host_shard_args() == {"host_id": process_id, "num_hosts": 2}

    from raft_stereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raft_stereo_tpu.parallel.mesh import shard_batch
    from raft_stereo_tpu.train.trainer import Trainer

    cfg = TrainConfig(
        # Reduced-width model: what this smoke proves is the 8-device 4x2
        # mesh, the per-host input sharding, and the cross-process gloo
        # collectives (gradient psum + spatial halo exchange) — none of
        # which depend on channel width, while XLA-on-one-CPU compile time
        # very much does (the tier-1 budget runs on a 1-core sandbox).
        model=RAFTStereoConfig(
            hidden_dims=(32, 32, 32), n_gru_layers=2, corr_levels=2, corr_radius=2
        ),
        batch_size=4,  # one sample per data-mesh row, global batch
        num_steps=1,
        train_iters=2,
        mesh_shape=(4, 2),
        checkpoint_every=10**9,
    )
    h, w = 64, 96
    trainer = Trainer(cfg, sample_shape=(h, w, 3))

    # One seeded GLOBAL batch; each process hands shard_batch only ITS half
    # of the data-axis rows (the per-host input sharding contract:
    # multi-host shard_batch assembles the global array from process-local
    # shards, so hosts feed different rows by design). The global batch —
    # and therefore the replicated loss — is identical to the single-host
    # equivalent.
    rng = np.random.default_rng(0)
    batch = {
        "image1": rng.uniform(0, 255, (4, h, w, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (4, h, w, 3)).astype(np.float32),
        "flow": rng.uniform(-8, 0, (4, h, w, 1)).astype(np.float32),
        "valid": np.ones((4, h, w), np.float32),
    }
    local = {k: v[2 * process_id : 2 * (process_id + 1)] for k, v in batch.items()}
    device_batch = shard_batch(trainer.mesh, local)
    state, metrics = trainer.train_step(trainer.state, device_batch)
    jax.block_until_ready(state.params)
    loss = float(metrics["live_loss"])
    assert np.isfinite(loss)
    assert int(state.step) == 1
    print(f"RESULT {process_id} {loss:.6f}", flush=True)


if __name__ == "__main__":
    main()
