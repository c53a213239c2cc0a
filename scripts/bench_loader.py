"""Input-pipeline throughput benchmark.

Answers the question the round-1 review left open: can the host-side loader
feed the device step rate? The device target is the MEASURED 2.35
steps/s/chip of the b4 training recipe (round-4 TPU calibration: ~0.426
s/step at batch 4; `--step_time` takes the current one, 0.406 s by the
ledger's `step_ms_p50.train`; the target
is >= 2x that so input never gates training, and the `input_bound` verdict
per config says in one bool whether it does). The reference sizes its
worker pool as SLURM_CPUS_PER_TASK-2 *processes* (reference
core/stereo_datasets.py:541-542); this framework uses threads + the native
GIL-free decode core, so the number must be measured, not assumed.

Builds synthetic on-disk trees at REAL frame geometry:
- SceneFlow-style: 540x960 RGB PNG pairs + PFM disparity, dense augmentor
  with 320x720 crops (the north-star training recipe).
- GatedStereo all-gated: 720x1280 8-bit PNGs, 10 per frame (5 slice types x
  2 eyes) + lidar npz, ambient-light augmentation (the heaviest item path,
  65,837-frame epoch in the reference's train_gatedstereo.txt).

Prints one JSON line per configuration: items/s, batches/s, MB/s, the ratio
to the device step rate at that batch size, and the `input_bound` verdict
(loader slower than the device step — the config would gate training).

Usage: python scripts/bench_loader.py [--batch_size 8] [--workers 2 6 10]
       [--step_time 0.4255] [--epochs 3]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from PIL import Image

from raft_stereo_tpu.config import AugmentConfig, CameraConfig
from raft_stereo_tpu.data.augment import StereoAugmentor
from raft_stereo_tpu.data.datasets import Gated, SceneFlowDatasets
from raft_stereo_tpu.data.frame_io import write_pfm
from raft_stereo_tpu.data.loader import DataLoader


def build_sceneflow_tree(root: str, n_frames: int, h: int = 540, w: int = 960):
    rng = np.random.default_rng(0)
    img_dir = os.path.join(root, "FlyingThings3D", "frames_cleanpass", "TRAIN", "A", "0000")
    disp_dir = os.path.join(root, "FlyingThings3D", "disparity", "TRAIN", "A", "0000")
    for side in ("left", "right"):
        os.makedirs(os.path.join(img_dir, side), exist_ok=True)
        os.makedirs(os.path.join(disp_dir, side), exist_ok=True)
    for i in range(n_frames):
        # Natural-image-ish content: smoothed noise compresses like real
        # frames (pure noise PNGs overstate decode cost ~2x).
        base = rng.integers(0, 256, (h // 8, w // 8, 3)).astype(np.uint8)
        img = np.asarray(Image.fromarray(base).resize((w, h), Image.BILINEAR))
        for side in ("left", "right"):
            Image.fromarray(img).save(os.path.join(img_dir, side, f"{i:04d}.png"))
            write_pfm(
                os.path.join(disp_dir, side, f"{i:04d}.pfm"),
                rng.uniform(1, 60, (h, w)).astype(np.float32),
            )


def build_gated_tree(root: str, n_frames: int, h: int = 720, w: int = 1280):
    from raft_stereo_tpu.data.datasets import GATED_SLICE_TYPES

    rng = np.random.default_rng(0)
    day = "2023-01-16_12-13-14"  # 'YYYY-MM-DD_HH-MM-SS'; hour 12 = day tables
    base = os.path.join(root, day, "framegrabber")
    for eye in ("left", "right"):
        for t in GATED_SLICE_TYPES:
            os.makedirs(os.path.join(base, eye, "bwv", t, "image_rect8"), exist_ok=True)
    lidar_dir = os.path.join(base, "left", "lidar_vls128_projected")
    os.makedirs(lidar_dir, exist_ok=True)
    small = rng.integers(0, 256, (h // 8, w // 8)).astype(np.uint8)
    img = np.asarray(Image.fromarray(small).resize((w, h), Image.BILINEAR))
    for i in range(n_frames):
        stem = f"{i:05d}"
        for eye in ("left", "right"):
            for t in GATED_SLICE_TYPES:
                Image.fromarray(img).save(
                    os.path.join(base, eye, "bwv", t, "image_rect8", stem + ".png")
                )
        depth = rng.uniform(3.5, 150.0, (h, w)).astype(np.float32)
        np.savez(os.path.join(lidar_dir, stem + ".npz"), depth)


def bench_loader(
    name: str,
    dataset,
    batch_size: int,
    workers: int,
    epochs: int,
    step_time: float,
    worker_type: str = "thread",
):
    loader = DataLoader(
        dataset, batch_size, seed=0, num_workers=workers, prefetch=2, worker_type=worker_type
    )
    n_batches = 0
    mbytes = 0.0
    # Warm one epoch (file cache, thread pool spin-up), then time.
    for batch in loader:
        pass
    t0 = time.perf_counter()
    for _ in range(epochs):
        for batch in loader:
            n_batches += 1
            mbytes += sum(
                v.nbytes for v in batch.values() if isinstance(v, np.ndarray)
            ) / 1e6
    dt = time.perf_counter() - t0
    batches_per_sec = n_batches / dt
    result = {
        "bench": f"loader/{name}",
        "batch_size": batch_size,
        "workers": workers,
        "worker_type": worker_type,
        "batches_per_sec": round(batches_per_sec, 3),
        "items_per_sec": round(batches_per_sec * batch_size, 2),
        "mb_per_sec": round(mbytes / dt, 1),
        "x_step_rate": round(batches_per_sec * step_time, 2),
        # The one-bool verdict: the loader delivers batches SLOWER than the
        # device consumes them, so this config would gate training (the
        # DevicePrefetcher can hide the placement hop, not a starved host).
        "input_bound": bool(batches_per_sec * step_time < 1.0),
    }
    print(json.dumps(result))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch_size", type=int, default=8)
    ap.add_argument("--workers", type=int, nargs="+", default=[2, 6, 10])
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--step_time", type=float, default=round(1 / 2.35, 4),
                    help="device train-step seconds to compare against "
                    "(default 1/2.35 ≈ 0.4255 s: the measured 2.35 "
                    "steps/s/chip of the b4 recipe, round-4 TPU "
                    "calibration — round 1's 0.62 s estimate is stale)")
    ap.add_argument("--frames", type=int, default=24)
    ap.add_argument("--worker_type", nargs="+", default=["thread"],
                    choices=["thread", "process"])
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="bench_loader_")
    try:
        build_sceneflow_tree(os.path.join(tmp, "sf"), args.frames)
        aug = StereoAugmentor(
            crop_size=(320, 720), min_scale=-0.2, max_scale=0.4, yjitter=True
        )
        sf = SceneFlowDatasets(aug, root=os.path.join(tmp, "sf"), dstype="frames_cleanpass")
        assert len(sf) >= args.batch_size, f"sceneflow tree too small: {len(sf)}"

        build_gated_tree(os.path.join(tmp, "gated"), args.frames)
        gated = Gated(os.path.join(tmp, "gated"), use_all_gated=True, camera=CameraConfig())
        assert len(gated) >= args.batch_size, f"gated tree too small: {len(gated)}"

        for wtype in args.worker_type:
            for workers in args.workers:
                bench_loader("sceneflow", sf, args.batch_size, workers,
                             args.epochs, args.step_time, worker_type=wtype)
            for workers in args.workers:
                bench_loader("gated", gated, args.batch_size, workers,
                             args.epochs, args.step_time, worker_type=wtype)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
