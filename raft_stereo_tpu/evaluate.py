"""Validation harness: per-dataset validators with the reference's exact
metric definitions (/root/reference/evaluate_stereo.py:19-189).

All four validators share one skeleton (pad÷32 → jitted test_mode forward →
unpad → EPE), differing in the bad-pixel threshold and valid-pixel rule:

- ETH3D: bad > 1px, valid = valid_gt >= 0.5 (:42-44)
- KITTI: bad > 3px, valid = valid_gt >= 0.5, plus FPS timing skipping the
  first 50 images (:77-81, 91-93); per-pixel D1 aggregation (:98)
- FlyingThings (TEST subset): bad > 1px, valid also requires |gt| < 192 (:133-135)
- Middlebury F/H/Q: bad > 2px, valid = valid_gt >= -0.5 & gt > -1000 (:173-175)

TPU notes: the forward is jitted per padded image shape (shape buckets — eval
sets have few distinct sizes, so compiles amortize); timing uses
block_until_ready so the KITTI FPS number measures device latency, not
dispatch.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_stereo_tpu.config import RAFTStereoConfig
from raft_stereo_tpu.models import RAFTStereo
from raft_stereo_tpu.obs import scopes
from raft_stereo_tpu.obs.trace import span
from raft_stereo_tpu.utils.padding import InputPadder

logger = logging.getLogger(__name__)


class Evaluator:
    """Jitted test-mode forward. jax.jit's cache gives one compile per padded
    shape; `pad_bucket` > 0 additionally rounds padded sizes up to a multiple
    of that bucket so mixed-size sets (ETH3D, KITTI) share a handful of
    compiles instead of recompiling per image. bucket padding is replicate-
    edge and cropped after the forward, so only border-context numerics can
    shift; pad_bucket=0 (default) reproduces the reference's exact minimal
    ÷32 padding.

    The feature encoder always runs one image at a time
    (`sequential_encoder`: same math, same params). Evaluation is one pair
    at full resolution, where memory and not batching is the constraint:
    with the two images as one batch the Middlebury-F forward needs 17.97 GB
    of a v5e chip's 15.75 (refused by the chip's compiler), one at a time
    it fits with room to spare."""

    def __init__(
        self,
        config: RAFTStereoConfig,
        variables,
        iters: int = 32,
        pad_bucket: int = 0,
    ):
        self.config = config = dataclasses.replace(config, sequential_encoder=True)
        self.model = RAFTStereo(config)
        self.variables = variables
        self.iters = iters
        self.pad_bucket = pad_bucket
        # Optional liveness callback, invoked after every completed forward:
        # the trainer wires the step watchdog here so an in-training
        # validation pass reports per-image progress — a hung forward then
        # fires the watchdog (stack traces + exit 16) while an arbitrarily
        # long eval set never does (train/trainer.py fit).
        self.heartbeat = None

        # The jitted forward closes over the model and the iteration count,
        # not over `self`: obs.scopes keeps it (to print its compiled module
        # on demand), and must not keep this object's variables alive.
        model, n_iters = self.model, iters

        @jax.jit
        def fwd(variables, image1, image2):
            _, up = model.apply(variables, image1, image2, iters=n_iters, test_mode=True)
            return up

        self._fwd = fwd
        self._registered_shapes = set()

    def _register_program(self, image1, image2) -> None:
        """Tell obs.scopes how to print the optimized module of this padded
        shape: the jitted forward and abstract arguments (shapes, dtypes,
        the shardings of committed leaves — no buffer), so that the lowering
        is the one that ran and its compile a cache hit. Nothing is lowered
        until a reader asks."""
        self._registered_shapes.add(image1.shape)
        fwd, args = self._fwd, scopes.abstract((self.variables, image1, image2))
        scopes.register(
            f"evaluate/forward/{image1.shape[1]}x{image1.shape[2]}",
            lambda: fwd.lower(*args).compile().as_text(),
        )

    def __call__(self, image1: np.ndarray, image2: np.ndarray) -> Tuple[np.ndarray, float]:
        """image1/2: (H, W, C) float arrays in [0, 255]. Returns
        ((H, W) disparity-flow, forward seconds).

        Spans (obs/trace.py): `evaluate/call` around the whole call, with
        `evaluate/stage` (conversion plus the dispatch of pad and
        host-to-device), `evaluate/forward` (dispatch to block_until_ready;
        its seconds are the second value returned) and `evaluate/fetch`
        (unpad dispatch and the device-to-host copy of the map)."""
        with span("evaluate/call"):
            with span("evaluate/stage"):
                i1 = jnp.asarray(image1, jnp.float32)[None]
                i2 = jnp.asarray(image2, jnp.float32)[None]
                padder = InputPadder(i1.shape, divis_by=32, bucket=self.pad_bucket)
                i1, i2 = padder.pad(i1, i2)
            if i1.shape not in self._registered_shapes:
                self._register_program(i1, i2)
            with span("evaluate/forward") as forward:
                up = self._fwd(self.variables, i1, i2)
                up = jax.block_until_ready(up)
            if self.heartbeat is not None:
                self.heartbeat()
            # Explicit fetch (not np.asarray): the unpad slice is host math on
            # the full map anyway, and device_get is legal under the trainer's
            # strict-mode transfer guard (utils/jit_hygiene.py) — validation
            # runs inside a whitelisted window, but stays guard-clean on its own.
            with span("evaluate/fetch"):
                out = jax.device_get(padder.unpad(up))[0, :, :, 0]
        return out, forward.seconds


def _epe_1d(flow_pred: np.ndarray, flow_gt: np.ndarray) -> np.ndarray:
    """Endpoint error; the reference's 2D norm reduces to |Δx| because both
    y components are identically zero."""
    return np.abs(flow_pred - flow_gt)


def validate_eth3d(evaluator: Evaluator, dataset=None, root="datasets/ETH3D") -> Dict[str, float]:
    from raft_stereo_tpu.data.datasets import ETH3D

    dataset = dataset if dataset is not None else ETH3D(None, root=root)
    epe_list, out_list = [], []
    for i in range(len(dataset)):
        item = dataset.get_item(i, np.random.default_rng(0))
        flow, _ = evaluator(item["image1"], item["image2"])
        epe = _epe_1d(flow, item["flow"][..., 0]).ravel()
        val = item["valid"].ravel() >= 0.5
        epe_list.append(epe[val].mean())
        out_list.append((epe[val] > 1.0).mean())
        logger.info("ETH3D %d/%d EPE %.4f D1 %.4f", i + 1, len(dataset), epe_list[-1], out_list[-1])
    result = {"eth3d-epe": float(np.mean(epe_list)), "eth3d-d1": 100 * float(np.mean(out_list))}
    print("Validation ETH3D: EPE %f, D1 %f" % (result["eth3d-epe"], result["eth3d-d1"]))
    return result


def validate_kitti(evaluator: Evaluator, dataset=None, root="datasets/KITTI") -> Dict[str, float]:
    from raft_stereo_tpu.data.datasets import KITTI

    dataset = dataset if dataset is not None else KITTI(None, root=root, image_set="training")
    epe_list, out_list, elapsed = [], [], []
    for i in range(len(dataset)):
        item = dataset.get_item(i, np.random.default_rng(0))
        flow, dt = evaluator(item["image1"], item["image2"])
        if i > 50:
            elapsed.append(dt)
        epe = _epe_1d(flow, item["flow"][..., 0]).ravel()
        val = item["valid"].ravel() >= 0.5
        epe_list.append(epe[val].mean())
        out_list.append(epe[val] > 3.0)
    result = {
        "kitti-epe": float(np.mean(epe_list)),
        "kitti-d1": 100 * float(np.concatenate(out_list).mean()),
    }
    if elapsed:
        result["kitti-fps"] = 1.0 / float(np.mean(elapsed))
        print(
            f"Validation KITTI: EPE {result['kitti-epe']}, D1 {result['kitti-d1']}, "
            f"{result['kitti-fps']:.2f}-FPS"
        )
    else:
        print(f"Validation KITTI: EPE {result['kitti-epe']}, D1 {result['kitti-d1']}")
    return result


def validate_things(evaluator: Evaluator, dataset=None, root="datasets") -> Dict[str, float]:
    from raft_stereo_tpu.data.datasets import SceneFlowDatasets

    dataset = (
        dataset
        if dataset is not None
        else SceneFlowDatasets(None, root=root, dstype="frames_finalpass", things_test=True)
    )
    epe_list, out_list = [], []
    for i in range(len(dataset)):
        item = dataset.get_item(i, np.random.default_rng(0))
        flow, _ = evaluator(item["image1"], item["image2"])
        gt = item["flow"][..., 0]
        epe = _epe_1d(flow, gt).ravel()
        val = (item["valid"].ravel() >= 0.5) & (np.abs(gt).ravel() < 192)
        epe_list.append(epe[val].mean())
        out_list.append(epe[val] > 1.0)
    result = {
        "things-epe": float(np.mean(epe_list)),
        "things-d1": 100 * float(np.concatenate(out_list).mean()),
    }
    print("Validation FlyingThings: %f, %f" % (result["things-epe"], result["things-d1"]))
    return result


def validate_middlebury(
    evaluator: Evaluator, dataset=None, split="F", root="datasets/Middlebury"
) -> Dict[str, float]:
    from raft_stereo_tpu.data.datasets import Middlebury

    dataset = dataset if dataset is not None else Middlebury(None, root=root, split=split)
    epe_list, out_list = [], []
    for i in range(len(dataset)):
        item = dataset.get_item(i, np.random.default_rng(0))
        flow, _ = evaluator(item["image1"], item["image2"])
        gt = item["flow"][..., 0]
        epe = _epe_1d(flow, gt).ravel()
        val = (item["valid"].ravel() >= -0.5) & (gt.ravel() > -1000)
        epe_list.append(epe[val].mean())
        out_list.append((epe[val] > 2.0).mean())
        logger.info(
            "Middlebury %d/%d EPE %.4f D1 %.4f", i + 1, len(dataset), epe_list[-1], out_list[-1]
        )
    result = {
        f"middlebury{split}-epe": float(np.mean(epe_list)),
        f"middlebury{split}-d1": 100 * float(np.mean(out_list)),
    }
    print(f"Validation Middlebury{split}: EPE %f, D1 %f" % tuple(result.values()))
    return result


VALIDATORS = {
    "eth3d": validate_eth3d,
    "kitti": validate_kitti,
    "things": validate_things,
    "middlebury_F": lambda ev, **kw: validate_middlebury(ev, split="F", **kw),
    "middlebury_H": lambda ev, **kw: validate_middlebury(ev, split="H", **kw),
    "middlebury_Q": lambda ev, **kw: validate_middlebury(ev, split="Q", **kw),
}


class SyntheticEvalDataset:
    """Drop-in dataset stub for `--dry_run` evaluation (README runbook):
    exercises the ENTIRE evaluate path — validator loop, padding, jitted
    forward, metric math, logging — without any downloaded data. Shapes are
    small (the dry run proves the path executes, not the accuracy); items
    follow the validators' item contract (image1/image2 uint8-range float,
    flow (H, W, 1) negative disparity, valid mask)."""

    # Default shape is deliberately NOT a multiple of 32 so the dry run
    # exercises real ÷32 padding and unpad cropping, not a zero pad.
    def __init__(self, n: int = 2, shape: Tuple[int, int] = (90, 158), channels: int = 3):
        self.n = n
        self.shape = shape
        self.channels = channels

    def __len__(self) -> int:
        return self.n

    def get_item(self, index: int, rng) -> Dict[str, np.ndarray]:
        h, w = self.shape
        r = np.random.default_rng(index)
        base = r.uniform(0, 255, (h, w + 4, self.channels)).astype(np.float32)
        return {
            "image1": base[:, 4:],
            "image2": base[:, :-4],
            "flow": np.full((h, w, 1), -4.0, np.float32),
            "valid": np.ones((h, w), np.float32),
        }


def make_validation_fn(
    model_config: RAFTStereoConfig,
    datasets,
    iters: int = 32,
    validator_kwargs: Dict[str, dict] | None = None,
    pad_bucket: int = 0,
):
    """Build the trainer's in-training validation hook: state -> metrics for
    each named validator (the role of the reference's commented-out
    `validate_things` call + `Logger.write_dict`, train_stereo.py:208-210,
    :120-127). One Evaluator is reused so the jitted forward compiles once
    per shape bucket across all validation rounds; `pad_bucket` > 0 is
    recommended for mixed-size sets so the first round doesn't stall
    training with per-image compiles."""
    evaluator = Evaluator(model_config, None, iters=iters, pad_bucket=pad_bucket)
    validator_kwargs = validator_kwargs or {}

    def validate(state) -> Dict[str, float]:
        evaluator.variables = {
            "params": state.params,
            "batch_stats": state.batch_stats,
        }
        results: Dict[str, float] = {}
        for name in datasets:
            results.update(VALIDATORS[name](evaluator, **validator_kwargs.get(name, {})))
        return results

    def set_heartbeat(fn) -> None:
        """Wire a per-image liveness callback (the trainer installs the
        step watchdog's beat here, so validation hangs are caught at image
        granularity instead of only at the whole-pass timeout)."""
        evaluator.heartbeat = fn

    validate.set_heartbeat = set_heartbeat
    return validate
