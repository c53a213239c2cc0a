"""`cli.run_training` -> `Trainer.fit` over seeded host batches: no loader, no
augmentation, no checkpoint inside the window.

Set-up builds ONE trainer (the compiled step with its state, the weights
replaced by the seed's draw), drives it through its first steps with
`cli.run_training` on rows that all differ, and keeps what the comparison
needs of them: each step's loss, Adam's first moment after step 1 (the
clipped gradient as the optimizer got it), the parameters after step 2. The
window hands the same trainer to the same call, fed by a generator that
cycles the batches until the time is up.

Step completion is read one step late: after dispatching step n the feed's
recorder waits for step n-1's loss, so the device always has a step queued
and the host still sees every completion. A rate is all samples over the
whole window; the median step is a layer metric beside it.
"""

from __future__ import annotations

import os
import shutil
import statistics
import tempfile
import time
from typing import Dict, List

import numpy as np

from benchmark import reference, traffic, weights
from benchmark.drivers import common

# The reference follows the first two steps (not three): a float32 row of
# this cell takes it 8 s on the chip, and every run of every later check pays.
CHECKED_STEPS = 2
ADAM_B1 = 0.9
# A leaf whose reference gradient is under this share of the median leaf's
# moves under Adam by round-off alone: it is left out of the change.
NOUGHT_GRADIENT = 1e-3


class _Recorder:
    """The trainer's metrics hook. `lag`: wait for step n-1's loss after step
    n was dispatched (the window); otherwise wait for each step's own loss
    and keep `snapshots[n](state)` after step n (set-up)."""

    def __init__(self, trainer, lag: bool, snapshots: Dict[int, object]):
        self.trainer, self.lag, self.snapshots = trainer, lag, snapshots
        self.losses: List[float] = []
        self.done_at: List[float] = []
        self.kept: Dict[int, object] = {}
        self._pending = None

    def _land(self, loss) -> None:
        import jax

        self.losses.append(float(jax.device_get(loss)))
        self.done_at.append(time.perf_counter())

    def push(self, metrics, step: int) -> None:
        import jax

        if self.lag:
            pending, self._pending = self._pending, metrics["live_loss"]
            if pending is not None:
                self._land(pending)
            return
        self._land(metrics["live_loss"])
        take = self.snapshots.get(len(self.losses))
        if take is not None:
            self.kept[len(self.losses)] = jax.device_get(take(self.trainer.state))

    def flush(self) -> None:
        if self._pending is not None:
            pending, self._pending = self._pending, None
            self._land(pending)

    def write(self, values, step: int) -> None:
        pass


def _first_moment(state):
    import jax

    found = [s.mu for s in jax.tree.leaves(state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0]


def _flat(tree) -> Dict[str, np.ndarray]:
    return {path: np.asarray(leaf) for path, leaf in weights.flatten(tree)}


def _norms(tree) -> Dict[str, float]:
    return {path: float(np.linalg.norm(leaf.astype(np.float64))) for path, leaf in _flat(tree).items()}


def _worst_gap(got: Dict[str, float], want: Dict[str, float], leaves) -> float:
    """The widest gap between the program's norm and the reference's, over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    median = statistics.median(want[k] for k in leaves)
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in leaves)


def numbers(program: dict, ref: dict) -> Dict[str, float]:
    """`program` and `ref`: {"losses", "grad" (leaf norms), "delta" (leaf
    norms)} of the first CHECKED_STEPS steps."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(program["losses"], ref["losses"]))
    leaves = sorted(ref["grad"])
    median_grad = statistics.median(ref["grad"][k] for k in leaves)
    moved = [k for k in leaves if ref["grad"][k] >= NOUGHT_GRADIENT * median_grad]
    out = {
        "loss_gap": loss_gap,
        "grad_norm_gap": _worst_gap(program["grad"], ref["grad"], leaves),
        "update_norm_gap": _worst_gap(program["delta"], ref["delta"], moved),
    }
    if len(program["losses"]) != len(ref["losses"]) or not all(np.isfinite(list(out.values()))):
        return {k: float("nan") for k in out}
    return out


class Run:
    def __init__(self, spec, config, seed, devices, tracer):
        self.spec, self.config, self.seed = spec, config, seed
        self.devices, self.tracer = devices, tracer
        self.workdir = None

    # -- the program ------------------------------------------------------

    def _train_config(self):
        from raft_stereo_tpu.config import AugmentConfig, TrainConfig

        spec = self.spec
        return TrainConfig(
            model=common.model_config(self.config),
            augment=AugmentConfig(crop_size=tuple(spec["image_hw"])),
            name=spec["traffic"],
            seed=self.seed & 0x7FFFFFFF,
            batch_size=spec["batch"],
            train_iters=spec["iters"],
            num_steps=spec["num_steps"],
            lr=spec["lr"],
            wdecay=spec["wdecay"],
            mesh_shape=(len(self.devices), 1),
            sharding_rules="dp",
            checkpoint_every=spec["num_steps"],
            handle_signals=False,
            checkpoint_dir=os.path.join(self.workdir, "checkpoints"),
            log_dir=os.path.join(self.workdir, "logs"),
        )

    def _fit(self, feed, recorder) -> None:
        from raft_stereo_tpu import cli

        rc = cli.run_training(self.trainer, feed, metrics_logger=recorder)
        if rc != 0:
            raise RuntimeError(f"run_training exited {rc}: {self.trainer.last_run_report.get('error')}")

    def setup(self) -> None:
        import jax

        from raft_stereo_tpu.train.trainer import Trainer, TrainState

        spec = self.spec
        self.phases = phases = common.Phases()
        self.workdir = tempfile.mkdtemp(prefix="bench_train_")
        h, w = spec["image_hw"]
        with phases("trainer"):
            self.trainer = trainer = Trainer(self._train_config(), sample_shape=(h, w, 3))
        with phases("weights"):
            variables = weights.draw(self.config["model"], self.seed)
            self.initial = jax.tree.map(np.asarray, variables)
        state = TrainState(
            step=trainer.state.step,
            params=variables["params"],
            batch_stats=variables["batch_stats"],
            opt_state=trainer.tx.init(variables["params"]),
        )
        trainer.state = trainer.sharding.place_state(state)
        with phases("batches"):
            self.batches = traffic.stereo_batches(
                self.seed, spec["batches"], spec["batch"], spec["image_hw"], spec["max_disp"])
        first = _Recorder(trainer, lag=False, snapshots={
            1: _first_moment, CHECKED_STEPS: lambda state: state.params})
        steps = max(spec["warm_steps"], CHECKED_STEPS)
        with phases("first_steps"):
            self._fit((self.batches[i % len(self.batches)] for i in range(steps)), first)
        self.first = first

    def window(self, seconds: float) -> dict:
        recorder = _Recorder(self.trainer, lag=True, snapshots={})
        clock = {}

        def feed():
            i = 0
            clock["start"] = start = time.perf_counter()
            while time.perf_counter() - start < seconds:
                yield self.batches[i % len(self.batches)]
                i += 1
            recorder.flush()

        with self.tracer.span("fit"):
            self._fit(feed(), recorder)
        recorder.flush()
        steps = len(recorder.done_at)
        elapsed = recorder.done_at[-1] - clock["start"]
        samples = steps * self.spec["batch"]
        gaps = np.diff([clock["start"], *recorder.done_at])
        report = self.trainer.last_run_report
        return {
            "attempted": steps,
            "failed": sum(not np.isfinite(x) for x in recorder.losses),
            "seconds": elapsed,
            "work": samples,
            "kernel_calls": samples * self.spec["iters"],
            "step_ms_p50": 1000.0 * float(np.median(gaps)),
            "compiles_in_window": report["jit_hygiene"]["compiles_post_grace"],
            "end_to_end": {"train_samples_per_s": samples / elapsed},
        }

    # -- the comparison ---------------------------------------------------

    def program_readings(self) -> dict:
        """What the program's first steps gave, as leaf norms. Tests plant
        faults here."""
        first = self.first
        grad = {k: v / (1.0 - ADAM_B1) for k, v in _norms(first.kept[1]).items()}
        initial = _flat(self.initial["params"])
        after = _flat(first.kept[CHECKED_STEPS])
        delta = {k: float(np.linalg.norm((after[k] - initial[k]).astype(np.float64))) for k in initial}
        return {"losses": first.losses[:CHECKED_STEPS], "grad": grad, "delta": delta}

    def reference_readings(self, precision: str = "float32", batch_rows=None) -> dict:
        import jax
        import jax.numpy as jnp

        spec = self.spec
        train = {k: spec[k] for k in ("iters", "lr", "num_steps", "wdecay")}
        train.update(grad_clip_norm=1.0, loss_gamma=0.9, max_flow=700.0)
        variables = jax.tree.map(jnp.asarray, self.initial)
        batches = [jax.tree.map(jnp.asarray, b) for b in self.batches[:CHECKED_STEPS]]
        losses, grad, params = reference.train_steps(
            self.config["model"], train, variables, batches, precision, batch_rows)
        initial = _flat(self.initial["params"])
        after = _flat(jax.device_get(params))
        delta = {k: float(np.linalg.norm((after[k] - initial[k]).astype(np.float64))) for k in initial}
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "grad": _norms(jax.device_get(grad)), "delta": delta}

    def _free(self) -> None:
        self.trainer = None
        common.free_device()
        if self.workdir:
            shutil.rmtree(self.workdir, ignore_errors=True)

    def check(self) -> dict:
        program = self.program_readings()
        self._free()
        got = numbers(program, self.reference_readings())
        return {k: common.compared(v, self.spec["limits"][k]) for k, v in got.items() if k in self.spec["limits"]}

    def control(self, fault: str = None) -> dict:
        """The reference in the control precision (or with a fault planted)
        in the program's place. Needs no set-up."""
        import jax

        spec = self.spec
        self.initial = jax.tree.map(np.asarray, weights.draw(self.config["model"], self.seed))
        self.batches = traffic.stereo_batches(
            self.seed, spec["batches"], spec["batch"], spec["image_hw"], spec["max_disp"])
        if fault == "half_batch":
            stand_in = self.reference_readings(batch_rows=slice(0, spec["batch"] // 2))
        elif fault is None:
            stand_in = self.reference_readings(spec["control"])
        else:
            raise ValueError(f"unknown fault {fault!r}")
        return numbers(stand_in, self.reference_readings())
