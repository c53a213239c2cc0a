"""`cli.run_training` -> `Trainer.fit` for the token family (`sdar-moe`): the
train driver's shape (benchmark/drivers/train.py, whose recorder and
comparison this file imports), with token batches, the family's weight draw
and its plain reference.

Set-up builds ONE trainer, replaces its weights by the seed's draw (the
trainer's own first state is deleted first: two 16-bytes-a-parameter states
do not fit the chip), drives the first steps and keeps what the comparison
needs. The window cycles the same host batches until the time is up. Beside
the train driver's numbers, `correct` holds the rows the held experts took
on the checked steps to the reference's count (`held_rows_gap`): a program
that drops rows at a capacity fails it.

The window also counts the calls its kernels served, as the program is
built: a layer's forward kernels run once more for each rebuild of the layer
(per-layer remat; the expert layer's chunks rebuild themselves once more).
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Dict

import numpy as np

from benchmark import sdar_reference, sdar_traffic, sdar_weights
from benchmark.drivers import common
from benchmark.drivers.train import ADAM_B1, CHECKED_STEPS, Run as TrainRun, _first_moment, _flat, _Recorder, numbers

COUNTERS = ("moe_held_rows", "moe_max_over_mean_load")


class _TokenRecorder(_Recorder):
    """The train driver's recorder, keeping each step's expert counters
    beside its loss (device arrays until `counters()` fetches them)."""

    def __init__(self, trainer, lag, snapshots):
        super().__init__(trainer, lag, snapshots)
        self._counters = []

    def push(self, metrics, step):
        self._counters.append({k: metrics[k] for k in COUNTERS})
        super().push(metrics, step)

    def counters(self) -> Dict[str, list]:
        import jax

        fetched = jax.device_get(self._counters)
        return {k: [float(step[k]) for step in fetched] for k in COUNTERS}


def model_config(config: Dict):
    from raft_stereo_tpu.config import SDARMoEConfig

    return SDARMoEConfig.from_hf_config(config, **config["program"])


def _norm(leaf: np.ndarray) -> float:
    """Summed in float64 without a float64 copy: the copy costs the check
    4 s a tree of 0.5G parameters, and a float32 sum errs by 7 parts in 1e4."""
    flat = leaf.ravel()
    return float(np.sqrt(np.einsum("i,i->", flat, flat, dtype=np.float64)))


def _delta(initial, after) -> Dict[str, float]:
    a, b = _flat(initial), _flat(after)
    return {k: _norm(b[k] - a[k]) for k in a}


class Run(TrainRun):
    # -- the program ------------------------------------------------------

    def _train_config(self):
        from raft_stereo_tpu.config import TrainConfig

        spec = self.spec
        return TrainConfig(
            model=model_config(self.config),
            name=spec["traffic"],
            seed=self.seed & 0x7FFFFFFF,
            batch_size=spec["batch"],
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

    def _batches(self):
        spec, program = self.spec, self.config["program"]
        # the mask token's row, the slice's last, is never data
        return sdar_traffic.token_batches(
            self.seed, spec["batches"], spec["batch"], spec["seq_len"], program["block_length"],
            program["mask_token_id"], spec["zipf_exponent"], spec["t_min"])

    def setup(self) -> None:
        import jax

        from raft_stereo_tpu.train.trainer import Trainer, TrainState

        spec = self.spec
        self.phases = phases = common.Phases()
        self.workdir = tempfile.mkdtemp(prefix="bench_train_tokens_")
        with phases("trainer"):
            self.trainer = trainer = Trainer(self._train_config(), sample_shape=(spec["seq_len"],))
        step = trainer.state.step
        stale, trainer.state = trainer.state, None
        jax.tree.map(lambda x: x.delete(), (stale.params, stale.opt_state))
        with phases("weights"):
            params = sdar_weights.draw(self.config, self.seed)["params"]
            self.initial = jax.tree.map(np.asarray, params)
        state = TrainState(step=step, params=params, batch_stats={}, opt_state=trainer.tx.init(params))
        trainer.state = trainer.sharding.place_state(state)
        with phases("batches"):
            self.batches = self._batches()
        first = _TokenRecorder(trainer, lag=False, snapshots={
            1: _first_moment, CHECKED_STEPS: lambda state: state.params})
        steps = max(spec["warm_steps"], CHECKED_STEPS)
        with phases("first_steps"):
            self._fit((self.batches[i % len(self.batches)] for i in range(steps)), first)
        self.first = first

    def window(self, seconds: float) -> dict:
        recorder = _TokenRecorder(self.trainer, lag=True, snapshots={})
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
        counters = recorder.counters()
        model = self.trainer.config.model
        layer_steps = steps * model.num_hidden_layers
        positions = self.spec["batch"] * 2 * self.spec["seq_len"]
        forwards = 1 + model.remat_layers
        expert_forwards = forwards + (positions > model.moe_chunk and positions % model.moe_chunk == 0)
        report = self.trainer.last_run_report
        return {
            "attempted": steps,
            "failed": sum(not np.isfinite(x) for x in recorder.losses),
            "seconds": elapsed,
            "work": samples,
            "attention_forward_calls": layer_steps * forwards,
            "attention_backward_calls": layer_steps,
            # passes of a layer's expert products under the kernel's name: its
            # forwards, and the backward's product with the transposed weights
            "grouped_matmul_calls": layer_steps * (expert_forwards + 1),
            "grouped_matmul_drhs_calls": layer_steps,
            "step_ms_p50": 1000.0 * float(np.median(gaps)),
            "compiles_in_window": report["jit_hygiene"]["compiles_post_grace"],
            "moe_held_rows_per_step": float(np.mean(counters["moe_held_rows"])),
            "moe_max_over_mean_load": float(np.mean(counters["moe_max_over_mean_load"])),
            "end_to_end": {"train_samples_per_s": samples / elapsed},
        }

    # -- the comparison ---------------------------------------------------

    def program_readings(self) -> dict:
        first = self.first
        # Adam's first moment after step 1 is (1 - b1) x the clipped gradient
        grad = {k: v / (1.0 - ADAM_B1) for k, v in _flat(first.kept[1]).items()}
        return {
            "losses": first.losses[:CHECKED_STEPS], "grad": {k: _norm(v) for k, v in grad.items()}, "grad_leaves": grad,
            "delta": _delta(self.initial, first.kept[CHECKED_STEPS]),
            "held_rows": first.counters()["moe_held_rows"][:CHECKED_STEPS],
        }

    def reference_readings(self, precision: str = "float32", fault: str = None) -> dict:
        import jax
        import jax.numpy as jnp

        spec = self.spec
        train = {k: spec[k] for k in ("lr", "num_steps", "wdecay")}
        train["grad_clip_norm"] = 1.0
        params = jax.tree.map(jnp.asarray, self.initial)
        batches = [jax.tree.map(jnp.asarray, b) for b in self.batches[:CHECKED_STEPS]]
        losses, grad, params, held = sdar_reference.train_steps(
            self.config, train, params, batches, precision, fault)
        grad = _flat(grad)
        return {
            "losses": [float(x) for x in jax.device_get(losses)],
            "grad": {k: _norm(v) for k, v in grad.items()}, "grad_leaves": grad,
            "delta": _delta(self.initial, jax.device_get(params)),
            "held_rows": [float(x) for x in jax.device_get(held)],
        }

    @staticmethod
    def _numbers(program: dict, ref: dict) -> Dict[str, float]:
        out = numbers(program, ref)
        out["held_rows_gap"] = max(abs(a - b) / b for a, b in zip(program["held_rows"], ref["held_rows"]))
        apart = sum(_norm(program["grad_leaves"][k] - g) ** 2 for k, g in ref["grad_leaves"].items())
        out["grad_gap"] = (apart / sum(v * v for v in ref["grad"].values())) ** 0.5
        return out

    def check(self) -> dict:
        program = self.program_readings()
        self.first = None  # the recorder holds the trainer, and the trainer 12 bytes a parameter of the chip
        self._free()
        got = self._numbers(program, self.reference_readings())
        return {k: common.compared(v, self.spec["limits"][k]) for k, v in got.items() if k in self.spec["limits"]}

    def control(self, fault: str = None) -> dict:
        """The reference in the control precision, or with a fault planted
        (`causal_mask`, `capacity`), in the program's place. Needs no set-up."""
        import jax

        self.initial = jax.tree.map(np.asarray, sdar_weights.draw(self.config, self.seed)["params"])
        self.batches = self._batches()
        if fault is None:
            stand_in = self.reference_readings(self.spec["control"])
        else:
            stand_in = self.reference_readings(fault=fault)
        return self._numbers(stand_in, self.reference_readings())
