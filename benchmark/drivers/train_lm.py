"""`cli.run_training` -> `Trainer.fit` for the hybrid family
(`granite-hybrid`) on the plain causal loss: the train driver's shape
(benchmark/drivers/train.py, whose recorder and comparison this file imports,
as benchmark/drivers/train_tokens.py does), with batches of ids alone, the
family's weight draw and its plain reference.

Set-up builds ONE trainer, replaces its weights by the seed's draw (the
trainer's own first state is deleted first: two 12-bytes-a-parameter states
do not fit the chip), drives the first steps and keeps what the comparison
needs, every snapshot on the host: a second device copy of a 3.1 GB tree does
not fit beside the step. The window cycles the same host batches until the
time is up. Beside the train driver's numbers, `correct` holds every leaf of
the first gradient to the reference's (`grad_gap`), and the root mean square
of the last state-space layer's state after a row's last position, which the
step reports as `ssm_final_state_rms`, to the reference's (`final_state_gap`):
a scan that drops the state between chunks fails it whatever the loss does.

The window also counts the calls its kernels served, as the program is
built: a layer's forward kernels run once more for each rebuild of the layer
(per-layer remat).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Dict

import numpy as np

from benchmark import granite_reference, granite_weights, sdar_traffic
from benchmark.drivers import common
from benchmark.drivers.train import ADAM_B1, CHECKED_STEPS, Run as TrainRun, _first_moment, _flat, _Recorder, numbers
from benchmark.drivers.train_tokens import _delta, _norm

COUNTER = "ssm_final_state_rms"
FAULTS = ("chunk_reset", "bidirectional_attention")


class _StateRecorder(_Recorder):
    """The train driver's recorder, keeping each step's final-state counter
    beside its loss (device scalars until `counters()` fetches them)."""

    def __init__(self, trainer, lag, snapshots):
        super().__init__(trainer, lag, snapshots)
        self._counters = []

    def push(self, metrics, step):
        self._counters.append(metrics[COUNTER])
        super().push(metrics, step)

    def counters(self) -> list:
        import jax

        return [float(x) for x in jax.device_get(self._counters)]


def model_config(config: Dict):
    from raft_stereo_tpu.config import GraniteHybridConfig

    return GraniteHybridConfig.from_hf_config(config, **config["program"])


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
        """Ids alone, one document a row: the token generator that is
        there, in blocks of one, its noise dropped."""
        spec = self.spec
        drawn = sdar_traffic.token_batches(
            self.seed, spec["batches"], spec["batch"], spec["seq_len"], 1, self.config["vocab_size"],
            spec["zipf_exponent"])
        return [{"tokens": batch["tokens"]} for batch in drawn]

    def setup(self) -> None:
        import jax

        from raft_stereo_tpu.train.trainer import Trainer, TrainState

        spec = self.spec
        self.phases = phases = common.Phases()
        self.workdir = tempfile.mkdtemp(prefix="bench_train_lm_")
        with phases("trainer"):
            self.trainer = trainer = Trainer(self._train_config(), sample_shape=(spec["seq_len"],))
        step = trainer.state.step
        stale, trainer.state = trainer.state, None
        jax.tree.map(lambda x: x.delete(), (stale.params, stale.opt_state))
        with phases("weights"):
            params = granite_weights.draw(self.config, self.seed)["params"]
            self.initial = jax.tree.map(np.asarray, params)
        state = TrainState(step=step, params=params, batch_stats={}, opt_state=trainer.tx.init(params))
        trainer.state = trainer.sharding.place_state(state)
        with phases("batches"):
            self.batches = self._batches()
        first = _StateRecorder(trainer, lag=False, snapshots={
            1: _first_moment, CHECKED_STEPS: lambda state: state.params})
        steps = max(spec["warm_steps"], CHECKED_STEPS)
        with phases("first_steps"):
            self._fit((self.batches[i % len(self.batches)] for i in range(steps)), first)
        self.first = first

    def window(self, seconds: float) -> dict:
        recorder = _StateRecorder(self.trainer, lag=True, snapshots={})
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
        model = self.trainer.config.model
        forwards = 1 + model.remat_layers
        scans, attentions = (steps * model.layer_types.count(kind) for kind in ("mamba", "attention"))
        report = self.trainer.last_run_report
        # the harness reads the larger of the two peaks; the footprint is their sum (PERF.md section 7)
        stats = self.devices[0].memory_stats() or {}
        print("memory_stats " + json.dumps({k: stats[k] for k in sorted(stats) if "bytes" in k}), file=sys.stderr)
        # and where a slow window lost its time: every step a little, or a few steps a lot
        print("step_gaps_ms " + json.dumps([round(1000.0 * float(g), 1) for g in gaps]), file=sys.stderr)
        return {
            "attempted": steps,
            "failed": sum(not np.isfinite(x) for x in recorder.losses),
            "seconds": elapsed,
            "work": samples,
            "ssd_chunk_calls": scans * forwards,
            "ssd_chunk_bwd_calls": scans,
            "attention_forward_calls": attentions * forwards,
            "attention_backward_calls": attentions,
            "step_ms_p50": 1000.0 * float(np.median(gaps)),
            "compiles_in_window": report["jit_hygiene"]["compiles_post_grace"],
            COUNTER: float(np.mean(recorder.counters())),
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
            "state_rms": first.counters()[:CHECKED_STEPS],
        }

    def reference_readings(self, precision: str = "float32", fault: str = None) -> dict:
        import jax
        import jax.numpy as jnp

        spec = self.spec
        train = {k: spec[k] for k in ("lr", "num_steps", "wdecay")}
        train["grad_clip_norm"] = 1.0
        params = jax.tree.map(jnp.asarray, self.initial)
        batches = [jax.tree.map(jnp.asarray, b) for b in self.batches[:CHECKED_STEPS]]
        losses, grad, params, state_rms = granite_reference.train_steps(
            self.config, train, params, batches, precision, fault)
        grad = _flat(grad)
        after = jax.device_get(params)
        jax.tree.map(lambda x: x.delete(), params)
        return {
            "losses": [float(x) for x in jax.device_get(losses)],
            "grad": {k: _norm(v) for k, v in grad.items()}, "grad_leaves": grad,
            "delta": _delta(self.initial, after),
            "state_rms": [float(x) for x in jax.device_get(state_rms)],
        }

    @staticmethod
    def _numbers(program: dict, ref: dict) -> Dict[str, float]:
        out = numbers(program, ref)
        apart = sum(_norm(program["grad_leaves"][k] - g) ** 2 for k, g in ref["grad_leaves"].items())
        out["grad_gap"] = (apart / sum(v * v for v in ref["grad"].values())) ** 0.5
        out["final_state_gap"] = max(abs(a - b) / b for a, b in zip(program["state_rms"], ref["state_rms"]))
        return out

    def check(self) -> dict:
        program = self.program_readings()
        self.first = None  # the recorder holds the trainer, and the trainer 12 bytes a parameter of the chip
        self._free()
        got = self._numbers(program, self.reference_readings())
        return {k: common.compared(v, self.spec["limits"][k]) for k, v in got.items() if k in self.spec["limits"]}

    def control(self, fault: str = None) -> dict:
        """The reference in the control precision, or with a fault planted
        (`chunk_reset`, `bidirectional_attention`), in the program's place.
        Needs no set-up."""
        import jax

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.initial = jax.tree.map(np.asarray, granite_weights.draw(self.config, self.seed)["params"])
        self.batches = self._batches()
        if fault is None:
            stand_in = self.reference_readings(self.spec["control"])
        else:
            stand_in = self.reference_readings(fault=fault)
        common.free_device()
        return self._numbers(stand_in, self.reference_readings())
