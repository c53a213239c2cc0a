"""`cli.run_training` -> `Trainer.fit` for the `laguna-moe` family on the
plain causal loss: the token driver's comparison (benchmark/drivers/
train_tokens.py: `held_rows_gap` and `grad_gap` beside the train driver's
numbers, its recorder of the expert counters) with the hybrid driver's
batches of ids alone (benchmark/drivers/train_lm.py), the family's weight
draw and its plain reference.

Set-up builds ONE trainer, replaces its weights by the seed's draw (the
trainer's own first state is deleted first: two 12-bytes-a-parameter states
do not fit the chip), drives the first steps and keeps what the comparison
needs, every snapshot on the host. The window cycles the same host batches
until the time is up.

The window also counts the calls its kernels served, by what the compiled
step holds (its custom calls by name, each `while` at its trip count: 4 + 2
`block_attention*`, 6 + 3 `window_attention*`, 24 `grouped_matmul`, 8
`grouped_matmul_drhs`, 20 + 10 `qk_norm_rope*` at this cell's five layers,
compile-only, PERF.md section 4): a layer's attention and head prologue run
once more for the rebuild of the layer (per-layer remat); the routed
products run forward, once more for the rebuild of a chunk and once for the
backward's product with the transposed weights, and the layer's rebuild
runs none (its output is dead there).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Dict

import numpy as np

from benchmark import laguna_reference, laguna_weights
from benchmark.drivers import common, train_lm, train_tokens
from benchmark.drivers.train import CHECKED_STEPS, _first_moment, _flat
from benchmark.drivers.train_tokens import _delta, _norm, _TokenRecorder

GATE = "attn_gate_mean"
FAULTS = laguna_reference.FAULTS


class _GateRecorder(_TokenRecorder):
    """The token driver's recorder, keeping each step's mean gate beside the
    expert counters."""

    def __init__(self, trainer, lag, snapshots):
        super().__init__(trainer, lag, snapshots)
        self._gates = []

    def push(self, metrics, step):
        self._gates.append(metrics[GATE])
        super().push(metrics, step)

    def gate_mean(self) -> float:
        import jax

        return float(np.mean(jax.device_get(self._gates)))


def model_config(config: Dict):
    from raft_stereo_tpu.config import LagunaConfig

    return LagunaConfig.from_hf_config(config, **config["program"])


class Run(train_tokens.Run):
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

    _batches = train_lm.Run._batches  # ids alone, one document a row

    def setup(self) -> None:
        import jax

        from raft_stereo_tpu.train.trainer import Trainer, TrainState

        spec = self.spec
        self.phases = phases = common.Phases()
        self.workdir = tempfile.mkdtemp(prefix="bench_train_laguna_")
        with phases("trainer"):
            self.trainer = trainer = Trainer(self._train_config(), sample_shape=(spec["seq_len"],))
        step = trainer.state.step
        stale, trainer.state = trainer.state, None
        jax.tree.map(lambda x: x.delete(), (stale.params, stale.opt_state))
        with phases("weights"):
            params = laguna_weights.draw(self.config, self.seed)["params"]
            self.initial = jax.tree.map(np.asarray, params)
        state = TrainState(step=step, params=params, batch_stats={}, opt_state=trainer.tx.init(params))
        trainer.state = trainer.sharding.place_state(state)
        with phases("batches"):
            self.batches = self._batches()
        first = _GateRecorder(trainer, lag=False, snapshots={
            1: _first_moment, CHECKED_STEPS: lambda state: state.params})
        steps = max(spec["warm_steps"], CHECKED_STEPS)
        with phases("first_steps"):
            self._fit((self.batches[i % len(self.batches)] for i in range(steps)), first)
        self.first = first

    def window(self, seconds: float) -> dict:
        recorder = _GateRecorder(self.trainer, lag=True, snapshots={})
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
        forwards = 1 + model.remat_layers
        full, window = (steps * model.layer_types.count(kind) for kind in ("full_attention", "sliding_attention"))
        sparse = steps * model.mlp_layer_types.count("sparse")
        positions = self.spec["batch"] * self.spec["seq_len"]
        chunked = positions > model.moe_chunk and positions % model.moe_chunk == 0
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
            "full_attention_forward_calls": full * forwards,
            "full_attention_backward_calls": full,
            "window_attention_forward_calls": window * forwards,
            "window_attention_backward_calls": window,
            # passes of a layer's routed products under the kernel's name: a
            # chunk's forward, its rebuild and the backward's product (the
            # layer's rebuild runs no expert kernel); unchunked, the layer's
            # forwards and the backward's product
            "grouped_matmul_calls": sparse * (3 if chunked else forwards + 1),
            "grouped_matmul_drhs_calls": sparse,
            # a layer's q and k calls, counted together
            "qk_norm_rope_calls": (full + window) * forwards,
            "qk_norm_rope_bwd_calls": full + window,
            "step_ms_p50": 1000.0 * float(np.median(gaps)),
            "compiles_in_window": report["jit_hygiene"]["compiles_post_grace"],
            "moe_held_rows_per_step": float(np.mean(counters["moe_held_rows"])),
            "moe_max_over_mean_load": float(np.mean(counters["moe_max_over_mean_load"])),
            GATE: recorder.gate_mean(),
            "end_to_end": {"train_samples_per_s": samples / elapsed},
        }

    # -- the comparison ---------------------------------------------------

    def reference_readings(self, precision: str = "float32", fault: str = None) -> dict:
        import jax
        import jax.numpy as jnp

        spec = self.spec
        train = {k: spec[k] for k in ("lr", "num_steps", "wdecay")}
        train["grad_clip_norm"] = 1.0
        params = jax.tree.map(jnp.asarray, self.initial)
        batches = [jax.tree.map(jnp.asarray, b) for b in self.batches[:CHECKED_STEPS]]
        losses, grad, params, held = laguna_reference.train_steps(
            self.config, train, params, batches, precision, fault)
        grad = _flat(grad)
        after = jax.device_get(params)
        jax.tree.map(lambda x: x.delete(), params)
        return {
            "losses": [float(x) for x in jax.device_get(losses)],
            "grad": {k: _norm(v) for k, v in grad.items()}, "grad_leaves": grad,
            "delta": _delta(self.initial, after),
            "held_rows": [float(x) for x in jax.device_get(held)],
        }

    def control(self, fault: str = None) -> dict:
        """The reference in the control precision, or with a fault planted
        (`window_off`, `gate_off`, `rotary_whole_head`), in the program's
        place. Needs no set-up."""
        import jax

        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
        self.initial = jax.tree.map(np.asarray, laguna_weights.draw(self.config, self.seed)["params"])
        self.batches = self._batches()
        if fault is None:
            stand_in = self.reference_readings(self.spec["control"])
        else:
            stand_in = self.reference_readings(fault=fault)
        common.free_device()
        return self._numbers(stand_in, self.reference_readings())
