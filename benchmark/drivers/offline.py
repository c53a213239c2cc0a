"""Closed loop, one client: seeded pairs one at a time through
`evaluate.Evaluator.__call__` — pad, host-to-device, forward, fetch, unpad all
inside the timed call, as `evaluate` runs a dataset."""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference, traffic, weights
from benchmark.drivers import common


class Run:
    def __init__(self, spec, config, seed, devices, tracer):
        self.spec, self.config, self.seed = spec, config, seed
        self.devices, self.tracer = devices, tracer

    def setup(self) -> None:
        import jax

        from raft_stereo_tpu.evaluate import Evaluator

        spec = self.spec
        self.phases = phases = common.Phases()
        with phases("weights"), jax.default_device(self.devices[0]):
            self.variables = jax.block_until_ready(weights.draw(self.config["model"], self.seed))
        with phases("frames"):
            self.frames = traffic.stereo_frames(self.seed, spec["frames"], spec["image_hw"], spec["max_disp"])
        with phases("first_call"):  # compiles or loads the one shape
            self.evaluator = Evaluator(common.model_config(self.config), self.variables, iters=spec["iters"])
            frame = self.frames[0]
            self.evaluator(frame["image1"], frame["image2"])

    def window(self, seconds: float) -> dict:
        maps, forward_s, order, call_s = [], [], [], []
        n = len(self.frames)
        start = now = time.perf_counter()
        while now - start < seconds:
            index = len(order) % n
            frame = self.frames[index]
            with self.tracer.span("evaluator_call"):
                disparity, fwd = self.evaluator(frame["image1"], frame["image2"])
            maps.append(disparity)
            forward_s.append(fwd)
            order.append(index)
            before, now = now, time.perf_counter()
            call_s.append(now - before)
        elapsed = now - start
        self.maps, self.order = maps, order
        return {
            "attempted": len(maps),
            "failed": 0,
            "seconds": elapsed,
            "work": len(maps),
            "forward_s": forward_s,
            "kernel_calls": len(maps) * self.spec["iters"],
            "call_ms_p50": 1000.0 * float(np.median(call_s)),
            "call_ms_max": 1000.0 * max(call_s),
            "end_to_end": {"offline_maps_per_s": len(maps) / elapsed},
        }

    def answers(self):
        """(frame index, map) of every map the window returned. Tests plant
        faults here."""
        return list(zip(self.order, self.maps))

    def _reference_map(self, variables, index, precision="float32"):
        import jax
        import jax.numpy as jnp

        frame = self.frames[index]
        want = reference.forward_staged(
            self.config["model"], variables, jnp.asarray(frame["image1"][None]),
            jnp.asarray(frame["image2"][None]), self.spec["iters"], precision,
        )
        return np.asarray(jax.device_get(want))[0]

    def _numbers(self, variables, answers) -> dict:
        return {"map_mae_px": common.map_mae_px(answers, lambda i: self._reference_map(variables, i))}

    def check(self) -> dict:
        """A sample of the window's maps, drawn from the seed, against the
        plain reference. The program's state is freed first."""
        import jax

        variables = jax.tree.map(np.asarray, self.variables)
        self.evaluator = self.variables = None
        common.free_device()
        numbers = self._numbers(variables, common.picked(self.seed, self.answers(), self.spec["checked_maps"]))
        return {k: common.compared(v, self.spec["limits"][k]) for k, v in numbers.items()}

    def control(self) -> dict:
        """The same numbers with the reference, computed one precision below
        the configuration's, in the program's place. Needs no set-up."""
        import jax

        with jax.default_device(self.devices[0]):
            variables = weights.draw(self.config["model"], self.seed)
        self.frames = traffic.stereo_frames(
            self.seed, self.spec["frames"], self.spec["image_hw"], self.spec["max_disp"])
        picked = common.picked(self.seed, [(i, None) for i in range(len(self.frames))], self.spec["checked_maps"])
        answers = [(i, self._reference_map(variables, i, self.spec["control"])) for i, _ in picked]
        return self._numbers(variables, answers)
