"""The toy family's driver: one jitted SGD step, built once in set-up, driven
through its first steps there and handed to the window as it is."""

import time

import numpy as np

from benchmark import toy_mlp_reference, toy_mlp_weights
from benchmark.drivers import common


def _batches(seed, n, batch, width):
    rng = np.random.default_rng(seed + 1)
    return [
        {"x": rng.standard_normal((batch, width)).astype(np.float32),
         "y": rng.standard_normal((batch, 1)).astype(np.float32)}
        for _ in range(n)
    ]


class Run:
    def __init__(self, spec, config, seed, devices, tracer):
        self.spec, self.config, self.seed = spec, config, seed
        self.devices, self.tracer = devices, tracer

    def setup(self):
        import jax
        import jax.numpy as jnp

        spec, layers = self.spec, self.config["num_hidden_layers"]

        def loss_fn(params, batch):
            h = batch["x"]
            for i in range(layers):
                h = jnp.tanh(h @ params[f"w{i}"])
            return jnp.mean((h @ params[f"w{layers}"] - batch["y"]) ** 2)

        @jax.jit
        def step(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            return jax.tree.map(lambda p, g: p - spec["lr"] * g, params, grads), loss

        self.step = step
        self.initial = toy_mlp_weights.draw(self.config, self.seed)
        self.batches = _batches(self.seed, spec["batches"], spec["batch"], self.config["input_size"])
        self.params = jax.device_put(self.initial, self.devices[0])
        self.first_losses = []
        for batch in self.batches[: spec["checked_steps"]]:
            self.params, loss = self.step(self.params, batch)
            self.first_losses.append(float(loss))

    def window(self, seconds):
        losses, i = [], 0
        start = now = time.perf_counter()
        while now - start < seconds:
            with self.tracer.span("step"):
                self.params, loss = self.step(self.params, self.batches[i % len(self.batches)])
                losses.append(float(loss))
            i += 1
            now = time.perf_counter()
        samples = i * self.spec["batch"]
        return {
            "attempted": i,
            "failed": sum(not np.isfinite(x) for x in losses),
            "seconds": now - start,
            "work": samples,
            "dense_calls": i * (self.config["num_hidden_layers"] - 1),
            "end_to_end": {"train_samples_per_s": samples / (now - start)},
        }

    def check(self):
        self.params = self.step = None
        checked = self.spec["checked_steps"]
        want = toy_mlp_reference.train_steps(self.config, self.initial, self.batches[:checked], self.spec["lr"])
        gap = max(abs(a - b) / abs(b) for a, b in zip(self.first_losses, want))
        return {"loss_gap": common.compared(gap, self.spec["limits"]["loss_gap"])}
