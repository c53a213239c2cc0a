"""Open loop against the in-process service: `StereoService.submit` at arrival
times fixed by the workload's rate and the seed.

One dispatcher thread sleeps until each request is due and submits it; a
request's latency runs from the time it was DUE (so a stalled generator or a
backed-up queue both count) to the moment its future resolves. A request
that fails, is shed, or is still unanswered a minute after the window closes
counts as the window's length. Every seed sends the same cyclic sequence of
gaps between arrivals, entered at another point, so the bursts a run meets do
not depend on the seed.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List

import numpy as np

from benchmark import reference, traffic, weights
from benchmark.drivers import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Fixed, inside the checkout, listed in .gitignore: the path is part of what
# a warm boot finds again.
AOT_DIR = os.path.join(ROOT, ".bench_aot")
ANSWER_WAIT_S = 60.0
# The arrival process's own seed: every run seed permutes the same gaps.
GAPS_SEED = 20260930


def arrivals(seed: int, rate_hz: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds): exponential gaps at `rate_hz` (a Poisson
    process), the same cycle of gaps for every seed, rotated by the seed."""
    n = max(1, int(round(rate_hz * seconds)))
    gaps = np.random.default_rng(GAPS_SEED).exponential(1.0 / rate_hz, n)
    gaps *= seconds / gaps.sum() * (n / (n + 1.0))  # the last arrival falls inside the window
    return np.cumsum(np.roll(gaps, int(np.random.default_rng(seed).integers(n))))


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class Run:
    def __init__(self, spec, config, seed, devices, tracer):
        self.spec, self.config, self.seed = spec, config, seed
        self.devices, self.tracer = devices, tracer
        self.service = None

    def _frames(self):
        spec = self.spec
        frames = traffic.stereo_frames(self.seed, spec["frames"], spec["request_hw"], spec["max_disp"])
        return [(np.round(f["image1"]).astype(np.uint8), np.round(f["image2"]).astype(np.uint8))
                for f in frames]

    def setup(self) -> None:
        from raft_stereo_tpu.config import ServeConfig
        from raft_stereo_tpu.serving.service import StereoService

        spec = self.spec
        self.phases = phases = common.Phases()
        with phases("weights"):
            self.variables = weights.draw(self.config["model"], self.seed)
        with phases("frames"):
            self.frames = self._frames()
        serve = ServeConfig(
            model=common.model_config(self.config),
            buckets=(tuple(spec["image_hw"]),),
            max_batch=spec["max_batch"],
            chunk_iters=spec["chunk_iters"],
            max_iters=spec["iters"],
            batch_window_ms=spec["batch_window_ms"],
            deadline_ms=0.0,
            aot_cache_dir=AOT_DIR,
        )
        with phases("boot"):
            self.service = StereoService(serve, self.variables).start()
        # The host path once, alone and in a burst, before anything is timed.
        with phases("first_requests"):
            self._drive(np.linspace(0.0, 0.2, spec["max_batch"]), 0)

    def _drive(self, due: np.ndarray, offset: int) -> Dict[str, list]:
        """Submit one request at each due time; wait for every answer."""
        n = len(due)
        sent = [0.0] * n
        done = [None] * n
        futures = [None] * n
        frames = self.frames

        def land(i):
            def record(_future):
                done[i] = time.monotonic()

            return record

        t0 = time.monotonic()

        def dispatch():
            for i in range(n):
                delay = t0 + due[i] - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                left, right = frames[(offset + i) % len(frames)]
                sent[i] = time.monotonic()
                try:
                    futures[i] = self.service.submit(left, right)
                    futures[i].add_done_callback(land(i))
                except Exception as exc:  # refused at admission: a failed request
                    futures[i] = exc

        with self.tracer.span("submit"):
            thread = threading.Thread(target=dispatch, name="bench-load")
            thread.start()
            thread.join()
        close = t0 + float(due[-1])
        answers: List[object] = [None] * n
        with self.tracer.span("wait"):
            for i, future in enumerate(futures):
                if isinstance(future, Exception):
                    continue
                left_s = max(0.0, close + ANSWER_WAIT_S - time.monotonic())
                try:
                    answers[i] = future.result(timeout=left_s)
                except Exception:
                    answers[i] = None
        return {"t0": t0, "due": due, "sent": sent, "done": done, "answers": answers,
                "frame": [(offset + i) % len(frames) for i in range(n)]}

    def window(self, seconds: float) -> dict:
        spec = self.spec
        due = arrivals(self.seed, spec["rate_hz"], seconds)
        log = self._drive(due, self.seed % len(self.frames))
        latencies, late, failed = [], [], 0
        for i in range(len(due)):
            late.append(1000.0 * (log["sent"][i] - (log["t0"] + due[i])))
            if log["answers"][i] is None or log["done"][i] is None:
                failed += 1
                latencies.append(1000.0 * seconds)
            else:
                latencies.append(1000.0 * (log["done"][i] - (log["t0"] + due[i])))
        self.log, self.unanswered = log, failed
        elapsed = max(d for d in log["done"] if d is not None) - log["t0"] if failed < len(due) else seconds
        metrics = self.service.metrics()
        attribution = self.service.batcher.metrics.attribution_summary()
        hygiene = self.service.engine.hygiene.report()
        return {
            "attempted": len(due),
            "failed": failed,
            "seconds": elapsed,
            "work": len(due) - failed,
            "kernel_calls": (len(due) - failed) * spec["iters"],
            "end_to_end": {
                "serve_p50_ms": percentile(latencies, 50),
                "serve_p95_ms": percentile(latencies, 95),
            },
            "generator_late_p95_ms": percentile(late, 95),
            "queue_wait_p50_ms": attribution["queue_wait_ms"]["p50"],
            "engine_device_p50_ms": attribution["device_ms"]["p50"],
            "engine_host_gap_p50_ms": attribution["host_gap_ms"]["p50"],
            "batch_fill_mean": metrics["batch_fill_mean"],
            "compiles_in_window": hygiene["compiles_post_grace"],
        }

    # -- the comparison ---------------------------------------------------

    def answers(self):
        """(frame index, served disparity) of every request the window
        answered. Tests plant faults here."""
        log = self.log
        return [(log["frame"][i], a["disparity"]) for i, a in enumerate(log["answers"]) if a is not None]

    def _reference_map(self, variables, index, precision="float32"):
        """The plain reference on one pair: pad to the bucket by repeating
        the edge (split evenly, the odd pixel right and below), run, crop."""
        import jax
        import jax.numpy as jnp

        spec = self.spec
        h, w = spec["request_hw"]
        pad_h, pad_w = spec["image_hw"][0] - h, spec["image_hw"][1] - w
        top, left = pad_h // 2, pad_w // 2
        pads = ((top, pad_h - top), (left, pad_w - left), (0, 0))
        images = [jnp.asarray(np.pad(x.astype(np.float32), pads, mode="edge")[None])
                  for x in self.frames[index]]
        forward = jax.jit(lambda v, a, b: reference.forward(
            self.config["model"], v, a, b, spec["iters"], precision))
        out = np.asarray(jax.device_get(forward(variables, *images)))[0]
        return out[top : top + h, left : left + w]

    def _numbers(self, variables, answers) -> dict:
        return {"map_mae_px": common.map_mae_px(answers, lambda i: self._reference_map(variables, i))}

    def check(self) -> dict:
        import jax

        variables = jax.tree.map(np.asarray, self.variables)
        self.service.drain(timeout_s=30.0)
        self.service = self.variables = None
        common.free_device()
        numbers = self._numbers(variables, common.picked(self.seed, self.answers(), self.spec["checked_requests"]))
        numbers["unanswered"] = float(self.unanswered)  # late is late; never is wrong
        return {k: common.compared(v, self.spec["limits"][k]) for k, v in numbers.items()}

    def control(self) -> dict:
        variables = weights.draw(self.config["model"], self.seed)
        self.frames = self._frames()
        picked = common.picked(self.seed, [(i, None) for i in range(len(self.frames))], self.spec["checked_requests"])
        answers = [(i, self._reference_map(variables, i, self.spec["control"])) for i, _ in picked]
        return self._numbers(variables, answers)
