#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, one TPU chip, the entry points a user calls, the full default
model (the published RAFT-Stereo widths: hidden dims 128x3, 3 GRU scales,
4 correlation levels, radius 4, 1/4-resolution field) with the Pallas
correlation kernels, mixed precision and bf16 correlation storage. Weights
are random, from `--seed`. The undecided levers (`fused_encoder`,
`prefetch_lookup`, `fused_gru_tail`) stay at their defaults, off.

    gate    the first jax call is `jax.devices()`; anything but a TPU exits
            non-zero before a phase runs
    infer   `evaluate.Evaluator` on a synthetic Middlebury-F pair (1984x2880,
            32 iterations); the Pallas lookup against the plain `ops/corr.py`
            lookup at that shape, the model against the float32 `reg`
            reference at 2 iterations, the bf16-storage EPE delta
    serve   `StereoService` + `make_http_server` as `cmd_serve` builds them,
            one KITTI bucket (384x1248), four /v1/predict requests over HTTP
            (two together, so a batch of 2 forms), /healthz, then a second
            boot from the same AOT cache: all hits, no compile, same answer
    train   `Trainer` through `cli.run_training`: batch 4, 320x720 crops,
            22 iterations, three steps; run_report.json validated

`--chips 4` runs instead, and only, what exists across chips: the
data-parallel train step on a (4, 1) mesh against two one-chip steps over the
same samples, and `serve` with four replicas from a shared AOT cache against
the one-chip answer.

Each phase prints one JSON line; a failed check raises, so nothing lets a
failed run exit 0. The last line of a passed run, and nothing else on it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Rehearse on the CPU at a tiny size with tests/test_chip_smoke.py (the only
place the kernels may run interpreted); measure on the chip through the chip
tool: `chiprun -- python3 chip_smoke.py`.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.metadata
import json
import os
import statistics
import sys
import tempfile
import threading
import time
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. The defaults are the real sizes; only the CPU
    rehearsal (tests/test_chip_smoke.py) passes smaller ones."""

    model: Dict[str, object] = dataclasses.field(
        default_factory=lambda: dict(
            corr_implementation="pallas", mixed_precision=True, corr_dtype="bfloat16"
        )
    )
    # infer: Middlebury-F after the reference's ÷32 padding, 32 iterations.
    infer_hw: Tuple[int, int] = (1984, 2880)
    infer_iters: int = 32
    # The model-level reference comparison (2 iterations: an untrained GRU
    # amplifies rounding chaotically beyond that, ops/corr.py).
    parity_hw: Tuple[int, int] = (384, 512)
    # serve: KITTI frames (375x1242) into the 384x1248 bucket.
    serve_image_hw: Tuple[int, int] = (375, 1242)
    serve_bucket: Tuple[int, int] = (384, 1248)
    max_batch: int = 2
    chunk_iters: int = 4
    max_iters: int = 32
    # Long enough for two HTTP requests sent together to meet in one batch
    # (each body is parsed for some hundred milliseconds first); a request
    # alone waits it out.
    batch_window_ms: float = 3000.0
    # --chips 4 serve: a quarter-scale frame, padded to the same bucket — the
    # device runs the full bucket either way, and small bodies arrive close
    # enough together for the router to spread them over every replica.
    fleet_image_hw: Tuple[int, int] = (94, 310)
    # train: the reference recipe's per-chip shape.
    train_batch: int = 4
    train_hw: Tuple[int, int] = (320, 720)
    train_iters: int = 22
    train_steps: int = 3


class SmokeFailure(AssertionError):
    """A phase's check did not hold. Never caught: the run exits non-zero."""


def _check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def _emit(phase: str, **fields) -> Dict[str, object]:
    line = {"phase": phase, "ok": True, **fields}
    print(json.dumps(line), flush=True)
    return line


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _memory(device) -> Dict[str, int]:
    """Allocator view of one device; zeros where the backend reports none
    (the CPU rehearsal). `peak_bytes_in_use` counts arrays only; what XLA
    takes for a program's temporaries while it runs shows as
    `peak_bytes_reserved` (on this runtime a 12 GB train step moves the
    second and not the first). Both are the process's peaks SO FAR — the
    allocator has no reset, so a later phase's values cover the earlier
    ones."""
    stats = device.memory_stats() or {}
    keys = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")
    return {k: int(stats.get(k, 0)) for k in keys}


def _free() -> int:
    """Collect what a phase no longer references before the next one starts
    (one process holds the chip; the train step alone needs 12 of its
    15.75 GB). Returns the bytes still in use on the first device."""
    import jax

    gc.collect()
    return _memory(jax.devices()[0])["bytes_in_use"]


def _kernel_calls(lowered_text: str) -> int:
    """Mosaic kernels in a lowered program. Interpreted kernels (the CPU
    rehearsal) lower to plain ops and count zero."""
    return lowered_text.count("tpu_custom_call")


def _check_kernels_compiled(phase: str, n_calls: int) -> None:
    _check(
        n_calls > 0 or not _on_tpu(),
        f"{phase}: no tpu_custom_call in the program — the Pallas kernel was "
        "interpreted or replaced on a TPU backend",
    )


def _frames(seed: int, n: int, hw: Tuple[int, int]):
    """`n` independent synthetic stereo frames with known disparity."""
    import numpy as np

    from raft_stereo_tpu.data.datasets import make_synthetic_sequence

    rng = np.random.default_rng(seed)
    return [make_synthetic_sequence(rng, 1, hw[0], hw[1])[0] for _ in range(n)]


# --------------------------------------------------------------------------
# gate
# --------------------------------------------------------------------------


def gate(chips: int) -> Dict[str, object]:
    import jax
    import jaxlib

    devices = jax.devices()  # the first jax call
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU and jax found platform {platform!r} "
            f"({devices[0].device_kind}); nothing was run. On the CPU, rehearse "
            "with tests/test_chip_smoke.py."
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, jax found "
            f"{len(devices)}; nothing was run."
        )
    from raft_stereo_tpu.utils.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    _emit(
        "gate",
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        device=device,
        compile_cache_dir=cache_dir,
        compile_cache_entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
    )
    return device


# --------------------------------------------------------------------------
# infer
# --------------------------------------------------------------------------


def _kernel_parity(sizes: Sizes, seed: int, cfg) -> Dict[str, float]:
    """Pallas lookup against the plain `ops/corr.py` lookup on the same
    pyramid and coordinates, at the infer phase's full shape, for float32 and
    bf16 storage. Both interpolate in float32 from the same stored values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_stereo_tpu.ops.corr import corr_lookup, corr_pyramid, corr_volume
    from raft_stereo_tpu.ops.corr_pallas import pad_pyramid, pallas_corr_lookup_padded

    f = cfg.downsample_factor
    h, w = sizes.infer_hw[0] // f, sizes.infer_hw[1] // f
    rng = np.random.default_rng(seed + 1)
    fmap1 = jnp.asarray(rng.standard_normal((1, h, w, 256)).astype(np.float32))
    fmap2 = jnp.asarray(rng.standard_normal((1, h, w, 256)).astype(np.float32))
    # The pixel grid minus a smooth disparity plus sub-pixel noise; a margin
    # of queries runs off both ends of the row (zero-padding semantics).
    xs = np.arange(w, dtype=np.float32)[None, None, :]
    disp = 0.1 * w * (0.5 + 0.5 * np.sin(np.linspace(0.0, 6.0, h, dtype=np.float32)))
    coords = jnp.asarray(
        xs - disp[None, :, None] + rng.uniform(-1.5, 1.5, (1, h, w)).astype(np.float32)
    )
    radius = cfg.corr_radius
    reference = jax.jit(lambda p, c: corr_lookup(p, c, radius))
    kernel = jax.jit(
        lambda p, c: pallas_corr_lookup_padded(pad_pyramid(p, c.shape), c, radius)
    )
    out = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        pyramid = jax.jit(
            lambda a, b: tuple(
                corr_pyramid(corr_volume(a, b, out_dtype=dtype), cfg.corr_levels)
            )
        )(fmap1, fmap2)
        want, got = reference(pyramid, coords), kernel(pyramid, coords)
        _check(got.shape == want.shape, f"lookup shape {got.shape} != {want.shape}")
        err = float(jax.device_get(jnp.max(jnp.abs(got - want))))
        # Same stored values, float32 interpolation on both sides: what is
        # left is the last ulp of the lerp on correlations of order 10.
        _check(
            np.isfinite(err) and err <= 1e-4,
            f"Pallas lookup ({name} storage) differs from ops/corr.py by {err}",
        )
        out[f"lookup_max_abs_err_{name}"] = err
        del pyramid, want, got
    return out


def _model_parity(sizes: Sizes, seed: int, cfg, variables) -> Dict[str, float]:
    """The model against the plain reference (same weights, `reg`
    correlation, float32, no mixed precision) at 2 iterations; and what bf16
    storage alone moves, as an EPE delta on a known-disparity pair."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_stereo_tpu.models import RAFTStereo
    from raft_stereo_tpu.ops.corr import BF16_CORR_EPE_BUDGET_PX

    frame = _frames(seed + 2, 1, sizes.parity_hw)[0]
    i1 = jnp.asarray(frame["image1"][None])
    i2 = jnp.asarray(frame["image2"][None])
    gt, valid = frame["flow"][..., 0], frame["valid"]

    def disparity(**overrides):
        model = RAFTStereo(dataclasses.replace(cfg, **overrides))
        fwd = jax.jit(lambda v, a, b: model.apply(v, a, b, iters=2, test_mode=True)[1])
        up = np.asarray(jax.device_get(fwd(variables, i1, i2)))[0, :, :, 0]
        _check(np.isfinite(up).all(), f"non-finite disparity under {overrides}")
        return up

    def epe(up):
        return float((np.abs(up - gt) * valid).sum() / valid.sum())

    f32 = dict(mixed_precision=False, corr_dtype="float32")
    ref = disparity(corr_implementation="reg", **f32)
    pallas = disparity(**f32)  # the kernel swapped in, nothing else
    bf16 = disparity(mixed_precision=False)  # plus bf16 storage
    kernel_err = float(np.abs(pallas - ref).max())
    # float32 end to end; the lookup's last-ulp differences pass through two
    # GRU iterations and the convex upsample of a field tens of pixels wide.
    _check(
        kernel_err <= 1e-2,
        f"pallas vs reg float32 model outputs differ by {kernel_err} px at 2 iters",
    )
    return {
        "model_pallas_vs_reg_max_abs_px": kernel_err,
        "bf16_storage_epe_delta_px": abs(epe(bf16) - epe(pallas)),
        "bf16_storage_epe_budget_px": BF16_CORR_EPE_BUDGET_PX,
    }


def phase_infer(sizes: Sizes, seed: int) -> Dict[str, object]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_stereo_tpu.config import RAFTStereoConfig
    from raft_stereo_tpu.evaluate import Evaluator
    from raft_stereo_tpu.models import init_model_variables

    cfg = RAFTStereoConfig(**sizes.model)
    variables = init_model_variables(cfg, seed=seed)
    frame = _frames(seed, 1, sizes.infer_hw)[0]
    evaluator = Evaluator(cfg, variables, iters=sizes.infer_iters)

    shape = jax.ShapeDtypeStruct((1, *sizes.infer_hw, cfg.in_channels), jnp.float32)
    kernels = _kernel_calls(evaluator._fwd.lower(variables, shape, shape).as_text())
    _check_kernels_compiled("infer", kernels)

    flow, first_s = evaluator(frame["image1"], frame["image2"])
    _check(flow.shape == tuple(sizes.infer_hw), f"disparity shape {flow.shape}")
    _check(np.isfinite(flow).all(), "non-finite disparity")
    steady = []
    for _ in range(3):
        again, seconds = evaluator(frame["image1"], frame["image2"])
        steady.append(seconds)
    _check(np.array_equal(again, flow), "the same pair gave a different map")
    seconds_per_map = statistics.median(steady)
    memory = _memory(jax.devices()[0])

    del evaluator
    _free()
    parity = _kernel_parity(sizes, seed, cfg)
    parity.update(_model_parity(sizes, seed, cfg, variables))
    return _emit(
        "infer",
        hw=list(sizes.infer_hw),
        iters=sizes.infer_iters,
        seconds_per_map=seconds_per_map,
        seconds_per_map_runs=steady,
        compile_s=first_s - seconds_per_map,
        kernel_calls=kernels,
        **memory,
        **parity,
    )


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


@contextlib.contextmanager
def _serving(config, variables):
    """Boot exactly what `cmd_serve` boots — StereoService.start() behind
    make_http_server — on an ephemeral port; always shut both down."""
    from raft_stereo_tpu.serving.service import StereoService, make_http_server

    service = StereoService(config, variables).start()
    server = None
    thread = None
    try:
        server = make_http_server(service, config.host, 0)
        thread = threading.Thread(
            target=server.serve_forever, name="chip-smoke-http", daemon=True
        )
        thread.start()
        yield service, server.server_address[1]
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        if thread is not None:
            thread.join(timeout=30)
        service.drain()


def _predict(port: int, frame):
    """One /v1/predict over HTTP → (disparity float32 array, response)."""
    import numpy as np

    from raft_stereo_tpu.utils.http import request_json

    resp = request_json(
        f"http://127.0.0.1:{port}/v1/predict",
        method="POST",
        payload={
            "image1": np.round(frame["image1"]).astype(np.uint8).tolist(),
            "image2": np.round(frame["image2"]).astype(np.uint8).tolist(),
        },
        timeout_s=600.0,
    )
    _check(resp.ok, f"/v1/predict answered {resp.status}: {resp.body[:300]!r}")
    body = resp.json()
    disparity = np.asarray(body["disparity"], np.float32)
    _check(np.isfinite(disparity).all(), "non-finite disparity in a response")
    return disparity, body


def _predict_together(port: int, frames) -> List:
    """Send one request per frame at the same moment; results in order."""
    results = [None] * len(frames)
    errors = []
    start = threading.Barrier(len(frames))

    def client(i):
        try:
            start.wait(timeout=60)
            results[i] = _predict(port, frames[i])[0]
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(len(frames))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    _check(not any(t.is_alive() for t in threads), "a client thread never finished")
    if errors:
        raise errors[0]
    return results


def _healthz(port: int) -> Dict[str, object]:
    from raft_stereo_tpu.utils.http import request_json

    resp = request_json(f"http://127.0.0.1:{port}/healthz", timeout_s=60.0)
    _check(resp.ok, f"/healthz answered {resp.status}")
    return resp.json()


def _check_healthy(health: Dict[str, object]) -> None:
    _check(
        health["serving"]["state"] == "healthy",
        f"serving state {health['serving']['state']!r}",
    )
    post_grace = health["jit_hygiene"]["compiles_post_grace"]
    _check(post_grace == 0, f"{post_grace} compile(s) on the request path")


def _check_warm_boot(boot: Dict[str, object], entries: int) -> None:
    _check(
        boot["cache_hits"] == boot["entries"] == entries and boot["cache_misses"] == 0,
        f"warm boot was not all AOT cache hits: {boot}",
    )
    _check(boot["compiles_total"] == 0, f"warm boot compiled: {boot}")


def _serve_config(sizes: Sizes, aot_dir: str, **overrides):
    from raft_stereo_tpu.config import RAFTStereoConfig, ServeConfig

    kw = dict(
        model=RAFTStereoConfig(**sizes.model),
        buckets=(tuple(sizes.serve_bucket),),
        max_batch=sizes.max_batch,
        chunk_iters=sizes.chunk_iters,
        max_iters=sizes.max_iters,
        batch_window_ms=sizes.batch_window_ms,
        aot_cache_dir=aot_dir,
    )
    kw.update(overrides)
    return ServeConfig(**kw)


def phase_serve(sizes: Sizes, seed: int, workdir: str) -> Dict[str, object]:
    import jax
    import numpy as np

    from raft_stereo_tpu.models import init_model_variables

    # hlo_audit (serve --audit) keeps each warmed executable's HLO text, which
    # is where the compiled chunk program shows its kernel.
    config = _serve_config(sizes, os.path.join(workdir, "aot"), hlo_audit=True)
    variables = init_model_variables(config.model, seed=seed)
    frame_a, frame_b = _frames(seed + 3, 2, sizes.serve_image_hw)

    with _serving(config, variables) as (service, port):
        cold = service.boot_block()
        kernels = sum(
            _kernel_calls(rec["hlo"])
            for rec in service.audit_records()
            if rec["kind"] == "chunk"
        )
        _check_kernels_compiled("serve", kernels)
        t0 = time.perf_counter()
        first, body = _predict(port, frame_a)
        alone_s = time.perf_counter() - t0
        _check(
            first.shape == tuple(sizes.serve_image_hw), f"disparity shape {first.shape}"
        )
        _check(
            body["iters_completed"] == sizes.max_iters and not body["early_exit"],
            f"request stopped early: {body['iters_completed']} iterations",
        )
        paired, other = _predict_together(port, [frame_a, frame_b])
        repeat, _ = _predict(port, frame_a)
        _check(np.array_equal(repeat, first), "a repeated request got another answer")
        _check(not np.array_equal(other, first), "two different pairs got one answer")
        health = _healthz(port)
        _check_healthy(health)
        serving = health["serving"]
        _check(
            serving["responses_total"] == 4 and serving["batches_total"] == 3,
            f"expected 4 responses in 3 batches (one of 2), got "
            f"{serving['responses_total']} in {serving['batches_total']}",
        )
        memory = _memory(jax.devices()[0])
        chunk_est_ms = service.warm_summary["chunk_est_ms"]
        audit = service.hlo_audit_block()
    _check(cold["cache_misses"] == cold["entries"] > 0, f"cold boot ledger: {cold}")
    _free()

    # The second boot: deserialize_and_load on the real device.
    with _serving(config, variables) as (service, port):
        warm = service.boot_block()
        _check_warm_boot(warm, cold["entries"])
        rebooted, _ = _predict(port, frame_a)
        _check(
            np.array_equal(rebooted, first),
            "the warm-booted service answered differently from the cold one",
        )
        _check_healthy(_healthz(port))
    return _emit(
        "serve",
        bucket=list(sizes.serve_bucket),
        max_batch=sizes.max_batch,
        chunk_iters=sizes.chunk_iters,
        max_iters=sizes.max_iters,
        cold_boot_s=cold["warmup_seconds"],
        cold_boot_compiles=cold["compiles_total"],
        warm_boot_s=warm["warmup_seconds"],
        warm_boot_compiles=warm["compiles_total"],
        aot_entries=cold["entries"],
        request_alone_s=alone_s,
        chunk_est_ms=chunk_est_ms,
        # Another executable (batch 2) on the same pair. With UNTRAINED
        # weights 32 iterations amplify its rounding differences without
        # bound, so this says nothing yet; it needs a contractive checkpoint.
        untrained_batch2_vs_batch1_max_abs_px=float(np.abs(paired - first).max()),
        kernel_calls=kernels,
        hlo_audit_violations=audit["violations"],
        **memory,
    )


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------


class _StepRecorder:
    """The trainer's metrics hook: waits for each step's loss, so the time
    between two pushes is one whole step on the device."""

    def __init__(self):
        self.losses: List[float] = []
        self.done_at: List[float] = []

    def push(self, metrics, step: int) -> None:
        import jax

        self.losses.append(float(jax.device_get(metrics["live_loss"])))
        self.done_at.append(time.perf_counter())

    def write(self, values, step: int) -> None:
        pass


def _train_config(sizes: Sizes, seed: int, workdir: str, name: str, **overrides):
    from raft_stereo_tpu.config import AugmentConfig, RAFTStereoConfig, TrainConfig

    kw = dict(
        model=RAFTStereoConfig(**sizes.model),
        augment=AugmentConfig(crop_size=tuple(sizes.train_hw)),
        name=name,
        seed=seed,
        batch_size=sizes.train_batch,
        train_iters=sizes.train_iters,
        num_steps=sizes.train_steps,
        mesh_shape=(1, 1),
        checkpoint_dir=os.path.join(workdir, "checkpoints"),
        log_dir=os.path.join(workdir, "logs", name),
    )
    kw.update(overrides)
    return TrainConfig(**kw)


def _batches(seed: int, n_batches: int, batch: int, hw: Tuple[int, int]):
    import numpy as np

    frames = _frames(seed, n_batches * batch, hw)
    return [
        {
            key: np.stack([f[key] for f in frames[i * batch : (i + 1) * batch]])
            for key in ("image1", "image2", "flow", "valid")
        }
        for i in range(n_batches)
    ]


def _train_kernel_calls(trainer, batch) -> int:
    import jax

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    return _kernel_calls(trainer.train_step.lower(trainer.state, shapes).as_text())


def _fit(trainer, batches) -> Tuple[_StepRecorder, List[float], Dict[str, object]]:
    """cli.run_training over `batches`; the loss of each step, the seconds
    each took, and the accepted run report."""
    import numpy as np

    from raft_stereo_tpu import cli

    scripts = os.path.join(ROOT, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import check_run_report

    recorder = _StepRecorder()
    t0 = time.perf_counter()
    rc = cli.run_training(trainer, batches, metrics_logger=recorder)
    _check(rc == 0, f"run_training exited {rc}: {trainer.last_run_report.get('error')}")
    _check(len(recorder.losses) == len(batches), f"{len(recorder.losses)} steps ran")
    _check(bool(np.isfinite(recorder.losses).all()), f"losses {recorder.losses}")
    report = trainer.last_run_report
    post_grace = report["jit_hygiene"]["compiles_post_grace"]
    _check(post_grace == 0, f"{post_grace} compile(s) after the grace steps")
    path = os.path.join(trainer.config.log_dir, "run_report.json")
    _check(
        check_run_report.main(["--quiet", path]) == 0,
        f"scripts/check_run_report.py rejects {path}",
    )
    step_s = list(np.diff([t0, *recorder.done_at]))
    return recorder, step_s, report


def phase_train(sizes: Sizes, seed: int, workdir: str) -> Dict[str, object]:
    import jax

    from raft_stereo_tpu.train.trainer import Trainer

    config = _train_config(sizes, seed, workdir, "chip-smoke")
    batches = _batches(seed + 4, sizes.train_steps, sizes.train_batch, sizes.train_hw)
    trainer = Trainer(config, sample_shape=(*sizes.train_hw, config.model.in_channels))
    kernels = _train_kernel_calls(trainer, batches[0])
    _check_kernels_compiled("train", kernels)
    recorder, step_s, report = _fit(trainer, batches)
    _check(
        len(set(recorder.losses)) == len(recorder.losses),
        f"the loss did not change across steps: {recorder.losses}",
    )
    return _emit(
        "train",
        batch=sizes.train_batch,
        hw=list(sizes.train_hw),
        iters=sizes.train_iters,
        losses=recorder.losses,
        step_s=step_s[-1],
        step_s_runs=step_s,
        compile_s=step_s[0] - step_s[-1],
        compiles_total=report["jit_hygiene"]["compiles_total"],
        kernel_calls=kernels,
        **_memory(jax.devices()[0]),
    )


# --------------------------------------------------------------------------
# --chips 4
# --------------------------------------------------------------------------


def _devices_of(array) -> List[int]:
    return sorted(shard.device.id for shard in array.addressable_shards)


def _check_memory_on(devices, what: str) -> List[int]:
    in_use = [_memory(d)["bytes_in_use"] for d in devices]
    _check(
        all(b > 0 for b in in_use) or not _on_tpu(),
        f"{what}: a device holds nothing: bytes_in_use {in_use}",
    )
    return in_use


def phase_train_dp(sizes: Sizes, seed: int, workdir: str, chips: int) -> Dict[str, object]:
    """The data-parallel step on a (chips, 1) mesh at twice the one-chip
    batch, against the one-chip step on each half of the same samples."""
    import jax
    import numpy as np

    from raft_stereo_tpu.train.trainer import Trainer

    devices = jax.devices()[:chips]
    sample = (*sizes.train_hw, 3)
    (batch,) = _batches(seed + 5, 1, 2 * sizes.train_batch, sizes.train_hw)
    halves = [
        {k: v[i * sizes.train_batch : (i + 1) * sizes.train_batch] for k, v in batch.items()}
        for i in range(2)
    ]

    config = _train_config(
        sizes, seed, workdir, "chip-smoke-dp",
        batch_size=2 * sizes.train_batch,
        mesh_shape=(chips, 1),
        sharding_rules="dp",
        num_steps=1,
    )
    trainer = Trainer(config, sample_shape=sample)
    param = jax.tree.leaves(trainer.state.params)[0]
    placed = trainer.sharding.place_batch(batch)["image1"]
    _check(
        _devices_of(param) == _devices_of(placed) == sorted(d.id for d in devices),
        f"state on devices {_devices_of(param)}, batch on {_devices_of(placed)}",
    )
    shard_shape = placed.addressable_shards[0].data.shape
    _check(
        shard_shape[0] * chips == placed.shape[0],
        f"batch shard {shard_shape} of {placed.shape} over {chips} devices",
    )
    state_bytes = _check_memory_on(devices, "dp train state")
    del placed
    kernels = _train_kernel_calls(trainer, batch)
    _check_kernels_compiled("train-dp", kernels)
    recorder, step_s, _ = _fit(trainer, [batch])
    loss_dp = recorder.losses[0]
    step_reserved = [_memory(d)["peak_bytes_reserved"] for d in devices]
    del trainer, param
    _free()

    # What it is compared with: the one-chip step, from the same initial
    # state (same seed), on each half. train_step donates its state, so each
    # half gets a fresh placement of the kept host copy.
    one_chip = Trainer(
        dataclasses.replace(
            config, name="chip-smoke-ref", batch_size=sizes.train_batch, mesh_shape=(1, 1)
        ),
        sample_shape=sample,
    )
    state0 = jax.device_get(one_chip.state)
    loss_halves = []
    for half in halves:
        _, metrics = one_chip.train_step(
            one_chip.sharding.place_state(state0), one_chip.sharding.place_batch(half)
        )
        loss_halves.append(float(jax.device_get(metrics["live_loss"])))
    want = float(np.mean(loss_halves))
    # Same samples, same weights, but another program per device (batch 2,
    # not 4) in bf16 compute: agreement to a few bf16 ulp (2^-8), not bits.
    rel = abs(loss_dp - want) / abs(want)
    _check(
        np.isfinite(loss_dp) and rel <= 2e-2,
        f"dp loss {loss_dp} vs mean of one-chip halves {want} ({loss_halves}): rel {rel}",
    )
    return _emit(
        "train-dp",
        mesh=[chips, 1],
        global_batch=2 * sizes.train_batch,
        loss_dp=loss_dp,
        loss_one_chip_halves=loss_halves,
        loss_rel_diff=rel,
        step_s_with_compile=step_s[0],
        state_devices=sorted(d.id for d in devices),
        state_bytes_in_use=state_bytes,
        step_peak_bytes_reserved=step_reserved,
        kernel_calls=kernels,
    )


def phase_serve_fleet(sizes: Sizes, seed: int, workdir: str, chips: int) -> Dict[str, object]:
    """`serve --replicas N` from one shared AOT cache: a cold boot fills the
    per-device entries, a warm boot loads each replica's onto its own device,
    and every replica's answer is the one-chip engine's, bit for bit."""
    import jax
    import numpy as np

    from raft_stereo_tpu.models import init_model_variables

    aot_dir = os.path.join(workdir, "aot-fleet")
    single = _serve_config(sizes, aot_dir, max_batch=1)
    fleet = dataclasses.replace(single, replicas=chips)
    variables = init_model_variables(single.model, seed=seed)
    (frame,) = _frames(seed + 6, 1, sizes.fleet_image_hw)
    n_requests = 2 * chips

    with _serving(single, variables) as (service, port):
        want, _ = _predict(port, frame)
        _check_healthy(_healthz(port))
    _free()
    with _serving(fleet, variables) as (service, port):
        cold = service.boot_block()
        _check(cold["cache_misses"] == cold["entries"] > 0, f"cold fleet boot: {cold}")
    _free()
    with _serving(fleet, variables) as (service, port):
        warm = service.boot_block()
        _check_warm_boot(warm, cold["entries"])
        replicas = service.engine.replicas
        replica_devices = [r.device.id for r in replicas]
        _check(
            len(set(replica_devices)) == chips,
            f"replicas share devices: {replica_devices}",
        )
        for r in replicas:
            leaf = jax.tree.leaves(r.engine.variables)[0]
            _check(
                _devices_of(leaf) == [r.device.id],
                f"replica {r.idx} weights on {_devices_of(leaf)}, not {r.device.id}",
            )
        weight_bytes = _check_memory_on([r.device for r in replicas], "fleet weights")
        answers = _predict_together(port, [frame] * n_requests)
        for i, got in enumerate(answers):
            _check(
                np.array_equal(got, want),
                f"fleet answer {i} differs from the one-chip answer by "
                f"{float(np.abs(got - want).max())} px",
            )
        health = _healthz(port)
        _check_healthy(health)
        by_replica = health["serving"]["batches_by_replica"]
        _check(
            len(by_replica) == chips and all(n >= 1 for n in by_replica.values()),
            f"not every replica answered: batches by replica {by_replica}",
        )
    return _emit(
        "serve-fleet",
        replicas=chips,
        requests=n_requests,
        replica_devices=replica_devices,
        batches_by_replica=by_replica,
        cold_boot_s=cold["warmup_seconds"],
        cold_boot_compiles=cold["compiles_total"],
        warm_boot_s=warm["warmup_seconds"],
        warm_boot_compiles=warm["compiles_total"],
        aot_entries=cold["entries"],
        weight_bytes_in_use=weight_bytes,
    )


# --------------------------------------------------------------------------


def run(sizes: Sizes, seed: int, chips: int) -> List[Dict[str, object]]:
    """Every phase for `chips`, in order, each freed before the next."""
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if chips == 1:
            phases = [
                lambda: phase_infer(sizes, seed),
                lambda: phase_serve(sizes, seed, workdir),
                lambda: phase_train(sizes, seed, workdir),
            ]
        else:
            phases = [
                lambda: phase_train_dp(sizes, seed, workdir, chips),
                lambda: phase_serve_fleet(sizes, seed, workdir, chips),
            ]
        lines = []
        for phase in phases:
            t0 = time.perf_counter()
            line = phase()
            lines.append(line)
            print(
                f"# {line['phase']}: {time.perf_counter() - t0:.1f} s, "
                f"{_free() / 1e9:.2f} GB left on device 0",
                file=sys.stderr,
                flush=True,
            )
        return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0, help="weights and inputs")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the paths that exist across chips (dp train step, "
        "replica fleet) and what they are compared with",
    )
    args = parser.parse_args(argv)
    device = gate(args.chips)
    t0 = time.perf_counter()
    run(Sizes(), args.seed, args.chips)
    print(f"# all phases: {time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
